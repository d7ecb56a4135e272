"""Device milliseconds of one train step in attention proper: ``attn.read``
(XLA), the flash and block-sparse kernels and their backwards, XLA or
kernel. The projections (``attn.proj``) are matmuls over weights and are
not in it."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.scope_ms(ctx, r"jit_step", ("attn.read", "attn.flash_fwd", "attn.flash_bwd",
                                              "attn.sparse_fwd", "attn.sparse_bwd"))
