"""Device milliseconds of one fused decode step under the scope
``attn.read``: q.k, the masks, softmax and .v over the cached rows (and the
paged kernel where it runs)."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    steps = int(ctx["cell"].spec["engine"]["chunk_steps"])
    return scopes.scope_ms(ctx, r"decode_impl", ("attn.read", "paged_attn"), per=steps)
