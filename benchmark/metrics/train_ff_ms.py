"""Device milliseconds of one train step under the scope ``ff``: the GEGLU
block forward, recomputed and backward."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.scope_ms(ctx, r"jit_step", ("ff",))
