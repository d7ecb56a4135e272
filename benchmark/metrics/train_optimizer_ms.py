"""Device milliseconds of one train step under the scope ``optimizer``: the
optax update and its apply."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.scope_ms(ctx, r"jit_step", ("optimizer",))
