"""Share of the train step's device time whose operations the join gives a
named scope (inherited ones included)."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return scopes.scoped_pct(ctx, r"jit_step")
