"""Share of the decode program's device time whose operations the join gives
a named scope (inherited ones included). Low where the traced program is
not the one the maps were compiled from, or a layer has no scope."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return scopes.scoped_pct(ctx, r"decode_impl")
