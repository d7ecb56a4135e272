"""The fullest expert's picks over the mean expert's, a routed layer a
decode step: the engine's ``moe_load_max`` counter over ``moe_picks`` / the
number of experts, both differenced over the window. 1 is an even load; a
capacity-factor design would drop what lies above its factor. None where
the engine has no such counters."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    fullest = scopes.counter_delta(ctx, "moe_load_max")
    picks = scopes.counter_delta(ctx, "moe_picks")
    experts = getattr(ctx["dims"], "experts", 0)
    if fullest is None or not picks or not experts:
        return None
    return fullest * experts / picks
