"""The share of the router's picks that fell on the experts held here: the
engine's ``moe_picks_held`` over ``moe_picks``, both differenced over the
window. The router scores and picks among all the published experts and
the chip computes its own: with an even load the share is experts held /
experts published. None where the engine holds every expert (it has no
such counter)."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    held = scopes.counter_delta(ctx, "moe_picks_held")
    picks = scopes.counter_delta(ctx, "moe_picks")
    if held is None or not picks:
        return None
    return 100.0 * held / picks
