"""Of the (token, pick) pair rows the router made, the share that the held
experts' grouped products were handed: the engine's ``moe_rows_computed``
over ``moe_picks``, both differenced over the window. A block that holds
a share of its experts hands the products the least step of a short
ladder of row counts that holds the step's held pairs
(``ops/moe.py row_ladder``): 100 where the call has no ladder (few pair
rows), the first step over the pair rows where no step of the window left
it, and anything above says how often a further step engaged. None where
the engine holds every expert, or a program from before the ladder (it
has no such counter)."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    rows = scopes.counter_delta(ctx, "moe_rows_computed")
    picks = scopes.counter_delta(ctx, "moe_picks")
    if rows is None or not picks:
        return None
    return 100.0 * rows / picks
