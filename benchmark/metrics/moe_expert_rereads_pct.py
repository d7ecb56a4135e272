"""Of the touched experts' reads a decode step needs, the share that the
row tiles read again: 100 x (the engine's ``moe_group_reads`` less its
``moe_experts_touched``) over ``moe_experts_touched``, both differenced
over the window. Where a routed layer's sorted pair rows are cut into
tiles of 64 (``ops/moe.py row_tiles``: a call without a ladder whose rows
are more than the chip's ridge and no more than the kernel's own), a tile
reads each expert that a row of it lies in, so an expert whose rows
straddle a tile boundary is read by both tiles: at most one re-read a
boundary. 0 where the products run as one tile; what the tiles pay for
the operations they save. ``moe_experts_roofline`` counts each touched expert once, so a
re-read lowers it. None from a program without the counter (from before
the tiles), an engine without routed layers, or a window without a
step."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    reads = scopes.counter_delta(ctx, "moe_group_reads")
    touched = scopes.counter_delta(ctx, "moe_experts_touched")
    if reads is None or not touched:
        return None
    return 100.0 * (reads - touched) / touched
