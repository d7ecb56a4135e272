"""Milliseconds by which the window's stalled chunks overran their
class's median interval: the engine's ``loop_stall_s`` counter (a chunk
whose harvest-to-harvest interval passes three times the running median
of its class is a stall, ``serve/engine.py STALL_FACTOR``), differenced
over the window. 0 in a clean run; a run whose ``images_per_s`` sits low
for a host pause reads the pause here. None where the engine has no such
counter."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    stalled = scopes.counter_delta(ctx, "loop_stall_s")
    return None if stalled is None else 1e3 * stalled
