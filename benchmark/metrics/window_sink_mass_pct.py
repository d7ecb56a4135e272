"""The share of a window layer's softmax weight that its learned sink
takes: 100 x the engine's ``window_sink_mass`` (the sink's weight summed
over window layers, query heads, active slots and decode steps) over
``window_sink_reads`` (the softmaxes it is summed over), both differenced
over the whole window (no capture). A property of the seeded draw (the
configuration's ``sink_logit_mean`` / ``sink_logit_std``), neither better
higher nor lower: it says in every run that the sink is in the served
softmax at the timed sizes (a read that drops it reports 0) and that it
takes the real share a trained one does. None for a train cell, where the
engine has no such counters, and where no window softmax ran."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    mass = scopes.counter_delta(ctx, "window_sink_mass")
    reads = scopes.counter_delta(ctx, "window_sink_reads")
    if mass is None or not reads:
        return None
    return 100.0 * mass / reads
