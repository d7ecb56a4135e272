"""Compiles inside the measured window of a serving cell: the engine's
``decode_compiles`` and ``prefill_compiles`` deltas from ``/stats`` plus
the benchmark's compile listener."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    s0, s1 = ctx["stats0"], ctx["stats1"]
    engine = sum(s1.get(k, 0) - s0.get(k, 0)
                 for k in ("decode_compiles", "prefill_compiles"))
    return engine + ctx["compiles_in_window"]
