"""Median device milliseconds of one run of an admission's prefill program
(``jit_prefill_b<bucket>`` in the trace's ``XLA Modules`` line), over the
admissions the capture holds: one or two a run, none in some."""

import statistics


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["kind"] != "serve":
        return None
    runs = red.module_runs(r"jit_prefill_b\d+")
    return 1e3 * statistics.median(s for _, s in runs) if runs else None
