"""Median ``prefill_admit`` span (pop from the queue to slotted) of the
requests admitted inside the window, in milliseconds."""

import statistics


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    durs = [s["dur_s"] for s in ctx["spans"]
            if s.get("span") == "prefill_admit"
            and ctx["t_open"] <= s["t0"] + s["dur_s"] < ctx["t_close"]]
    return 1e3 * statistics.median(durs) if durs else None
