"""Median milliseconds from the end of a chunk's ``decode_chunk`` span
(the engine's harvest, its own clock) to the client's receipt of that
chunk's tokens: the sink, the wake-up of the client thread and whatever
held the interpreter in between. Both clocks are ``perf_counter``."""

import statistics


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    lags = []
    for rec in ctx["records"]:
        chunks = [s for s in rec["spans"] if s.get("span") == "decode_chunk"]
        if len(chunks) != len(rec["events"]):
            continue                # a replayed or dropped delivery
        for span, (t, _pos, _toks) in zip(chunks, rec["events"]):
            if ctx["t_open"] <= t < ctx["t_close"]:
                lags.append(t - (span["t0"] + span["dur_s"]))
    return 1e3 * statistics.median(lags) if lags else None
