"""What an admission costs the streams that were decoding, in
milliseconds: a chunk's time is the median ``dur_s`` of the
``decode_chunk`` spans that carry its number (they tile harvest to
harvest; an admitted request's first starts at its admission, and the
median leaves it out); the median over the window's chunks that ran
behind an admission (``admits_ahead`` over 0), less the median over those
that did not. Every admission of the window is in it, where the capture
behind ``prefill_device_ms`` holds one or two. None where the spans name
no ``chunk`` (a program without the chunk ledger)."""

import statistics


def chunk_medians(ctx) -> dict | None:
    """{behind an admission?: [a chunk's median span seconds, ...]} over
    the chunks whose spans end inside the window."""
    if ctx["kind"] != "serve":
        return None
    chunks: dict = {}
    for s in ctx["spans"]:
        if s.get("span") == "decode_chunk" and "chunk" in s \
                and ctx["t_open"] <= s["t0"] + s["dur_s"] < ctx["t_close"]:
            chunks.setdefault((s["chunk"], s["admits_ahead"] > 0),
                              []).append(s["dur_s"])
    out: dict = {False: [], True: []}
    for (_chunk, behind), durs in chunks.items():
        out[behind].append(statistics.median(durs))
    return out if chunks else None


def read(ctx):
    got = chunk_medians(ctx)
    if not got or not got[True] or not got[False]:
        return None
    return 1e3 * (statistics.median(got[True]) - statistics.median(got[False]))
