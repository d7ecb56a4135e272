"""Slot-seconds busy over slot-seconds of the window, from the requests'
spans: a request holds a slot from the end of its ``prefill_admit`` span
to the end of its last ``decode_chunk`` span."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    t0, t1 = ctx["t_open"], ctx["t_close"]
    busy = 0.0
    for rec in ctx["records"]:
        admit = [s for s in rec["spans"] if s.get("span") == "prefill_admit"]
        chunks = [s for s in rec["spans"] if s.get("span") == "decode_chunk"]
        if not admit or not chunks:
            continue
        start = admit[-1]["t0"] + admit[-1]["dur_s"]
        end = chunks[-1]["t0"] + chunks[-1]["dur_s"]
        busy += max(min(end, t1) - max(start, t0), 0.0)
    slots = int(ctx["cell"].spec["num_slots"])
    return 100.0 * busy / (slots * (t1 - t0))
