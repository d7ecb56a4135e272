"""Share of the window's deliveries that waited behind an admission: the
``decode_chunk`` spans ending inside the window whose chunk was dispatched
with a prefill in the device's queue in front of it (``admits_ahead`` over
0), over all of them. ``tpot_ms_p95`` stands on the admissions where this
reads over 5, and on the plain step where it reads under. None where the
spans name no ``chunk`` (a program without the chunk ledger)."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    behind = [s["admits_ahead"] > 0 for s in ctx["spans"]
              if s.get("span") == "decode_chunk" and "chunk" in s
              and ctx["t_open"] <= s["t0"] + s["dur_s"] < ctx["t_close"]]
    return 100.0 * sum(behind) / len(behind) if behind else None
