"""The chunk ledger's readers (ISSUE 37) on a hand-made window: ten
chunks of two streams at 80 ms a chunk, two of them behind an admission
(104 and 106 ms for the streams that were decoding, 20 ms for the stream
each admitted), one clean chunk that a 3 s pause held, and a stall
counter that moved by the pause."""

import types

import pytest

from benchmark import harness, scopes
from test_benchmark_contract import (CHUNK_LEDGER, EVERY_SERVE_CELL,
                                     check_declared)

NEW = CHUNK_LEDGER
BEHIND = {3: 0.104, 7: 0.106}       # chunk -> seconds, behind an admission
PAUSED = {5: 3.08}                  # a clean chunk a host pause held


def span(chunk, end, dur, behind, request_id=0, **meta):
    return {"event": "span", "span": "decode_chunk", "t0": end - dur,
            "dur_s": dur, "request_id": request_id, "tokens": 8,
            "chunk": chunk, "admits_ahead": int(behind), **meta}


def window_spans():
    out, t = [], 10.0
    for chunk in range(10):
        dur = BEHIND.get(chunk, PAUSED.get(chunk, 0.080))
        t += dur
        behind = chunk in BEHIND
        out += [span(chunk, t, dur, behind, request_id=r) for r in (1, 2)]
        if behind:      # the request admitted: its first span starts there
            out.append(span(chunk, t, 0.020, True, request_id=10 + chunk))
    # outside the window, and other spans of a request's timeline
    out.append(span(99, 200.0, 5.0, True))
    out.append({"event": "span", "span": "prefill_admit", "t0": 10.0,
                "dur_s": 0.005, "admit": 4, "rows": 4})
    return out


def ctx(kind="serve", spans=None, **over):
    cell = types.SimpleNamespace(spec={"engine": {"chunk_steps": 8}})
    base = {"kind": kind, "cell": cell, "t_open": 10.0, "t_close": 100.0,
            "spans": window_spans() if spans is None else spans,
            "stats0": {"loop_stall_s": 0.25, "loop_stalls": 1},
            "stats1": {"loop_stall_s": 3.25, "loop_stalls": 2}}
    base.update(over)
    return base


def read(name, c):
    return harness.load_reader(name)(c)


def test_readers_on_a_window_with_two_admissions_and_one_stall():
    c = ctx()
    # 6 of the 22 spans that end inside the window ran behind an admission
    assert read("admit_delayed_delivery_pct", c) == pytest.approx(
        100.0 * 6 / 22)
    # a chunk is its median span (the admitted request's short first span
    # is left out), the admission's cost the behind chunks' median less
    # the clean chunks': (104 + 106) / 2 - 80 ms, and the pause moves
    # neither median
    assert read("admit_stream_stall_ms", c) == pytest.approx(25.0)
    assert read("chunk_interval_ms", c) == pytest.approx(10.0)
    assert read("loop_stall_ms", c) == pytest.approx(3000.0)
    assert scopes.counter_delta(c, "loop_stalls") == 1


@pytest.mark.parametrize("name", NEW)
def test_readers_read_nothing_where_there_is_nothing(name):
    # a train cell
    assert read(name, ctx(kind="train")) is None
    # a program without the ledger: spans without ``chunk``, stats without
    # the counter (the parent's run of these files): nothing, not zero
    old = [{k: v for k, v in s.items() if k not in ("chunk", "admits_ahead")}
           for s in window_spans()]
    assert read(name, ctx(spans=old, stats0={"harvests": 1},
                          stats1={"harvests": 9})) is None


def test_a_window_without_an_admission_has_a_step_and_no_stall_cost():
    clean = [s for s in window_spans() if not s.get("admits_ahead")]
    c = ctx(spans=clean)
    assert read("admit_delayed_delivery_pct", c) == 0.0
    assert read("admit_stream_stall_ms", c) is None
    assert read("chunk_interval_ms", c) == pytest.approx(10.0)


def test_the_new_metrics_are_the_engines_in_every_serve_cell():
    # found by name: entries that later PRs append stand after these four
    declared = [m["name"] for m in harness.load_benchmark()["per_layer"]
                if m["name"] in NEW]
    assert declared == NEW
    for name in NEW:
        check_declared(name, **EVERY_SERVE_CELL[name])
