"""Observability-layer tests (ISSUE 15 acceptance criteria).

The load-bearing ones: per-request trace spans TILE (their durations sum
back to the caller-observed latency), survive the socket transport
byte-faithfully, stay transfer-clean in the steady state, and link a
failover replay to the original trace with a visible ``replayed_from``
gap — with the victim's flight-recorder dump embedded in the fence event
(parent-side mirror, so a SIGKILL cannot destroy it). Plus the /metrics
exposition (histogram counts == distinct delivered requests), the
/debug/events surface, the typed /admin/profile 409, and the
MetricsLogger thread-safety fix (concurrent appends, zero torn lines).

All CPU, tiny model (total_len 24) so the file stays cheap inside
tier-1; the one process+socket test is the SIGKILL acceptance row.
"""

import json
import os
import threading
import urllib.error
import urllib.request

import jax
import pytest

from dalle_pytorch_tpu.analysis import guards
from dalle_pytorch_tpu.obs.flight import FlightRecorder, RecordingMetrics
from dalle_pytorch_tpu.obs.registry import (Histogram, LabeledHistogram,
                                            Registry)
from dalle_pytorch_tpu.obs.trace import Trace, new_trace_id
from dalle_pytorch_tpu.resilience import faults
from dalle_pytorch_tpu.serve import OK, Request, RequestHandle, RequestQueue
from dalle_pytorch_tpu.serve.engine import Engine, ProfileError
from tiny_model import CFG, FAST_BRINGUP, _no_leaked_plan, bundle  # noqa: F401
from tiny_model import MORE_REQS as REQS

# ---------------------------------------------------------------------------
# obs/trace.py
# ---------------------------------------------------------------------------

class TestTrace:
    def test_spans_tile_and_sum(self):
        tr = Trace(new_trace_id(7), 7, t0=100.0)
        tr.span("submit", 100.0)
        tr.span("queue_wait", 100.5)
        tr.span("prefill_admit", 100.75, bucket=4, mode="cold")
        tr.span("decode_chunk", 101.0, tokens=4)
        tr.span("decode_chunk", 101.25, tokens=4)
        s = tr.summary()
        assert s["request_id"] == 7 and s["attempts"] == 1
        names = [x["name"] for x in s["spans"]]
        assert names == ["submit", "queue_wait", "prefill_admit",
                         "decode_chunk"]
        # tiling: the sum of durations IS the wall interval
        assert s["span_total_s"] == pytest.approx(1.25)
        chunk = next(x for x in s["spans"]
                     if x["name"] == "decode_chunk")
        assert chunk["n"] == 2 and chunk["total_s"] == pytest.approx(0.5)

    def test_replay_marker_covers_the_gap_visibly(self):
        """The fence gap is a LABELED span, not fabricated decode time
        and not a hole: the replayed_from marker's duration is the gap,
        so span sums still tile while the timeline shows the fence."""
        tr = Trace("t", 1, t0=0.0)
        tr.span("queue_wait", 0.1)
        tr.span("decode_chunk", 0.4, tokens=4)
        rec = tr.replay(1.4, reason="crash: boom", replica=1)
        assert rec["span"] == "replayed_from"
        assert rec["dur_s"] == pytest.approx(1.0)       # the gap
        assert rec["from_attempt"] == 0 and rec["attempt"] == 1
        tr.span("queue_wait", 1.5)
        tr.span("decode_chunk", 2.0, tokens=8)
        s = tr.summary()
        assert s["attempts"] == 2
        assert s["replays"] == [{"from_attempt": 0,
                                 "reason": "crash: boom",
                                 "gap_s": pytest.approx(1.0)}]
        assert s["span_total_s"] == pytest.approx(2.0)

    def test_has_in_attempt_resets_per_attempt(self):
        tr = Trace("t", 1, t0=0.0)
        tr.span("queue_wait", 0.1)
        assert tr.has_in_attempt("queue_wait")
        tr.replay(0.2, reason="fence")
        assert not tr.has_in_attempt("queue_wait")

    def test_wire_spans_cross_the_frame_codec_byte_faithfully(self):
        """Float timestamps/durations survive the JSON frame protocol
        exactly (repr round-trip — the same rule Request.to_wire
        relies on), so a child's spans merge bit-identical."""
        from dalle_pytorch_tpu.serve import ipc
        tr = Trace("abc-123", 9, t0=12345.678901234567)
        tr.span("queue_wait", 12345.981234567891)
        tr.span("decode_chunk", 12346.123456789012, tokens=3)
        spans = tr.wire_spans()
        frame = ipc.encode_frame(
            ipc.HARVEST, {"results": [{"spans": spans}]}, seq=4)
        kind, payload, seq = ipc.decode_frame(frame)
        assert payload["results"][0]["spans"] == spans

    def test_merge_wire_skips_malformed_and_reanchors(self):
        tr = Trace("t", 1, t0=0.0)
        tr.span("route", 0.5, replica=0)
        n = tr.merge_wire(
            [{"span": "queue_wait", "dur_s": 0.25, "t0": 0.5,
              "attempt": 0, "event": "span"},
             "garbage", {"nope": 1}, None], now=1.0)
        assert n == 1
        tr.span("postprocess", 1.5)
        s = tr.summary()
        assert [x["name"] for x in s["spans"]] == \
            ["route", "queue_wait", "postprocess"]
        assert s["spans"][-1]["total_s"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# obs/flight.py
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_bounded_ring_and_since(self):
        fl = FlightRecorder(capacity=4)
        for i in range(10):
            fl.record({"i": i})
        assert len(fl) == 4
        assert [r["i"] for r in fl.dump()] == [6, 7, 8, 9]
        assert [r["i"] for r in fl.tail(2)] == [8, 9]
        seq, recs = fl.since(0)
        assert seq == 10 and [r["i"] for r in recs] == [6, 7, 8, 9]
        fl.record({"i": 10})
        seq2, recs2 = fl.since(seq)
        assert [r["i"] for r in recs2] == [10] and seq2 == 11

    def test_recording_metrics_tees_and_forwards(self):
        fl = FlightRecorder(capacity=8)

        class Sink:
            events: list = []

            def event(self, **f):
                self.events.append(f)

        sink = Sink()
        m = RecordingMetrics(fl, sink)
        m.event(event="resilience", kind="x", a=1)
        assert fl.dump()[0]["kind"] == "x"
        assert sink.events[0]["a"] == 1
        # no sink: the ring still records (always-on is the point)
        m2 = RecordingMetrics(FlightRecorder(4), None)
        m2.event(kind="y")
        assert m2.flight.dump()[0]["kind"] == "y"

    def test_wrap_never_chains_rings(self):
        from dalle_pytorch_tpu.obs.flight import wrap_metrics
        base = object()
        inner = RecordingMetrics(FlightRecorder(4), base)
        outer = wrap_metrics(FlightRecorder(4), inner)
        assert outer.inner is base


# ---------------------------------------------------------------------------
# obs/registry.py
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_histogram_buckets_count_sum_percentile(self):
        h = Histogram(buckets=(0.1, 1.0), window=100)
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        assert h.count == 3 and h.sum == pytest.approx(5.55)
        assert h.counts == [1, 1, 1]
        assert h.percentile(0.5) == pytest.approx(0.5)
        assert h.percentile(0.99) == pytest.approx(5.0)

    def test_labeled_histogram_renders_prometheus_text(self):
        reg = Registry()
        lh = reg.histogram("x_seconds", "help text", buckets=(0.1, 1.0))
        lh.observe(0.05, weights_version="v1")
        lh.observe(0.5, weights_version="v2")
        text = reg.render()
        assert "# TYPE x_seconds histogram" in text
        assert 'x_seconds_bucket{le="0.1",weights_version="v1"} 1' \
            in text
        assert 'x_seconds_bucket{le="+Inf",weights_version="v1"} 1' \
            in text
        assert 'x_seconds_count{weights_version="v2"} 1' in text
        assert lh.total_count() == 2
        # merged percentiles across children (the /stats surface)
        p = lh.percentiles_ms()
        assert p["p50"] == pytest.approx(50.0) \
            or p["p50"] == pytest.approx(500.0)

    def test_counters_gauges_and_escaping(self):
        reg = Registry()
        text = reg.render(
            counters=[("c_total", "a counter",
                       [({"k": 'we"ird\nvalue\\x'}, 3)])],
            gauges=[("g", "a gauge", [(None, 1.5)]),
                    ("empty", "dropped", [])])
        assert "# TYPE c_total counter" in text
        assert 'c_total{k="we\\"ird\\nvalue\\\\x"} 3' in text
        assert "g 1.5" in text
        assert "empty" not in text      # no samples -> no headers

    def test_bad_metric_name_rejected(self):
        with pytest.raises(ValueError):
            Registry().histogram("9bad-name", "x")


# ---------------------------------------------------------------------------
# utils.metrics.MetricsLogger thread-safety (satellite)
# ---------------------------------------------------------------------------

class TestMetricsLoggerConcurrency:
    def test_concurrent_events_no_torn_lines(self, tmp_path):
        from dalle_pytorch_tpu.utils.metrics import MetricsLogger
        path = tmp_path / "m.jsonl"
        m = MetricsLogger(str(path))
        n_threads, n_events = 8, 200

        def spam(tid):
            for i in range(n_events):
                m.event(event="serve", tid=tid, i=i,
                        pad="x" * 64)      # wide enough to tear

        threads = [threading.Thread(target=spam, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        m.close()
        lines = path.read_text().splitlines()
        assert len(lines) == n_threads * n_events
        seen = set()
        for line in lines:
            rec = json.loads(line)      # a torn line would fail here
            seen.add((rec["tid"], rec["i"]))
        assert len(seen) == n_threads * n_events


# ---------------------------------------------------------------------------
# engine-level tracing
# ---------------------------------------------------------------------------

class TestEngineTracing:
    def test_trace_rides_result_and_sums_to_latency(self, bundle):
        params, _ = bundle
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4)
        handles = [queue.submit(r) for r in REQS[:3]]
        engine.run_until_idle()
        for h in handles:
            res = h.result(timeout=5)
            assert res.status == OK
            tr = res.trace
            assert tr is not None and tr["attempts"] == 1
            names = [s["name"] for s in tr["spans"]]
            assert names[:3] == ["submit", "queue_wait",
                                 "prefill_admit"]
            assert "decode_chunk" in names
            # tiling: single-process spans sum EXACTLY to the
            # caller-observed latency (same clock, no process gaps;
            # total_s rounds to 6 places)
            assert tr["span_total_s"] == pytest.approx(res.total_s,
                                                       abs=2e-5)
            chunk = next(s for s in tr["spans"]
                         if s["name"] == "decode_chunk")
            assert chunk["n"] == engine.harvests or chunk["n"] >= 1

    def test_span_stamping_is_transfer_clean(self, bundle):
        """The tracing layer adds ZERO host<->device traffic: the full
        steady-state iteration — admission, chunk dispatch, emit-ring
        harvest, span stamps, flight-ring appends — runs under
        guards.no_transfers, the same contract the pre-obs engine
        pinned."""
        params, _ = bundle
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4)
        # warm run compiles the decode program + both buckets
        for r in REQS[:2]:
            queue.submit(r)
        engine.run_until_idle()
        with guards.no_transfers():
            handles = [queue.submit(r) for r in REQS[:2]]
            engine.run_until_idle()
        for h in handles:
            res = h.result(timeout=5)
            assert res.status == OK and res.trace is not None
            assert any(s["name"] == "decode_chunk"
                       for s in res.trace["spans"])

    def test_spans_and_events_land_in_flight_ring(self, bundle):
        params, _ = bundle
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4)
        h = queue.submit(REQS[0])
        engine.run_until_idle()
        assert h.result(timeout=5).status == OK
        # (the zero-dur submit marker is stamped by the QUEUE, which
        # has no ring — it reaches /debug/events via the trace dumps)
        kinds = {r.get("span") for r in engine.flight.dump()
                 if r.get("event") == "span"}
        assert {"queue_wait", "prefill_admit", "decode_chunk"} <= kinds
        assert engine.stats()["flight_events"] == len(engine.flight)

    def test_profile_409_while_active_and_completes(self, bundle,
                                                    tmp_path):
        params, _ = bundle
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4)
        rec = engine.request_profile(str(tmp_path / "prof"), chunks=2)
        assert rec["kind"] == "serve_profile_armed"
        with pytest.raises(ProfileError) as ei:
            engine.request_profile(str(tmp_path / "other"), chunks=1)
        assert ei.value.record["reason"] == "capture_active"
        queue.submit(REQS[0])
        engine.run_until_idle()
        assert not engine.profile_active()
        assert engine.profiles_taken == 1
        assert any((tmp_path / "prof").iterdir())
        # re-armable once the capture completed
        engine.request_profile(str(tmp_path / "prof2"), chunks=1)


# ---------------------------------------------------------------------------
# the engine loop: phases on the profiler's clock, seconds in stats()
# ---------------------------------------------------------------------------

def _host_events(log_dir, prefix):
    """[(name, start_ns, end_ns, line)] of the capture's host events whose
    name starts with ``prefix``."""
    import glob

    from jax.profiler import ProfileData
    path, = glob.glob(str(log_dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     line.name) for e in line.events
                    if e.name.startswith(prefix)]
    return sorted(out, key=lambda e: e[1])


class TestEngineLoop:
    def test_phases_are_host_events_of_a_capture_and_nest(self, bundle,
                                                          tmp_path):
        params, _ = bundle
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4)
        queue.submit(REQS[0])
        engine.run_until_idle()             # compiles, outside the capture
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for r in (REQS[0], REQS[3], REQS[0]):   # three admissions
                queue.submit(r)
                engine.run_until_idle()
        finally:
            jax.profiler.stop_trace()
        events = _host_events(tmp_path, "engine.")
        by_name = {}
        for e in events:
            by_name.setdefault(e[0], []).append(e)
        assert set(by_name) == {
            "engine.step", "engine.expire", "engine.admit",
            "engine.admit.plan", "engine.admit.put",
            "engine.admit.prefill", "engine.dispatch",
            "engine.harvest_wait", "engine.deliver"}
        # one engine.admit an admission, each holding its three parts
        assert len(by_name["engine.admit"]) == 3
        assert len(by_name["engine.admit.prefill"]) == 3
        assert len(by_name["engine.dispatch"]) \
            == len(by_name["engine.harvest_wait"]) \
            == len(by_name["engine.deliver"])

        def inside(inner, outer):
            return [o for o in by_name[outer]
                    if o[1] <= inner[1] and inner[2] <= o[2]
                    and o[3] == inner[3]]

        for name in ("engine.expire", "engine.admit", "engine.dispatch",
                     "engine.harvest_wait", "engine.deliver"):
            assert all(len(inside(e, "engine.step")) == 1
                       for e in by_name[name]), name
        for name in ("engine.admit.plan", "engine.admit.put",
                     "engine.admit.prefill"):
            assert all(len(inside(e, "engine.admit")) == 1
                       for e in by_name[name]), name

    def test_loop_seconds_are_monotone_and_add_up(self, bundle):
        params, _ = bundle
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4)
        from dalle_pytorch_tpu.serve.engine import LOOP_SECONDS
        seen = [engine.stats()]
        assert all(seen[0][k] == 0.0 for k in LOOP_SECONDS)
        assert seen[0]["prefill_runs"] == seen[0]["warm_admits"] == 0
        for r in REQS[:2]:
            queue.submit(r)
        engine.run_until_idle()
        seen.append(engine.stats())
        # the steady loop with its clock reads and annotations stays
        # transfer-clean
        with guards.no_transfers():
            for r in REQS[:2]:
                queue.submit(r)
            while not engine.idle():
                engine.step_once()
                seen.append(engine.stats())
        for a, b in zip(seen, seen[1:]):
            assert all(b[k] >= a[k] for k in LOOP_SECONDS)
        last = seen[-1]
        assert last["prefill_runs"] >= 2 and last["harvest_wait_s"] > 0
        assert last["engine_loop_s"] >= last["harvest_wait_s"] \
            + last["admit_s"] + last["deliver_s"]
        assert last["admit_s"] >= last["admit_prefill_s"] > 0

    def test_prefill_admit_span_carries_the_dispatch_seconds(self, bundle):
        params, _ = bundle
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4)
        h = queue.submit(REQS[0])
        engine.run_until_idle()
        assert h.result(timeout=5).status == OK
        span, = [r for r in engine.flight.dump()
                 if r.get("span") == "prefill_admit"]
        # the seconds inside the prefill call: a part of the span, which
        # also holds the wait since the pop
        assert 0 < span["dispatch_s"] <= span["dur_s"]
        assert span["mode"] == "cold" and span["bucket"] in engine.buckets


# ---------------------------------------------------------------------------
# the chunk ledger: numbers on the spans, one row a chunk, a stall an event
# ---------------------------------------------------------------------------

class _Clock:
    """A clock the test moves: a read costs a microsecond, a turn of the
    loop what ``_drive`` gives it, a pause what ``jump`` is told."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-6
        return self.t

    def jump(self, seconds):
        self.t += seconds


class _Sink:
    def __init__(self):
        self.events = []

    def event(self, **fields):
        self.events.append(fields)


def _ledger_engine(params, **kw):
    clock = _Clock()
    queue = RequestQueue(max_depth=64, clock=clock)
    engine = Engine(params, CFG, queue, clock=clock, **kw)
    return engine, queue, clock


def _drive(engine, clock, steps=10_000):
    """Turn the loop, a tenth of a second between its steps, until the
    engine is idle or ``steps`` are spent."""
    for _ in range(steps):
        if engine.idle():
            break
        clock.jump(0.1)
        engine.step_once()


def _rows(engine):
    return [r for r in engine.loop_ring.dump() if "kind" not in r]


def _stalls(records):
    return [r for r in records if r.get("kind") == "serve_loop_stall"]


class TestChunkLedger:
    def test_spans_name_their_chunk_and_their_admission(self, bundle):
        params, _ = bundle
        engine, queue, clock = _ledger_engine(params, num_slots=2,
                                              chunk_steps=2)
        first = queue.submit(REQS[0])
        _drive(engine, clock, steps=3)
        second = queue.submit(REQS[3])      # joins mid-stream
        _drive(engine, clock)
        results = [h.result(timeout=5) for h in (first, second)]
        assert all(r.status == OK for r in results)
        spans = [s for h in (first, second) for s in h.trace.spans()]
        chunks = [s for s in spans if s["span"] == "decode_chunk"]
        # every span of one harvest carries that harvest's chunk, and
        # the ids rise by one a dispatch
        by_end = {}
        for s in chunks:
            by_end.setdefault(round(s["t0"] + s["dur_s"], 9),
                              set()).add(s["chunk"])
        assert all(len(ids) == 1 for ids in by_end.values())
        ids = [min(by_end[t]) for t in sorted(by_end)]
        assert ids == list(range(ids[0], ids[0] + len(ids)))
        # admits_ahead counts the admission calls of the same step: the
        # chunk dispatched behind each of the two, for BOTH streams
        admits = sorted((s["admit"], s["rows"]) for s in spans
                        if s["span"] == "prefill_admit")
        assert [a for a, _ in admits] == [0, 1]
        assert all(rows in engine.prefill_groups for _, rows in admits)
        behind = {s["chunk"] for s in chunks if s["admits_ahead"]}
        assert len(behind) == 2
        assert all(s["admits_ahead"] == (1 if s["chunk"] in behind else 0)
                   for s in chunks)
        joined = [s for s in second.trace.spans()
                  if s["span"] == "decode_chunk"][0]["chunk"]
        assert joined in behind and sum(
            s["chunk"] == joined for s in chunks) == 2
        # the tiling contract holds with the new keys on the spans
        for r in results:
            assert r.trace["span_total_s"] == pytest.approx(r.total_s,
                                                            abs=1e-4)

    def test_rows_tile_the_loop_and_sum_to_the_counters(self, bundle):
        from dalle_pytorch_tpu.serve.engine import (LOOP_PHASES,
                                                    LOOP_SECONDS)
        params, _ = bundle
        engine, queue, clock = _ledger_engine(params, num_slots=2,
                                              chunk_steps=2)
        for r in REQS[:4]:
            queue.submit(r)
        _drive(engine, clock)
        rows, st = _rows(engine), engine.stats()
        assert [r["chunk"] for r in rows] == list(range(len(rows)))
        assert len(rows) == st["harvests"] \
            == st["decode_steps"] // st["chunk_steps"]
        for r in rows:
            assert sum(r[p] for p in LOOP_PHASES) == pytest.approx(
                r["interval_s"])
            assert r["t_dispatch"] < r["t_harvest"] and r["unix_ns"] > 0
        # one source: a cumulative counter is the sum of the rows' phases
        # that count in it (and of the open interval's: the last step's
        # tail)
        for k in LOOP_SECONDS[:-1]:
            assert st[k] == pytest.approx(sum(
                r[p] for r in rows + [engine._lap_s]
                for p, sums in LOOP_PHASES.items() if k in sums)), k
        assert st["engine_loop_s"] > st["harvest_wait_s"] + st["admit_s"] \
            + st["deliver_s"]
        assert st["chunks_behind_admit"] == sum(
            r["admits_ahead"] > 0 for r in rows) >= 2
        assert sum(r["tokens"] for r in rows) == st["tokens_decoded"]
        assert st["loop_stalls"] == 0 and st["last_stalls"] == []

    @pytest.mark.parametrize("where", ["harvest_wait_s", "between_s"])
    def test_a_pause_is_one_stall_that_names_its_phase(self, bundle,
                                                       monkeypatch, where):
        params, _ = bundle
        sink = _Sink()
        engine, queue, clock = _ledger_engine(
            params, num_slots=1, chunk_steps=1, metrics=sink)
        for r in (REQS[0], REQS[3], REQS[0]):
            queue.submit(r)
        _drive(engine, clock, steps=14)     # a median to be held against
        assert engine.loop_stalls == 0
        if where == "between_s":
            clock.jump(5.0)                 # between two step_once calls
        else:
            fetch, armed = jax.device_get, [True]

            def late(tree):
                if armed.pop() if armed else False:
                    clock.jump(5.0)         # inside the ring's fetch
                return fetch(tree)

            monkeypatch.setattr(jax, "device_get", late)
        _drive(engine, clock, steps=1)
        st = engine.stats()
        assert st["loop_stalls"] == 1
        assert st["loop_stall_s"] == pytest.approx(5.0, abs=0.01)
        # whole at once: the record waits one harvest for next_wait_s
        assert st["last_stalls"][0]["next_wait_s"] is None
        assert not _stalls(sink.events)
        _drive(engine, clock)
        stall, = _stalls(sink.events)
        assert stall["phase"] == where and stall[where] > 4.9
        assert stall["interval_s"] > 3 * stall["median_s"] > 0
        rows = {r["chunk"]: r for r in _rows(engine)}
        assert stall["next_wait_s"] \
            == rows[stall["chunk"] + 1]["harvest_wait_s"]
        assert stall["thread_cpu_s"] >= 0 and stall["gc_runs"] >= 0 \
            and stall["queue_depth"] >= 0
        st = engine.stats()
        assert st["loop_stalls"] == 1 and len(st["last_stalls"]) == 1
        assert st["last_stalls"][0]["chunk"] == stall["chunk"]
        assert st["last_stalls"][0]["next_wait_s"] == stall["next_wait_s"]
        # the stall outlives a thousand later spans: the ledger's ring is
        # not the span ring
        assert len(_stalls(engine.flight.dump())) == 1
        for i in range(1000):
            engine.flight.record({"event": "span", "span": "decode_chunk"})
        assert not _stalls(engine.flight.dump())
        assert len(_stalls(engine.loop_ring.dump())) == 1

    def test_an_admission_and_a_compile_are_no_stall(self, bundle):
        params, _ = bundle
        engine, queue, clock = _ledger_engine(params, num_slots=3,
                                              chunk_steps=1)
        admit, cost = engine._admit, [0.0]

        def slow_admit(handles, now):
            clock.jump(cost[0])
            admit(handles, now)

        engine._admit = slow_admit
        queue.submit(REQS[0])
        _drive(engine, clock, steps=14)
        # an ordinary admission: half a chunk's time, a program that is
        # compiled (REQS[3] has REQS[0]'s bucket)
        cost[0] = 0.05
        queue.submit(REQS[3])
        _drive(engine, clock, steps=3)
        assert engine.chunks_behind_admit == 2 and engine.loop_stalls == 0
        # a cold bucket: seconds inside an interval that traced a program
        cost[0] = 5.0
        traces = engine.prefill_traces
        queue.submit(Request(codes=(1, 2, 3, 4, 5, 6, 7), seed=3))
        _drive(engine, clock, steps=3)
        assert engine.prefill_traces == traces + 1
        assert max(r["interval_s"] for r in _rows(engine)) > 5.0
        assert engine.loop_stalls == 0
        # the same seconds with nothing traced are a stall
        queue.submit(REQS[3])
        _drive(engine, clock)
        assert engine.loop_stalls == 1
        assert engine.stats()["last_stalls"][0]["phase"] == "admit_plan_s"

    def test_a_workers_stall_reaches_the_parents_mirror(self, bundle):
        """A process worker ships its flight ring's increments in every
        snapshot frame (``serve/worker.py``); the stall is an event in
        that ring, so the parent's mirror has it by the path that is
        there."""
        import time

        from dalle_pytorch_tpu.serve import ipc
        params, _ = bundle
        engine, queue, clock = _ledger_engine(params, num_slots=1,
                                              chunk_steps=1)
        queue.submit(REQS[0])
        _drive(engine, clock, steps=14)
        clock.jump(5.0)
        _drive(engine, clock)
        _seq, events = engine.flight.since(0)
        kind, payload, _ = ipc.decode_frame(ipc.encode_frame(
            ipc.HEARTBEAT, {"snap": None, "events": events}))
        client = ipc.ChildEngineClient.__new__(ipc.ChildEngineClient)
        client.clock = time.perf_counter
        client.flight = FlightRecorder(capacity=512)
        client._dispatch(kind, payload)
        stall, = _stalls(client.flight.dump())
        assert stall["phase"] == "between_s" \
            and stall["next_wait_s"] is not None
        # the counters ride the heartbeat's snapshot as the rest do
        assert ipc.engine_snapshot(engine, 0, 0, False)["counters"][
            "loop_stalls"] == 1


# ---------------------------------------------------------------------------
# replica-set tracing: thread-mode failover replay link
# ---------------------------------------------------------------------------

class TestReplicaTracing:
    pytestmark = pytest.mark.faults

    def test_thread_crash_yields_linked_replay_trace(self, bundle):
        from dalle_pytorch_tpu.serve.replica import ReplicaSet
        params, _ = bundle
        queue = RequestQueue(max_depth=16)
        with faults.injected(fault_replica=1, replica_crash_at_chunk=2):
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=4, bringup_policy=FAST_BRINGUP)
            handles = [queue.submit(r) for r in REQS]
            rs.run_until_idle(max_steps=500_000)
        assert rs.failovers == 1
        traces = [h.result(timeout=5).trace for h in handles]
        assert all(t is not None for t in traces)
        replayed = [t for t in traces if t["replays"]]
        assert replayed, "the crash replayed nothing?"
        for t in replayed:
            assert t["attempts"] >= 2
            assert "crash" in t["replays"][0]["reason"]
            # the gap is visible AND the sums still tile
            assert any(s["name"] == "replayed_from"
                       for s in t["spans"])
            res = next(h.result(timeout=0) for h in handles
                       if h.result(timeout=0).trace is t)
            assert t["span_total_s"] == pytest.approx(res.total_s,
                                                      abs=2e-5)
        # routed requests carry the router's spans
        assert any(s["name"] == "route"
                   for t in traces for s in t["spans"])
        # the fence event embedded the victim's flight dump, and the
        # set-level /debug surface serves it
        dump = rs.debug_events()
        fences = [e for e in dump["server"]
                  if e.get("kind") == "serve_replica_fenced"]
        assert fences and fences[0].get("flight"), \
            "fence event carries no flight dump"
        assert any(e.get("event") == "span"
                   for e in fences[0]["flight"])
        assert dump["fenced"], "no fenced-replica dump retained"

    def test_scale_error_embeds_flight_tail(self, bundle):
        from dalle_pytorch_tpu.serve.replica import ReplicaSet, ScaleError
        params, _ = bundle
        queue = RequestQueue(max_depth=8)
        rs = ReplicaSet(params, CFG, queue, replicas=1, num_slots=2,
                        chunk_steps=4, bringup_policy=FAST_BRINGUP)
        with pytest.raises(ScaleError) as ei:
            rs.remove_replica(0)
        assert isinstance(ei.value.record.get("flight"), list)


# ---------------------------------------------------------------------------
# THE acceptance row: process+socket SIGKILL -> linked trace + dumps
# ---------------------------------------------------------------------------

class TestProcessObsAcceptance:
    pytestmark = pytest.mark.faults

    def test_sigkill_linked_trace_and_flight_dump_socket(self, bundle):
        """A process+socket 2-replica run with a mid-decode SIGKILL:
        the victim's flight-recorder dump (parent-side mirror — the
        corpse answers nothing), a replayed trace linked to the
        original trace_id whose span durations sum to the caller-
        observed latency within one harvest chunk of slop, and zero
        requests lost."""
        import time as _time

        from dalle_pytorch_tpu.serve.replica import RUNNING, ReplicaSet

        def wait_all_ready(rs, timeout=180.0):
            # same deflake as test_replica's helper: children come up
            # seconds apart, and the first-ready replica's admission
            # window could swallow the burst before the fault target
            # ever decodes a chunk
            deadline = _time.perf_counter() + timeout
            while _time.perf_counter() < deadline:
                rs.step_once()
                live = [r for r in rs.replicas if r.state == RUNNING
                        and r.engine is not None]
                if len(live) == rs.n_replicas and all(
                        getattr(r.engine, "ready", True) for r in live):
                    return
                _time.sleep(0.01)
            raise AssertionError("replicas never all became ready")
        params, _ = bundle
        queue = RequestQueue(max_depth=16)
        with faults.injected(fault_replica=1,
                             replica_sigkill_at_chunk=2):
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=4, isolation="process",
                            transport="socket",
                            bringup_policy=FAST_BRINGUP)
            try:
                wait_all_ready(rs)
                t_submit = _time.perf_counter()
                handles = [queue.submit(r) for r in REQS]
                rs.run_until_idle(max_steps=500_000)
                assert rs.failovers == 1
                wall = _time.perf_counter() - t_submit
                results = [h.result(timeout=10) for h in handles]
                assert all(r.status == OK for r in results), \
                    [(r.status, r.reason) for r in results]
                traces = [r.trace for r in results]
                assert all(t is not None for t in traces)
                # original trace ids survive the replay: attempts > 1
                # under the SAME trace_id, linked by replayed_from
                replayed = [t for t in traces if t["replays"]]
                assert replayed, "the SIGKILL replayed nothing?"
                for t in replayed:
                    assert t["attempts"] >= 2
                    assert any(s["name"] == "replayed_from"
                               for s in t["spans"])
                # span sums reconstruct caller latency: cross-process
                # tiling leaves only IPC-absorb gaps, bounded by one
                # harvest chunk of slop per attempt
                for r in results:
                    t = r.trace
                    assert 0 < t["span_total_s"] <= r.total_s + 1e-4
                    assert r.total_s - t["span_total_s"] \
                        < 0.5 * wall + 0.25, (t, r.total_s)
                # child-side spans crossed the socket and merged
                assert any(s["name"] == "decode_chunk"
                           for t in traces for s in t["spans"])
                # the victim's mirror dump: embedded in the fence
                # event AND retained under fenced[]
                dump = rs.debug_events()
                fences = [e for e in dump["server"]
                          if e.get("kind") == "serve_replica_fenced"]
                assert fences
                victim = fences[0].get("flight")
                assert victim, "SIGKILL destroyed the flight dump?"
                assert any(e.get("event") == "span" for e in victim), \
                    "no spans survived in the parent-side mirror"
                assert dump["fenced"].get("1") is not None
            finally:
                rs.close()


# ---------------------------------------------------------------------------
# server surface: /metrics, /debug/events, /admin/profile over HTTP
# ---------------------------------------------------------------------------

class TestServerObs:
    @pytest.fixture()
    def server(self, bundle, tmp_path):
        from dalle_pytorch_tpu.serve.server import (InferenceServer,
                                                    make_http_server)
        params, vae_params = bundle
        srv = InferenceServer(params, vae_params, CFG, num_slots=2,
                              chunk_steps=4, decode_images=False,
                              profile_dir=str(tmp_path / "prof"))
        srv.start()
        httpd = make_http_server(srv, port=0)
        port = httpd.server_address[1]
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        try:
            yield srv, port
        finally:
            httpd.shutdown()
            httpd.server_close()
            srv.close()

    @staticmethod
    def _get(port, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}") as r:
            return r.status, r.read().decode()

    @staticmethod
    def _post(port, path, body, token=None):
        headers = {}
        if token:
            headers["Authorization"] = f"Bearer {token}"
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(body).encode(), method="POST",
            headers=headers)
        try:
            with urllib.request.urlopen(req) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def test_metrics_stats_debug_and_profile(self, server):
        srv, port = server
        for i, r in enumerate(REQS[:3]):
            res = srv.submit(r.codes, seed=r.seed).result(timeout=60)
            assert res.ok
        # /stats: operator latency percentiles off the histogram window
        stats = srv.stats()
        lat = stats["latency_ms"]
        assert lat["e2e"]["p50"] > 0
        assert set(lat["queue_wait"]) == {"p50", "p95", "p99"}
        # /metrics: required families + count == delivered requests
        st, text = self._get(port, "/metrics")
        assert st == 200
        for fam in ("dalle_serve_requests_submitted_total",
                    "dalle_serve_requests_completed_total",
                    "dalle_serve_tokens_decoded_total",
                    "dalle_serve_queue_depth",
                    "dalle_serve_e2e_latency_seconds_bucket",
                    "dalle_serve_queue_wait_seconds_count",
                    "dalle_serve_decode_ms_per_token_count",
                    "dalle_serve_prefill_runs_total",
                    "dalle_serve_engine_loop_seconds_total",
                    "dalle_serve_harvest_wait_seconds_total",
                    "dalle_serve_admit_prefill_seconds_total",
                    "dalle_serve_chunks_behind_admit_total",
                    "dalle_serve_loop_stalls_total",
                    "dalle_serve_loop_stall_seconds_total",
                    "dalle_serve_info"):
            assert fam in text, f"missing family {fam}"
        count = [ln for ln in text.splitlines()
                 if ln.startswith("dalle_serve_e2e_latency_seconds_"
                                  "count")]
        assert count and count[0].split()[-1] == "3", count
        # the prefill family is fed from the trace summary, which must
        # exist BEFORE the on_fulfill hook runs (regression: it was
        # attached only later, inside handle.fulfill, leaving the
        # family headers-only forever)
        pre = [ln for ln in text.splitlines()
               if ln.startswith("dalle_serve_prefill_seconds_count")]
        assert pre and int(pre[0].split()[-1]) == 3, pre
        # /debug/events: span records served with no sink configured
        st, body = self._get(port, "/debug/events")
        events = json.loads(body)["server"]
        assert any(e.get("event") == "span" for e in events)
        # and the chunk ledger's rows, one a harvested chunk
        ledger = [r for r in json.loads(body)["loop"] if "kind" not in r]
        assert ledger and ledger[-1]["chunk"] == len(ledger) - 1
        assert ledger[-1]["harvest_wait_s"] >= 0
        # HTTP result bodies carry the trace summary
        st, gen = self._post(port, "/generate",
                             {"codes": [1, 2], "seed": 3})
        assert st == 200 and "trace" in gen \
            and gen["trace"]["span_total_s"] > 0
        # /admin/profile: 401 unauthenticated, 200 armed, 409 active
        st, _ = self._post(port, "/admin/profile", {})
        assert st == 401
        st, rec = self._post(port, "/admin/profile", {"chunks": 500},
                             token=srv.admin_token)
        assert st == 200 and rec["kind"] == "serve_profile_armed"
        # the capture's directory holds the join to the named scopes,
        # written before the capture was armed; the lowering it took is
        # no retrace of the serving path
        with open(os.path.join(srv.profile_dir, "scopes.json")) as f:
            maps = json.load(f)
        assert "_decode_impl" in maps and any(
            name.startswith("prefill_b") for name in maps)
        assert {e["scope"] for e in maps["_decode_impl"].values()} \
            >= {"kv.store", "attn.read", "ff", "sample"}
        assert srv.stats()["decode_compiles"] == 1
        st, rec = self._post(port, "/admin/profile", {"chunks": 1},
                             token=srv.admin_token)
        assert st == 409 and rec["reason"] == "capture_active"

    def test_profile_thread_set_guard_is_process_wide(self, bundle,
                                                      tmp_path):
        """jax.profiler is one trace per PROCESS: in a thread-isolation
        replica set a capture on any replica must 409 arms targeting
        its siblings — a second start_trace would crash the sibling's
        decode step mid-request."""
        import time as _time

        from dalle_pytorch_tpu.serve.server import InferenceServer
        params, vae_params = bundle
        srv = InferenceServer(params, vae_params, CFG, num_slots=2,
                              chunk_steps=4, decode_images=False,
                              replicas=2,
                              profile_dir=str(tmp_path / "prof"))
        srv.start()
        try:
            deadline = _time.perf_counter() + 120.0
            while _time.perf_counter() < deadline:
                if all(r.engine is not None
                       for r in srv.engine.replicas):
                    break
                _time.sleep(0.05)
            rec = srv.profile(replica=0)
            assert rec["kind"] == "serve_profile_armed"
            with pytest.raises(ProfileError) as ei:
                srv.profile(replica=1)
            assert ei.value.record["reason"] == "capture_active"
            assert ei.value.record["replica"] == 0
        finally:
            srv.close()

    def test_profile_without_dir_typed_reject(self, bundle):
        from dalle_pytorch_tpu.serve.server import InferenceServer
        params, vae_params = bundle
        srv = InferenceServer(params, vae_params, CFG, num_slots=2,
                              decode_images=False)
        try:
            with pytest.raises(ProfileError) as ei:
                srv.profile()
            assert ei.value.record["reason"] == "no_profile_dir"
        finally:
            srv.close()
