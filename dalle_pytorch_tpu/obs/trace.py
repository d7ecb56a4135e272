"""Per-request tracing: where did this request's milliseconds go?

One ``Trace`` per submitted request, carried on its ``RequestHandle``
(parent-side; a process worker builds a local stand-in trace whose spans
ship back with the result frame and merge into the parent's). The trace
is a TILING sequence of spans: every span starts exactly where the
previous one ended (``span(name, now)`` records ``[last_t, now)`` and
advances ``last_t``), so the sum of span durations reconstructs the
caller-observed latency — the acceptance contract the serve tests pin.

Span taxonomy (docs/OBSERVABILITY.md):

  ``submit``         zero-duration marker at queue admission
  ``queue_wait``     shared-queue (or single-engine queue) wait
  ``route``          zero-duration router hand-off (replica sets);
                     carries the replica index + weights_version
  ``prefill_admit``  pop -> admitted into a slot (cold bucket prefill
                     or warm prefix-cache admission; ``mode`` says which;
                     ``admit`` numbers the admission call that did it)
  ``decode_chunk``   one fused-K harvest's worth of emitted tokens
                     (``chunk`` numbers the dispatch it came from,
                     ``admits_ahead`` the admission calls queued in
                     front of it: the cause keys, shared with the engine
                     loop's annotations and its chunk ledger)
  ``evict``          paged-pool eviction marker (the request replays)
  ``replayed_from``  failover replay link: covers the FENCE GAP between
                     the victim's last progress and the re-queue, under
                     its own name — the gap is visible and labeled, not
                     silently absorbed into a work span
  ``postprocess``    VAE decode + CLIP scoring

Timestamps are ``perf_counter`` values supplied by the caller (the serve
clocks) — CLOCK_MONOTONIC on Linux, one epoch machine-wide, which is
what lets a child process's spans tile against the parent's on the same
host (serve/ipc.py's existing cross-process clock rule). Spans are plain
dicts of JSON scalars, so the socket transport round-trips them
byte-faithfully (ints verbatim, floats via repr).
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional

# span-record keys every consumer can rely on; everything else is
# per-span metadata (bucket, tokens, replica, reason, ...)
SPAN_KEYS = ("event", "span", "trace_id", "request_id", "attempt",
             "t0", "dur_s")


def new_trace_id(request_id: int) -> str:
    """Unique across replicas, restarts, and replays: the request id
    (unique per queue) plus entropy (unique across queues/restarts)."""
    return f"{int(request_id) & 0xFFFFFFFF:08x}-{os.urandom(6).hex()}"


class Trace:
    """Append-only span timeline for ONE request. Thread-safe: the
    router's control thread, an engine thread, and the postprocess
    worker all stamp the same trace at different lifecycle stages (and
    a fenced engine waking mid-step can race the replay)."""

    __slots__ = ("trace_id", "request_id", "attempt", "_spans",
                 "_last_t", "_lock")

    def __init__(self, trace_id: str, request_id: int, t0: float,
                 attempt: int = 0):
        self.trace_id = str(trace_id)
        self.request_id = int(request_id)
        self.attempt = int(attempt)
        self._spans: List[dict] = []
        self._last_t = float(t0)
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._spans)

    def span(self, name: str, now: float, **meta) -> dict:
        """Record the span [last span's end, ``now``) under ``name`` and
        advance the tiling pointer. Pure host work (one dict + one list
        append) — safe inside transfer-guarded serving loops."""
        with self._lock:
            rec = {"event": "span", "span": str(name),
                   "trace_id": self.trace_id,
                   "request_id": self.request_id,
                   "attempt": self.attempt,
                   "t0": self._last_t,
                   "dur_s": max(float(now) - self._last_t, 0.0)}
            rec.update(meta)
            self._spans.append(rec)
            self._last_t = float(now)
            return rec

    def has_in_attempt(self, name: str) -> bool:
        """Was ``name`` already stamped since the last replay? (The
        engine uses this to stamp ``queue_wait`` exactly once per
        attempt whether or not a router stamped it first.)"""
        with self._lock:
            for rec in reversed(self._spans):
                if rec["attempt"] != self.attempt:
                    break
                if rec["span"] == name:
                    return True
            return False

    def replay(self, now: float, reason: str = "", **meta) -> dict:
        """Mark a failover/scale-in replay: close the fence gap under
        the ``replayed_from`` span (its duration IS the gap — visible
        and labeled, never credited to decode) and open the next
        attempt. Returns the marker record (flight-recorder material)."""
        with self._lock:
            prev = self.attempt
            self.attempt = prev + 1
            rec = {"event": "span", "span": "replayed_from",
                   "trace_id": self.trace_id,
                   "request_id": self.request_id,
                   "attempt": self.attempt,
                   "from_attempt": prev,
                   "t0": self._last_t,
                   "dur_s": max(float(now) - self._last_t, 0.0),
                   "reason": str(reason)}
            rec.update(meta)
            self._spans.append(rec)
            self._last_t = float(now)
            return rec

    def wire_spans(self) -> List[dict]:
        """The spans as JSON-scalar dicts (they already are) — what a
        process worker attaches to the result frame. A snapshot copy:
        the worker may keep stamping while the frame encodes."""
        with self._lock:
            return [dict(rec) for rec in self._spans]

    def merge_wire(self, spans, now: float) -> int:
        """Absorb a child worker's spans into this (parent) trace and
        re-anchor the tiling pointer at ``now`` (the absorb time) so
        the next parent-side span — postprocess — tiles from here.
        Tolerant of malformed entries (observability must never fence a
        replica over an advisory field): non-dict or key-less entries
        are skipped, counted in the return value's complement."""
        merged = 0
        with self._lock:
            for rec in spans or ():
                if not isinstance(rec, dict) or "span" not in rec \
                        or "dur_s" not in rec:
                    continue
                rec = dict(rec)
                rec.setdefault("event", "span")
                rec["trace_id"] = self.trace_id
                self._spans.append(rec)
                merged += 1
            self._last_t = float(now)
        return merged

    def spans(self) -> List[dict]:
        with self._lock:
            return list(self._spans)

    def summary(self) -> dict:
        """The compact per-request record ``Result.trace`` (and the
        HTTP response) carries: spans aggregated by name in first-seen
        order, the replay edges, and the span-duration sum — which
        tiles back to the caller-observed latency (± the gaps a
        process boundary can't see; docs/OBSERVABILITY.md)."""
        with self._lock:
            order: List[str] = []
            agg: dict = {}
            replays: List[dict] = []
            total = 0.0
            for rec in self._spans:
                name = rec["span"]
                dur = float(rec["dur_s"])
                total += dur
                if name not in agg:
                    order.append(name)
                    agg[name] = {"name": name, "n": 0, "total_s": 0.0}
                agg[name]["n"] += 1
                agg[name]["total_s"] += dur
                if name == "replayed_from":
                    replays.append({
                        "from_attempt": int(rec.get("from_attempt", 0)),
                        "reason": rec.get("reason", ""),
                        "gap_s": round(dur, 6)})
            for name in order:
                agg[name]["total_s"] = round(agg[name]["total_s"], 6)
            return {"trace_id": self.trace_id,
                    "request_id": self.request_id,
                    "attempts": self.attempt + 1,
                    "replays": replays,
                    "spans": [agg[n] for n in order],
                    "span_total_s": round(total, 6)}


def attach(handle, request_id: int, now: float,
           trace_id: Optional[str] = None, attempt: int = 0) -> Trace:
    """Create and attach a trace to a handle (submit, or the child-side
    wire reconstruction). One definition site for the attach rule."""
    tr = Trace(trace_id or new_trace_id(request_id), request_id,
               t0=now, attempt=attempt)
    handle.trace = tr
    return tr
