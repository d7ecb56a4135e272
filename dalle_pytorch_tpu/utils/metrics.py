"""Training metrics: throughput counters + JSONL logging.

The reference's observability is bare ``print`` of per-interval batch loss
and per-epoch averages (reference trainVAE.py:98-102,116-117,
trainDALLE.py:201-210). SURVEY.md §5.5 asks the rebuild for real counters —
tokens/sec/chip is what a trainer pays for, so the training CLIs log it
per interval.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

# NOTE: jax is imported lazily inside MetricsLogger — ``structured_event``
# must be importable before any backend exists (resilience.retry emits
# bring-up failure records from a caller whose jax import must stay inside
# the deadline-bounded claim thread).


def structured_event(kind: str, **fields) -> dict:
    """The canonical resilience-event record: every failure/retry/rollback/
    preempt/resume event in the system is one of these, so benches and
    VERDICT can distinguish "stale because wedged" from "retried and
    recovered" by grepping one shape. ``kind`` ∈ {bringup_retry,
    bringup_failure, rollback, diverged, step_checkpoint, preempt_signal,
    preempted, resume, prefetch_bad_record, prefetch_restart, ...}."""
    # jaxlint: disable=JL007 — epoch timestamp in the event record, not
    # duration math (durations here always come from perf_counter deltas)
    return {"time": time.time(), "event": "resilience", "kind": kind,
            **fields}


class MetricsLogger:
    """Per-step metrics with wall-clock throughput, echoed to stdout and
    appended as JSONL (one object per log call) for post-hoc analysis."""

    def __init__(self, path: Optional[str] = None, log_interval: int = 10,
                 n_devices: Optional[int] = None):
        """``n_devices`` is the number of chips actually participating in
        the training mesh (NOT all local devices — a --dp subset must not
        deflate the per-chip rate). Defaults to jax.device_count()."""
        # multi-host: only process 0 prints and writes the JSONL (every
        # host sees the same replicated loss; racing appends interleave)
        import jax
        from dalle_pytorch_tpu.parallel.multihost import is_primary
        self.primary = is_primary()
        # the train loops feed host-LOCAL units; per-host work is equalized
        # by data.shard_for_host, so the global rate is local_rate × hosts
        self.process_count = jax.process_count()
        self.path = path if self.primary else None
        self.log_interval = log_interval
        self.n_devices = n_devices
        self._t_last = time.perf_counter()
        self._units_since = 0
        # one persistent handle behind one lock: the serving stack's
        # threads (engine loops, postprocess, supervisors, autoscaler)
        # all append structured events concurrently, and the old
        # per-call open(..., "a") raced them — two interleaved
        # buffered writes could tear a JSONL line. Flush per record
        # keeps the file current for live tail readers.
        self._lock = threading.Lock()
        self._fh = None
        if self.path:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)

    def _write(self, rec: dict) -> None:
        if not self.path:
            return
        with self._lock:
            if self._fh is None:
                self._fh = open(self.path, "a")
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def step(self, step: int, loss: float, *, epoch: Optional[int] = None,
             units: int = 0, unit_name: str = "tokens", **extra) -> None:
        """Call once per train step; prints/writes every ``log_interval``.
        ``units`` is the work done this step (tokens, images...)."""
        self._units_since += units
        if step % self.log_interval != 0:
            return
        import jax
        now = time.perf_counter()
        dt = max(now - self._t_last, 1e-9)
        rate = self._units_since / dt
        # rate is host-local, so the default denominator must be too —
        # jax.device_count() would understate per-chip by process_count
        n_dev = max(self.n_devices or jax.local_device_count(), 1)
        rec = {
            "step": step, "loss": float(loss),
            f"{unit_name}_per_sec": round(rate * self.process_count, 2),
            f"{unit_name}_per_sec_per_chip": round(rate / n_dev, 2),
            "time": time.time(),  # jaxlint: disable=JL007 — epoch stamp
        }
        if epoch is not None:
            rec["epoch"] = epoch
        rec.update(extra)
        self._t_last = now
        self._units_since = 0
        head = f"epoch {epoch} " if epoch is not None else ""
        if self.primary:
            print(f"{head}step {step}  loss {rec['loss']:.6f}  "
                  f"{rec[f'{unit_name}_per_sec_per_chip']:.1f} "
                  f"{unit_name}/s/chip", flush=True)
        self._write(rec)

    def event(self, **fields) -> None:
        """Free-form record (epoch summaries, checkpoint writes...)."""
        rec = {"time": time.time(), **fields}  # jaxlint: disable=JL007 — epoch stamp
        self._write(rec)

    def resilience(self, kind: str, **fields) -> None:
        """Structured failure/retry/rollback record — echoed to stdout
        (these are the events an operator must see even without a JSONL
        sink) and appended like any other event."""
        rec = structured_event(kind, **fields)
        if self.primary:
            detail = {k: v for k, v in rec.items()
                      if k not in ("time", "event")}
            print(f"[resilience] {json.dumps(detail)}", flush=True)
        self._write(rec)
