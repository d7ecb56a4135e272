"""Serving-native observability (docs/OBSERVABILITY.md).

Three pieces, each deliberately dependency-free (no jax import — the
same lazy-import discipline as ``serve.scheduler`` and
``utils.metrics``, so every serve module can pull them before a backend
exists):

  * ``obs.trace`` — per-request span timelines: every submitted request
    gets a ``trace_id`` and a tiling sequence of ``perf_counter``-delta
    spans stamped at the existing serving seams (queue wait, route,
    prefill admission, per-chunk decode, postprocess); a span names
    what caused it: ``decode_chunk`` its ``chunk`` (the engine's
    dispatch number) and ``prefill_admit`` its ``admit`` (the admission
    call), the keys the engine loop's annotations and its chunk ledger
    (``Engine.loop_ring``) carry too. Failover replay
    LINKS rather than lies: the replay marker span covers the fence gap
    under its own name, so a kill shows up in the timeline as a visible
    labeled gap, never as fabricated decode time.
  * ``obs.flight`` — the flight recorder: a bounded ring of the last N
    structured events + span records per replica, ALWAYS on (no JSONL
    sink required), dumped into fence/abort event payloads and served
    at ``GET /debug/events``.
  * ``obs.registry`` — a small counter/gauge/histogram registry with
    Prometheus text exposition (``GET /metrics``), including the
    sliding-window latency histograms behind ``/stats``'s
    ``latency_ms`` percentiles.
"""

from dalle_pytorch_tpu.obs.flight import (  # noqa: F401
    FlightRecorder, RecordingMetrics)
from dalle_pytorch_tpu.obs.registry import (  # noqa: F401
    Histogram, LabeledHistogram, Registry)
from dalle_pytorch_tpu.obs.trace import Trace, new_trace_id  # noqa: F401
