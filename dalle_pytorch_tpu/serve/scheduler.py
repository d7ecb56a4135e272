"""Request queue with admission control: priorities, deadlines,
backpressure.

The serving contract (docs/SERVING.md) is that overload is STRUCTURED:
a full queue rejects at submit time with a typed error carrying the same
``utils.metrics.structured_event`` record shape the resilience runtime
uses, and a request whose deadline passes — in the queue or mid-decode —
completes with a typed ``deadline_exceeded`` result. Nothing hangs,
nothing is silently dropped; every terminal state is one of
``Result.status``'s enumerated strings, observable both by the caller
(through ``RequestHandle.result``) and post-hoc (through the JSONL
metrics stream).

Ordering is (priority, arrival): lower ``priority`` values run first,
FIFO within a priority class. Deadlines do not reorder the queue — a
deadline is a promise about when a result stops being useful, not a
scheduling hint — they only gate admission to a slot.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import threading
import time
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from dalle_pytorch_tpu.obs import trace as otrace
from dalle_pytorch_tpu.utils.metrics import structured_event

# Result.status values — the full set of terminal request states.
OK = "ok"
REJECTED = "rejected"
DEADLINE_EXCEEDED = "deadline_exceeded"
CANCELLED = "cancelled"
ERROR = "error"


def prefill_buckets(text_seq_len: int) -> Tuple[int, ...]:
    """The default prompt-length buckets: powers of two up to (and always
    including) ``text_seq_len``. Admission pads every prompt up to its
    bucket, so the engine's prefill program compiles once per BUCKET for
    the engine's life — a small fixed set — instead of once per distinct
    prompt length seen (docs/SERVING.md "Prompt-length bucketing")."""
    if text_seq_len < 1:
        raise ValueError(f"text_seq_len must be >= 1, got {text_seq_len}")
    out: List[int] = []
    b = 1
    while b < text_seq_len:
        out.append(b)
        b *= 2
    out.append(text_seq_len)
    return tuple(out)


SMALL_PREFILL_GROUP = 4


def prefill_groups(num_slots: int) -> Tuple[int, ...]:
    """The row counts an admission's prefill program is compiled for: a
    small group and the whole of the slots. An engine in steady state
    admits one request as one slot frees (a guided pair two rows, a
    fan-out of four its four), and a ``num_slots``-row prefill for that
    one row is the dearest thing in an image (an admission of 461 ms
    against 60 at 32 slots of a 32-layer block, PERF.md section 6, PR 35);
    a cold start or a burst fills many slots at once and takes the whole
    group. Two shapes a bucket, both a pure function of ``num_slots``, so
    the set of programs stays small and fixed; with four slots or fewer
    there is one."""
    return tuple(sorted({min(SMALL_PREFILL_GROUP, num_slots), num_slots}))


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket holding a length-``n`` prompt (or, of
    ``prefill_groups``, an ``n``-row admission). ``buckets`` must be
    sorted ascending; raises for a prompt no bucket can hold (callers
    validate prompt length before bucketing)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds the largest bucket "
                     f"{buckets[-1]}")


def group_by_bucket(handles: Sequence["RequestHandle"],
                    buckets: Sequence[int]
                    ) -> Dict[int, List["RequestHandle"]]:
    """Bucket-aware admission grouping: handles keyed by the bucket their
    prompt pads up to, preserving pop order within a bucket. One prefill
    dispatch per KEY — bounded by ``len(buckets)``, not by the distinct
    prompt lengths seen."""
    groups: Dict[int, List[RequestHandle]] = defaultdict(list)
    for h in handles:
        groups[bucket_for(len(h.request.codes), buckets)].append(h)
    return groups


class ServeRejected(RuntimeError):
    """Typed submit-time rejection. ``record`` is the structured event
    (kind ``serve_reject``) describing why — the backpressure contract's
    machine-readable half."""

    def __init__(self, record: dict):
        super().__init__(f"{record.get('reason', 'rejected')} "
                         f"(queue_depth={record.get('queue_depth')})")
        self.record = record


class QueueFull(ServeRejected):
    """The bounded queue is at capacity — shed load at the edge instead
    of letting latency grow without bound."""


class InvalidRequest(ServeRejected):
    """The request can never run (empty prompt, or prompt longer than the
    model's text span) — rejected at submit so a malformed request cannot
    reach the engine, let alone take down its decode loop."""


class QueueClosed(ServeRejected):
    """The server is shutting down; a submit racing ``close()`` gets this
    typed reject instead of landing in a queue nobody will ever drain."""


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs — the same surface ``generate_images``
    exposes (models/dalle.py), carried per slot by the engine."""
    temperature: float = 1.0
    filter_thres: float = 0.5
    top_p: float = 0.0

    def __post_init__(self):
        if self.temperature <= 0:
            raise ValueError(f"temperature must be > 0, got "
                             f"{self.temperature}")
        if not 0.0 <= self.top_p <= 1.0:
            raise ValueError(f"top_p must be in [0, 1], got {self.top_p}")


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request: ``codes`` is the (unpadded) prompt token
    ids, exactly what ``generate_images`` takes as one text row.
    ``cfg_scale > 0`` asks for classifier-free guidance — the engine
    admits a cond/uncond slot pair and image tokens sample from
    ``l_u + cfg_scale * (l_c - l_u)``, exactly ``generate_images``'
    ``guidance`` knob (1.0 reduces to conditional sampling but still
    pays the pair; 0, the default, is off). ``tenant`` names the
    admitting tenant for weighted-fair queueing and per-tenant
    accounting — ``""`` (the default) is the anonymous tenant, which
    keeps single-tenant deployments byte-identical to before.

    ``stream`` marks the request as a live token stream: the engine
    pushes every harvested chunk into the handle's attached sink
    (serve/stream.py) as it lands, in addition to the terminal Result.
    ``n_samples > 1`` asks for a best-of-N sample GROUP — the serving
    tier fans the prompt out into N member requests with per-sample
    derived seeds (serve/fanout.py) and re-ranks the finished set by
    CLIP score; the field rides the wire so a gateway/transport hop
    can charge and route the whole group as one unit.
    ``image_seq_len_override`` (0 = off) caps the generated image span
    at fewer tokens than the model's full grid: decode stops once the
    override span is sampled, a train-free short-grid draft that rides
    the existing prefill buckets unchanged."""
    codes: Tuple[int, ...]
    seed: int = 0
    sampling: SamplingParams = SamplingParams()
    priority: int = 0                    # lower runs first
    deadline_s: Optional[float] = None   # relative to submit time
    cfg_scale: float = 0.0               # classifier-free guidance
    tenant: str = ""                     # admitting tenant (gateway)
    stream: bool = False                 # live token sink wanted
    n_samples: int = 1                   # best-of-N group size
    image_seq_len_override: int = 0      # 0 = full grid
    request_id: int = -1                 # assigned by the queue
    submit_t: float = 0.0                # perf_counter, set by the queue

    def __post_init__(self):
        if self.cfg_scale < 0:
            raise ValueError(f"cfg_scale must be >= 0, got "
                             f"{self.cfg_scale}")
        if self.n_samples < 1:
            raise ValueError(f"n_samples must be >= 1, got "
                             f"{self.n_samples}")
        if self.image_seq_len_override < 0:
            raise ValueError(f"image_seq_len_override must be >= 0, "
                             f"got {self.image_seq_len_override}")

    @property
    def deadline_t(self) -> Optional[float]:
        if self.deadline_s is None:
            return None
        return self.submit_t + self.deadline_s

    def to_wire(self, now: float) -> dict:
        """Flat-dict form for the process-isolation IPC (serve/ipc.py
        frames, versions, and checksums it). Two clocks never cross the
        boundary: the deadline ships as the REMAINING budget at send
        time (``perf_counter`` bases differ between processes), and the
        receiver re-anchors it on its own clock. Every field is a JSON
        scalar/list, so the round trip is exact — ints verbatim, floats
        via repr round-tripping — which is what lets a replayed request
        decode bit-identically on a survivor in another process."""
        return {
            "id": int(self.request_id),
            "codes": [int(c) for c in self.codes],
            "seed": int(self.seed),
            "priority": int(self.priority),
            "temperature": float(self.sampling.temperature),
            "filter_thres": float(self.sampling.filter_thres),
            "top_p": float(self.sampling.top_p),
            "deadline_left_s": (None if self.deadline_s is None
                                else max(self.deadline_t - now, 0.0)),
            "cfg_scale": float(self.cfg_scale),
            "tenant": str(self.tenant),
            "stream": bool(self.stream),
            "n_samples": int(self.n_samples),
            "image_seq_len_override": int(self.image_seq_len_override),
        }

    @classmethod
    def from_wire(cls, d: dict, now: float) -> "Request":
        """Inverse of ``to_wire``, validating by construction (the
        ``SamplingParams`` range checks run again on this side — a
        corrupt frame becomes a typed error, never a poisoned engine).
        ``submit_t`` is re-anchored to the receiver's clock."""
        deadline = d["deadline_left_s"]
        return cls(
            codes=tuple(int(c) for c in d["codes"]),
            seed=int(d["seed"]),
            sampling=SamplingParams(
                temperature=float(d["temperature"]),
                filter_thres=float(d["filter_thres"]),
                top_p=float(d["top_p"])),
            priority=int(d["priority"]),
            deadline_s=None if deadline is None else float(deadline),
            # .get: frames from a pre-guidance peer simply decode as
            # unguided instead of failing the whole attach
            cfg_scale=float(d.get("cfg_scale", 0.0)),
            # .get: pre-tenancy frames decode as the anonymous tenant
            tenant=str(d.get("tenant", "")),
            # .get x3: pre-streaming frames decode as plain one-shot
            # full-grid requests — the same tolerance rule as above
            stream=bool(d.get("stream", False)),
            n_samples=int(d.get("n_samples", 1)),
            image_seq_len_override=int(
                d.get("image_seq_len_override", 0)),
            request_id=int(d["id"]),
            submit_t=float(now))


@dataclasses.dataclass
class Result:
    """Terminal state of a request. ``tokens`` is the sampled image-token
    sequence (image ids, no text offset — ``generate_images``'s
    ``img_seq``); ``text_tokens`` is the COMPLETED text span (the prompt
    plus the model-sampled text tokens filling it out to ``text_seq_len``
    — ``generate_images``'s ``full[:, :text_seq_len]``), what CLIP
    rerank scores; ``image`` is filled by the postprocess stage when
    image decoding is enabled. ``weights_version`` names the weight
    generation that produced the tokens (stamped by the engine that
    decoded them) — the rolling-upgrade contract is that same-seed
    tokens are byte-identical PER weights_version, so a caller or a
    replay audit can always tell which generation a result came from."""
    status: str
    request_id: int
    tokens: object = None
    text_tokens: object = None
    image: object = None
    clip_score: Optional[float] = None
    reason: str = ""
    weights_version: str = ""
    queued_s: float = 0.0
    decode_s: float = 0.0
    total_s: float = 0.0
    # the trace summary (obs/trace.py): span timeline aggregated by
    # name + replay edges. Attached by RequestHandle.fulfill from the
    # handle's trace — never crosses the wire itself (a child's spans
    # ride the result frame raw; the parent re-summarizes its merged
    # trace, so the summary always describes the CALLER's timeline)
    trace: Optional[dict] = None
    # best-of-N group assembly (serve/fanout.py): the member Results
    # ranked best-first by CLIP score. Parent-side only — members
    # cross the wire individually; the group is re-assembled wherever
    # the caller's GroupFuture lives, so this never ships in a frame
    samples: Optional[list] = None

    @property
    def ok(self) -> bool:
        return self.status == OK

    def to_wire(self) -> dict:
        """Flat-dict form for the process-isolation IPC. Token arrays
        ship as plain int lists; ``image``/``clip_score`` never cross
        the boundary (the child runs decode only — VAE/CLIP postprocess
        stays in the parent, downstream of the fulfilled handle)."""
        return {
            "id": int(self.request_id),
            "status": str(self.status),
            "tokens": (None if self.tokens is None
                       else [int(t) for t in self.tokens]),
            "text_tokens": (None if self.text_tokens is None
                            else [int(t) for t in self.text_tokens]),
            "reason": str(self.reason),
            "weights_version": str(self.weights_version),
            "queued_s": float(self.queued_s),
            "decode_s": float(self.decode_s),
            "total_s": float(self.total_s),
        }

    @classmethod
    def from_wire(cls, d: dict) -> "Result":
        status = str(d["status"])
        if status not in (OK, REJECTED, DEADLINE_EXCEEDED, CANCELLED,
                          ERROR):
            raise ValueError(f"unknown Result.status {status!r}")
        import numpy as np
        toks = d["tokens"]
        text = d["text_tokens"]
        return cls(
            status=status, request_id=int(d["id"]),
            tokens=None if toks is None else np.asarray(
                [int(t) for t in toks], np.int32),
            text_tokens=None if text is None else np.asarray(
                [int(t) for t in text], np.int32),
            reason=str(d["reason"]),
            # .get: frames from a pre-upgrade peer decode as unversioned
            # instead of failing the attach (Request.from_wire's rule)
            weights_version=str(d.get("weights_version", "")),
            queued_s=float(d["queued_s"]),
            decode_s=float(d["decode_s"]),
            total_s=float(d["total_s"]))


class RequestHandle:
    """Future for one request: ``result(timeout)`` blocks until the
    engine/postprocess fulfils it. Always fulfilled with a ``Result`` —
    including rejects and expiries — so callers never hang on overload.

    ``fulfill`` is FIRST-WRITE-WINS: replica failover re-queues a fenced
    replica's in-flight requests for deterministic replay on a survivor,
    so two engines can transiently both believe they own a handle (the
    wedged one waking mid-step, and the replay). The first terminal
    result sticks; a late second fulfil is a no-op, never an overwrite
    of a result the caller may already have read."""

    def __init__(self, request: Request):
        self.request = request
        self._done = threading.Event()
        self._result: Optional[Result] = None
        self._fulfill_lock = threading.Lock()
        # the request's span timeline (obs/trace.py), attached at
        # submit (None for hand-built handles — canaries, raw-queue
        # tests — which trace nothing)
        self.trace: Optional[otrace.Trace] = None
        # arrival order within the priority class, assigned at submit;
        # requeue (eviction/page-defer) re-inserts with the SAME seq so
        # a request never loses its place in line — without this, a
        # large-prompt request deferred on pages would re-enter behind a
        # steady stream of small requests and could starve forever
        self.queue_seq: int = -1
        # the weights generation this request first routed to (set by
        # the replica-set router, parent-side only — it never crosses
        # the wire because reclaim always reads the parent's handle).
        # While pinned, failover replay routes ONLY to a replica on the
        # same version: replayed tokens must be byte-identical to the
        # undisturbed run, and a newer generation's logits are not.
        # None = unpinned (fresh request, or pin released because the
        # version left the fleet entirely — see replica._route).
        self.replay_version: Optional[str] = None
        # weighted-fair queueing tags (WeightedFairQueue): the virtual
        # start/finish stamps assigned ONCE at submit and reused by
        # every requeue — a request's place in the fair order, like its
        # queue_seq, must survive eviction/failover replay unchanged or
        # determinism (and the no-starvation argument) breaks
        self.vstart: Optional[float] = None
        self.vfinish: Optional[float] = None
        # live token sink (serve/stream.py TokenSink), attached by the
        # server when request.stream is set. None for everything else —
        # the engine's harvest feeds it when present and never blocks
        # on it. Parent-side only: a process-isolation stand-in handle
        # has no sink, which is why streaming there is a typed reject.
        self.sink = None

    def done(self) -> bool:
        return self._done.is_set()

    def fulfill(self, result: Result) -> bool:
        with self._fulfill_lock:
            if self._done.is_set():
                return False
            if self.trace is not None and result.trace is None:
                # the ONE summary site: every terminal path (completion,
                # postprocess, expiry, cancellation, failover replay)
                # funnels through fulfill, so the caller always sees
                # the timeline that actually produced its result
                result.trace = self.trace.summary()
            self._result = result
            self._done.set()
        # outside the lock: closing the stream sink can wake a consumer
        # thread that immediately calls back into handle methods — and
        # fulfill is the ONE terminal funnel, so every path (completion,
        # postprocess, expiry, error, cancel) ends the stream exactly
        # once. A sink failure must never lose the result itself.
        if self.sink is not None:
            try:
                self.sink.close(result)
            except Exception:
                pass
        return True

    def result(self, timeout: Optional[float] = None) -> Result:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request.request_id} not done after "
                f"{timeout}s (still queued or decoding)")
        return self._result

    def to_wire(self, now: float) -> dict:
        """The request's wire form plus the handle-level ``queue_seq`` —
        the original arrival position MUST survive the process boundary,
        or a request reclaimed from a dead child and replayed would lose
        its no-starvation guarantee (``requeue`` re-enters at
        ``queue_seq``). The trace identity (id + attempt) rides along so
        the child's span records carry the SAME trace_id the caller's
        timeline is keyed by."""
        d = {**self.request.to_wire(now), "seq": int(self.queue_seq)}
        if self.trace is not None:
            d["trace_id"] = self.trace.trace_id
            d["attempt"] = int(self.trace.attempt)
        return d

    @classmethod
    def from_wire(cls, d: dict, now: float) -> "RequestHandle":
        """Child-side reconstruction: a LOCAL stand-in handle whose
        fulfillment the worker observes and ships back as a result
        frame — the parent's real handle (the caller's future) never
        leaves the parent process. The stand-in gets its own trace
        under the wire's trace_id/attempt: its spans ship back with
        the result and merge into the parent trace (.get: frames from
        a pre-tracing peer simply decode traceless)."""
        handle = cls(Request.from_wire(d, now))
        handle.queue_seq = int(d["seq"])
        tid = d.get("trace_id")
        if tid is not None:
            otrace.attach(handle, handle.request.request_id, now,
                          trace_id=str(tid),
                          attempt=int(d.get("attempt", 0)))
        return handle


class RequestQueue:
    """Bounded, thread-safe priority queue.

    ``submit`` raises ``QueueFull`` past ``max_depth`` (the structured
    reject), ``InvalidRequest`` for a prompt the engine could never run
    (empty, or longer than ``max_prompt_len`` when one is set — the
    server sets it to ``cfg.text_seq_len``), and ``QueueClosed`` after
    ``close()``; ``pop_ready`` hands the engine up to ``n`` admissible
    requests in (priority, arrival) order, separating out entries whose
    deadline already passed so the engine can fulfil them as
    ``deadline_exceeded`` without spending a slot."""

    def __init__(self, max_depth: int = 64,
                 max_prompt_len: Optional[int] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 on_event=None):
        self.max_depth = int(max_depth)
        self.max_prompt_len = max_prompt_len
        self.clock = clock
        self.on_event = on_event
        self._heap: list = []
        self._seq = itertools.count()
        self._lock = threading.Lock()
        self._closed = False
        self._drained = False
        self.submitted = 0
        self.rejected = 0
        self.requeued = 0

    def depth(self) -> int:
        with self._lock:
            return len(self._heap)

    def _order_key(self, handle: RequestHandle):
        """The heap's primary sort key for one handle, computed under
        ``_lock``. The base queue orders by priority alone (FIFO within
        a class via ``queue_seq``, the tuple's second element);
        ``WeightedFairQueue`` overrides this with (priority, virtual
        finish time). MUST be stable across requeues of the same handle
        — a request's place in line is part of the replay contract."""
        return handle.request.priority

    def _on_pop(self, handle: RequestHandle) -> None:
        """Hook called under ``_lock`` for each handle handed to the
        engine by ``pop_ready`` — ``WeightedFairQueue`` advances the
        system virtual clock here. Base queue: no-op."""

    def close(self) -> None:
        """Gate further ``submit``s (typed ``QueueClosed``). Set BEFORE
        the shutdown drain so a submit racing ``close()`` cannot land in
        the queue after the drain and leave its caller blocked."""
        with self._lock:
            self._closed = True

    def _reject(self, exc_type, **fields):
        self.rejected += 1
        record = structured_event("serve_reject", **fields)
        if self.on_event is not None:
            self.on_event(record)
        raise exc_type(record)

    def submit(self, request: Request, sink=None) -> RequestHandle:
        """``sink`` (serve/stream.py TokenSink) must be attached HERE,
        under the same lock that publishes the handle to the heap — an
        attach after submit returns would race the engine thread, which
        can pop, prefill, and harvest the first chunk before the caller
        runs again, silently losing the stream's opening tokens."""
        now = self.clock()
        with self._lock:
            if self._closed:
                self._reject(QueueClosed, reason="queue_closed",
                             queue_depth=len(self._heap),
                             priority=request.priority)
            n_codes = len(request.codes)
            if n_codes == 0 or (self.max_prompt_len is not None
                                and n_codes > self.max_prompt_len):
                self._reject(InvalidRequest, reason="invalid_prompt",
                             prompt_len=n_codes,
                             max_prompt_len=self.max_prompt_len,
                             queue_depth=len(self._heap),
                             priority=request.priority)
            if len(self._heap) >= self.max_depth:
                self._reject(QueueFull, reason="queue_full",
                             queue_depth=len(self._heap),
                             max_depth=self.max_depth,
                             priority=request.priority)
            rid = self.submitted
            self.submitted += 1
            request = dataclasses.replace(request, request_id=rid,
                                          submit_t=now)
            handle = RequestHandle(request)
            handle.queue_seq = next(self._seq)
            handle.sink = sink
            # every submitted request is traced (obs/trace.py): the
            # zero-duration submit marker anchors the tiling timeline
            # at the exact instant the caller's latency clock starts
            otrace.attach(handle, rid, now).span(
                "submit", now, priority=int(request.priority),
                prompt_len=len(request.codes))
            heapq.heappush(self._heap, (self._order_key(handle),
                                        handle.queue_seq, handle))
            return handle

    def requeue(self, handle: RequestHandle, count: bool = True) -> None:
        """Push an already-admitted request BACK into the queue — the
        paged engine's eviction/page-backpressure path and replica
        failover's reclaim path (a victim's pages are freed, or its dead
        replica fenced, and the request re-enters the line, never
        dropped). The handle and its original ``submit_t`` are
        preserved, so the caller's future stays live and latency
        accounting covers both attempts. Deliberately not subject to
        ``max_depth`` (the request already passed admission once;
        shedding it here would turn backpressure into a silent drop)
        nor to ``close()`` gating. It re-enters at its ORIGINAL arrival
        position (``queue_seq``), not the back of its priority class:
        together with the engine's head-of-line page reservation this
        is what makes 'no request starves forever' true — later-
        arriving requests can never leapfrog a page-deferred one
        indefinitely. A requeue landing AFTER the shutdown drain
        fulfils the handle as ``cancelled`` on the spot: the heap is
        dead by then, nobody would ever pop it, and leaving it there
        would strand the caller in ``result()``.

        ``count=False`` is the replica-set router's hand-off into a
        replica's private queue — a normal dispatch, not backpressure —
        so ``requeued`` keeps meaning evictions/deferrals/failovers."""
        with self._lock:
            if self._drained:
                handle.fulfill(Result(
                    status=CANCELLED,
                    request_id=handle.request.request_id,
                    reason="server shutdown"))
                return
            if any(entry[2] is handle for entry in self._heap):
                # already back in line: the failover reclaim sweep and a
                # fenced engine waking from a wedge can both try to
                # return the same handle — a double entry would admit
                # (and decode) the request twice
                return
            if count:
                self.requeued += 1
            heapq.heappush(self._heap, (self._order_key(handle),
                                        handle.queue_seq, handle))

    def pop_ready(self, n: int,
                  now: Optional[float] = None
                  ) -> Tuple[List[RequestHandle], List[RequestHandle]]:
        """Up to ``n`` (ready, expired) handles. EVERY deadline-expired
        queued entry is reaped on every call — including ``n == 0`` (a
        full slot pool): a dead entry must neither hold queue capacity
        against fresh submissions nor wait for a free slot to receive its
        typed deadline_exceeded result."""
        if now is None:
            now = self.clock()
        ready: List[RequestHandle] = []
        dead: list = []
        with self._lock:
            keep = []
            for entry in self._heap:          # reap expired everywhere
                dt = entry[2].request.deadline_t
                (dead if dt is not None and now > dt
                 else keep).append(entry)
            if dead:
                heapq.heapify(keep)
                self._heap = keep
            while self._heap and len(ready) < n:
                popped = heapq.heappop(self._heap)[2]
                self._on_pop(popped)
                ready.append(popped)
        return ready, [e[2] for e in dead]

    def pending_prompt_lens(self) -> List[int]:
        """Prompt lengths of everything currently queued — the engine's
        ``compile_pending`` probe (is any queued prompt's bucket still
        uncompiled?) without reaching into the heap layout."""
        with self._lock:
            return [len(entry[2].request.codes) for entry in self._heap]

    def pending_prompt_codes(self) -> List[Tuple[Tuple[int, ...], float]]:
        """(codes, cfg_scale) of everything currently queued — the
        prefix-cache half of the engine's ``compile_pending`` probe
        (could a queued prompt be the first WARM admission, whose
        program has its own one-time compile?)."""
        with self._lock:
            return [(entry[2].request.codes, entry[2].request.cfg_scale)
                    for entry in self._heap]

    def drain(self) -> List[RequestHandle]:
        """Remove and return everything still queued (shutdown path — the
        server fulfils them as ``cancelled``). After the drain the heap
        is dead: a late ``requeue`` (e.g. an engine thread that outlived
        ``close()``'s join timeout evicting a victim) is fulfilled as
        ``cancelled`` instead of being stranded."""
        with self._lock:
            self._drained = True
            out = [h for _, _, h in self._heap]
            self._heap.clear()
        return out


class WeightedFairQueue(RequestQueue):
    """Start-time fair queueing (SFQ) across tenants, generalizing the
    base queue's arrival-position machinery to per-tenant VIRTUAL time.

    Each tenant ``i`` with weight ``w_i`` keeps a running finish tag;
    a request costing ``c`` (default 1.0 — fair in requests; pass
    ``cost_fn`` for fair-in-image-tokens) is stamped at submit with

        vstart  = max(V, F_i)          # V = system virtual time
        vfinish = vstart + c / w_i     # F_i := vfinish

    and the heap drains by (priority, vfinish, queue_seq): strict
    priority classes still dominate (the base queue's contract), and
    WITHIN a class tenants share throughput in proportion to their
    weights — a weight-2 tenant's tags advance half as fast as a
    weight-1 tenant's, so under saturation it drains twice the work.
    ``V`` advances to the popped request's vstart, and the ``max(V,
    F_i)`` clamp is both fairness directions at once: a tenant idle
    while others ran resumes at ``V`` (no banked credit from the past),
    and a tenant whose backlog pushed ``F_i`` far ahead of ``V`` owes
    nothing once it drains — next submit after ``V`` catches up starts
    at ``V``. No permanent debt, no permanent credit.

    Tags are stamped ONCE (cached on the handle) so a requeue —
    eviction, page-deferral, failover replay — re-enters at the
    request's ORIGINAL virtual position, exactly as ``queue_seq``
    preserves arrival order in the base queue. Determinism of replay
    and the no-starvation argument are inherited unchanged."""

    def __init__(self, max_depth: int = 64,
                 max_prompt_len: Optional[int] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 on_event=None,
                 weight_of: Optional[Callable[[str], float]] = None,
                 cost_fn: Optional[Callable[[Request], float]] = None):
        super().__init__(max_depth=max_depth,
                         max_prompt_len=max_prompt_len,
                         clock=clock, on_event=on_event)
        self.weight_of = weight_of if weight_of is not None \
            else (lambda tenant: 1.0)
        self.cost_fn = cost_fn if cost_fn is not None \
            else (lambda request: 1.0)
        self._vtime = 0.0
        self._ftime: Dict[str, float] = {}

    def _order_key(self, handle: RequestHandle):
        if handle.vfinish is None:       # stamp once, at first insert
            tenant = handle.request.tenant
            weight = max(float(self.weight_of(tenant)), 1e-9)
            vstart = max(self._vtime, self._ftime.get(tenant, 0.0))
            handle.vstart = vstart
            handle.vfinish = vstart + \
                float(self.cost_fn(handle.request)) / weight
            self._ftime[tenant] = handle.vfinish
        return (handle.request.priority, handle.vfinish)

    def _on_pop(self, handle: RequestHandle) -> None:
        if handle.vstart is not None:
            self._vtime = max(self._vtime, handle.vstart)

    def virtual_time(self) -> float:
        with self._lock:
            return self._vtime

    def finish_tag(self, tenant: str) -> float:
        """The tenant's last virtual finish tag (0.0 if never seen) —
        the observability hook the starvation tests pin: a tag at or
        below ``virtual_time()`` means the tenant carries no debt."""
        with self._lock:
            return self._ftime.get(tenant, 0.0)
