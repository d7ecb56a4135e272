"""Slot-pool continuous-batching decode engine, device-resident.

The one-shot path (``cli/gen_dalle.py`` -> ``models.dalle.generate_images``)
pays full compile + prefill + ~1024 sequential decode steps PER REQUEST,
with no batching across requests. This engine is the serving answer: a
fixed ``[num_slots]`` decode batch compiled ONCE, where requests join and
leave via masking (the slot-based continuous batching standard on TPU —
PAPERS.md "Ragged Paged Attention", "Serving Gemma on Cloud TPU"), and —
since one host round-trip per decode step is the dominant non-compute
cost on a real chip — a steady-state loop the host is NOT in:

  * ALL per-slot decode state lives on device: ``cur_tok``, ``pos``, an
    ``active`` mask, per-slot RNG keys, temperature, top-k and top-p,
    plus the slot-pool KV cache (``ops.decode.init_cache`` at
    batch = num_slots). The host keeps only request bookkeeping
    (``_Slot``: handle, emitted-so-far, timestamps);
  * the steady-state program is ``chunk_steps`` (K) decode steps FUSED
    into one jitted ``lax.scan`` (``ops.decode.decode_loop``) that
    writes each step's emitted tokens into a device-side
    ``[num_slots, K]`` emit ring. The engine dispatches chunk programs
    back-to-back and harvests a chunk's ring with a single
    ``jax.device_get`` one chunk LATER (double-buffered: the blocking
    get on chunk N overlaps the device computing chunk N+1), so ~1024
    blocking syncs per request become ~1024/K overlapped ones;
  * a slot that emits its last token deactivates itself INSIDE the fused
    program (it keeps computing into a dead mask, parked at pos 0,
    until the harvest notices) — finished-slot detection costs no
    mid-chunk sync. Completion, and therefore the request's latency, is
    timestamped at harvest (what the caller actually observes; a request
    can wait up to K-1 dead steps plus one in-flight chunk for it —
    docs/SERVING.md "Choosing K");
  * admission pads prompts up to a small fixed set of BUCKET lengths
    (``scheduler.prefill_buckets``) and prefills the smallest GROUP of
    rows that holds the need (``scheduler.prefill_groups``: a small
    group or all ``num_slots``; unused rows scatter to a dropped
    out-of-range slot index), so prefill compiles exactly once per
    bucket and group for the engine's life — asserted by tests through
    ``analysis.guards.compile_count``. Padding is causal-safe: cache
    rows [0, t0) and the first sampled token depend only on positions
    < t0, and every padded garbage row [t0, bucket) is overwritten by
    the decode step for that position before any later step can attend
    to it.

Equivalence contract (tests/test_serve.py pins it): for the same params /
prompt / seed / sampling knobs, a slot's emitted image tokens are
IDENTICAL to ``generate_images`` at batch 1 — for every chunk size K —
because the fused loop reuses ``decode_token_embed`` / ``to_logits`` /
``models.dalle.sample_per_slot`` (the per-slot traced-parameter form of
the one-shot sampler's filters) with the same
``fold_in(request_rng, position)`` key discipline, and K only changes
where the host reads the stream, never what the device computes.

KV layouts (``kv=``): the default ``"dense"`` slot pool reserves
``num_slots × seq_len`` KV rows up front; ``"paged"`` replaces it with a
shared page pool + per-slot block tables (``serve/kv_pool.py``,
``ops.decode.decode_loop_paged``) so HBM residency tracks where requests
actually ARE in their sequences, not where they could end up — the same
budget sustains strictly more concurrent requests (tests/test_serve.py
holds it). Pages are allocated at admission for the prompt span, grown ahead
of each fused chunk as ``pos`` crosses page boundaries, and freed at
completion/expiry/eviction; when the pool runs dry mid-decode the
lowest-priority active request is EVICTED back to the queue (typed
``PagePoolExhausted`` path — pages freed, request re-queued, its handle
preserved; deterministic sampling replays its exact tokens on
re-admission). The steady-state loop stays in the identical one-compile,
transfer-clean, emit-ring regime: the only paged-specific host traffic
is an explicit ``device_put`` of the tiny block table when it changes.

Cross-request prefix cache (``prefix_cache=True``, paged only): prompt
KV pages become a refcounted, copy-on-write, content-addressed resource
(``serve/prefix_cache.py`` + the refcounted ``PageAllocator``). On
admission, a prompt whose key is indexed takes the WARM path: the
entry's full prompt pages map straight into the new slot's block table
(refcount++ — zero prefill FLOPs, zero new pages for the shared span),
the partial boundary page is forked copy-on-write from the entry's
device snapshot into one private page, and the first token is sampled
from the entry's cached last hidden row by a tiny warm-admission
program (one ``to_logits`` + per-slot sample, compiled once, ever).
Sharing is read-only BY CONSTRUCTION: shared pages lie wholly below the
prompt length t0, and decode only ever appends at positions >= t0 —
asserted at every warm mapping. Slot teardown releases references;
pages return to the free list only at refcount zero, so an eviction
victim can never hand a sibling's mapped page to the next allocation.
Under page pressure the LRU end of the index is dropped BEFORE any live
request is evicted.

Per-request classifier-free guidance (``Request.cfg_scale > 0``): the
request admits a cond/uncond SLOT PAIR — the uncond member is a shadow
slot running the all-PAD null caption — and the guided logit mix
``l_u + scale * (l_c - l_u)`` is folded into the fused decode program
itself (``models.dalle.sample_per_slot``'s partner/cfg_scale/uncond
arguments), so ``decode_traces == 1`` still holds and the pair's tokens
are byte-identical to ``generate_images(guidance=scale)``. With the
prefix cache on, the pair shares every cacheable prompt span physically
(the null caption is ONE entry shared by all guided requests of a given
prompt length) and diverges copy-on-write only over the generated span
— which is what makes per-request guidance affordable: < 2x pages, not
2x everything.

Not supported per-request: padded prompt masks (requests carry unpadded
codes, gen_dalle's default mode).

The engine is deliberately single-threaded and drivable iteration-by-
iteration (``step_once`` = expire/admit/dispatch-one-chunk/harvest-one)
so tests and the bench can run it deterministically; ``serve.server``
wraps it in a thread for live traffic.
"""

from __future__ import annotations

import gc
import statistics
import threading
import time
import weakref
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from dalle_pytorch_tpu.serve import scheduler as S

# the engine's lifetime counters, as one tuple so every aggregation
# site — the replica set's retired-counter fold, the IPC heartbeat
# snapshot a child worker ships, the parent-side client's mirror —
# reads the SAME set and cannot drift from stats()
COUNTERS = ("tokens_decoded", "decode_steps", "harvests",
            "occupancy_sum", "completed", "expired",
            "decode_traces", "prefill_traces", "evicted",
            "prefix_hits", "cfg_pairs", "reaped",
            "chunks_behind_admit", "loop_stalls")

# the engine loop's cumulative seconds (stats(), /metrics): always on,
# sums of the chunk ledger's laps (LOOP_PHASES). engine_loop_s >=
# harvest_wait_s + admit_s + deliver_s, and admit_prefill_s is a part of
# admit_s. They answer, with no profiler: does the engine thread wait for
# the device (harvest_wait_s near engine_loop_s) or the device for the
# engine thread. loop_stall_s: the seconds by which stalled chunks
# overran their class's median (STALL_FACTOR)
LOOP_SECONDS = ("engine_loop_s", "harvest_wait_s", "admit_s",
                "admit_prefill_s", "deliver_s", "loop_stall_s")

# the chunk ledger (Engine.loop_ring, docs/OBSERVABILITY.md): every
# second of the loop thread lies in exactly one phase (Engine._lap: one
# clock read a boundary), and each names the cumulative LOOP_SECONDS it
# also counts in. tail_s is a step's end after its last harvest: the
# periodic serve record, a capture's close, and the interpreter handed to
# the client threads that the delivery woke. between_s is everything
# outside step_once: the run loop's turn-round and nap, the step lock, an
# iteration that found nothing to do, and whatever held the interpreter
LOOP_PHASES = {
    "expire_s": ("engine_loop_s",),
    "admit_plan_s": ("engine_loop_s", "admit_s"),
    "admit_put_s": ("engine_loop_s", "admit_s"),
    "admit_prefill_s": ("engine_loop_s", "admit_s", "admit_prefill_s"),
    "dispatch_s": ("engine_loop_s",),
    "harvest_wait_s": ("engine_loop_s", "harvest_wait_s"),
    "deliver_s": ("engine_loop_s", "deliver_s"),
    "tail_s": ("engine_loop_s",),
    "between_s": (),
}
LOOP_ROWS = 512         # loop_ring: 40-100 s of chunks
# a chunk whose interval exceeds STALL_FACTOR x the median of the last
# STALL_WINDOW judged intervals of its class (behind an admission or
# not) is a stall, once the class holds STALL_MIN of them
STALL_FACTOR = 3
STALL_WINDOW = 64
STALL_MIN = 8
STALLS_KEPT = 8         # stats()["last_stalls"]

# the routed layers' load of a described block (stats() of such an
# engine), summed over its routed layers and every decode step: what the
# fused decode program returns beside the ring, in the order it stacks
# them (ops.moe.dropless_apply): token-picks routed, experts that received
# one, the fullest expert's picks, the reads of an expert's weights (the
# (row tile, expert) pairs that hold a row, ops.moe.dropless_experts: the
# experts touched where the products run as one tile, or in the repo's
# kernel a whole expert a grid step; FOURTH, so that the slice below ends
# a block that holds every expert there) and, where the
# block holds a share of its experts, the picks that fell on the share and
# the rows its grouped products were handed (ops.moe.row_ladder)
MOE_COUNTERS = ("moe_picks", "moe_experts_touched", "moe_load_max",
                "moe_group_reads", "moe_picks_held", "moe_rows_computed")
# of a block whose window layers' softmax holds a learned sink
# (ops.transformer.WindowGQABlock.sink): the sink's softmax weight summed
# over the window layers, the query heads, the active slots and every
# decode step, and the number of softmaxes it is summed over: the last two
# numbers of such a block's load (ops.decode.decode_step_block)
SINK_COUNTERS = ("window_sink_mass", "window_sink_reads")


def load_counters(blk) -> tuple:
    """The names of a described block's load, in the order in which its
    decode program returns them after the ring (the first four of
    ``MOE_COUNTERS``, ``moe_group_reads`` the last of them, where the
    block holds every expert; ``moe_picks_held`` and ``moe_rows_computed``
    too where it holds a share; the sink's two only where it has one)."""
    from dalle_pytorch_tpu.ops.moe import load_width
    return MOE_COUNTERS[:load_width(blk)] + (
        SINK_COUNTERS if blk.sink else ())


def _phase(name: str, **meta):
    """One phase of the engine loop as a ``jax.profiler.TraceAnnotation``:
    an atomic read when no capture runs; inside one (``POST
    /admin/profile``, ``--profile_dir``, the benchmark's traced window)
    a host event on the clock the device's ``XLA Ops`` are on. The
    names are the span tree of docs/OBSERVABILITY.md."""
    import jax
    return jax.profiler.TraceAnnotation(name, **meta)


def _watch_collector(engine) -> list:
    """``[runs, seconds]`` of the interpreter's garbage collector since
    ``engine`` was built, kept by a ``gc.callbacks`` hook that takes
    itself off the list once the engine is gone. A collection stops
    every thread, the engine's too: a stall record says how much of its
    interval was one."""
    seen, t0, alive = [0, 0.0], [0.0], weakref.ref(engine)

    def hook(phase, info):
        if phase == "start":
            t0[0] = time.perf_counter()
        elif alive() is None:
            gc.callbacks.remove(hook)
        else:
            seen[0] += 1
            seen[1] += time.perf_counter() - t0[0]

    gc.callbacks.append(hook)
    return seen


class ProfileError(RuntimeError):
    """Typed rejection of a serve-side profiler capture request
    (``Engine.request_profile`` / ``POST /admin/profile``): a capture
    is already active (jax.profiler allows exactly one trace at a
    time), or the target replica cannot be profiled (a child-process
    engine's programs run in another interpreter). ``record`` is the
    structured event — the HTTP facade maps ``capture_active`` to a
    409, mirroring ``replica.ScaleError``."""

    def __init__(self, record: dict):
        super().__init__(f"{record.get('reason', 'profile rejected')}")
        self.record = record


class MigrationError(RuntimeError):
    """Typed failure of a live slot migration (export or import) — the
    signal that flips the replica set from "move the KV pages" to the
    replay fallback (requeue + deterministic re-decode from token
    zero), never a dropped request. ``reason`` is a short machine slug
    (``kv_dense``, ``not_found``, ``fenced``, ``weights_version``,
    ``page_size``, ``layout``, ``target_slots``, ``target_pages``,
    ``source_dead``, ``target_dead``, ``transfer``) the structured
    ``serve_migrate_fallback`` event carries."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(f"migration failed ({reason})"
                         + (f": {detail}" if detail else ""))
        self.reason = reason


# what a MIGRATE payload's page snapshots hold: 2 = a page of whole rows
# (``kv_pool.page_layout``: (depth, page_size, heads * dim_head)); 1 was
# the page per head, (depth, heads, page_size, dim_head)
MIGRATE_FORMAT = 2


def _pack_array(a) -> dict:
    """One host array as a JSON-safe dict (dtype/shape/base64 bytes) —
    the page-snapshot wire form MIGRATE frames carry. Exact: raw bytes,
    no float text round-trip."""
    import base64
    a = np.ascontiguousarray(a)
    name = a.dtype.str
    if a.dtype.kind == "V":
        # ml_dtypes extension types (bfloat16 pools): numpy's .str is
        # an opaque void tag ("|V2") the importer could not rebuild —
        # ship the real name instead
        name = a.dtype.name
    return {"dtype": name, "shape": list(a.shape),
            "data": base64.b64encode(a.tobytes()).decode("ascii")}


def _unpack_array(d: dict) -> np.ndarray:
    import base64
    raw = base64.b64decode(d["data"])
    try:
        dtype = np.dtype(d["dtype"])
    except TypeError:
        import ml_dtypes
        dtype = np.dtype(getattr(ml_dtypes, d["dtype"]))
    return np.frombuffer(raw, dtype=dtype).reshape(
        [int(s) for s in d["shape"]])


class _Slot:
    """Host-side bookkeeping for one slot of the pool. Decode state
    (position, current token) lives on device; the host only accumulates
    harvested tokens against the handle.

    A classifier-free-guidance pair is two slots: the cond slot carries
    ``pair`` (its uncond partner's index) and the uncond SHADOW slot
    carries ``shadow_of`` (the cond index) — the shadow holds the same
    handle but is never credited, completed, or evicted on its own; it
    lives and dies with its cond slot.

    ``need`` is the request's total emit budget (text fill + image
    span) when ``image_seq_len_override`` caps the grid, None for a
    full-length request: harvest truncates the final chunk at the
    budget and completes the slot early — the device keeps the full
    sequence shape (one compiled program), the host just stops
    delivering at the override span. ``since_preview`` counts harvested
    chunks since the last progressive-preview request (streaming)."""

    __slots__ = ("handle", "t0", "emitted", "t_admit", "pair",
                 "shadow_of", "need", "since_preview")

    def __init__(self, handle: S.RequestHandle, t0: int, t_admit: float,
                 pair: Optional[int] = None,
                 shadow_of: Optional[int] = None,
                 need: Optional[int] = None):
        self.handle = handle
        self.t0 = t0
        self.emitted: List[int] = []
        self.t_admit = t_admit
        self.pair = pair
        self.shadow_of = shadow_of
        self.need = need
        self.since_preview = 0


class _Chunk:
    """One in-flight fused-decode dispatch: the device-side emit ring and
    post-chunk active mask (still futures until harvested), plus the
    host's snapshot of which request occupied each slot at dispatch time
    — a slot expired and re-admitted while the chunk is in flight must
    not leak the old request's tokens into the new one."""

    __slots__ = ("ring", "active", "owners", "load", "chunk",
                 "t_dispatch", "admits_ahead", "view_read_pct")

    def __init__(self, ring, active, owners, load=None, *, chunk,
                 t_dispatch, admits_ahead, view_read_pct=None):
        self.ring = ring
        self.active = active
        self.owners = owners
        self.load = load        # load_counters of the chunk, or None
        self.chunk = chunk      # the dispatch's number: its spans',
        #                         annotations' and ledger row's cause key
        self.t_dispatch = t_dispatch
        # the admission calls made since the previous dispatch: the
        # prefill programs in the device's queue in front of this chunk
        self.admits_ahead = admits_ahead
        # of the table columns of its steps' gather reads of the ordered
        # pool, the share the width rule read (None: no such reads)
        self.view_read_pct = view_read_pct


class _Row:
    """One SLOT's worth of admission plan. A plain request is one row; a
    guided request is two (cond + uncond shadow, ``pair_row`` linking
    them). ``mode`` is the prefix-cache disposition: ``cold`` runs the
    bucket prefill; ``warm`` maps an indexed entry's pages (zero prefill
    FLOPs); ``warm_pending`` is a warm-after — its key is being
    prefilled by an earlier cold row of the SAME admission (the
    N-samples-of-one-prompt fan-out), so it resolves against the index
    after the cold groups land."""

    __slots__ = ("handle", "codes", "uncond", "pair_row", "t0", "bucket",
                 "total_pages", "mode", "shared_n", "key", "entry",
                 "grants", "window_grants", "slot", "group_idx")

    def __init__(self, handle: S.RequestHandle, codes, uncond: bool):
        self.handle = handle
        self.codes = codes
        self.uncond = uncond
        self.pair_row: Optional["_Row"] = None
        self.t0 = len(codes)
        self.bucket = 0
        self.total_pages = 0
        self.mode = "cold"
        self.shared_n = 0
        self.key: Optional[str] = None
        self.entry = None
        self.grants: List[int] = []
        self.window_grants: List[int] = []    # of a window pool
        self.slot = -1
        self.group_idx = -1


class Engine:
    """The continuous-batching loop. Pulls from a ``scheduler.RequestQueue``,
    fulfils handles (directly, or through ``complete`` — the postprocess
    hand-off) with ``scheduler.Result``s."""

    def __init__(self, params: dict, cfg, queue: S.RequestQueue, *,
                 num_slots: int = 4,
                 chunk_steps: int = 8,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 complete: Optional[Callable] = None,
                 metrics=None, log_every: int = 0,
                 quantize_cache: bool = False,
                 kv: str = "dense",
                 page_size: int = 0,
                 num_pages: int = 0,
                 paged_attn: str = "gather",
                 sparse_reads: bool = False,
                 speculative: int = 0,
                 draft_layers: int = 0,
                 prefix_cache: bool = False,
                 prefix_entries: int = 256,
                 preview_every: int = 0,
                 model_version: str = "0",
                 weights_version: str = "0",
                 flight_events: int = 256,
                 clock: Callable[[], float] = time.perf_counter,
                 device=None):
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models import dalle as D
        from dalle_pytorch_tpu.obs import flight as oflight
        from dalle_pytorch_tpu.ops import decode as decode_ops

        # replica placement: committing the params pins every program
        # this engine runs (and, transitively, all its decode state) to
        # ONE device, so a ReplicaSet can put each replica on its own
        # chip and their chunk programs genuinely overlap. device=None
        # (the single-engine default) keeps jax's default placement.
        # Placement flows through the _place_*/_put hooks so a subclass
        # can swap "one device" for "one mesh" (serve/mesh_engine.py's
        # MeshEngine: params/KV pjit-sharded, host-visible state
        # replicated) without touching any of the loop's logic.
        self.device = device
        self.cfg = cfg
        # a described block (ops.transformer.LatentMoEBlock) runs on the
        # paged gather path alone; every other option is refused here,
        # by the one typed error that names the block and the option
        self.block = cfg.transformer.block
        self.window = None      # a window-and-full block's second pool
        if self.block is not None:
            from dalle_pytorch_tpu.ops import transformer as T_ops
            for option, on in (
                    ("kv='dense'", kv != "paged"),
                    ("paged_attn='kernel'", paged_attn != "gather"),
                    ("sparse_reads", sparse_reads),
                    ("speculative", speculative),
                    ("quantize_cache", quantize_cache),
                    ("prefix_cache", prefix_cache),
                    ("a device mesh", self._decode_out_sync() is not None)):
                if on:
                    raise T_ops.BlockOptionError(self.block.name, option)
        self.params = self._place_params(params)
        params = self.params
        self.queue = queue
        self.num_slots = int(num_slots)
        self.chunk_steps = int(chunk_steps)
        if self.chunk_steps < 1:
            raise ValueError(f"chunk_steps must be >= 1, got {chunk_steps}")
        self.complete = complete
        # the flight recorder (docs/OBSERVABILITY.md): the last N
        # structured events + span records, ALWAYS on — no JSONL sink
        # required. Every event this engine emits tees into the ring
        # through the RecordingMetrics wrap (the configured sink, if
        # any, still gets everything it got before), and a fence dumps
        # the ring into the fence event payload so post-mortems don't
        # depend on anyone having configured logging in advance.
        self.flight = oflight.FlightRecorder(capacity=int(flight_events))
        self.metrics = oflight.wrap_metrics(self.flight, metrics)
        self.log_every = int(log_every)
        self.quantize_cache = bool(quantize_cache)
        self.clock = clock
        self.kv = str(kv)
        if self.kv not in ("dense", "paged"):
            raise ValueError(f"kv must be 'dense' or 'paged', got {kv!r}")
        # the paged K/V read implementation: 'gather' materializes the
        # dense view through the block tables (the parity oracle);
        # 'kernel' consumes them in place via the Pallas ragged
        # paged-attention kernel (ops/paged_attention.py) — same fused
        # one-compile emit-ring program, only the per-step read changes
        self.paged_attn = str(paged_attn)
        if self.paged_attn not in ("gather", "kernel"):
            raise ValueError(f"paged_attn must be 'gather' or 'kernel', "
                             f"got {paged_attn!r}")
        if self.paged_attn == "kernel" and self.kv != "paged":
            raise ValueError("paged_attn='kernel' requires kv='paged' "
                             "(the kernel reads the page pool through "
                             "block tables; the dense slot cache has "
                             "neither)")
        # sparsity-aware decode reads: sparse layers read only their
        # statically visible pages (ops.sparse.visible_pages) instead of
        # the whole cached prefix — tokens stay byte-identical (the
        # skipped pages carry exactly-zero attention weight), only the
        # per-token KV read traffic shrinks (docs/SERVING.md "Sparse
        # decode reads"). All three preconditions are typed here, at
        # construction, not as trace-time surprises.
        self.sparse_reads = bool(sparse_reads)
        if self.sparse_reads:
            if self.kv != "paged":
                raise ValueError("sparse_reads requires kv='paged' — "
                                 "page visibility lives in the paged "
                                 "KV layout (block tables)")
            pattern = cfg.transformer.sparse_pattern
            if not any(pattern):
                raise ValueError(
                    "sparse_reads on a config with no sparse layers "
                    "would be a silent no-op (every layer reads the "
                    "full prefix either way) — drop the flag")
            from dalle_pytorch_tpu.ops import transformer as T_ops
            period = T_ops._pattern_period(pattern)
            if period > T_ops._MAX_UNROLL_PERIOD:
                raise ValueError(
                    f"sparse_reads needs a periodic dense/sparse "
                    f"pattern (period <= {T_ops._MAX_UNROLL_PERIOD}) "
                    f"so the per-layer read shapes resolve statically "
                    f"in the fused decode program; pattern {pattern} "
                    f"has period {period}")
        # speculative decode (docs/SERVING.md "Speculative decode"):
        # each fused round drafts k-1 tokens with a shallow early-exit
        # head (the first draft_layers transformer layers + the same
        # logit head — no extra weights) and verifies all k in ONE
        # k-wide full-model pass. Deterministic fold_in(rng, pos)
        # sampling makes acceptance an equality test, so the emitted
        # stream is byte-identical to eager — speculation only changes
        # how many sequential full-depth passes each token costs.
        self.speculative = int(speculative)
        if self.speculative < 0:
            raise ValueError(
                f"speculative must be >= 0, got {speculative}")
        depth = cfg.transformer.depth
        self.draft_layers = int(draft_layers) or max(depth // 2, 1)
        self._draft_cfg = None
        if self.speculative:
            if self.sparse_reads:
                raise ValueError(
                    "speculative does not compose with sparse_reads — "
                    "the k-wide verify reads the full cached prefix "
                    "per query (masked, not trimmed); run one or the "
                    "other")
            if not 1 <= self.draft_layers <= depth:
                raise ValueError(
                    f"draft_layers must be in [1, depth={depth}], "
                    f"got {self.draft_layers}")
            self._draft_cfg = D.draft_transformer_config(
                cfg.transformer, self.draft_layers)
        # the per-dispatch device-pos advance: chunk_steps fused rounds,
        # each emitting up to k tokens (1 when not speculating)
        self._chunk_span = self.chunk_steps * max(self.speculative, 1)

        if prefill_buckets is None:
            buckets = S.prefill_buckets(cfg.text_seq_len)
        else:
            buckets = tuple(sorted(set(int(b) for b in prefill_buckets)))
            if not buckets or buckets[0] < 1 \
                    or buckets[-1] != cfg.text_seq_len:
                raise ValueError(
                    f"prefill_buckets must be >= 1 and end at "
                    f"text_seq_len ({cfg.text_seq_len}), got {buckets}")
        self.buckets = buckets
        self.prefill_groups = S.prefill_groups(self.num_slots)

        S_ = self.num_slots
        self.total_len = cfg.seq_len
        # device state: EVERYTHING the steady-state loop touches stays on
        # device between chunks — the KV cache, per-slot token/position/
        # active mask, RNG keys and sampling knobs. The host writes them
        # only through the admission/kill programs (device-side scatter),
        # and reads only the emit ring, one explicit device_get per
        # chunk. Cache dtype follows the embedding table — the dtype that
        # flows into qkv, so the admission scatter matches what prefill
        # allocates (under bf16 params an f32 default would promote the
        # whole decode carry)
        if self.kv == "paged":
            from dalle_pytorch_tpu.serve import kv_pool as KV
            self.page_size = int(page_size) or min(16, self.total_len)
            if not 1 <= self.page_size <= self.total_len:
                raise ValueError(
                    f"page_size must be in [1, seq_len={self.total_len}], "
                    f"got {self.page_size}")
            if self.paged_attn == "kernel":
                # typed, at pool init, naming the kernel tile constraint
                # — not an opaque Mosaic failure inside pallas_call
                KV.validate_page_size(self.page_size)
            # logical pages one full-length sequence spans = the block
            # table width; also the floor on the pool (ONE request must
            # always be able to run alone, or eviction could livelock)
            self.slot_max_pages = KV.pages_for(self.total_len,
                                               self.page_size)
            full = S_ * self.slot_max_pages + 1   # + trash page
            self.num_pages = int(num_pages) or full
            if self.num_pages - 1 < self.slot_max_pages:
                raise ValueError(
                    f"num_pages={self.num_pages} cannot hold even one "
                    f"full sequence ({self.slot_max_pages} pages of "
                    f"{self.page_size} rows + the reserved trash page)")
            # a window-and-full block holds a second pool for its window
            # layers, with an allocator and ring tables of its own
            # (``KV.WindowPages``): ``self.alloc`` and ``_bt_host`` are
            # then the FULL layers' pool
            window_pages = KV.window_pool_pages(
                cfg.transformer, S_, self.total_len, self.page_size,
                self.num_pages)
            self.window = None if not window_pages else KV.WindowPages(
                S_, window_pages, self.block.ring_pages(
                    self.page_size, self.total_len), self.page_size)
            if window_pages and self.num_pages * window_pages >= 2 ** 31:
                raise ValueError(
                    f"{self.num_pages} x {window_pages} pages: an "
                    f"admission names a row's page in both pools by one "
                    f"int32 (_prefill_fn)")
            self.cache = self._place_kv(KV.init_page_pool(
                cfg.transformer, self.num_pages, self.page_size,
                dtype=params["text_emb"]["w"].dtype,
                quantized=self.quantize_cache, window_pages=window_pages,
                num_slots=S_))
            self.alloc = KV.PageAllocator(self.num_pages)
            # the host owns the authoritative block tables (it owns the
            # allocator); the device copy is pushed — one explicit
            # device_put of a few KB — only when the mapping changes
            self._bt_host = np.zeros((S_, self.slot_max_pages), np.int32)
            self.block_tables = self._put(self._tables_host())
            self._bt_dirty = False
            self._slot_pages: List[List[int]] = [[] for _ in range(S_)]
            # safe host-side upper bound of each slot's device pos
            # (t0 + K per dispatched chunk, capped): mapping ahead off
            # this bound can over-allocate by at most one chunk, never
            # lag the device
            self._pos_est = [0] * S_
            self._pages_samples: deque = deque(maxlen=10_000)
            self.evicted = 0
            self.deferred = 0            # DISTINCT page-deferred requests
            self._deferred_ids: set = set()
            # head-of-line page reservation: the oldest page-deferred
            # request's id and need — while set, admission stops popping
            # until that many pages are free, so completions' freed
            # pages accumulate for it instead of being consumed by
            # later, smaller requests (the no-starvation guarantee)
            self._hol_rid: Optional[int] = None
            self._hol_need = 0
            # the smallest prompt span any admission could need: below
            # this many free pages, popping the queue could only churn
            # (pop -> defer -> requeue once per chunk)
            self._min_admit_pages = KV.pages_for(min(self.buckets),
                                                 self.page_size)
        else:
            if prefix_cache:
                raise ValueError(
                    "prefix_cache requires kv='paged' — physical prompt "
                    "sharing lives in the page pool's block-table "
                    "indirection; the dense slot cache has neither "
                    "pages nor refcounts")
            self.cache = self._place_kv(decode_ops.init_cache(
                cfg.transformer, S_, self.total_len,
                dtype=params["text_emb"]["w"].dtype,
                quantized=self.quantize_cache))
        # commit the per-slot state too: nothing this engine carries
        # between chunks may sit on the default device for jit to
        # migrate per call (on a placed replica it lands on its chip;
        # on a mesh engine it is replicated across the slice)
        (self.key_mask, self.cur_tok, self.pos, self.active, self.rng,
         self.temp, self.topk_k, self.top_p) = self._place_state((
            jnp.ones((S_, self.total_len), bool),
            jnp.zeros((S_,), jnp.int32),
            jnp.zeros((S_,), jnp.int32),
            jnp.zeros((S_,), bool),
            jnp.zeros((S_, 2), jnp.uint32),
            jnp.ones((S_,), jnp.float32),
            jnp.ones((S_,), jnp.int32),
            jnp.zeros((S_,), jnp.float32)))
        # classifier-free-guidance pair state: per-slot partner index
        # (self when unpaired), guidance scale (0 = off — the mix and
        # the partner-copy in sample_per_slot are exact identities
        # then), and the uncond-shadow flag. Host-authoritative like the
        # block tables: admission/teardown edit the host arrays and one
        # explicit device_put pushes them before the next chunk.
        self._cfg_partner_host = np.arange(S_, dtype=np.int32)
        self._cfg_scale_host = np.zeros((S_,), np.float32)
        self._cfg_uncond_host = np.zeros((S_,), bool)
        (self.cfg_partner, self.cfg_scale,
         self.cfg_uncond) = self._place_state((
             jnp.arange(S_, dtype=jnp.int32),
             jnp.zeros((S_,), jnp.float32),
             jnp.zeros((S_,), bool)))
        self._cfg_dirty = False
        self.slots: List[Optional[_Slot]] = [None] * S_
        # the weight generation this engine serves: stamped on every
        # Result it fulfils (rolling weight hot-swap makes "which
        # weights produced these tokens" a per-replica fact, and the
        # byte-identity contract holds PER version). Distinct from
        # model_version below, which keys the prefix cache — though the
        # replica set feeds the same string to both, so an upgraded
        # replica can never serve another generation's cached prompt KV.
        self.weights_version = str(weights_version)
        # the prefix cache (kv='paged' only): content-addressed prompt
        # KV sharing over the refcounted allocator
        self.model_version = str(model_version)
        self.prefix = None
        if prefix_cache:
            from dalle_pytorch_tpu.serve import prefix_cache as PC
            self.prefix = PC.PrefixIndex(self.alloc,
                                         max_entries=prefix_entries)
            self._layer_sig = PC.layer_signature(cfg.transformer)
        # progressive image previews (streaming): every preview_every
        # harvested chunks per streaming slot, hand the image-token
        # prefix to on_preview (the postprocess stage pads it to the
        # full grid and decodes it through the ONE batch-1 VAE program
        # — serve/postprocess.py). 0 disables; the hook is set by the
        # server after construction, like ``complete``.
        self.preview_every = int(preview_every)
        if self.preview_every < 0:
            raise ValueError(f"preview_every must be >= 0, got "
                             f"{preview_every}")
        self.on_preview: Optional[Callable] = None
        self._pending: deque = deque()   # dispatched, un-harvested chunks
        # memo for the config-static /stats read-bytes model, keyed by
        # the sparse_reads flag it was asked for
        self._modeled_read_bytes: Dict[bool, int] = {}
        # serve-side profiler capture (POST /admin/profile): armed by
        # request_profile as a REQUEST the engine thread consumes at
        # its next chunk dispatch (so the start index is read on the
        # one thread that advances it — no HTTP-thread race), stopped
        # after a relative countdown of harvests (so the capture covers
        # the device actually executing the chunks, not just the async
        # dispatches). One at a time — jax.profiler's rule.
        self._profile_req: Optional[Tuple[str, int]] = None
        self._profiler = None
        self._profile_left = 0
        self._profile_lock = threading.Lock()
        self.profiles_taken = 0

        # counters (stats() reads these)
        self.decode_traces = 0          # bumped only while TRACING: the
        self.prefill_traces = 0         # fixed-shape contract keeps the
        #                                 decode program at 1 and prefill
        #                                 at 1 per bucket and group
        self.warm_admit_traces = 0      # the warm-admission program: 1,
        #                                 ever (no bucket dependence)
        self._prefill_trace_counts: Dict[tuple, int] = {}
        self._scopes_thread = None      # the thread inside device_scopes()
        self._scope_maps: Dict[str, dict] = {}
        self.prefill_runs = 0           # prefill DISPATCHES (a warm hit
        #                                 runs zero of these)
        self.warm_admits = 0            # requests admitted zero-FLOP
        self.prefix_hits = 0            # warm admissions (engine-level:
        #                                 counted when the hit is USED,
        #                                 not merely probed)
        self.cfg_pairs = 0              # guided pairs admitted
        self.reaped = 0                 # externally-cancelled slots
        #                                 reclaimed (stream disconnect,
        #                                 group cancel, hedge loser)
        for k in LOOP_SECONDS:
            setattr(self, k, 0.0)
        # the chunk ledger: one row a harvested chunk in a ring of its
        # own (the span ring turns over in a second or two), the laps
        # of the open interval, and what a stall is held against
        self.loop_ring = oflight.FlightRecorder(capacity=LOOP_ROWS)
        self._lap_s = dict.fromkeys(LOOP_PHASES, 0.0)
        self._t_lap = self.clock()
        self._gc = _watch_collector(self)   # [runs, seconds]
        # at the last row's end: the clock, the loop thread's CPU
        # seconds, the programs traced, the collector's runs and seconds
        self._cut = (self._t_lap, time.thread_time(), 0, 0, 0.0)
        self._intervals = {False: deque(maxlen=STALL_WINDOW),
                           True: deque(maxlen=STALL_WINDOW)}
        self._admit_calls = 0           # prefill / warm-admission CALLS
        self._admits_ahead: List[dict] = []     # since the last dispatch
        self.chunks_behind_admit = 0    # harvested chunks that had one
        self.loop_stalls = 0
        self._stalls: deque = deque(maxlen=STALLS_KEPT)
        self._stall_open: Optional[dict] = None     # awaits next_wait_s
        self.decode_steps = 0           # fused steps dispatched (chunks*K)
        self.harvests = 0               # emit-ring device_gets — the ONLY
        #                                 host syncs in steady state
        self.sample_sorted_chunks = 0   # chunks dispatched while a resident
        #                                 request asks for nucleus sampling:
        #                                 the only ones that sort the vocabulary
        self.tokens_decoded = 0
        self.completed = 0
        self.expired = 0
        self.occupancy_sum = 0
        # speculative accounting: DELIVERED tokens over rounds that
        # emitted anything — tokens_decoded/occupancy already count only
        # ring entries >= 0, so rejected drafts never inflate them; these
        # two add the acceptance-rate numerator/denominator
        self.spec_rounds = 0            # verify rounds that delivered
        self.spec_delivered = 0         # tokens those rounds delivered
        self.spec_proposed = 0          # positions those rounds COULD
        #                                 have delivered: k, clamped to
        #                                 the sequence end — so a
        #                                 perfect draft scores exactly
        #                                 1.0, not "1.0 minus the last
        #                                 round's truncation"
        for k in MOE_COUNTERS:          # a described block's routed load
            setattr(self, k, 0)
        self.window_sink_mass = 0.0     # SINK_COUNTERS: a weight, a count
        self.window_sink_reads = 0
        self.kv_view_groups = 1         # slot groups a layer's paged gather
        #                                 read was traced with (ops.decode
        #                                 view_slot_groups; 1 = no such read)
        # table columns that the dispatched chunks' gather reads of the
        # ordered pool read, summed over layers and steps, and what they
        # would have read at full width (``_view_columns``; 0 / 0 where no
        # layer reads by the width rule)
        self.kv_view_columns_read = 0
        self.kv_view_columns_full = 0
        self._view_plan = None  # ops.decode.ViewPlan, set where traced
        self._t_start = None
        self._last_log = 0

        # replica supervision surface (serve/replica.py): the heartbeat
        # is stamped at every step and every harvest — a wedged device
        # sync stops it advancing, which is how a hang is detected
        # without touching the wedged thread. ``fenced`` is the one-way
        # kill switch the supervisor flips BEFORE reclaiming this
        # engine's in-flight requests: a fenced engine never fulfils a
        # handle, hands a completion downstream, or re-queues anything
        # — its requests belong to whoever fenced it.
        self.fenced = False
        self.last_heartbeat = self.clock()
        # True while a KNOWN first call of a jitted program is tracing/
        # compiling (cold prefill bucket, first decode chunk): compiles
        # take seconds on a cold cache, and the supervisor must not
        # read the stalled heartbeat as a hang and fence a healthy
        # replica mid-compile
        self.compiling = False
        # a fenced engine mid-step may hold handles it just popped that
        # are in neither its queue nor its slots; this hook (set by the
        # replica supervisor) returns them to the shared queue instead
        # of dropping them
        self.on_fenced_orphan: Optional[Callable] = None
        # handles popped from the queue but not yet slotted — published
        # BEFORE admission so a reclaim sweep can see work held by a
        # thread wedged inside the admission prefill (a cold compile
        # blocks for seconds with these in step locals)
        self._admitting: List[S.RequestHandle] = []

        # donating the cache lets XLA update the K/V buffers in place
        # per chunk instead of copying them
        from dalle_pytorch_tpu.parallel._compat import donate_if_accelerator
        donate = donate_if_accelerator(1)
        impl = self._decode_impl_paged if self.kv == "paged" \
            else self._decode_impl
        self._decode_fn = self._jit_decode(impl, donate)
        self._kill_fn = jax.jit(lambda active, keep: active & keep)
        self._prefill_fns: Dict = {}
        self._warm_fn = None            # built lazily (prefix_cache)
        self._install_fn = None         # built lazily (migration import)
        if self.kv == "paged":
            from dalle_pytorch_tpu.serve import kv_pool as KV
            # the page copy pair, shared by the prefix cache's
            # copy-on-write fork AND live migration's export/import:
            # snapshot one physical page, restore one physical page.
            # Pool updates go through the _jit_pool_update hook so a
            # mesh engine can pin the KV shardings — an unpinned
            # restore that drifted the pool's placement would silently
            # retrace the fused decode program (decode_traces catches
            # it, but pin instead of hope).
            self._snap_fn = self._jit_pool_read(
                lambda pool, pid: KV.snapshot_page(pool, pid))
            self._restore_fn = self._jit_pool_update(
                lambda pool, pid, snap: KV.restore_page(pool, pid, snap))
        self._lock = threading.Lock()   # step_once is not reentrant

    # -- placement hooks (the mesh seam: serve/mesh_engine.py) --------------
    #
    # Every host<->device placement the engine performs flows through
    # these five methods, and the two jit hooks own program construction.
    # The base implementations reproduce the single-device behaviour
    # exactly; MeshEngine overrides them to pjit-shard params and the KV
    # store over a device mesh while replicating everything the host
    # protocol touches — which is why the entire serving loop above them
    # (admission, fused chunks, emit-ring harvest, fencing, supervision)
    # runs unmodified on a mesh.

    def _put(self, a):
        """One explicit host->device transfer of a small host array
        (admission tensors, block tables, kill masks)."""
        import jax
        return jax.device_put(a, self.device)

    def _place_params(self, params):
        import jax
        return params if self.device is None \
            else jax.device_put(params, self.device)

    def _place_kv(self, cache: dict) -> dict:
        import jax
        return cache if self.device is None \
            else jax.device_put(cache, self.device)

    def _place_state(self, state: tuple) -> tuple:
        import jax
        return state if self.device is None \
            else jax.device_put(state, self.device)

    def _jit_decode(self, impl, donate):
        import jax
        return jax.jit(impl, donate_argnums=donate)

    def _jit_prefill_program(self, pre):
        import jax
        return jax.jit(pre)

    def _jit_warm_program(self, warm):
        """The warm-admission program (prefix cache): same jit seam as
        prefill — the mesh engine pins replicated output shardings so
        the per-slot state's placement can never drift."""
        import jax
        return jax.jit(warm)

    def _jit_pool_read(self, fn):
        """Page snapshot (prefix insert): pool -> one page's rows."""
        import jax
        return jax.jit(fn)

    def _jit_pool_update(self, fn):
        """Page restore (COW fork): returns the UPDATED pool, so a mesh
        engine must pin the pool's shardings on the output."""
        import jax
        return jax.jit(fn)

    def _logits_sync(self, logits):
        """Traced hook over the per-step logits, identity here. The mesh
        engine re-replicates here: its logits head is vocab-sharded
        (column-parallel, elementwise-exact), and the sampler's softmax/
        cumsum reductions must never run over a sharded axis or the
        byte-identity contract dies to float reassociation."""
        return logits

    def _decode_out_sync(self):
        """The ``ops.decode`` ``out_sync`` seam: None here; the mesh
        engine returns a replicate-constraint applied to the per-head
        attention output before the out projection."""
        return None

    # -- jitted programs ----------------------------------------------------

    def _count_trace(self, counter: str, program: Optional[tuple] = None):
        """Bump a trace counter from inside a program being TRACED — the
        fixed-shape contract's proof. ``device_scopes()`` lowers the same
        programs again from shapes, on its caller's thread; that is no
        retrace of the serving path and is not counted."""
        if threading.get_ident() == self._scopes_thread:
            return
        setattr(self, counter, getattr(self, counter) + 1)
        if program is not None:     # a prefill's (bucket, rows)
            self._prefill_trace_counts[program] = \
                self._prefill_trace_counts.get(program, 0) + 1

    def _cfg_closures(self, params, active, keys, temp, topk_k, top_p,
                      partner, cfgs, uncond):
        """The embed/sample closures BOTH fused decode programs share,
        with per-request classifier-free guidance folded in: a guided
        pair's cond slot samples image positions from the mixed logits
        (its partner's row is the uncond stream — the gather happens on
        the replicated post-``_logits_sync`` logits, so the mesh
        engine's vocab sharding never reorders the mix), the uncond
        shadow copies its partner's drawn token, and the shadow's TEXT
        positions embed PAD — ``generate_images``' guided scan
        verbatim. With every scale at 0 (no guided request in the
        pool) each extra op is an exact identity, so the unguided
        byte-identity contract is untouched. ``active`` is the chunk's
        live mask at dispatch: the sampler sorts the vocabulary only
        while one of those slots asks for nucleus sampling."""
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models import dalle as D

        def embed_fn(tok, p):
            # the null stream's text stays PAD — feeding it the sampled
            # caption would make it conditional (one-shot: cur_tok =
            # where(is_text & uncond_rows, 0, cur_tok))
            tok = jnp.where(uncond & (p < self.cfg.text_seq_len), 0, tok)
            return D.decode_token_embed(params, self.cfg, tok, p)

        def sample_fn(h, pred_pos):
            logits = self._logits_sync(D.to_logits(params, h, self.cfg))
            return D.sample_per_slot(logits, pred_pos, keys, temp,
                                     topk_k, top_p, self.cfg,
                                     partner=partner, cfg_scale=cfgs,
                                     uncond=uncond, live=active)

        return embed_fn, sample_fn

    def _decode_impl(self, params, cache, cur_tok, pos, active, keys, temp,
                     topk_k, top_p, partner, cfgs, uncond):
        """The fused steady-state program: ``chunk_steps`` decode steps
        for ALL slots in one ``lax.scan`` (``ops.decode.decode_loop``),
        emitted tokens collected into the device-side (num_slots, K)
        ring. Traced exactly once (fixed shapes) — the side-effecting
        counter below proves it; the guidance-pair state rides as three
        more (num_slots,) arrays, never a new trace."""
        self._count_trace("decode_traces")
        from dalle_pytorch_tpu.models import dalle as D
        from dalle_pytorch_tpu.ops import decode as decode_ops

        embed_fn, sample_fn = self._cfg_closures(
            params, active, keys, temp, topk_k, top_p, partner, cfgs,
            uncond)
        if self.speculative:
            # the draft weights are a leading-layers slice of the SAME
            # resident params, taken inside the traced fn so hot-swap,
            # donation and mesh placement all flow through unchanged
            draft_p = D.draft_transformer_params(
                params["transformer"], self.draft_layers)
            return decode_ops.decode_loop_spec(
                params["transformer"], draft_p, cur_tok, pos, active,
                cache, cfg=self.cfg.transformer,
                draft_cfg=self._draft_cfg, key_mask=self.key_mask,
                steps=self.chunk_steps, k=self.speculative,
                embed_fn=embed_fn, sample_fn=sample_fn,
                out_sync=self._decode_out_sync())
        return decode_ops.decode_loop(
            params["transformer"], cur_tok, pos, active, cache,
            cfg=self.cfg.transformer, key_mask=self.key_mask,
            steps=self.chunk_steps, embed_fn=embed_fn, sample_fn=sample_fn,
            out_sync=self._decode_out_sync())

    def _decode_impl_paged(self, params, cache, block_tables, cur_tok, pos,
                           active, keys, temp, topk_k, top_p, partner,
                           cfgs, uncond):
        """The paged twin of ``_decode_impl``: identical fused K-step
        emit-ring program, but K/V reads go through the block tables —
        each layer's pages gathered inside the layer scan, or the
        in-place Pallas ragged paged-attention kernel under
        ``paged_attn='kernel'`` — and writes land in the page pool
        (``ops.decode.decode_loop_paged``). The block tables are a
        per-chunk constant — the host maps every page the chunk could
        write before dispatch — so this too traces exactly once."""
        self._count_trace("decode_traces")
        from dalle_pytorch_tpu.models import dalle as D
        from dalle_pytorch_tpu.ops import decode as decode_ops

        embed_fn, sample_fn = self._cfg_closures(
            params, active, keys, temp, topk_k, top_p, partner, cfgs,
            uncond)
        if self.speculative:
            draft_p = D.draft_transformer_params(
                params["transformer"], self.draft_layers)
            return decode_ops.decode_loop_spec_paged(
                params["transformer"], draft_p, cur_tok, pos, active,
                cache, block_tables, cfg=self.cfg.transformer,
                draft_cfg=self._draft_cfg, key_mask=self.key_mask,
                total_len=self.total_len, steps=self.chunk_steps,
                k=self.speculative, embed_fn=embed_fn,
                sample_fn=sample_fn, attn_impl=self.paged_attn,
                out_sync=self._decode_out_sync())
        if self.paged_attn == "gather":
            # what the step reads of the ordered pool, from the shapes
            # (the full layers' groups, of a window-and-full block)
            plan = None if self.sparse_reads else decode_ops.paged_view_plan(
                self.cfg.transformer, params["transformer"], cache,
                self.num_slots, self.total_len)
            self._view_plan = plan if plan and plan.by_rule else None
            self.kv_view_groups = plan.groups if self._view_plan \
                else decode_ops.pool_view_groups(
                    cache, self.num_slots, self.slot_max_pages,
                    ordered=False)
        return decode_ops.decode_loop_paged(
            params["transformer"], cur_tok, pos, active, cache,
            block_tables, cfg=self.cfg.transformer,
            key_mask=self.key_mask, total_len=self.total_len,
            steps=self.chunk_steps, embed_fn=embed_fn,
            sample_fn=sample_fn, attn_impl=self.paged_attn,
            sparse_reads=self.sparse_reads,
            out_sync=self._decode_out_sync())

    def _prefill_fn(self, bucket: int, n_rows: Optional[int] = None):
        """Admission program for one prompt-length BUCKET and one GROUP
        of ``n_rows`` rows (of ``self.prefill_groups``; all ``num_slots``
        where not given): batched prefill of the group (prompts padded
        to ``bucket``, unused rows aimed at the dropped out-of-range
        slot index), scatter of the KV rows into the slot pool, each
        request's FIRST sampled token (position t0 = the TRUE prompt
        length, key ``fold_in(rng, t0)`` — ``generate_images``'s
        first_tok), and the device-side merge of the new slots' decode
        state. One jitted function a (bucket, n_rows), which only ever
        sees that shape: compiled once for the engine's life."""
        import jax
        import jax.numpy as jnp
        n_rows = self.num_slots if n_rows is None else n_rows
        if (bucket, n_rows) in self._prefill_fns:
            return self._prefill_fns[bucket, n_rows]
        paged = self.kv == "paged"

        def pre(params, cache, cur_tok, pos, active, rng, temp, topk_k,
                top_p, text, lens, slots, n_seed, n_temp,
                n_topk, n_top_p, n_partner, n_cfgs, n_uncond,
                page_rows=None):
            # page_rows rides only the paged trace: dense admission
            # omits it entirely (no dead argument, no wasted transfer)
            self._count_trace("prefill_traces", (bucket, n_rows))
            from dalle_pytorch_tpu.models import dalle as D
            from dalle_pytorch_tpu.ops import decode as decode_ops

            # seed -> key ON DEVICE (identical to the eager
            # PRNGKey(seed) the one-shot path uses): the host ships
            # plain int32 seeds, so admission stays free of implicit
            # transfers under guards.no_transfers
            with jax.named_scope("sample"):
                n_rng = jax.vmap(jax.random.PRNGKey)(n_seed)
            tokens = D.embed_prompt(params, self.cfg, text)
            h, group = decode_ops.prefill(
                params["transformer"], tokens, cfg=self.cfg.transformer,
                total_len=self.total_len, prompt_mask=None,
                quantize_cache=self.quantize_cache,
                out_sync=self._decode_out_sync(), lens=lens)
            if paged:
                # the group's rows go in as WHOLE pages, every block's
                # (``ops.decode._store_prompt_pages``). Page w of
                # group-row g is the physical page of its row w *
                # page_size (trash for the unused dummy rows and past a
                # prompt's grants)
                with jax.named_scope("prefill.scatter"):
                    ids = page_rows[:, ::self.page_size].reshape(-1)
                    # a page id of each pool in one int32: full id x
                    # the window pool's pages + window id (the trash
                    # page for a page the ring no longer holds)
                    n_win = 1 if self.window is None \
                        else self.window.alloc.num_pages
                    at = {"full": ids // n_win, "window": ids % n_win}
                    new = {}
                    # the classic block holds one pool, every buffer of it
                    pools = {"full": tuple(cache)} if self.block is None \
                        else self.block.pools(self.cfg.transformer.depth)
                    for pool, names in pools.items():
                        for name in names:
                            rows = group[name]
                            if pool == "state":
                                # not pages: each admitted row's state
                                # after its own prompt, over whatever the
                                # slot's last request left there
                                new[name] = cache[name].at[:, slots].set(
                                    rows, mode="drop")
                                continue
                            if self.block is None:
                                # the classic prefill returns a dense
                                # cache, rows per head
                                rows = decode_ops._token_rows(
                                    rows[:, :, :, :bucket])
                            # a row: the kv heads side by side
                            new[name] = decode_ops._store_prompt_pages(
                                cache[name], rows.reshape(
                                    rows.shape[:3] + (-1,)), at[pool])
                    cache = new
            else:
                with jax.named_scope("prefill.scatter"):
                    cache = {k: cache[k].at[:, slots].set(group[k],
                                                          mode="drop")
                             for k in cache}
            # logits at each row's TRUE last prompt position: rows are
            # padded to the bucket, but causality makes h[:, lens-1]
            # identical to the unpadded prefill's last row
            with jax.named_scope("head"):
                h_last = jnp.take_along_axis(
                    h, (lens - 1)[:, None, None], axis=1)[:, 0]
            logits = self._logits_sync(D.to_logits(params, h_last, self.cfg))
            # n_partner is the GROUP-row index of a guided row's pair
            # (both members admit in the same bucket group: the null
            # caption has the cond prompt's length); the same
            # mix/copy as the fused decode step covers the FIRST
            # sampled token — at position t0 == text_seq_len that
            # token is already an image position and must be guided
            first = D.sample_per_slot(logits, lens, n_rng, n_temp,
                                      n_topk, n_top_p, self.cfg,
                                      partner=n_partner,
                                      cfg_scale=n_cfgs,
                                      uncond=n_uncond)
            with jax.named_scope("prefill.scatter"):
                cur_tok = cur_tok.at[slots].set(first, mode="drop")
                pos = pos.at[slots].set(lens, mode="drop")
                active = active.at[slots].set(True, mode="drop")
                rng = rng.at[slots].set(n_rng, mode="drop")
                temp = temp.at[slots].set(n_temp, mode="drop")
                topk_k = topk_k.at[slots].set(n_topk, mode="drop")
                top_p = top_p.at[slots].set(n_top_p, mode="drop")
            # h_last rides back out for the prefix cache's insert (the
            # warm path's first token samples from exactly this row)
            return (cache, cur_tok, pos, active, rng, temp, topk_k,
                    top_p, h_last)

        # the program's name in a trace's XLA Modules line: jit_prefill_b64
        # for the whole group, jit_prefill_b64_g4 for a group of 4 rows
        pre.__name__ = f"prefill_b{bucket}" + (
            "" if n_rows == self.num_slots else f"_g{n_rows}")
        fn = self._jit_prefill_program(pre)
        self._prefill_fns[bucket, n_rows] = fn
        return fn

    def _warm_admit_fn(self):
        """Admission program for prefix-cache WARM hits: the prompt's KV
        already sits in shared pages and its last hidden row is cached,
        so admission is ONE ``to_logits`` + per-slot first-token sample
        + the device-side state merge — zero transformer FLOPs, and no
        bucket dependence (h_last is (G, dim) whatever the prompt
        length), so it compiles exactly once for the engine's life.
        Byte-identity with the cold path holds because prefill rows are
        batch-row-independent: the cached h_last IS the row the cold
        program would have computed, and the sample math is the same
        ``sample_per_slot`` call."""
        if self._warm_fn is not None:
            return self._warm_fn
        import jax

        def warm(params, cur_tok, pos, active, rng, temp, topk_k, top_p,
                 h_last, lens, slots, n_seed, n_temp, n_topk, n_top_p,
                 n_partner, n_cfgs, n_uncond):
            self._count_trace("warm_admit_traces")
            from dalle_pytorch_tpu.models import dalle as D
            with jax.named_scope("sample"):
                n_rng = jax.vmap(jax.random.PRNGKey)(n_seed)
            logits = self._logits_sync(D.to_logits(params, h_last, self.cfg))
            first = D.sample_per_slot(logits, lens, n_rng, n_temp,
                                      n_topk, n_top_p, self.cfg,
                                      partner=n_partner,
                                      cfg_scale=n_cfgs, uncond=n_uncond)
            with jax.named_scope("prefill.scatter"):
                cur_tok = cur_tok.at[slots].set(first, mode="drop")
                pos = pos.at[slots].set(lens, mode="drop")
                active = active.at[slots].set(True, mode="drop")
                rng = rng.at[slots].set(n_rng, mode="drop")
                temp = temp.at[slots].set(n_temp, mode="drop")
                topk_k = topk_k.at[slots].set(n_topk, mode="drop")
                top_p = top_p.at[slots].set(n_top_p, mode="drop")
            return cur_tok, pos, active, rng, temp, topk_k, top_p

        warm.__name__ = "warm_admit"
        self._warm_fn = self._jit_warm_program(warm)
        return self._warm_fn

    # -- request lifecycle --------------------------------------------------

    def fence(self) -> None:
        """One-way kill switch (replica failover / operator drain): after
        this, the engine drops every completion/expiry/error instead of
        fulfilling it, skips every requeue, and ``step_once`` bails on
        entry. Set by the supervisor BEFORE it reclaims this engine's
        in-flight handles, so a wedged thread waking mid-step cannot race
        the replay with a stale result (``RequestHandle.fulfill`` being
        first-write-wins is the belt to this suspender)."""
        self.fenced = True

    def inflight_handles(self) -> List[S.RequestHandle]:
        """Host-side snapshot of every request this engine holds: the
        in-slot handles plus any mid-admission ones (popped, published
        in ``_admitting``, not yet slotted) — the failover reclaim
        surface. Pure bookkeeping (no device sync), so a supervisor can
        read it even while the engine thread is wedged inside a chunk
        or an admission compile."""
        out: List[S.RequestHandle] = []
        seen: set = set()
        for h in [s.handle for s in list(self.slots) if s is not None] \
                + list(self._admitting):
            rid = h.request.request_id
            if rid not in seen:
                seen.add(rid)
                out.append(h)
        return out

    def progress_snapshot(self) -> Dict[int, int]:
        """``{request_id: tokens_emitted_so_far}`` for every in-slot
        request — pure host bookkeeping, no device sync. This is the
        supervision surface that works WITHOUT a shared heap: a child-
        process worker ships it in every heartbeat/harvest frame, and
        the parent's retire math subtracts exactly these prefixes for
        the requests it reclaims (replay re-credits every token, so the
        aggregate keeps counting distinct delivered tokens even though
        parent and child never share memory)."""
        return {s.handle.request.request_id: len(s.emitted)
                for s in list(self.slots)
                if s is not None and s.shadow_of is None}

    def counters(self) -> Dict[str, int]:
        """The ``COUNTERS`` block as a dict (heartbeat/retire surface)."""
        return {k: int(getattr(self, k, 0)) for k in COUNTERS}

    def compile_pending(self) -> bool:
        """True when the NEXT ``step_once`` may block in a trace/compile
        (cold decode program, or a queued prompt whose bucket has no
        compiled prefill yet). A child-process worker cannot stamp a
        heartbeat MID-step the way the in-process loop flips
        ``self.compiling``, so it asks this before stepping and sends a
        compiling=True heartbeat first — otherwise the supervisor would
        read the compile-length silence as a hang and hard-kill a
        healthy child warming up."""
        if self.decode_traces == 0 and (self.active_slots() > 0
                                        or self.queue.depth() > 0):
            return True
        if self.prefix is not None and self.warm_admit_traces == 0 \
                and self.queue.depth() > 0:
            # only when a queued prompt would ACTUALLY admit warm (its
            # key is indexed, or a same-key sibling is queued ahead of
            # it — the warm-after fan-out) does the next step risk the
            # warm program's one-time compile. A blanket True here
            # would stretch a process worker's hang deadline from
            # heartbeat_s to compile_grace_s for the engine's whole
            # life under unique-prompt traffic.
            from dalle_pytorch_tpu.serve import prefix_cache as PC
            seen: set = set()
            for codes, cfg_scale in self.queue.pending_prompt_codes():
                rows = [tuple(int(c) for c in codes)]
                if cfg_scale > 0:
                    rows.append((0,) * len(codes))
                for row in rows:
                    key = PC.prefix_key(
                        row, model_version=self.model_version,
                        layer_sig=self._layer_sig,
                        quantized=self.quantize_cache)
                    if key in self.prefix or key in seen:
                        return True
                    seen.add(key)
        queued: Dict[int, int] = {}
        for n in self.queue.pending_prompt_lens():
            try:
                b = S.bucket_for(n, self.buckets)
            except ValueError:
                continue            # admission rejects it, no compile
            queued[b] = queued.get(b, 0) + 1
        free = sum(s is None for s in self.slots)
        for b, count in queued.items():
            # a request is one row, a guided pair two: the admission may
            # take any group up to the one that holds them all
            most = S.bucket_for(max(min(free, 2 * count), 1),
                                self.prefill_groups)
            if free and any((b, g) not in self._prefill_fns
                            for g in self.prefill_groups if g <= most):
                return True
        return False

    def _orphan_handles(self, handles) -> None:
        """Hand fenced-mid-step handles back to the supervisor (they
        are in neither this engine's queue nor its slots, so the
        reclaim sweep cannot see them) — the ONE definition of the
        fence-orphan contract, shared by every admission bail-out."""
        for h in handles:
            if not h.done() and self.on_fenced_orphan is not None:
                self.on_fenced_orphan(h)

    def _requeue_or_orphan(self, handle: S.RequestHandle) -> None:
        """Return a handle to the line: via this engine's own queue
        normally, via the supervisor's orphan hook once fenced — the
        fence may land MID-STEP (after the entry checks, while a device
        op blocks), and by then the private queue is drained, so its
        ``requeue`` would fulfil the handle ``cancelled`` and race the
        failover replay with a spurious terminal result."""
        if self.fenced:
            self._orphan_handles([handle])
            return
        self.queue.requeue(handle)

    def _span(self, handle: S.RequestHandle, name: str, now: float,
              **meta) -> None:
        """Stamp one trace span and land the record in the flight ring.
        Pure host bookkeeping (a dict + two list appends) — stamping
        inside the transfer-guarded steady state is free and safe."""
        tr = handle.trace
        if tr is not None:
            self.flight.record(tr.span(name, now, **meta))

    def _lap(self, phase: str) -> float:
        """Charge the seconds since the previous lap to ``phase`` (one of
        ``LOOP_PHASES``): into the open ledger interval and into the
        cumulative ``LOOP_SECONDS`` it counts in. The ONE clock read a
        phase boundary; returns it."""
        t = self.clock()
        dt = t - self._t_lap
        self._t_lap = t
        self._lap_s[phase] += dt
        for k in LOOP_PHASES[phase]:
            setattr(self, k, getattr(self, k) + dt)
        return t

    def _finish(self, handle: S.RequestHandle, result: S.Result) -> None:
        if self.fenced:
            return
        result.weights_version = self.weights_version
        if result.status == S.OK and self.complete is not None:
            self.complete(handle, result)
        else:
            handle.fulfill(result)

    def _expire(self, handle: S.RequestHandle, now: float,
                where: str) -> None:
        req = handle.request
        self.expired += 1
        if self.metrics is not None:
            self.metrics.event(**S.structured_event(
                "serve_deadline", request_id=req.request_id, where=where,
                deadline_s=req.deadline_s,
                waited_s=round(now - req.submit_t, 4)))
        self._finish(handle, S.Result(
            status=S.DEADLINE_EXCEEDED, request_id=req.request_id,
            reason=f"deadline_s={req.deadline_s:g} exceeded ({where})",
            queued_s=round(now - req.submit_t, 6),
            total_s=round(now - req.submit_t, 6)))

    def _error(self, handle: S.RequestHandle, now: float,
               reason: str) -> None:
        req = handle.request
        if self.metrics is not None:
            self.metrics.event(**S.structured_event(
                "serve_error", request_id=req.request_id, error=reason))
        self._finish(handle, S.Result(
            status=S.ERROR, request_id=req.request_id, reason=reason,
            queued_s=round(now - req.submit_t, 6),
            total_s=round(now - req.submit_t, 6)))

    def _cfg_wire(self, i: int, j: int, scale: float) -> None:
        """Host-side pairing of cond slot i with uncond shadow j."""
        self._cfg_partner_host[i] = j
        self._cfg_partner_host[j] = i
        self._cfg_scale_host[i] = np.float32(scale)
        self._cfg_scale_host[j] = np.float32(scale)
        self._cfg_uncond_host[i] = False
        self._cfg_uncond_host[j] = True
        self._cfg_dirty = True

    def _cfg_reset(self, i: int) -> None:
        """Back to unpaired: partner = self, scale 0 (every guidance op
        in the fused program is then an exact identity for slot i)."""
        self._cfg_partner_host[i] = i
        self._cfg_scale_host[i] = 0.0
        self._cfg_uncond_host[i] = False
        self._cfg_dirty = True

    def _sync_cfg(self) -> None:
        """Push the host-authoritative guidance-pair state — same
        explicit-device_put discipline as the block tables."""
        if self._cfg_dirty:
            (self.cfg_partner, self.cfg_scale,
             self.cfg_uncond) = (
                self._put(self._cfg_partner_host),
                self._put(self._cfg_scale_host),
                self._put(self._cfg_uncond_host))
            self._cfg_dirty = False

    def _plan_rows(self, take: List[S.RequestHandle]
                   ) -> Dict[int, List[_Row]]:
        """Expand handles into per-slot admission rows: one for a plain
        request, a cond/uncond pair for a guided one (the uncond row
        runs the all-PAD null caption of the SAME length, so the pair
        always lands in one prefill bucket)."""
        per_handle: Dict[int, List[_Row]] = {}
        for h in take:
            r = h.request
            rc = _Row(h, tuple(int(c) for c in r.codes), uncond=False)
            hrows = [rc]
            if r.cfg_scale > 0:
                ru = _Row(h, (0,) * len(r.codes), uncond=True)
                rc.pair_row = ru
                ru.pair_row = rc
                hrows.append(ru)
            for p in hrows:
                p.bucket = S.bucket_for(p.t0, self.buckets)
            per_handle[r.request_id] = hrows
        return per_handle

    def _classify_row(self, p: _Row, pending: set) -> None:
        """Prefix-cache disposition of one row (paged mode). The lookup
        verifies the stored token tuple, so a hash collision reads as a
        miss, never as another prompt's KV."""
        from dalle_pytorch_tpu.serve import kv_pool as KV
        from dalle_pytorch_tpu.serve import prefix_cache as PC
        p.total_pages = KV.pages_for(p.bucket, self.page_size)
        if self.prefix is None:
            return
        p.key = PC.prefix_key(p.codes, model_version=self.model_version,
                              layer_sig=self._layer_sig,
                              quantized=self.quantize_cache)
        p.entry = self.prefix.lookup(p.key, p.codes)
        if p.entry is not None:
            p.mode = "warm"
            p.shared_n = len(p.entry.full_pages)
        elif p.key in pending:
            # an earlier cold row of THIS admission is prefilling the
            # same prompt (the N-samples fan-out): admit warm after
            # its insert lands — the shared span is allocated once
            p.mode = "warm_pending"
            p.shared_n = p.t0 // self.page_size

    def _admit(self, handles: List[S.RequestHandle], now: float) -> None:
        if self.fenced:
            # fenced mid-step after the pop: these handles are in
            # neither the queue nor a slot, so the reclaim sweep cannot
            # see them — hand them back to the shared queue (replay)
            # rather than dropping them on the floor
            self._orphan_handles(handles)
            return
        with _phase("engine.admit.plan"):
            rows, free = self._plan_admission(handles, now)
        self._lap("admit_plan_s")
        free = self._admit_cold(rows, free, now)
        self._admit_warm(rows, free, now)

    def _plan_admission(self, handles: List[S.RequestHandle], now: float
                        ) -> Tuple[List[_Row], List[int]]:
        """Validate the popped handles, fit them to the free slots (and,
        paged, to the free pages) in arrival order, and plan each one's
        rows -> (rows to admit, free slot indices). What does not fit
        is re-queued here."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        valid = []
        for h in handles:
            if h.done():
                # cancelled while queued (stream disconnect, group
                # cancel, hedge loser): its terminal result already
                # stuck — slotting it would decode tokens nobody reads
                continue
            # the server's queue validates at submit; a raw queue may
            # not — a prompt the pool can't hold must become a typed
            # error result, never a crash of the serving loop
            n = len(h.request.codes)
            if not 1 <= n <= self.cfg.text_seq_len:
                self._error(h, now, f"invalid prompt length {n} "
                            f"(need 1..{self.cfg.text_seq_len})")
                continue
            if h.request.cfg_scale > 0 and self.num_slots < 2:
                self._error(h, now, "cfg_scale needs a cond/uncond "
                            "slot pair: num_slots must be >= 2")
                continue
            L = int(h.request.image_seq_len_override)
            if L and not 1 <= L <= self.cfg.image_seq_len:
                self._error(h, now, f"image_seq_len_override {L} out "
                            f"of range (need 1.."
                            f"{self.cfg.image_seq_len})")
                continue
            valid.append(h)
        # slot budget in arrival order: a guided request takes TWO
        # slots, so the pop (one handle per free slot) can overshoot —
        # the overflow re-enters at its original position, never drops
        budget = len(free)
        take: List[S.RequestHandle] = []
        for k, h in enumerate(valid):
            width = 2 if h.request.cfg_scale > 0 else 1
            if width > budget:
                for hh in valid[k:]:
                    self._requeue_or_orphan(hh)
                break
            budget -= width
            take.append(h)
        per_handle = self._plan_rows(take)

        rows: List[_Row] = []
        if self.kv == "paged" and take:
            # admission is gated on FREE PAGES, not just free slots: the
            # prompt span (rows [0, bucket), which prefill writes) must
            # be mapped up front. The fit check runs in ARRIVAL order
            # (pop_ready's priority+seq order, BEFORE bucket grouping)
            # and stops at the first request that doesn't fit: it and
            # everything behind it are re-queued — typed backpressure,
            # not a drop. The blocked head's need is remembered
            # (``_hol_need``) and step_once stops popping until that
            # many pages are free; with requeue preserving arrival
            # order, later/smaller requests can never consume the pages
            # freed for it. A full sequence always fits the pool alone
            # (constructor invariant), so the head always eventually
            # fits and no request starves. Need is PREFIX-AWARE: a warm
            # row pays only its private span, and the LRU end of the
            # prefix index is dropped before a request is deferred.
            fits: List[S.RequestHandle] = []
            pending: set = set()
            for k, h in enumerate(take):
                rid = h.request.request_id
                hrows = per_handle[rid]
                for p in hrows:
                    self._classify_row(p, pending)
                if len(hrows) == 2:
                    # a MIXED pair (one side warm, one cold) admits
                    # whole-cold: the pair's first token mixes both
                    # streams' logits inside ONE program, and that
                    # program is the bucket prefill
                    modes = {p.mode for p in hrows}
                    if "cold" in modes and modes != {"cold"}:
                        for p in hrows:
                            p.mode, p.shared_n, p.entry = "cold", 0, None
                for p in hrows:
                    if p.mode == "cold" and p.key is not None:
                        pending.add(p.key)
                need = sum(p.total_pages - p.shared_n for p in hrows)
                if self.alloc.free < need and self.prefix is not None:
                    # cached prefixes are a perf lever, live requests
                    # are work: drop LRU entries before deferring
                    self.prefix.shrink(need)
                window_need = 0 if self.window is None else sum(
                    self.window.prompt_need(p.t0) for p in hrows)
                if self.alloc.free < need or (
                        window_need
                        and self.window.alloc.free < window_need):
                    # head-of-line block: requeue this and every later
                    # pop (arrival order preserved by queue_seq)
                    for hh in take[k:]:
                        self._requeue_or_orphan(hh)
                    self._hol_rid = rid
                    self._hol_need = need
                    # a waiting request is re-popped once it could fit;
                    # count it (and log it) only on the transition INTO
                    # the deferred state, so stats()["deferred"] means
                    # distinct requests, not churn
                    if rid not in self._deferred_ids:
                        self._deferred_ids.add(rid)
                        self.deferred += 1
                        if self.metrics is not None:
                            self.metrics.event(**S.structured_event(
                                "serve_page_defer",
                                request_id=rid,
                                pages_needed=need,
                                pages_free=self.alloc.free))
                    break
                for p in hrows:
                    p.grants = self.alloc.alloc(
                        p.total_pages - p.shared_n)
                    if self.window is not None:
                        p.window_grants = self.window.alloc.alloc(
                            self.window.prompt_need(p.t0))
                fits.append(h)
                rows.extend(hrows)
                self._deferred_ids.discard(rid)
                if rid == self._hol_rid:
                    self._hol_rid = None
                    self._hol_need = 0
            take = fits
        else:
            for h in take:
                rows.extend(per_handle[h.request.request_id])

        return rows, free

    def _admit_cold(self, rows: List[_Row], free: List[int],
                    now: float) -> List[int]:
        """Bucket-grouped prefill admission of the plan's cold rows.
        Returns the free-slot indices left for the warm phase."""
        groups: Dict[int, List[_Row]] = {}
        for p in rows:
            if p.mode == "cold":
                groups.setdefault(p.bucket, []).append(p)
        for bucket, group in groups.items():
            if self.fenced:
                # fenced between groups: the rest of the admission is
                # step locals the reclaim sweep cannot see — orphan
                # them back to the shared queue
                self._orphan_handles(self._unique_handles(group))
                continue
            idx = free[:len(group)]
            free = free[len(group):]
            G = S.bucket_for(len(group), self.prefill_groups)
            # fixed-shape group, the smallest that holds the rows:
            # prompts padded to the bucket, unused rows parked on slot
            # index num_slots — out of range, so every scatter drops
            # them (mode='drop' in the program)
            text = np.zeros((G, bucket), np.int32)
            lens = np.ones((G,), np.int32)
            slots = np.full((G,), self.num_slots, np.int32)
            # paged only — unused rows' prompt rows scatter into the
            # trash page 0; dense prefill takes no page_rows at all
            page_rows = np.zeros((G, bucket), np.int32) \
                if self.kv == "paged" else None
            n_seed = np.zeros((G,), np.int32)
            n_temp = np.ones((G,), np.float32)
            n_topk = np.ones((G,), np.int32)
            n_top_p = np.zeros((G,), np.float32)
            n_partner = np.arange(G, dtype=np.int32)
            n_cfgs = np.zeros((G,), np.float32)
            n_uncond = np.zeros((G,), bool)
            for j, p in enumerate(group):
                p.slot, p.group_idx = idx[j], j
                self._fill_admit_row(p, j, lens, n_seed, n_temp, n_topk,
                                     n_top_p, n_cfgs, n_uncond)
                text[j, :p.t0] = p.codes
                slots[j] = idx[j]
                if self.kv == "paged":
                    self._bt_host[idx[j], :] = 0
                    self._bt_host[idx[j], :len(p.grants)] = p.grants
                    page_rows[j] = self._bt_host[
                        idx[j], np.arange(bucket) // self.page_size]
                    if self.window is not None:
                        # the row's page in both pools, as one int32
                        # (_prefill_fn): the full pool's id x the window
                        # pool's pages + the window pool's id
                        ring = self.window.admit(idx[j], p.t0,
                                                 p.window_grants)
                        ring = np.pad(ring, (0, p.total_pages - len(ring)))
                        page_rows[j] = page_rows[j] \
                            * self.window.alloc.num_pages + ring[
                                np.arange(bucket) // self.page_size]
            for j, p in enumerate(group):
                # a pair's rows always share the bucket, hence the group
                if p.pair_row is not None and p.pair_row in group:
                    n_partner[j] = p.pair_row.group_idx
            try:
                # explicit-transfer discipline: the admission path's
                # host->device traffic is device_put at the site, never
                # implicit conversion (guards.no_transfers-clean).
                # device=None is jax's default placement; a placed
                # replica ships straight to its own chip, a mesh engine
                # replicates across its slice
                put = self._put
                cold = (bucket, G) not in self._prefill_fns
                if cold:
                    self.compiling = True
                try:
                    self._lap("admit_plan_s")   # the group's host arrays
                    with _phase("engine.admit.put"):
                        group_args = [put(a) for a in (
                            text, lens, slots, n_seed, n_temp, n_topk,
                            n_top_p, n_partner, n_cfgs, n_uncond)]
                        paged_kw = {"page_rows": put(page_rows)} \
                            if self.kv == "paged" else {}
                    t_pre = self._lap("admit_put_s")
                    admit = self._admit_calls
                    with _phase("engine.admit.prefill", bucket=bucket,
                                mode="cold", admit=admit):
                        outs = self._prefill_fn(bucket, G)(
                            self.params, self.cache, self.cur_tok,
                            self.pos, self.active, self.rng, self.temp,
                            self.topk_k, self.top_p, *group_args,
                            **paged_kw)
                    dispatch_s = self._lap("admit_prefill_s") - t_pre
                    self._admitted(admit, G, bucket, "cold")
                    self.prefill_runs += 1
                finally:
                    if cold:
                        self.compiling = False
                        self.last_heartbeat = self.clock()
            except Exception as e:  # noqa: BLE001 — no-hangs contract
                # the group's slots were never assigned (still None) and
                # the device state is rebound only on success below, so
                # the pool stays consistent; the group's callers get a
                # typed error instead of hanging on a dead loop
                if self.kv == "paged":
                    for j, p in enumerate(group):
                        self.alloc.release(p.grants)
                        p.grants = []
                        self._bt_host[idx[j], :] = 0
                        if self.window is not None:
                            self.window.release(idx[j])
                    self._bt_dirty = True
                for h in self._unique_handles(group):
                    self._error(h, now, f"prefill failed: {e!r}")
                continue
            if self.fenced:
                # fence landed DURING the prefill call (a cold compile
                # is seconds long — exactly where a supervisor's hang
                # deadline can fire): the reclaim sweep could not see
                # this group (neither queued nor slotted, just step
                # locals), so hand it back to the shared queue instead
                # of slotting it into a dead engine
                self._orphan_handles(self._unique_handles(group))
                continue
            (self.cache, self.cur_tok, self.pos, self.active, self.rng,
             self.temp, self.topk_k, self.top_p, h_last) = outs
            t_slotted = self.clock()
            for p in group:
                i = p.slot
                self.slots[i] = _Slot(p.handle, p.t0, now,
                                      need=self._slot_need(
                                          p.handle.request, p.t0))
                if self.kv == "paged":
                    self._slot_pages[i] = list(p.grants)
                    self._pos_est[i] = p.t0
                    self._bt_dirty = True
                if not p.uncond:    # one admit span per request, not
                    #                 per slot of a guided pair
                    self._span(p.handle, "prefill_admit", t_slotted,
                               bucket=bucket, mode="cold", slot=i,
                               dispatch_s=dispatch_s, admit=admit, rows=G)
            self._wire_pairs(group)
            if self.prefix is not None:
                for p in group:
                    self._prefix_insert(p, h_last)
        return free

    def _admitted(self, admit: int, rows: int, bucket: int,
                  mode: str) -> None:
        """Admission call ``admit`` is in the device's queue: the next
        chunk dispatched runs behind it."""
        self._admit_calls = admit + 1
        self._admits_ahead.append({"admit": admit, "rows": rows,
                                   "bucket": bucket, "mode": mode})

    def _fill_admit_row(self, p: _Row, j: int, lens, n_seed, n_temp,
                        n_topk, n_top_p, n_cfgs, n_uncond) -> None:
        """One admission row's sampling knobs (shared by the cold and
        warm programs; an uncond shadow carries its cond request's
        knobs — its own draw is overwritten by the partner copy)."""
        req = p.handle.request
        lens[j] = p.t0
        # two's-complement truncation to int32: PRNGKey keeps
        # only the low 32 bits under the default x64-off mode,
        # so this is value-identical to PRNGKey(seed) eager
        s = int(req.seed) & 0xFFFFFFFF
        n_seed[j] = s - (1 << 32) if s >= (1 << 31) else s
        n_temp[j] = np.float32(req.sampling.temperature)
        n_topk[j] = max(
            int((1 - req.sampling.filter_thres) * self.cfg.total_tokens),
            1)
        n_top_p[j] = np.float32(req.sampling.top_p)
        n_cfgs[j] = np.float32(req.cfg_scale)
        n_uncond[j] = p.uncond

    def _slot_need(self, req: S.Request, t0: int) -> Optional[int]:
        """The slot's total emit budget under ``image_seq_len_override``
        (text fill + capped image span), None for a full-length request.
        Decode stops at the budget on the HOST — harvest truncates the
        final chunk and completes the slot early — so the one compiled
        full-length program serves every override; the cost ceiling is
        at most one chunk of wasted device steps past the cap."""
        L = int(req.image_seq_len_override)
        if not L:
            return None
        return (self.cfg.text_seq_len - t0) + L

    def _unique_handles(self, group: List[_Row]) -> List[S.RequestHandle]:
        out, seen = [], set()
        for p in group:
            rid = p.handle.request.request_id
            if rid not in seen:
                seen.add(rid)
                out.append(p.handle)
        return out

    def _wire_pairs(self, group: List[_Row]) -> None:
        """Link freshly slotted cond/uncond pairs (host bookkeeping +
        the device-side partner/scale/uncond state)."""
        for p in group:
            if p.pair_row is None or p.uncond:
                continue
            i, j = p.slot, p.pair_row.slot
            self.slots[i].pair = j
            self.slots[j].shadow_of = i
            self._cfg_wire(i, j, p.handle.request.cfg_scale)
            self.cfg_pairs += 1

    def _prefix_insert(self, p: _Row, h_last) -> None:
        """Index a cold row's freshly prefilled prompt span: the full
        prompt pages (retained by the index), a COW snapshot of the
        partial boundary page, and the last hidden row. Taken NOW —
        before any decode chunk can write rows >= t0 into the boundary
        page — so the cached copy is immutable from here on."""
        if p.key is None or p.key in self.prefix:
            return
        from dalle_pytorch_tpu.serve import prefix_cache as PC
        i = p.slot
        s_full = p.t0 // self.page_size
        snap = None
        if p.t0 % self.page_size:
            pid = self._slot_pages[i][s_full]
            snap = self._snap_fn(self.cache, self._put(np.int32(pid)))
        self.prefix.insert(PC.PrefixEntry(
            p.key, p.codes, p.t0, self._slot_pages[i][:s_full], snap,
            h_last[p.group_idx]))

    def _admit_warm(self, rows: List[_Row], free: List[int],
                    now: float) -> None:
        """Zero-prefill admission of the plan's warm rows: map shared
        pages (refcount++), fork boundary pages copy-on-write, and run
        the ONE warm-admission program for first tokens + state merge."""
        warm: List[_Row] = []
        for p in rows:
            if p.mode not in ("warm", "warm_pending"):
                continue
            if p.pair_row is not None and p.uncond:
                continue            # handled with its cond row below
            hrows = [p] + ([p.pair_row] if p.pair_row is not None else [])
            resolved = True
            for q in hrows:
                if q.entry is None:
                    q.entry = self.prefix.lookup(q.key, q.codes)
                if q.entry is None \
                        or len(q.entry.full_pages) != q.shared_n:
                    # the cold sibling whose insert this warm-after
                    # rode never landed (its prefill failed): give the
                    # pages back and retry cold next pop
                    resolved = False
            if not resolved or self.fenced:
                for q in hrows:
                    if q.grants:
                        self.alloc.release(q.grants)
                        q.grants = []
                self._requeue_or_orphan(p.handle)
                continue
            warm.extend(hrows)
        if not warm:
            return
        import jax
        import jax.numpy as jnp
        G = self.num_slots
        lens = np.ones((G,), np.int32)
        slots = np.full((G,), self.num_slots, np.int32)
        n_seed = np.zeros((G,), np.int32)
        n_temp = np.ones((G,), np.float32)
        n_topk = np.ones((G,), np.int32)
        n_top_p = np.zeros((G,), np.float32)
        n_partner = np.arange(G, dtype=np.int32)
        n_cfgs = np.zeros((G,), np.float32)
        n_uncond = np.zeros((G,), bool)
        h_rows = []
        mapped: List[_Row] = []
        coldw = self.warm_admit_traces == 0
        if coldw:
            self.compiling = True
        try:
            try:
                for j, p in enumerate(warm):
                    i = free[j]
                    p.slot, p.group_idx = i, j
                    entry = p.entry
                    # the tentpole's read-only-sharing proof, asserted
                    # at every warm mapping: shared pages all lie wholly
                    # below t0, and decode only ever appends at
                    # positions >= t0 — so _store_rows_paged can never
                    # scatter into a shared page
                    assert p.t0 >= p.shared_n * self.page_size, \
                        "shared prefix pages must end at/below the " \
                        "prompt length"
                    self.alloc.retain(entry.full_pages)
                    mapped.append(p)
                    pages = list(entry.full_pages) + list(p.grants)
                    self._bt_host[i, :] = 0
                    self._bt_host[i, :len(pages)] = pages
                    if entry.boundary_snap is not None:
                        # COW fork: the consumer's private boundary page
                        # starts as a byte copy of the cached one, then
                        # diverges under its own decode writes
                        self.cache = self._restore_fn(
                            self.cache, self._put(np.int32(p.grants[0])),
                            entry.boundary_snap)
                    self._fill_admit_row(p, j, lens, n_seed, n_temp,
                                         n_topk, n_top_p, n_cfgs,
                                         n_uncond)
                    slots[j] = i
                    h_rows.append(entry.h_last)
                for j, p in enumerate(warm):
                    if p.pair_row is not None and p.pair_row in warm:
                        n_partner[j] = p.pair_row.group_idx
                if len(h_rows) < G:
                    # pad with a live row, not zeros_like (whose fill
                    # scalar would be an implicit host->device
                    # transfer): pad rows scatter to the dropped
                    # out-of-range slot index, so their values never
                    # land anywhere
                    h_rows = h_rows + [h_rows[0]] * (G - len(h_rows))
                h_stack = jnp.stack(h_rows)
                put = self._put
                self._lap("admit_plan_s")
                with _phase("engine.admit.put"):
                    group_args = [put(a) for a in (
                        lens, slots, n_seed, n_temp, n_topk, n_top_p,
                        n_partner, n_cfgs, n_uncond)]
                t_warm = self._lap("admit_put_s")
                admit = self._admit_calls
                with _phase("engine.admit.prefill", mode="warm",
                            admit=admit):
                    outs = self._warm_admit_fn()(
                        self.params, self.cur_tok, self.pos, self.active,
                        self.rng, self.temp, self.topk_k, self.top_p,
                        h_stack, *group_args)
                dispatch_s = self._lap("admit_prefill_s") - t_warm
                self._admitted(admit, G, 0, "warm")
            finally:
                if coldw:
                    self.compiling = False
                    self.last_heartbeat = self.clock()
        except Exception as e:  # noqa: BLE001 — no-hangs contract
            # nothing was slotted: give back every reference the
            # mapping loop took (shared retains + private grants) and
            # the un-mapped rows' grants, then fail the callers typed
            for p in warm:
                if p in mapped:
                    self.alloc.release(list(p.entry.full_pages)
                                       + list(p.grants))
                    self._bt_host[p.slot, :] = 0
                elif p.grants:
                    self.alloc.release(p.grants)
                p.grants = []
            self._bt_dirty = True
            for h in self._unique_handles(warm):
                self._error(h, now, f"warm admission failed: {e!r}")
            return
        if self.fenced:
            # same contract as the prefill-call fence: not slotted, so
            # the reclaim sweep cannot see these — orphan them
            self._orphan_handles(self._unique_handles(warm))
            return
        (self.cur_tok, self.pos, self.active, self.rng, self.temp,
         self.topk_k, self.top_p) = outs
        t_slotted = self.clock()
        for p in warm:
            i = p.slot
            self.slots[i] = _Slot(p.handle, p.t0, now,
                                  need=self._slot_need(
                                      p.handle.request, p.t0))
            self._slot_pages[i] = list(p.entry.full_pages) + \
                list(p.grants)
            self._pos_est[i] = p.t0
            self._bt_dirty = True
            self.prefix_hits += 1
            self.warm_admits += 1
            if not p.uncond:
                self._span(p.handle, "prefill_admit", t_slotted,
                           mode="warm", slot=i,
                           pages_shared=p.shared_n,
                           dispatch_s=dispatch_s, admit=admit, rows=G)
            if self.metrics is not None:
                self.metrics.event(**S.structured_event(
                    "serve_prefix_hit",
                    request_id=p.handle.request.request_id,
                    uncond=p.uncond, pages_shared=p.shared_n,
                    pages_private=len(p.grants)))
        self._wire_pairs(warm)

    # -- page-pool lifecycle (kv='paged') -----------------------------------

    def _release_slot_pages(self, i: int) -> None:
        """Drop slot i's page REFERENCES back to the pool and zero its
        block-table row (completion/expiry/eviction/terminate). Under
        prefix sharing a reference drop is not necessarily a free: a
        shared prompt page stays resident while the index (or a sibling
        slot) still maps it — the refcounted allocator frees only at
        zero. The stale device-side row needs no urgent push: the dead
        slot's writes are redirected to the trash page inside the
        program (active=False), and reads of re-assigned pages are
        causally masked."""
        if self._slot_pages[i]:
            self.alloc.release(self._slot_pages[i])
            self._slot_pages[i] = []
        if self.window is not None:
            self.window.release(i)
        self._bt_host[i, :] = 0
        self._pos_est[i] = 0
        self._bt_dirty = True

    def _free_slot(self, i: int) -> List[int]:
        """The one slot-teardown path (completion/expiry/eviction/
        terminate): vacate the slot and, in paged mode, return its page
        references to the pool — forgetting the paged branch would leak
        pages until the pool wedged, so no call site spells it out by
        hand. A guided pair tears down ATOMICALLY: freeing the cond
        slot frees its uncond shadow too (the shadow is never freed on
        its own — it has no life of its own to end). Returns the freed
        slot indices, so callers that must clear device active bits
        (expiry, eviction) kill every member."""
        slot = self.slots[i]
        freed = [i]
        self.slots[i] = None
        if self.kv == "paged":
            self._release_slot_pages(i)
        self._cfg_reset(i)
        j = slot.pair if slot is not None else None
        if j is not None and self.slots[j] is not None \
                and self.slots[j].shadow_of == i:
            self.slots[j] = None
            if self.kv == "paged":
                self._release_slot_pages(j)
            self._cfg_reset(j)
            freed.append(j)
        return freed

    def _evict_lowest_priority(self, now: float) -> bool:
        """The PagePoolExhausted backpressure path: evict the LOWEST-
        priority active request (highest priority value; ties broken by
        latest admission) back to the queue. Its page references are
        dropped — under sharing, a page a sibling (or the prefix index)
        still maps stays OUT of the free list: the refcounted release
        is what makes eviction safe next to copy-on-write sharing — its
        device slot(s) killed, and its handle re-queued intact; on
        re-admission, deterministic sampling (same seed, same fold_in
        positions) replays its exact token stream, so eviction costs
        latency, never correctness. A guided pair evicts whole. Returns
        False when no slot is active."""
        if self.fenced:
            return False    # the reclaim sweep owns every in-slot handle
        cand = [(s.handle.request.priority, s.t_admit, i)
                for i, s in enumerate(self.slots)
                if s is not None and s.shadow_of is None]
        if not cand:
            return False
        _, _, i = max(cand)
        slot = self.slots[i]
        free_before = self.alloc.free
        killed = self._free_slot(i)
        freed = self.alloc.free - free_before
        keep = np.ones((self.num_slots,), bool)
        keep[killed] = False
        self.active = self._kill_fn(self.active, self._put(keep))
        self.evicted += 1
        # un-credit the victim's harvested tokens: re-admission replays
        # them all, so leaving the prefix counted would inflate
        # tokens_decoded/mean_occupancy by one prefix per eviction (the
        # same double-count _harvest_chunk avoids by dropping the
        # orphaned mid-flight ring row)
        self.tokens_decoded -= len(slot.emitted)
        self.occupancy_sum -= len(slot.emitted)
        # the eviction is a visible timeline marker: the victim's next
        # spans (re-queue wait, re-admission, full replay) follow it
        self._span(slot.handle, "evict", now, pages_freed=freed)
        self._requeue_or_orphan(slot.handle)
        if self.metrics is not None:
            self.metrics.event(**S.structured_event(
                "serve_evict", request_id=slot.handle.request.request_id,
                priority=slot.handle.request.priority, pages_freed=freed,
                pages_free=self.alloc.free,
                waited_s=round(now - slot.handle.request.submit_t, 4)))
        return True

    def _map_ahead(self, now: float) -> None:
        """Grow-by-one-page, BEFORE every chunk dispatch: each active
        slot's block table must map every row the K fused steps could
        write ([pos, pos+K)), so a page-boundary crossing inside the
        chunk never needs a host sync. Growth works off the host's safe
        pos upper bound (``_pos_est``); when the free list runs dry the
        typed ``PagePoolExhausted`` is converted into evictions of the
        lowest-priority active request until the remainder fits (a full
        sequence always fits the pool alone, so this terminates — in the
        limit the growing slot evicts itself and re-queues)."""
        from dalle_pytorch_tpu.serve import kv_pool as KV
        for i in range(self.num_slots):
            while self.slots[i] is not None:
                # _chunk_span covers the SPECULATIVE horizon: a chunk
                # can write up to chunk_steps*k rows, and every one must
                # find its page mapped (rejected offsets write too —
                # their rows are stale-by-invariant, not unmapped)
                target = min(self._pos_est[i] + self._chunk_span,
                             self.total_len)
                short = max(KV.pages_for(target, self.page_size)
                            - len(self._slot_pages[i]), 0)
                # a window pool's ring grows to its width and then turns:
                # its pages are reused in place (KV.WindowPages.grow)
                window_short = 0 if self.window is None \
                    else self.window.short(i, target)
                if not short and not window_short:
                    if self.window is not None:
                        self.window.grow(i, target)
                    break
                window_ok = self.window is None or \
                    self.window.alloc.free >= window_short
                if self.alloc.free < short and self.prefix is not None:
                    # drop cached prefixes (LRU first) before evicting
                    # live work — an index-held page a live slot no
                    # longer shares frees immediately at release
                    self.prefix.shrink(short)
                if self.alloc.free >= short and window_ok:
                    for p in self.alloc.alloc(short):
                        self._bt_host[i, len(self._slot_pages[i])] = p
                        self._slot_pages[i].append(p)
                        self._bt_dirty = True
                    if self.window is not None:
                        self.window.grow(i, target)
                    break
                # pool exhausted mid-decode: typed backpressure — the
                # victim may be slot i itself, which ends its while loop
                if not self._evict_lowest_priority(now):
                    # unreachable while slot i is active (it is its own
                    # candidate); defensive: never spin on a dry pool
                    break

    def _sync_block_tables(self) -> None:
        """Push the host's authoritative block tables to the device when
        the mapping changed — ONE explicit device_put of a few KB, the
        only paged-specific host->device traffic in steady state."""
        if self._bt_dirty or (self.window is not None
                              and self.window.dirty):
            self.block_tables = self._put(self._tables_host())
            self._bt_dirty = False
            if self.window is not None:
                self.window.dirty = False

    def _tables_host(self):
        """What the decode program takes as its block tables: the one
        table, or a table a pool of a window-and-full block."""
        if self.window is None:
            return self._bt_host
        return {"full": self._bt_host, "window": self.window.tables}

    # -- the fused-chunk pipeline -------------------------------------------

    def _dispatch_chunk(self, now: float) -> None:
        """Launch one K-step fused program from the current device state
        and queue its emit ring for a later harvest. No host sync here:
        the outputs are futures, and the device starts computing while
        the host goes on to admit/harvest."""
        cold = self.decode_traces == 0      # first call traces+compiles
        if self._profile_req is not None and self._profiler is None \
                and self.decode_traces > 0:
            # consume the armed request HERE, on the engine thread that
            # advances the dispatch counter — "profile the next K
            # chunks" starts at exactly this dispatch, whatever index
            # it happens to be (an HTTP-thread-precomputed index could
            # be skipped forever if a dispatch raced the arm). Never on
            # the COLD dispatch: a trace wrapping the one-time decode
            # compile swamps the capture AND its stop-time xplane
            # serialization can stall this thread past the replica
            # hang deadline — a capture armed before warm-up simply
            # begins at the first steady-state chunk
            with self._profile_lock:
                req, self._profile_req = self._profile_req, None
            if req is not None:
                from dalle_pytorch_tpu.utils.profiling import StepProfiler
                log_dir, chunks = req
                start = self.decode_steps // self.chunk_steps
                prof = StepProfiler(log_dir, start=start, steps=chunks)
                # stop after the chunks already in flight (they harvest
                # first, FIFO) plus ours have ALL harvested — a relative
                # countdown, immune to any dispatch/harvest skew a past
                # fail_active left behind
                self._profile_left = len(self._pending) + chunks
                # publish BEFORE start_trace: the call can block for
                # seconds syncing behind another replica's in-flight
                # compile, and profile_active() is the supervisor's
                # hang-deadline exemption for exactly that stall
                self._profiler = prof
                try:
                    prof.maybe_start(start)
                except BaseException:
                    self._profiler = None
                    raise
        if cold:
            self.compiling = True
        try:
            self._sync_cfg()
            if self.kv == "paged":
                self._map_ahead(now)
                self._sync_block_tables()
                self._pages_samples.append(self.alloc.in_use)
                outs = self._decode_fn(self.params, self.cache,
                                       self.block_tables, self.cur_tok,
                                       self.pos, self.active, self.rng,
                                       self.temp, self.topk_k, self.top_p,
                                       self.cfg_partner, self.cfg_scale,
                                       self.cfg_uncond)
            else:
                outs = self._decode_fn(self.params, self.cache,
                                       self.cur_tok, self.pos,
                                       self.active, self.rng, self.temp,
                                       self.topk_k, self.top_p,
                                       self.cfg_partner, self.cfg_scale,
                                       self.cfg_uncond)
        finally:
            if cold:
                self.compiling = False
                self.last_heartbeat = self.clock()
        # a described block's program returns its routed load after the ring
        self.cur_tok, self.pos, self.active, self.cache, ring, *load = outs
        owners = [(i, s) for i, s in enumerate(self.slots)
                  if s is not None]
        view_read_pct = self._view_columns(owners)
        if self.kv == "paged":
            for i, _ in owners:
                self._pos_est[i] = min(self._pos_est[i] + self._chunk_span,
                                       self.total_len)
        self._pending.append(_Chunk(
            ring, self.active, owners, *load,
            chunk=self.decode_steps // self.chunk_steps,
            t_dispatch=self._lap("dispatch_s"),
            admits_ahead=self._admits_ahead, view_read_pct=view_read_pct))
        self._admits_ahead = []
        self.decode_steps += self.chunk_steps
        self.sample_sorted_chunks += any(
            s.handle.request.sampling.top_p > 0 for _, s in owners)

    def _view_columns(self, owners) -> Optional[float]:
        """Count what the chunk being dispatched reads of its block
        table: the width rule of the paged gather reads is a pure
        function of the slots' positions and the program's shapes, so
        the host evaluates it too (``ops.decode.ViewPlan.columns_read``:
        the layers that read at the step's width profile and those that
        read whole, as the traced program has them), at the positions of
        every step of the chunk (``_pos_est`` before the chunk and one
        further a step; a free slot is parked at 0), into
        ``kv_view_columns_read`` / ``kv_view_columns_full``. -> the
        chunk's own share in percent (the ledger row's
        ``view_read_pct``), None where no layer reads by the rule (the
        dense cache, the kernel, ``sparse_reads``, the speculative
        verify, a block whose ordered reads are all runs of one layer)."""
        if self._view_plan is None:
            return None
        pos = np.zeros((self.num_slots,), np.int64)
        for i, _ in owners:
            pos[i] = self._pos_est[i]
        read, full = self._view_plan.columns_read(pos, self.chunk_steps)
        self.kv_view_columns_read += read
        self.kv_view_columns_full += full
        return 100.0 * read / full

    def _harvest_chunk(self) -> None:
        """Fetch the OLDEST in-flight chunk's emit ring — the single
        explicit host sync per K steps. Distributes each slot's tokens
        to its owner at dispatch time and completes slots whose request
        finished inside the chunk. Completion is timestamped HERE: a
        request that emitted its last token mid-chunk becomes observable
        to its caller only when the ring lands on the host, so harvest
        time is the honest fulfillment time (docs/SERVING.md)."""
        import jax
        rec = self._pending.popleft()
        with _phase("engine.harvest_wait", chunk=rec.chunk):
            # the routed load rides the ring's fetch (None: an empty tree)
            ring, active_after, load = jax.device_get(
                [rec.ring, rec.active, rec.load])
        t_got = self._lap("harvest_wait_s")
        # jaxlint: disable=JL007 — the profiler's host line is on this
        # clock (docs/OBSERVABILITY.md): a reading, not duration math
        unix_ns = time.time_ns()
        if load is not None:
            for k, v in zip(load_counters(self.block), load):
                have = getattr(self, k)     # an int; a sink's weight a float
                setattr(self, k, have + type(have)(v))
        with _phase("engine.deliver", chunk=rec.chunk):
            tokens = self._deliver_chunk(rec, ring, active_after)
        self._ledger_row(rec, t_got, unix_ns, tokens)

    def _ledger_row(self, rec: _Chunk, t_harvest: float, unix_ns: int,
                    tokens: int) -> None:
        """Close the ledger's open interval with this chunk's row, and
        hold it against its class's median: a stall is an event."""
        t_cut = self._lap("deliver_s")
        phases = self._lap_s
        self._lap_s = dict.fromkeys(LOOP_PHASES, 0.0)
        cut_t, cut_cpu, cut_traces, cut_gc_runs, cut_gc_s = self._cut
        self._cut = (t_cut, time.thread_time(), self.decode_traces
                     + self.prefill_traces + self.warm_admit_traces,
                     *self._gc)
        interval = t_cut - cut_t
        behind = bool(rec.admits_ahead)
        self.chunks_behind_admit += behind
        row = {"chunk": rec.chunk, "t_dispatch": rec.t_dispatch,
               "t_harvest": t_harvest, "unix_ns": unix_ns,
               "interval_s": interval,
               "steps": self.chunk_steps, "live_slots": len(rec.owners),
               "tokens": tokens, "admits_ahead": len(rec.admits_ahead),
               "admit_rows": sum(a["rows"] for a in rec.admits_ahead),
               "admit_calls": self._admit_calls,
               "pending": len(self._pending),
               "view_read_pct": rec.view_read_pct, **phases}
        self.loop_ring.record(row)
        # the device's answer to the stall before: near zero, it had gone
        # on and only that fetch was late; a whole chunk, it stood still
        if self._stall_open is not None:
            self._emit_stall(phases["harvest_wait_s"] if rec.chunk
                             == self._stall_open["chunk"] + 1 else None)
        # an interval that opened on an empty pipeline holds idle time,
        # one that traced a program its compile: neither is judged
        if rec.t_dispatch > cut_t or self._cut[2] != cut_traces:
            return
        seen = self._intervals[behind]
        median = statistics.median(seen) if len(seen) >= STALL_MIN else None
        seen.append(interval)
        if median is None or interval <= STALL_FACTOR * median:
            return
        self.loop_stalls += 1
        self.loop_stall_s += interval - median
        self._stall_open = {
            **row, "phase": max(phases, key=phases.get),
            "median_s": median, "excess_s": interval - median,
            "thread_cpu_s": self._cut[1] - cut_cpu,
            "gc_runs": self._cut[3] - cut_gc_runs,
            "gc_s": self._cut[4] - cut_gc_s,
            "queue_depth": self.queue.depth(), "next_wait_s": None}
        self._stalls.append(self._stall_open)
        if not self._pending:       # no chunk behind it to answer
            self._emit_stall(None)

    def _emit_stall(self, next_wait_s: Optional[float]) -> None:
        """The open stall, whole: to the ledger's ring and the event
        sinks (a JSONL log, the flight ring, ``GET /debug/events``)."""
        stall, self._stall_open = self._stall_open, None
        stall["next_wait_s"] = next_wait_s
        event = S.structured_event("serve_loop_stall", **stall)
        self.loop_ring.record(event)
        if self.metrics is not None:
            self.metrics.event(**event)

    def _deliver_chunk(self, rec: _Chunk, ring, active_after) -> int:
        """The host's half of a harvest, once the ring has landed: rings
        to sinks and owners, spans, completions, the kill mask. Returns
        the tokens delivered."""
        self.harvests += 1
        if self._profiler is not None:
            # chunks harvest FIFO, so the countdown set at capture
            # start reaches zero exactly when the LAST captured chunk
            # has finished EXECUTING (the device_get above synced it),
            # not merely been dispatched
            self._profile_left -= 1
            if self._profile_left <= 0:
                self._finish_profile()
        now = self.clock()
        # the harvest's device_get is the one blocking sync in steady
        # state — exactly where a wedged device stalls the thread, so
        # stamping the heartbeat here makes the supervisor's missed-
        # heartbeat deadline measure real progress, not loop liveness
        self.last_heartbeat = now
        emitted = 0
        kill: List[int] = []
        for i, slot in rec.owners:
            if slot.shadow_of is not None:
                # uncond shadow of a guided pair: its ring row mirrors
                # the cond stream (partner copy) — crediting it would
                # double-count delivered tokens, and it completes with
                # its cond slot, never on its own
                continue
            if slot.handle.done() or self.slots[i] is not slot:
                # expired/killed/errored/EVICTED since dispatch — its
                # ring row is dead (an evicted request replays every
                # token on re-admission, so crediting these to the
                # orphaned slot would double-count them in
                # tokens_decoded/occupancy), and slot i may already
                # belong to a newer request whose tokens start in a
                # later chunk
                continue
            row = ring[i]
            toks = row[row >= 0]
            capped = False
            if slot.need is not None:
                # image_seq_len_override: the device decodes the full
                # sequence shape, the host stops delivering at the
                # budget — truncate the final chunk and complete early
                left = slot.need - len(slot.emitted)
                if len(toks) >= left:
                    toks = toks[:left]
                    capped = True
            slot.emitted.extend(int(t) for t in toks)
            emitted += len(toks)
            sink = slot.handle.sink
            if sink is not None and len(toks):
                # live token stream: positions are absolute sequence
                # offsets (>= text_seq_len means image tokens), which
                # is what lets the sink dedupe an eviction/failover
                # REPLAY — re-delivered positions below its high-water
                # mark are dropped, so the consumer sees each position
                # exactly once. Never blocks: overflow is the sink's
                # typed drop policy, not engine backpressure.
                sink.push_tokens(
                    slot.t0 + len(slot.emitted) - len(toks),
                    [int(t) for t in toks])
                if (self.on_preview is not None and self.preview_every
                        and not capped):
                    slot.since_preview += 1
                    img_done = len(slot.emitted) \
                        - (self.cfg.text_seq_len - slot.t0)
                    if slot.since_preview >= self.preview_every \
                            and img_done > 0:
                        slot.since_preview = 0
                        prefix = np.asarray(
                            slot.emitted[self.cfg.text_seq_len
                                         - slot.t0:], np.int32)
                        self.on_preview(slot.handle, prefix)
            if self.speculative:
                # acceptance accounting over DELIVERED tokens only: a
                # round's k-wide ring window holds its accepted prefix,
                # -1 past it — rejected drafts never reach the host, so
                # tokens_decoded/occupancy stay exact for free. The
                # denominator is each round's true potential (k,
                # clamped to the sequence end — pos before the round is
                # recoverable by walking the windows cumulatively), so
                # a full-depth draft scores exactly 1.0
                kk = self.speculative
                pos_cursor = slot.t0 + len(slot.emitted) - len(toks)
                for w in ring[i].reshape(-1, kk):
                    n = int((w >= 0).sum())
                    if n == 0:
                        continue
                    self.spec_rounds += 1
                    self.spec_proposed += min(
                        kk, self.total_len - pos_cursor)
                    pos_cursor += n
                self.spec_delivered += len(toks)
            if self.kv == "paged" and self.speculative:
                # tighten the host's pos bound with the truth the ring
                # just delivered: the dispatch-time advance assumed full
                # acceptance (k per round), so under low acceptance the
                # estimate (and page map-ahead) would creep ahead of the
                # device; pos == t0 + len(emitted) is exact, plus one
                # full span per chunk still in flight
                later = sum(1 for c in self._pending
                            if any(j == i for j, _ in c.owners))
                exact = slot.t0 + len(slot.emitted)
                bound = min(exact + self._chunk_span * later,
                            self.total_len)
                self._pos_est[i] = min(self._pos_est[i], bound)
                if slot.pair is not None:
                    # the uncond shadow's stream is the partner copy —
                    # identical accepted lengths, identical pos
                    self._pos_est[slot.pair] = \
                        min(self._pos_est[slot.pair], bound)
            if len(toks):
                # per-chunk decode attribution: one span per harvested
                # chunk per request, tiling from the previous harvest
                # (or the admit) to THIS harvest — where the request's
                # decode milliseconds actually went
                self._span(slot.handle, "decode_chunk", now,
                           tokens=int(len(toks)), chunk=rec.chunk,
                           admits_ahead=len(rec.admits_ahead))
            if capped:
                # the budget is met mid-sequence: the device bit is
                # still up, so completion must also kill the slot's
                # mask entry (and its shadow's) or the freed slot
                # would keep decoding a ghost
                pair = slot.pair
                self._complete(i, slot, now)
                kill.append(i)
                if pair is not None:
                    kill.append(pair)
            elif not bool(active_after[i]):
                self._complete(i, slot, now)
        if kill:
            keep = np.ones((self.num_slots,), bool)
            keep[kill] = False
            self.active = self._kill_fn(self.active, self._put(keep))
        self.tokens_decoded += emitted
        self.occupancy_sum += emitted
        return emitted

    def _complete(self, i: int, slot: _Slot, now: float) -> None:
        """Fulfil a finished slot's request and free the slot (its device
        state already parked itself inside the fused program)."""
        req = slot.handle.request
        full = list(req.codes) + slot.emitted
        # override requests deliver their capped span (full holds
        # text_seq_len + L tokens then — the host stopped at the budget)
        L = int(req.image_seq_len_override) or self.cfg.image_seq_len
        img_seq = np.asarray(full[-L:], np.int32)
        # the completed text span (prompt + sampled text tokens) —
        # generate_images' full[:, :text_seq_len], what CLIP rerank
        # scores (postprocess.py)
        text_seq = np.asarray(full[:self.cfg.text_seq_len], np.int32)
        self.completed += 1
        self._finish(slot.handle, S.Result(
            status=S.OK, request_id=req.request_id, tokens=img_seq,
            text_tokens=text_seq,
            queued_s=round(slot.t_admit - req.submit_t, 6),
            decode_s=round(now - slot.t_admit, 6),
            total_s=round(now - req.submit_t, 6)))
        self._free_slot(i)

    # -- live migration (kv='paged') ----------------------------------------
    #
    # A slot's entire decode state is movable: KV pages (fp32 or
    # int8+scales), block-table order, device sampling state (pos,
    # cur_tok, the base RNG key, temp/topk/top_p), the CFG shadow, and
    # the host's emitted-token prefix. export_slot snapshots all of it
    # into one JSON-safe payload (MIGRATE frames CRC+seq-check it like
    # every other frame) and vacates the slot WITHOUT fulfilling or
    # requeueing the handle — the request now lives in the payload, and
    # import_slot installs it on a target engine with freshly allocated
    # pages. Byte-identity holds because sampling is deterministic in
    # (rng row, position) — fold_in(key, pos) — and every input to the
    # fused program's next step ships: the continuation is the exact
    # token stream the undisturbed run would have produced. Any failure
    # is the typed MigrationError; the caller falls back to replay.

    def _migrate_install_fn(self):
        """The import-side state merge: same ``.at[slots].set`` scatter
        as warm admission (unused rows aimed at the dropped out-of-range
        index), but with pos/cur_tok/rng taken verbatim from the
        exported device rows instead of re-derived. Compiled once."""
        if self._install_fn is not None:
            return self._install_fn

        def install(cur_tok, pos, active, rng, temp, topk_k, top_p,
                    slots, n_tok, n_pos, n_rng, n_temp, n_topk, n_top_p):
            cur_tok = cur_tok.at[slots].set(n_tok, mode="drop")
            pos = pos.at[slots].set(n_pos, mode="drop")
            active = active.at[slots].set(True, mode="drop")
            rng = rng.at[slots].set(n_rng, mode="drop")
            temp = temp.at[slots].set(n_temp, mode="drop")
            topk_k = topk_k.at[slots].set(n_topk, mode="drop")
            top_p = top_p.at[slots].set(n_top_p, mode="drop")
            return cur_tok, pos, active, rng, temp, topk_k, top_p

        self._install_fn = self._jit_warm_program(install)
        return self._install_fn

    def _refuse_block_migration(self) -> None:
        """A described block's slots are not exported or imported (the
        payload's format is the classic block's one pool): the typed
        ``MigrationError`` whose callers fall back to replay, naming the
        block and the option as every other refusal of it does."""
        if self.block is not None:
            from dalle_pytorch_tpu.ops.transformer import BlockOptionError
            raise MigrationError("block", str(BlockOptionError(
                self.block.name, "slot export/import (MIGRATE frames)")))

    def find_slot(self, request_id: int) -> Optional[int]:
        """The cond slot index holding ``request_id`` (None when not
        in-slot — queued, mid-admission, or already gone)."""
        for i, s in enumerate(self.slots):
            if s is not None and s.shadow_of is None \
                    and s.handle.request.request_id == int(request_id):
                return i
        return None

    def _export_pages(self, pages: List[int]) -> List[dict]:
        import jax
        out = []
        for pid in pages:
            snap = self._snap_fn(self.cache, self._put(np.int32(pid)))
            host = jax.device_get(snap)
            out.append({k: _pack_array(v) for k, v in host.items()})
        return out

    def export_slot(self, i: int):
        """Snapshot slot ``i``'s full decode state into a JSON-safe
        migration payload and VACATE the slot (pages released, device
        active bit cleared, handle neither fulfilled nor requeued — the
        caller owns it now). A guided pair exports atomically: the
        uncond shadow's pages and device rows ride in the same payload.
        Returns ``(payload, handle)``; raises the typed
        ``MigrationError`` on any precondition failure, leaving the
        slot untouched."""
        import jax
        with self._lock:
            if self.fenced:
                raise MigrationError("fenced")
            self._refuse_block_migration()
            if self.kv != "paged":
                raise MigrationError(
                    "kv_dense", "migration moves KV pages; the dense "
                    "slot cache has none")
            # flush the in-flight pipeline first: the device pos and the
            # host's emitted list must describe the SAME point in the
            # stream, and no orphaned ring row may outlive the export
            self._lap("between_s")
            while self._pending:
                # racelint: disable=RL003 — deliberate: _lock IS the
                # step serializer; an export must flush (and sync) under
                # it or the snapshot tears against a concurrent step
                self._harvest_chunk()
            slot = self.slots[i] if 0 <= i < self.num_slots else None
            if slot is None or slot.shadow_of is not None:
                raise MigrationError("not_found", f"slot {i}")
            if slot.handle.done():
                # completed inside the flushed chunks — nothing to move
                raise MigrationError("not_found",
                                     "request completed during export")
            now = self.clock()
            # racelint: disable=RL003 — deliberate: the exported decode
            # state must be fetched under the step serializer, or the
            # snapshot tears against a concurrent step
            snap = jax.device_get((self.pos, self.cur_tok, self.rng,
                                   self.temp, self.topk_k, self.top_p))
            (pos_h, tok_h, rng_h, temp_h, topk_h, topp_h) = snap

            def rows(j):
                return {"pos": int(pos_h[j]), "cur_tok": int(tok_h[j]),
                        "rng": [int(x) for x in rng_h[j]],
                        "temp": float(temp_h[j]),
                        "topk_k": int(topk_h[j]),
                        "top_p": float(topp_h[j]),
                        "pages": self._export_pages(self._slot_pages[j])}

            payload = {
                "format": MIGRATE_FORMAT,
                "request_id": int(slot.handle.request.request_id),
                "handle": slot.handle.to_wire(now),
                "emitted": [int(t) for t in slot.emitted],
                "t0": int(slot.t0),
                "weights_version": self.weights_version,
                "page_size": int(self.page_size),
                "quantized": bool(self.quantize_cache),
                "cond": rows(i),
                "uncond": None,
            }
            j = slot.pair
            if j is not None and self.slots[j] is not None \
                    and self.slots[j].shadow_of == i:
                payload["uncond"] = rows(j)
                payload["uncond"]["cfg_scale"] = float(
                    slot.handle.request.cfg_scale)
            handle = slot.handle
            self._span(handle, "migrate_out", now,
                       slot=i, pos=int(pos_h[i]),
                       tokens_done=len(slot.emitted))
            killed = self._free_slot(i)
            keep = np.ones((self.num_slots,), bool)
            keep[killed] = False
            self.active = self._kill_fn(self.active, self._put(keep))
            return payload, handle

    def export_request(self, request_id: int):
        """``export_slot`` addressed by request id (the MIGRATE_OUT
        frame's form — a parent names requests, not slot indices)."""
        i = self.find_slot(request_id)
        if i is None:
            raise MigrationError("not_found", f"request {request_id} "
                                 "is not in a slot on this engine")
        return self.export_slot(i)

    def import_slot(self, payload: dict,
                    handle: Optional[S.RequestHandle] = None) -> int:
        """Install an exported slot on THIS engine: allocate fresh
        pages, restore the snapshot into them, scatter the exported
        device rows into free slot(s), and resume harvesting where the
        source left off. ``handle`` is the live handle in-process
        (thread replicas); None reconstructs a stand-in from the
        payload's wire form (a child worker). Returns the cond slot
        index; raises the typed ``MigrationError`` (target unchanged)
        when the request cannot land here."""
        with self._lock:
            if self.fenced:
                raise MigrationError("fenced")
            self._refuse_block_migration()
            if self.kv != "paged":
                raise MigrationError("kv_dense")
            if str(payload.get("weights_version")) != self.weights_version:
                raise MigrationError(
                    "weights_version",
                    f"snapshot from {payload.get('weights_version')!r}, "
                    f"target serves {self.weights_version!r} — tokens "
                    "are byte-identical PER weight generation only")
            if int(payload.get("page_size", 0)) != self.page_size:
                raise MigrationError(
                    "page_size", f"snapshot pages hold "
                    f"{payload.get('page_size')} rows, target pool "
                    f"holds {self.page_size}")
            if bool(payload.get("quantized")) != self.quantize_cache:
                raise MigrationError(
                    "layout", "int8-KV snapshot into an fp32 pool (or "
                    "vice versa)")
            if int(payload.get("format", 0)) != MIGRATE_FORMAT:
                raise MigrationError(
                    "layout", f"snapshot pages of format "
                    f"{payload.get('format')}, target pool holds format "
                    f"{MIGRATE_FORMAT} (whole rows)")
            now = self.clock()
            if handle is None:
                handle = S.RequestHandle.from_wire(payload["handle"], now)
            parts = [payload["cond"]]
            if payload.get("uncond") is not None:
                parts.append(payload["uncond"])
            free = [k for k, s in enumerate(self.slots) if s is None]
            if len(free) < len(parts):
                raise MigrationError(
                    "target_slots", f"need {len(parts)} free slots, "
                    f"have {len(free)}")
            need = sum(len(p["pages"]) for p in parts)
            if self.alloc.free < need and self.prefix is not None:
                self.prefix.shrink(need)
            try:
                grants = self.alloc.alloc(need)
            except Exception as e:
                raise MigrationError(
                    "target_pages", f"need {need} pages: {e}") from e
            idx = free[:len(parts)]
            G = self.num_slots
            slots_arr = np.full((G,), G, np.int32)
            n_tok = np.zeros((G,), np.int32)
            n_pos = np.zeros((G,), np.int32)
            n_rng = np.zeros((G, 2), np.uint32)
            n_temp = np.ones((G,), np.float32)
            n_topk = np.ones((G,), np.int32)
            n_top_p = np.zeros((G,), np.float32)
            try:
                taken = 0
                for j, part in enumerate(parts):
                    k = idx[j]
                    pages = grants[taken:taken + len(part["pages"])]
                    taken += len(part["pages"])
                    for pid, packed in zip(pages, part["pages"]):
                        snap = {key: self._put(_unpack_array(packed[key]))
                                for key in packed}
                        self.cache = self._restore_fn(
                            self.cache, self._put(np.int32(pid)), snap)
                    self._bt_host[k, :] = 0
                    self._bt_host[k, :len(pages)] = pages
                    self._slot_pages[k] = list(pages)
                    self._pos_est[k] = int(part["pos"])
                    slots_arr[j] = k
                    n_tok[j] = np.int32(part["cur_tok"])
                    n_pos[j] = np.int32(part["pos"])
                    n_rng[j] = np.asarray(part["rng"], np.uint32)
                    n_temp[j] = np.float32(part["temp"])
                    n_topk[j] = np.int32(part["topk_k"])
                    n_top_p[j] = np.float32(part["top_p"])
                self._bt_dirty = True
                put = self._put
                (self.cur_tok, self.pos, self.active, self.rng,
                 self.temp, self.topk_k, self.top_p) = \
                    self._migrate_install_fn()(
                        self.cur_tok, self.pos, self.active, self.rng,
                        self.temp, self.topk_k, self.top_p,
                        put(slots_arr), put(n_tok), put(n_pos),
                        put(n_rng), put(n_temp), put(n_topk),
                        put(n_top_p))
            except Exception as e:  # noqa: BLE001 — discard, never wedge
                # a torn/corrupt snapshot mid-install: discard the
                # partial import whole (no slot was assigned, the
                # device active bits were never raised) so the source's
                # replay fallback owns the request — page contents
                # written before the failure are unreachable garbage
                # behind the zeroed block-table rows
                self.alloc.release(grants)
                for k in idx:
                    self._bt_host[k, :] = 0
                    self._slot_pages[k] = []
                    self._pos_est[k] = 0
                self._bt_dirty = True
                raise MigrationError("transfer", repr(e)) from e
            i = idx[0]
            t0 = int(payload["t0"])
            # the emit budget is re-derived from the request riding the
            # payload's wire form (legacy frames decode override=0 →
            # full length), so a capped request completes at the same
            # token on the target as it would have at the source
            self.slots[i] = _Slot(handle, t0, now,
                                  need=self._slot_need(handle.request,
                                                       t0))
            self.slots[i].emitted = [int(t) for t in payload["emitted"]]
            if len(parts) == 2:
                j = idx[1]
                self.slots[j] = _Slot(handle, t0, now, shadow_of=i)
                self.slots[i].pair = j
                self._cfg_wire(i, j, payload["uncond"]["cfg_scale"])
            self._span(handle, "migrate_in", now, slot=i,
                       pos=int(payload["cond"]["pos"]),
                       tokens_done=len(payload["emitted"]))
            return i

    # -- the loop -----------------------------------------------------------

    def active_slots(self) -> int:
        return sum(s is not None for s in self.slots)

    def step_once(self) -> bool:
        """One engine iteration: expire, admit, dispatch ONE fused
        K-step chunk, harvest the previous one. Returns True when any
        work happened.

        Transfer discipline: the steady-state loop performs NO implicit
        host<->device traffic at all — per-slot decode state never
        leaves the device, admission writes it through device_put +
        jitted scatter, and the one host read is ``_harvest_chunk``'s
        explicit ``jax.device_get`` of the emit ring, once per K steps
        and overlapped with the next chunk's compute. Tests pin the
        whole iteration (including a mid-stream join) under
        ``analysis.guards.no_transfers()``."""
        with self._lock:
            if self.fenced:
                if self._profiler is not None:
                    # a capture orphaned by the fence: close it on THIS
                    # thread (jax.profiler is process-global — left
                    # open it would poison every future capture and
                    # crash the next start_trace anywhere in-process)
                    self._profiler.close()
                    self._profiler = None
                return False        # reclaimed: this pool is dead weight
            now = self._lap("between_s")
            self.last_heartbeat = now
            if self._t_start is None:
                self._t_start = now

            with _phase("engine.step"):
                # racelint: disable=RL003 — deliberate: _lock IS the step
                # serializer; the step's blocking calls (the harvest's
                # device_get, an admission's compile) must run under it,
                # each for the reason given at its own site in _step
                did = self._step(now)
            self._lap("tail_s" if did else "between_s")
            return did

    def _expire_and_pop(self, now: float):
        """The head of an iteration: reap externally-cancelled slots,
        expire slots and queued requests past their deadline, pop what
        the free slots (and pages) can take -> (did work, popped)."""
        did = False
        # mid-decode deadlines: chunk-boundary granularity — a slot
        # past its deadline is cancelled before the next chunk is
        # dispatched (its bit in the device mask is cleared, so the
        # in-flight chunk's leftover tokens die with the owner check)
        kill = []
        for i, slot in enumerate(self.slots):
            if slot is None or slot.shadow_of is not None:
                continue        # a shadow expires with its cond slot
            if slot.handle.done():
                # cancelled externally mid-decode (stream client
                # disconnected, group cancelled, hedge lost): the
                # terminal result already stuck via first-write-
                # wins — reclaim the slot and its pages NOW instead
                # of decoding to the end for nobody
                self.reaped += 1
                if self.metrics is not None:
                    self.metrics.event(**S.structured_event(
                        "serve_slot_reaped",
                        request_id=slot.handle.request.request_id,
                        tokens_done=len(slot.emitted)))
                kill.extend(self._free_slot(i))
                continue
            dt = slot.handle.request.deadline_t
            if dt is not None and now > dt:
                self._expire(slot.handle, now, where="decoding")
                kill.extend(self._free_slot(i))
        if kill:
            keep = np.ones((self.num_slots,), bool)
            keep[kill] = False
            self.active = self._kill_fn(self.active, self._put(keep))
            did = True

        free = self.num_slots - self.active_slots()
        if self.kv == "paged":
            # don't pop just to defer/requeue every chunk (n=0 still
            # reaps queued deadline expiries): with a head-of-line
            # request waiting, hold admission until ITS need is
            # free — freed pages accumulate for it; otherwise the
            # floor is the smallest bucket's prompt span
            floor = self._hol_need if self._hol_rid is not None \
                else self._min_admit_pages
            if self.alloc.free < floor and self.prefix is not None \
                    and self.queue.depth() > 0:
                # an idle pool held hostage by cached prefixes
                # would gate admission forever: shrink the LRU end
                # until the floor could pop
                self.prefix.shrink(floor)
            if self.alloc.free < floor:
                free = 0
        ready, expired = self.queue.pop_ready(free, now)
        for h in expired:
            self._expire(h, now, where="queued")
            if self.kv == "paged":
                self._deferred_ids.discard(h.request.request_id)
                if h.request.request_id == self._hol_rid:
                    self._hol_rid = None
                    self._hol_need = 0
        for h in ready:
            # queue_wait closes HERE for a single-engine pop; a
            # replica-set router already stamped it at routing
            # (has_in_attempt keeps the two shapes from double-
            # counting), and a page-deferred re-pop folds its extra
            # wait into the next prefill_admit span
            if h.trace is not None \
                    and not h.trace.has_in_attempt("queue_wait"):
                self._span(h, "queue_wait", now)
        return did or bool(ready or expired), ready

    def _step(self, now: float) -> bool:
        """``step_once`` under its lock, phase by phase."""
        with _phase("engine.expire"):
            did, ready = self._expire_and_pop(now)
        # an iteration that finds nothing to do is the run loop's
        # turn-round, not the engine's work
        self._lap("expire_s" if did or self._pending
                  or self.active_slots() else "between_s")
        if ready:
            # published for the reclaim sweep BEFORE admission can
            # block on a compile (see _admitting)
            self._admitting = list(ready)
            try:
                # racelint: disable=RL003 — deliberate: admission
                # compiles/donates into live slot buffers; it MUST
                # run under the step serializer (_lock), and the
                # reclaim sweep uses a timed acquire + _admitting
                # precisely so a slow compile cannot wedge it
                with _phase("engine.admit"):
                    self._admit(ready, now)
            finally:
                self._admitting = []
                self._lap("admit_plan_s")   # slotting, spans, pair wiring

        dispatched = False
        if self.active_slots() > 0:
            with _phase("engine.dispatch",
                        chunk=self.decode_steps // self.chunk_steps):
                self._dispatch_chunk(now)
            dispatched = did = True

        # double buffer: while dispatching, keep exactly one chunk
        # in flight un-harvested — the device_get below blocks on
        # chunk N while the device computes chunk N+1. Once nothing
        # new is dispatched (pool drained), flush the pipeline.
        target = 1 if dispatched else 0
        while len(self._pending) > target:
            # racelint: disable=RL003 — deliberate: the harvest
            # device_get is THE step; _lock is the step serializer,
            # and the double-buffer above already bounds the stall
            # to one chunk
            self._harvest_chunk()
            did = True

        if self._profiler is not None and not dispatched \
                and not self._pending:
            # the engine drained before the capture's K chunks ran:
            # close it NOW with what it got (partial but valid) —
            # an open process-global trace slows every replica in
            # this process until the next traffic arrives, and "the
            # next K chunks" cannot honestly outlive the work
            self._finish_profile(partial=True)

        if (self.metrics is not None and self.log_every
                and self.decode_steps - self._last_log
                >= self.log_every):
            self._last_log = self.decode_steps
            self.metrics.event(event="serve", **self.stats())
        return did

    def idle(self) -> bool:
        """True when there is nothing left to do: queue empty, every slot
        free, every in-flight chunk harvested. The termination predicate
        for any caller driving ``step_once`` by hand (``run_until_idle``,
        tests that watch the slots fill)."""
        return self.queue.depth() == 0 and self.active_slots() == 0 \
            and not self._pending

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        """Drive until the queue is empty, every slot is free, and every
        in-flight chunk is harvested (tests, bench). ``max_steps`` is a
        runaway guard, not a budget."""
        for _ in range(max_steps):
            busy = self.step_once()
            if not busy and self.idle():
                return
        raise RuntimeError(f"engine did not go idle in {max_steps} steps")

    def run(self, stop: threading.Event, idle_sleep_s: float = 0.002):
        """Serving loop for a dedicated thread (serve.server): spin while
        there is work, nap briefly when idle. An exception out of
        ``step_once`` must NOT kill the loop — one bad step would leave
        every queued and future request hanging forever while /healthz
        still answers. Instead the implicated in-slot requests are
        fulfilled with typed ``error`` results, the pool is reset to a
        consistent idle state, and serving continues."""
        while not stop.is_set():
            try:
                busy = self.step_once()
            except Exception as e:  # noqa: BLE001 — no-hangs contract
                # recovery FIRST, observability second: a raising
                # metrics sink must not kill the thread before the
                # in-slot handles are fulfilled
                n = self.fail_active(f"engine step failed: {e!r}")
                if self.metrics is not None:
                    try:
                        self.metrics.event(**S.structured_event(
                            "serve_engine_error", error=repr(e),
                            failed=n))
                    except Exception:   # noqa: BLE001
                        pass
                stop.wait(idle_sleep_s)     # never hot-spin on a
                continue                    # persistent fault
            if not busy and self.idle():
                stop.wait(idle_sleep_s)
        if self._profiler is not None:
            # clean shutdown with a capture in flight: stop the
            # process-global trace (partial but valid) on the way out
            self._profiler.close()
            # racelint: disable=RL001 — _profiler is run-loop-thread-
            # private (armed via the _profile_req handoff); this is the
            # loop's own epilogue, no other thread ever writes it
            self._profiler = None

    def _terminate_active(self, status: str, reason: str) -> int:
        """Fulfil every in-slot request with a typed terminal result and
        reset the pool to idle (slot state may be mid-update on the error
        path, and in-flight chunks may hold poisoned futures, so the only
        consistent continuation is an empty pool and an empty pipeline).
        Returns the number terminated."""
        import jax.numpy as jnp
        if self.fenced:
            return 0        # the reclaim sweep owns the in-slot handles
        n = 0
        with self._lock:
            now = self.clock()
            for i, slot in enumerate(self.slots):
                if slot is None or slot.shadow_of is not None:
                    continue        # a shadow dies with its cond slot
                req = slot.handle.request
                slot.handle.fulfill(S.Result(
                    status=status, request_id=req.request_id,
                    reason=reason,
                    weights_version=self.weights_version,
                    queued_s=round(slot.t_admit - req.submit_t, 6),
                    total_s=round(now - req.submit_t, 6)))
                self._free_slot(i)
                n += 1
            self._pending.clear()
            if self._profiler is not None:
                # the chunks a capture was waiting on died with the
                # pipeline; close the trace (partial but valid) rather
                # than leaving jax.profiler wedged open forever
                self._profiler.close()
                self._profiler = None
            self.cur_tok = jnp.zeros((self.num_slots,), jnp.int32)
            self.pos = jnp.zeros((self.num_slots,), jnp.int32)
            self.active = jnp.zeros((self.num_slots,), bool)
            self._sync_cfg()
            if self.kv == "paged":
                self._sync_block_tables()
        return n

    def fail_active(self, reason: str) -> int:
        """Typed ``error`` results for every in-slot request — the
        run-loop's recovery path after an unexpected step failure."""
        return self._terminate_active(S.ERROR, reason)

    def cancel_active(self, reason: str = "server shutdown") -> int:
        """Typed ``cancelled`` results for every in-slot request — the
        shutdown path (the no-hangs contract must cover requests already
        admitted, not just queued ones)."""
        return self._terminate_active(S.CANCELLED, reason)

    # -- observability ------------------------------------------------------

    def device_scopes(self, buckets: Optional[Sequence[int]] = None
                      ) -> Dict[str, dict]:
        """{program name: {instruction name: scope entry}} for the decode
        program and each admission program this engine has built (or,
        given ``buckets``, those buckets' prefill programs at every
        group of ``prefill_groups``): the join
        from a profiler capture's ``XLA Ops`` events to the model's
        named scopes (``obs/device.py``). The program names are the ones
        a capture's ``XLA Modules`` line shows after ``jit_``.

        Each program is lowered and compiled once more from the SHAPES
        of the engine's own state — nothing runs, nothing is donated,
        the serving thread is not touched — and the map is kept, since
        an engine's programs never change. Never on a hot path: the first
        call costs each program's compile time on the caller's thread
        (``POST /admin/profile`` pays it on the HTTP thread, before it
        arms the capture); the persistent compile cache serves it only
        from an earlier call with the same code
        (``obs.device.scopes_of_lowered``)."""
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.obs import device as odev

        shapes = odev.abstract
        # a host array goes where _put places it: as the per-slot state
        rep = shapes(self.pos).sharding

        def host(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

        state = shapes((self.cur_tok, self.pos, self.active, self.rng,
                        self.temp, self.topk_k, self.top_p))

        def group(G):       # lens, slots, n_seed, then the sampling knobs
            return (host((G,), jnp.int32),) * 3 + (
                host((G,), jnp.float32), host((G,), jnp.int32),
                host((G,), jnp.float32), host((G,), jnp.int32),
                host((G,), jnp.float32), host((G,), jnp.bool_))

        params, cache = shapes(self.params), shapes(self.cache)
        paged = self.kv == "paged"
        programs = {self._decode_fn.__name__: (self._decode_fn, (
            params, cache,
            *((shapes(self.block_tables),) if paged else ()),
            *state, *shapes((self.cfg_partner, self.cfg_scale,
                             self.cfg_uncond))))}
        built = sorted(self._prefill_fns) if buckets is None else [
            (b, g) for b in sorted(buckets) for g in self.prefill_groups]
        for b, G in built:
            fn = self._prefill_fn(b, G)
            text = host((G, b), jnp.int32)
            programs[fn.__name__] = (fn, (
                params, cache, *state, text, *group(G),
                *((text,) if paged else ())))       # page_rows
        if self._warm_fn is not None:
            h_last = host((self.num_slots, self.cfg.dim),
                          self.params["text_emb"]["w"].dtype)
            programs[self._warm_fn.__name__] = (self._warm_fn, (
                params, *state, h_last, *group(self.num_slots)))
        self._scopes_thread = threading.get_ident()
        try:
            for name, (fn, args) in programs.items():
                if name not in self._scope_maps:
                    self._scope_maps[name] = odev.scopes_of_lowered(
                        fn.lower(*args))
        finally:
            self._scopes_thread = None
        return {name: self._scope_maps[name] for name in programs}

    def _finish_profile(self, partial: bool = False) -> None:
        """Stop the in-flight capture and emit ``serve_profile_done``
        (``partial`` when the engine drained before the requested K
        chunks ran). Engine-thread only."""
        prof = self._profiler
        if prof is None:
            return
        # close BEFORE clearing: stop_trace serializes the xplane for
        # seconds, and profile_active() must stay true the whole time —
        # it is the supervisor's hang-deadline exemption (clearing
        # first opens a window where a sweep sees a stale heartbeat,
        # no capture, and fences a healthy replica mid-serialization)
        prof.close()
        self._profiler = None
        self.profiles_taken += 1
        rec = S.structured_event(
            "serve_profile_done", dir=prof.log_dir,
            chunks=prof.stop_at - prof.start)
        if partial:
            rec["partial"] = True
        self.metrics.event(**rec)

    def request_profile(self, log_dir: str, chunks: int = 8) -> dict:
        """Arm a ``jax.profiler`` capture over the NEXT ``chunks`` fused
        decode chunks (``POST /admin/profile``; reuses
        ``utils.profiling.StepProfiler``). The capture starts at the
        next STEADY-STATE chunk dispatch (the one-time cold compile is
        never captured — it would swamp the trace and stall the serving
        thread past supervision deadlines) and stops once that many
        chunks have been harvested — kernel tuning on a real chip
        without stopping the server. Typed ``ProfileError`` (reason
        ``capture_active``, HTTP 409) while a capture is in flight:
        jax.profiler allows exactly one trace at a time."""
        chunks = int(chunks)
        if chunks < 1:
            raise ValueError(f"chunks must be >= 1, got {chunks}")
        if not log_dir:
            raise ValueError("request_profile needs a log_dir "
                             "(serve_dalle --profile_dir sets the "
                             "default sink)")
        with self._profile_lock:
            prof = self._profiler
            if prof is not None:
                raise ProfileError(S.structured_event(
                    "serve_profile_reject", reason="capture_active",
                    dir=prof.log_dir, start_chunk=prof.start,
                    chunks=prof.stop_at - prof.start))
            if self._profile_req is not None:
                raise ProfileError(S.structured_event(
                    "serve_profile_reject", reason="capture_active",
                    dir=self._profile_req[0],
                    chunks=self._profile_req[1]))
            self._profile_req = (str(log_dir), chunks)
        rec = S.structured_event(
            "serve_profile_armed", dir=str(log_dir), chunks=chunks,
            # advisory: the engine thread picks the REAL start index at
            # its next dispatch (_dispatch_chunk consumes the request)
            start_chunk=self.decode_steps // self.chunk_steps)
        self.metrics.event(**rec)
        return rec

    def profile_active(self) -> bool:
        """A capture is pending or running — the arm-time 409 surface
        (a second arm must be refused in either state)."""
        return self._profiler is not None or self._profile_req is not None

    def capturing(self) -> bool:
        """A jax.profiler trace is actually RUNNING (start_trace called
        or in progress, not yet closed) — the supervision-exemption
        surface. An armed-but-not-yet-started request slows nothing,
        and exempting it would let a wedged replica that never reaches
        its next dispatch evade the hang deadline forever."""
        return self._profiler is not None

    def prefill_trace_count(self, bucket: int,
                            n_rows: Optional[int] = None) -> int:
        """Traces of one bucket's prefill program for a group of
        ``n_rows`` (all the slots where not given; contract: <= 1 for
        the engine's life; the guards.compile_count counter in tests)."""
        return self._prefill_trace_counts.get(
            (bucket, self.num_slots if n_rows is None else n_rows), 0)

    def kv_hbm_bytes(self) -> int:
        """Resident HBM bytes of the KV store — the page pool under
        ``kv='paged'``, the full slot cache under ``kv='dense'``."""
        from dalle_pytorch_tpu.serve import kv_pool as KV
        return KV.pool_bytes(self.cache)

    def modeled_kv_read_bytes_per_token(self, sparse_reads=None) -> int:
        """Analytic per-token KV READ bytes for this engine's decode
        configuration (paged mode only; 0 otherwise) — HBM counters are
        not host-observable, so /stats carries the model
        (``ops.paged_attention.modeled_kv_read_bytes_per_token``,
        averaged over a decode span starting at the smallest prefill
        bucket). ``sparse_reads=False`` asks for the dense-reads
        baseline of the same config, which is how /stats can show the
        dense-vs-sparse read ratio this engine is getting. Config-
        static, so the value is computed once per mode and memoized —
        /stats, /healthz, and worker STATS frames poll this."""
        if self.kv != "paged":
            return 0
        sr = self.sparse_reads if sparse_reads is None else bool(sparse_reads)
        if sr in self._modeled_read_bytes:
            return self._modeled_read_bytes[sr]
        from dalle_pytorch_tpu.ops import paged_attention as PA
        tcfg = self.cfg.transformer
        if self.block is not None:
            # the gather reads every page of a layer's table, whole, once
            # a layer that READS the pool (a full pool of one layer may
            # have many readers): no live-page trimming. A latent pool has
            # one row a token and no V; a window layer's table is its
            # ring; a state-space layer reads its slot's state
            kinds = self.block.layer_kinds(tcfg.depth)
            columns = {"full": self.slot_max_pages, "state": 1,
                       "window": self.window.ring if self.window else 0}
            out = sum(sum(k.pool == pool for k in kinds) * columns[pool]
                      * int(np.prod(self.cache[name].shape[2:]))
                      * self.cache[name].dtype.itemsize
                      for pool, names in self.block.pools(
                          tcfg.depth).items() for name in names)
            self._modeled_read_bytes[sr] = out
            return out
        out = int(PA.modeled_kv_read_bytes_per_token(
            depth=tcfg.depth, heads=tcfg.heads, dim_head=tcfg.dim_head,
            total_len=self.total_len, page_size=self.page_size,
            prompt_len=min(self.buckets),
            itemsize=self.cache["k"].dtype.itemsize,
            impl=self.paged_attn, quantized=self.quantize_cache,
            sparse_reads=sr,
            sparse_pattern=tcfg.sparse_pattern if sr else None,
            sparse_block=tcfg.sparse_block, causal=tcfg.causal))
        self._modeled_read_bytes[sr] = out
        return out

    def _block_cache_stats(self) -> dict:
        """A described block's caches beside the one page pool. With a
        window pool, the two pools side by side: physical pages in use of
        each (``pages_in_use`` is the full pool's), the window pool's
        ring, the pages it reused in place, and what an unshared,
        unwindowed cache would hold at the same positions (every
        attention layer's pages to each slot's mapped position: the
        denominator of the saving). With recurrent layers, the state
        pool's bytes: together (``state_bytes``) and each buffer's under
        its own name (``<buffer>_bytes``)."""
        if self.block is None:
            return {}
        blk, depth = self.block, self.cfg.transformer.depth
        kinds = blk.layer_kinds(depth)
        out = {}
        if self.window is not None:
            w = self.window
            full_layers, win_layers = (len(blk.cache_layers(p, depth))
                                       for p in ("full", "window"))
            readers = sum(k.pool == "full" for k in kinds)
            full_in_use = self.alloc.in_use
            out.update({
                "full_pages_in_use": full_in_use,
                "window_pages_in_use": w.alloc.in_use,
                "window_pages_reused": w.reused,
                # layer-pages held now, and what they would be were
                # every attention layer a full one with rows of its own
                # (a slot's full-pool pages are its pages to its mapped
                # position)
                "layer_pages_in_use": full_layers * full_in_use
                + win_layers * w.alloc.in_use,
                "layer_pages_all_full": (readers + win_layers)
                * full_in_use,
            })
        state = blk.pools(depth).get("state")
        if state:
            out.update({f"{n}_bytes": int(self.cache[n].nbytes)
                        for n in state})
            out["state_bytes"] = sum(out[f"{n}_bytes"] for n in state)
        return out

    def pages_in_use_p95(self) -> int:
        """Nearest-rank p95 of pages in use, sampled at every chunk
        dispatch (paged mode only; 0 before any dispatch)."""
        if self.kv != "paged" or not self._pages_samples:
            return 0
        s = sorted(self._pages_samples)
        return s[min(int(0.95 * len(s)), len(s) - 1)]

    def _mesh_stats(self) -> dict:
        """The mesh-observability block /stats carries (mesh satellite):
        a plain engine is one chip, and its whole KV store lives there.
        ``MeshEngine`` overrides with its mesh shape and the per-SHARD
        residency — where the pool actually lives."""
        return {"devices_per_replica": 1,
                "mesh_shape": None,
                "kv_hbm_bytes_per_shard": self.kv_hbm_bytes()}

    def stats(self) -> dict:
        elapsed = None if self._t_start is None \
            else max(self.clock() - self._t_start, 1e-9)
        paged = {}
        if self.kv == "paged":
            paged = {
                "paged_attn": self.paged_attn,
                "sparse_reads": self.sparse_reads,
                # modeled per-token KV read traffic, current mode vs the
                # dense-reads baseline — the pair whose ratio is the
                # sparse-reads win (equal when sparse_reads is off)
                "kv_read_bytes_per_token":
                    self.modeled_kv_read_bytes_per_token(),
                "kv_read_bytes_per_token_dense_reads":
                    self.modeled_kv_read_bytes_per_token(
                        sparse_reads=False),
                "page_size": self.page_size,
                "num_pages": self.num_pages,
                # PHYSICAL pages: the refcounted allocator counts a
                # page shared by N block tables (or held by the prefix
                # index) exactly once, which is what keeps this gauge —
                # and kv_hbm_bytes, the pool's resident bytes — exact
                # under sharing
                "pages_in_use": self.alloc.in_use,
                "pages_free": self.alloc.free,
                "pages_peak": self.alloc.peak_in_use,
                "pages_in_use_p95": self.pages_in_use_p95(),
                "pages_shared": self.alloc.pages_shared,
                "pages_shared_saved": self.alloc.refs_saved,
                **self._block_cache_stats(),
                "evicted": self.evicted,
                "deferred": self.deferred,
                "requeued": self.queue.requeued,
            }
            if self.prefix is not None:
                paged.update({
                    "prefix_cache": True,
                    "prefix_hits": self.prefix_hits,
                    "prefix_entries": len(self.prefix),
                    "prefix_pages_held": self.prefix.pages_held,
                    "prefix_evictions": self.prefix.evicted,
                })
        spec = {}
        if self.speculative:
            k = self.speculative
            spec = {
                "speculative": k,
                "draft_layers": self.draft_layers,
                "spec_rounds": self.spec_rounds,
                # delivered / proposed: the fraction of proposed
                # positions that survived verify — 1/k is the total-
                # rejection floor (the verify sample always lands), 1.0
                # means every draft matched (end-of-sequence clamping
                # is excluded from the denominator, so a perfect draft
                # really scores 1.0)
                "spec_acceptance_rate": round(
                    self.spec_delivered / max(self.spec_proposed, 1),
                    4),
                "spec_tokens_per_round": round(
                    self.spec_delivered / max(self.spec_rounds, 1), 3),
            }
        moe = {}
        if self.block is not None:
            moe = {k: getattr(self, k) for k in load_counters(self.block)}
        return {
            "kv": self.kv,
            "kv_hbm_bytes": self.kv_hbm_bytes(),
            **self._mesh_stats(),
            **paged,
            **spec,
            **moe,
            "queue_depth": self.queue.depth(),
            "active_slots": self.active_slots(),
            "num_slots": self.num_slots,
            "chunk_steps": self.chunk_steps,
            "decode_steps": self.decode_steps,
            "tokens_decoded": self.tokens_decoded,
            "tokens_per_s": (round(self.tokens_decoded / elapsed, 2)
                             if elapsed else 0.0),
            "mean_occupancy": (round(self.occupancy_sum
                                     / max(self.decode_steps, 1), 3)),
            "completed": self.completed,
            "expired": self.expired,
            "cfg_pairs": self.cfg_pairs,
            "reaped": self.reaped,
            "rejected": self.queue.rejected,
            "decode_compiles": self.decode_traces,
            "prefill_compiles": self.prefill_traces,
            # admissions dispatched, and the loop's cumulative seconds
            # by phase (LOOP_SECONDS): unrounded, they are differenced
            "prefill_runs": self.prefill_runs,
            "warm_admits": self.warm_admits,
            **{k: getattr(self, k) for k in LOOP_SECONDS},
            "prefill_buckets": list(self.buckets),
            "prefill_groups": list(self.prefill_groups),
            "harvests": self.harvests,
            "host_round_trips_per_token": round(
                self.harvests / max(self.tokens_decoded, 1), 6),
            # the chunk ledger (loop_ring): of the harvested chunks,
            # those that ran behind an admission; the stalls, and the
            # newest of them whole (phase, thread_cpu_s, next_wait_s)
            "chunks_behind_admit": self.chunks_behind_admit,
            "loop_stalls": self.loop_stalls,
            "last_stalls": [dict(st) for st in list(self._stalls)],
            "sample_sorted_chunks": self.sample_sorted_chunks,
            "kv_view_groups": self.kv_view_groups,
            "kv_view_columns_read": self.kv_view_columns_read,
            "kv_view_columns_full": self.kv_view_columns_full,
            # the obs surface: flight-recorder occupancy (retention is
            # the ring capacity, /debug/events serves the contents) and
            # the serve-side profiler state
            "flight_events": len(self.flight),
            "profile_active": self.profile_active(),
            "profiles_taken": self.profiles_taken,
        }
