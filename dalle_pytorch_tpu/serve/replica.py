"""Replica-set serving: N supervised engines behind ONE queue, with
zero-loss failover via deterministic replay.

One ``Engine`` is one replica: one compiled decode program over one slot
pool on (ideally) one chip. This module is the layer the ROADMAP's
multi-replica item asks for — a single shared ``RequestQueue`` fronting N
engines (thread-per-engine; the Gemma-on-TPU serving paper's replicated-
engine + health-driven-routing shape, PAPERS.md), where a replica
crashing, hanging, or being drained by an operator costs LATENCY on the
requests it held, never a lost request and never a wrong token.

The key enabler is the same one paged eviction proved (PR 5): sampling
is deterministic in (seed, position) — ``fold_in(request_rng, pos)`` per
step — so an in-flight request is *migratable*. Kill the replica mid-
stream, re-queue the handle at its ORIGINAL arrival position
(``RequestQueue.requeue`` preserves ``queue_seq``), admit it on a
survivor, and the replay emits a token stream bit-identical to an
undisturbed run. The caller cannot tell a failover happened except by
the clock.

Supervision (one supervisor per set, not per request):

  * every replica's serving loop stamps ``Engine.last_heartbeat`` at
    each step and each emit-ring harvest — the harvest ``device_get``
    is the one blocking sync in steady state, so a wedged device stalls
    the stamp exactly where the wedge is;
  * CRASH: the replica loop catches the exception, records it, and
    exits; the supervisor notices the dead loop.
    HANG: ``now - last_heartbeat > heartbeat_s`` while the loop thread
    is still "running". Either way the replica is FENCED
    (``Engine.fence()`` — a fenced engine never fulfils a handle, hands
    a completion downstream, or re-queues anything; the wedged thread
    is abandoned, daemon-style, the same move ``resilience.retry``
    makes for an uncancellable pending claim);
  * RECLAIM: the supervisor snapshots the fenced replica's host-side
    bookkeeping — its private queue (routed, not yet admitted) and its
    in-slot handles (``Engine.inflight_handles``) — and re-queues every
    not-yet-done handle into the shared queue at its original arrival
    position for replay. ``RequestHandle.fulfill`` is first-write-wins,
    so even a fenced thread waking at the worst moment cannot race the
    replay with a stale result;
  * BRING-UP: the replica is rebuilt (fresh ``Engine``, fresh private
    queue). Repeated bring-up failure circuit-breaks the replica with
    exponential backoff (``resilience.retry.RetryPolicy.backoff``)
    while the set keeps serving on the survivors — capacity shrinks,
    the shared queue's ``max_depth`` turns the shrinkage into typed
    ``QueueFull`` backpressure at submit, and nothing ever hangs;
  * DRAIN: ``drain_replica(i)`` is the operator's planned-maintenance
    path — identical fence + reclaim, but the replica stays down until
    ``undrain_replica(i)``.

Routing is least-loaded with page-awareness: the router moves requests
from the shared queue into per-replica private queues (``requeue`` with
``count=False`` — a hand-off, not backpressure; the handle keeps its
shared-queue ``queue_seq`` and ``request_id``), preferring the replica
with the most free slot capacity and, among paged engines, one whose
page pool can map the request's prompt span NOW (free pages from the
replica's kv-pool stats break ties).

Like ``Engine``, the set is drivable two ways: ``step_once``/
``run_until_idle`` single-threaded (tests, bench — deterministic, and
the whole steady state still holds under ``guards.no_transfers`` with
one decode compile per replica), or ``start()`` for live traffic
(thread per replica + one control thread for routing/supervision, what
``serve.server`` uses). With more than one jax device visible, replica
i's engine is committed to device ``i % len(devices)`` so the replicas'
fused chunks genuinely overlap — on a pod slice that is replica-per-
chip serving; on the CPU fallback it still overlaps the async dispatch.

ISOLATION SHAPES. ``isolation='thread'`` (the default) is the above:
replicas are threads sharing this process — cheap, transfer-guardable,
but a segfault in XLA, a host OOM, or a `kill -9` still takes the whole
set down. ``isolation='process'`` runs each replica's engine in a
SPAWNED CHILD PROCESS (own interpreter, own jax client, pinned to its
device — ``serve/worker.py``) behind the typed IPC layer in
``serve/ipc.py``. The fence/reclaim/replay protocol is identical; what
changes is who holds the truth: the parent keeps a SHADOW of every
handle routed to a child (``ChildEngineClient.shadow``) and reclaims
from that, never from the child — a SIGKILLed process answers nothing.
Supervision gains a second liveness signal: child PID liveness with
exit-code/signal decoding (SIGKILL, SIGSEGV, the exit-137 RSS-watchdog
OOM convention) layered on top of the same missed-heartbeat deadline,
where heartbeats are now frames on the pipe rather than a shared-heap
timestamp. A hard-killed child is fenced exactly like a crash or hang:
its pipe is drained for frames written before death (those results
stand), everything still open replays byte-identically on a survivor,
and the dead replica restarts through the same circuit-breaker backoff.

TRANSPORT SHAPES (process isolation only). ``transport='pipe'`` (the
default) carries the frames over a duplex pipe — local children only.
``transport='socket'`` makes isolation HOST-shaped: the parent opens
one dial-in endpoint (``serve/transport.py``'s ``WorkerListener``;
``worker_endpoint`` picks the bind address) and every worker CONNECTS
BACK with an authenticated HELLO (shared token + protocol version +
replica index), then receives its engine spec over the socket. Three
ways a worker comes to exist — a locally spawned child that dials back
(the default), a launcher command per replica (``worker_cmd`` with
``{endpoint}``/``{index}`` placeholders, e.g. an ssh line; the token
travels in the ``DALLE_WORKER_TOKEN`` env var), or a worker an
operator starts BY HAND on another host (``worker_cmd=''``) — and all
three are supervised identically: shadow bookkeeping, heartbeat
deadline, fence→reclaim→replay at original arrival position. A worker
with no local PID is declared dead off its socket (EOF/reset), and the
frame protocol's per-connection sequence numbers + the transport's
torn-frame detection turn every network failure mode — reset
mid-frame, partial frame, stalled link, duplicated or reordered
delivery — into the same typed fence + byte-identical replay a local
`kill -9` gets (docs/SERVING.md 'Host isolation & socket transport').

ELASTIC FLEET. The set is a MOVING TARGET at runtime (docs/SERVING.md
'Elastic fleet'): ``add_replica()`` appends a new supervised slot —
thread, spawned child, launcher-started or hand-started remote worker,
whichever shape the set already runs — that joins routing atomically
once serving (process children once READY); ``remove_replica(i)``
drains in-flight work to the survivors (the same fence→reclaim→replay
that makes failover zero-loss) and RETIRES the slot for good. Illegal
transitions are typed ``ScaleError``\\ s, never partial states: removing
the last live replica, growing past ``max_replicas`` (the page-budget
cap — every replica allocates its own KV pool), reshaping mid-upgrade.
``rolling_upgrade(version=...)`` hot-swaps weights replica-by-replica
with zero dropped requests: drain (in-flight work replays on survivors
still serving the OLD weights), re-bring-up on the new weights (new
params pytree, or a new ``worker_ckpt`` path for checkpoint-path
attach), health-gate behind N CANARY requests decoded by the new engine
alone — token-exact against the first upgraded replica's canary tokens,
so every replica of a generation provably samples identical streams —
and only then rejoin routing. A canary or bring-up failure ABORTS the
upgrade typed (``UpgradeAborted``): the replica rolls back to the old
weights and the whole fleet keeps serving the old version. Every
``Result`` is stamped with the ``weights_version`` that decoded it, and
failover replay is VERSION-PINNED: a request reclaimed mid-upgrade
replays only on a replica of the generation it started on (same-seed
tokens are byte-identical PER version; a newer generation's logits are
not) — the pin is released, with a structured event, only when that
generation has left the fleet entirely, because zero-loss outranks a
stale pin. ``serve/autoscale.py``'s policy loop drives the same two
scale calls off /stats occupancy, queue depth, and page pressure.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Callable, List, Optional

from dalle_pytorch_tpu.serve import scheduler as S
from dalle_pytorch_tpu.serve.engine import COUNTERS as _COUNTERS
from dalle_pytorch_tpu.serve.engine import LOOP_SECONDS as _LOOP_SECONDS
from dalle_pytorch_tpu.serve.engine import MigrationError

# replica lifecycle states (``replica_states()`` / ``stats()``)
RUNNING = "running"
BROKEN = "broken"        # circuit open: waiting out the bring-up backoff
DRAINED = "drained"      # operator drain: down until undrain_replica()
RETIRED = "retired"      # scale-in tombstone: the slot never comes back
#                          (indices stay stable; routing, supervision,
#                          and capacity all skip it forever)

ISOLATION_MODES = ("thread", "process")
TRANSPORT_MODES = ("pipe", "socket")
# replica roles (disaggregated serving): a ``prefill`` replica admits
# and prefills, then live-migrates the warm request to a decode-capable
# replica; a ``decode`` replica is skipped for fresh admissions while
# any prefill-capable replica has capacity. ``both`` (the default) is
# the classic undifferentiated shape. Roles are a PREFERENCE, never a
# capability: every engine can prefill and decode, and zero-loss
# routing outranks the role split whenever honoring it would strand a
# request.
REPLICA_ROLES = ("prefill", "decode", "both")


class ScaleError(RuntimeError):
    """Typed rejection of an illegal fleet reshape: removing the last
    live replica, adding past the ``max_replicas`` page-budget cap,
    naming a retired/unknown slot, or scaling while a rolling upgrade
    owns the fleet. ``record`` is the structured event (kind
    ``serve_scale_reject``) — the operator API's machine-readable
    half, mirroring ``scheduler.ServeRejected``."""

    def __init__(self, record: dict):
        super().__init__(f"{record.get('reason', 'scale rejected')} "
                         f"(op={record.get('op')})")
        self.record = record


class UpgradeAborted(RuntimeError):
    """A rolling upgrade that could not complete safely: a canary
    failed the health gate, the new weights' bring-up failed or timed
    out, or the fresh replica died mid-canary. By the time this is
    raised the aborting replica has been rolled back to the OLD
    weights and the whole fleet serves the old version — the abort is
    an event, never a mixed-version end state. ``record`` is the
    structured event (kind ``serve_upgrade_aborted``)."""

    def __init__(self, record: dict):
        super().__init__(
            f"rolling upgrade to {record.get('to')!r} aborted at "
            f"replica {record.get('replica')}: {record.get('error')} "
            f"(fleet left on {record.get('fleet_version')!r})")
        self.record = record


class ReplayVersionMismatch(RuntimeError):
    """Invariant guard on version-pinned replay: a handle pinned to one
    weights generation reached a replica serving another. The router's
    candidate filter makes this unreachable in normal operation (a
    pinned request is HELD in the shared queue until a same-version
    replica has capacity, or the pin is released once the generation
    left the fleet); raising typed here — instead of silently decoding
    on the wrong weights — is what keeps 'byte-identical per
    weights_version' a contract rather than a hope."""

    def __init__(self, record: dict):
        super().__init__(
            f"request {record.get('request_id')} is pinned to weights "
            f"{record.get('pinned')!r} but was offered replica "
            f"{record.get('replica')} on {record.get('version')!r}")
        self.record = record


class ChipOwnershipError(RuntimeError):
    """Typed start-up refusal of ``isolation='process'`` with locally
    spawned workers on a TPU host. A chip belongs to ONE process: the
    parent has already initialised jax on the TPU backend (it restored
    and placed the weights), so its libtpu client holds every chip of
    the host, and a child that builds its own jax client can only fail
    on libtpu's lockfile or hang waiting for it. Making it work needs
    a parent that never touches the accelerator plus a per-child chip
    visibility — not a local repair (ROADMAP Queue 3 item 7). One
    process drives all of a host's chips in thread isolation, one
    engine per chip; workers on OTHER hosts still attach over
    ``transport='socket'`` with an operator-started ``worker_cmd``.
    ``record`` is the structured event (kind
    ``serve_isolation_unsupported``)."""

    def __init__(self, record: dict):
        super().__init__(
            f"isolation='process' cannot spawn workers on this host: "
            f"the parent process already holds its "
            f"{record.get('device_count')} {record.get('platform')} "
            f"chip(s) ({record.get('device_kind')}) and a chip belongs "
            f"to one process — a child's jax client would fail on "
            f"libtpu's lock or hang. Use isolation='thread' (one "
            f"engine per chip in this process), or attach workers "
            f"from other hosts (transport='socket', worker_cmd='').")
        self.record = record


def check_chip_ownership(isolation: str, worker_cmd) -> None:
    """Raise ``ChipOwnershipError`` when process isolation would spawn
    a worker onto a chip this process already holds. ``worker_cmd``
    None means "spawn locally" (pipe, or the dial-back socket child);
    any other value hands the launch to an operator or launcher whose
    workers own their own host's chips."""
    if isolation != "process" or worker_cmd is not None:
        return
    from dalle_pytorch_tpu.utils.device import describe_device
    device = describe_device()
    if device["platform"] == "tpu":
        raise ChipOwnershipError(S.structured_event(
            "serve_isolation_unsupported", isolation=isolation,
            platform=device["platform"], device_kind=device["kind"],
            device_count=device["count"]))


class _Replica:
    """One supervised slot of the set: the engine + its private queue,
    its loop thread (threaded mode), and the supervisor's bookkeeping
    (lifecycle state, consecutive bring-up failures, backoff clock)."""

    __slots__ = ("index", "state", "engine", "queue", "thread", "stop",
                 "device", "attempt", "bringups", "next_bringup_t",
                 "last_error", "dead", "await_ready", "last_exit",
                 "conns", "version", "canary", "params_override",
                 "ckpt_override", "born_scaled", "role")

    def __init__(self, index: int, device=None, version: str = "0",
                 role: str = "both"):
        self.index = index
        self.state = BROKEN          # until the first bring-up succeeds
        self.engine = None
        self.queue: Optional[S.RequestQueue] = None
        self.thread: Optional[threading.Thread] = None
        self.stop: Optional[threading.Event] = None
        self.device = device
        self.attempt = 0             # consecutive bring-up failures
        self.bringups = 0            # lifetime bring-up calls (faults)
        self.next_bringup_t = 0.0
        self.last_error = ""
        self.dead = False            # loop thread recorded a crash
        self.await_ready = False     # process child spawned, READY due
        self.last_exit = ""          # decoded exit of the last child
        self.conns = 0               # workers that reached READY here
        self.version = str(version)  # weights generation this slot serves
        self.canary = False          # upgrading: serving canaries only,
        #                              excluded from routing until gated
        self.params_override = None  # upgrade: bring up on THESE params
        self.ckpt_override = None    # upgrade: ... or this ckpt path
        self.born_scaled = False     # created by add_replica (faults)
        self.role = str(role)        # prefill | decode | both


class ReplicaSet:
    """N supervised ``Engine`` replicas behind one shared
    ``scheduler.RequestQueue``. Presents the same drive surface as a
    single engine (``step_once`` / ``run_until_idle`` / ``idle`` /
    ``stats`` plus the counters ``bench._serve_load_point`` reads), so
    everything that can drive an engine can drive a set."""

    def __init__(self, params: dict, cfg, queue: S.RequestQueue, *,
                 replicas: int = 2,
                 num_slots: int = 4,
                 chunk_steps: int = 8,
                 prefill_buckets=None,
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
                 preview_every: int = 0,
                 clock: Callable[[], float] = time.perf_counter,
                 heartbeat_s: float = 5.0,
                 bringup_policy=None,
                 place_on_devices: bool = True,
                 idle_sleep_s: float = 0.002,
                 isolation: str = "thread",
                 child_rss_limit_mb: int = 0,
                 spawn_timeout_s: float = 120.0,
                 compile_grace_s: float = 120.0,
                 transport: str = "pipe",
                 worker_endpoint: str = "127.0.0.1:0",
                 worker_cmd: Optional[str] = None,
                 attach_token: Optional[str] = None,
                 worker_ckpt: Optional[str] = None,
                 worker_use_ema: bool = False,
                 worker_quantize: str = "none",
                 devices_per_replica: int = 1,
                 weights_version: str = "0",
                 max_replicas: int = 0,
                 roles=None):
        import jax

        from dalle_pytorch_tpu.resilience import faults
        from dalle_pytorch_tpu.resilience import retry as rretry

        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        self.roles = tuple(str(x) for x in roles) if roles else ()
        for role in self.roles:
            if role not in REPLICA_ROLES:
                raise ValueError(f"replica role must be one of "
                                 f"{REPLICA_ROLES}, got {role!r}")
        if self.roles and len(self.roles) != replicas:
            raise ValueError(
                f"roles names {len(self.roles)} replicas but the set "
                f"starts with {replicas}")
        if self.roles and kv != "paged" \
                and any(x != "both" for x in self.roles):
            # disaggregated roles work by LIVE-MIGRATING warm requests
            # from prefill to decode replicas, and migration ships KV
            # pages — a dense cache has no transferable pages
            raise ValueError(
                "prefill/decode replica roles need kv='paged' (the "
                "prefill->decode handoff live-migrates KV pages)")
        self.weights_version = str(weights_version)
        self.max_replicas = int(max_replicas)
        if self.max_replicas and self.max_replicas < replicas:
            raise ValueError(
                f"max_replicas={max_replicas} is below the initial "
                f"replica count {replicas}")
        if isolation not in ISOLATION_MODES:
            raise ValueError(f"isolation must be one of "
                             f"{ISOLATION_MODES}, got {isolation!r}")
        if transport not in TRANSPORT_MODES:
            raise ValueError(f"transport must be one of "
                             f"{TRANSPORT_MODES}, got {transport!r}")
        if transport == "socket" and isolation != "process":
            raise ValueError("transport='socket' requires "
                             "isolation='process' (threads share a "
                             "heap; there is nothing to socket)")
        if worker_cmd is not None and transport != "socket":
            raise ValueError("worker_cmd needs transport='socket' — a "
                             "pipe cannot cross a launcher boundary")
        if worker_ckpt is not None and transport != "socket":
            raise ValueError(
                "worker_ckpt needs transport='socket': its point is "
                "that a worker on ANOTHER host loads weights from its "
                "local checkpoint store instead of receiving pickled "
                "params over the wire")
        check_chip_ownership(isolation, worker_cmd)
        self.worker_use_ema = bool(worker_use_ema)
        self.worker_quantize = str(worker_quantize)
        if self.worker_quantize not in ("none", "int8", "int8_kv"):
            raise ValueError(f"worker_quantize must be 'none', 'int8' "
                             f"or 'int8_kv', got {worker_quantize!r}")
        if (self.worker_use_ema or self.worker_quantize != "none") \
                and worker_ckpt is None:
            # these describe the WORKER's local load path; without a
            # ckpt-path spec the parent's (already transformed) params
            # cross the boundary and the flags would silently do
            # nothing — the same misconfiguration hazard as worker_cmd
            raise ValueError(
                "worker_use_ema/worker_quantize transform the "
                "checkpoint a worker loads locally — they need "
                "worker_ckpt (without it, pass params you transformed "
                "yourself)")
        self.devices_per_replica = int(devices_per_replica)
        if self.devices_per_replica < 1:
            raise ValueError(f"devices_per_replica must be >= 1, got "
                             f"{devices_per_replica}")
        if self.devices_per_replica > 1 and paged_attn == "kernel":
            # fail at construction with the typed error, not once per
            # circuit-broken bring-up attempt forever
            from dalle_pytorch_tpu.serve.mesh_engine import \
                MeshPagedAttnError
            from dalle_pytorch_tpu.utils.metrics import structured_event
            raise MeshPagedAttnError(structured_event(
                "serve_mesh_paged_attn_unsupported",
                paged_attn="kernel"))
        # the CLI-harness fault path (DALLE_FAULTS): child plans are cut
        # at spawn time, so the env plan must be live before the first
        # bring-up — no-op when unset or already active
        faults.maybe_activate_from_env()
        self.params = params
        self.cfg = cfg
        self.queue = queue
        self.n_replicas = int(replicas)
        self.complete = complete
        # set-level flight recorder (docs/OBSERVABILITY.md): routing/
        # supervision/scale/upgrade lifecycle events and the router's
        # trace spans, always on — fence events land here WITH the
        # victim replica's own ring embedded, so /debug/events can
        # reconstruct a failover after the victim is gone
        from dalle_pytorch_tpu.obs import flight as oflight
        self.flight = oflight.FlightRecorder(capacity=512)
        self.metrics = oflight.wrap_metrics(self.flight, metrics)
        self.fence_dumps: dict = {}     # replica index -> last dump
        self.clock = clock
        self.heartbeat_s = float(heartbeat_s)
        self.kv = str(kv)
        self.isolation = str(isolation)
        self.transport = str(transport)
        self.worker_cmd = worker_cmd
        self.child_rss_limit_mb = int(child_rss_limit_mb)
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.compile_grace_s = float(compile_grace_s)
        self.listener = None
        if self.transport == "socket":
            from dalle_pytorch_tpu.serve import transport as T
            host, port = T.parse_endpoint(worker_endpoint)
            self.listener = T.WorkerListener(
                host, port, token=attach_token,
                on_event=(lambda rec: self._event(rec.pop("kind"),
                                                  **rec)))
        self._engine_kwargs = dict(
            num_slots=num_slots, chunk_steps=chunk_steps,
            prefill_buckets=prefill_buckets, metrics=metrics,
            log_every=log_every, quantize_cache=quantize_cache,
            kv=kv, page_size=page_size, num_pages=num_pages,
            paged_attn=paged_attn, sparse_reads=sparse_reads,
            speculative=speculative, draft_layers=draft_layers,
            prefix_cache=prefix_cache, preview_every=preview_every)
        # progressive-preview hook (serve/stream.py): installed by the
        # server AFTER construction, copied onto each replica engine at
        # bring-up. Thread isolation only — a child-process engine has
        # stand-in handles with no sink, so previews (like streaming)
        # are a typed reject there, and _child_kwargs deliberately
        # omits preview_every.
        self.on_preview: Optional[Callable] = None
        self.worker_ckpt = worker_ckpt
        if self.isolation == "process":
            import numpy as np
            # what crosses the spawn boundary: a host numpy pytree of
            # the params (one device_get here, one upload in the child
            # — the child owns its own device copy), and a picklable
            # subset of the engine kwargs (the metrics sink stays in
            # the parent; supervision events are parent-side). With
            # worker_ckpt set, NO params cross at all: the spec carries
            # the checkpoint path and each worker loads + validates
            # locally (serve/worker.py) — the attach spec shrinks from
            # the full weight pytree to a string
            self._np_params = None if worker_ckpt is not None \
                else jax.tree.map(np.asarray, params)
            self._child_kwargs = dict(
                num_slots=num_slots, chunk_steps=chunk_steps,
                prefill_buckets=prefill_buckets,
                quantize_cache=quantize_cache,
                kv=kv, page_size=page_size, num_pages=num_pages,
                paged_attn=paged_attn, sparse_reads=sparse_reads,
                speculative=speculative, draft_layers=draft_layers,
                prefix_cache=prefix_cache)
            # routing needs page math without an Engine in-process:
            # mirror the engine's bucket/page-size resolution
            self._buckets = (S.prefill_buckets(cfg.text_seq_len)
                             if prefill_buckets is None
                             else tuple(sorted(set(
                                 int(b) for b in prefill_buckets))))
            self._page_size = (int(page_size)
                               or min(16, cfg.seq_len)) if kv == "paged" \
                else 0
        # circuit-breaker backoff between bring-up attempts; serving
        # wants short first retries and a firm cap, not training's
        # minutes-scale defaults
        self.bringup_policy = bringup_policy or rretry.RetryPolicy(
            max_attempts=1, deadline_s=None, base_backoff_s=0.5,
            backoff_multiplier=2.0, max_backoff_s=30.0, jitter=0.0)
        self._idle_sleep_s = float(idle_sleep_s)

        devices = jax.devices()
        self._placed = place_on_devices and len(devices) > 1
        self.replicas: List[_Replica] = []
        for i in range(self.n_replicas):
            self.replicas.append(_Replica(
                i, device=self._device_for(i),
                version=self.weights_version,
                role=self.roles[i] if self.roles else "both"))

        # supervisor counters + retired-engine counter base: a fenced
        # engine's numbers are folded in here at reclaim time (minus the
        # reclaimed requests' harvested prefixes — replay re-credits
        # every token, the same distinct-delivered-tokens discipline as
        # paged eviction), so the set's aggregates survive failovers
        self._retired = {k: 0 for k in _COUNTERS}
        self.failovers = 0
        self.reclaimed = 0
        self.expired = 0             # router-side queued-deadline reaps
        self.bringup_failures = 0
        # elastic-fleet bookkeeping (scale API + rolling upgrade)
        self.scale_outs = 0
        self.scale_ins = 0
        self.upgrades = 0            # completed rolling upgrades
        self._upgrading = False      # one reshape owner at a time
        # live KV migration (drain/scale-in/upgrade/role handoff):
        # set-level counters — migration is a SET concern (a request
        # moving between engines), so the counters live here rather
        # than in every engine's COUNTERS tuple
        self.migrations = 0
        self.migrate_fallbacks = 0
        self.migrated_tokens_saved = 0
        self.migration_seconds: List[float] = []  # histogram samples
        self._role_sweep_t = 0.0     # prefill->decode handoff pacing
        # set-level HOL page reservations handed back by fenced/drained
        # replicas: {request_id: pages_needed}. The router routes such a
        # request with its EXACT (prefix-aware) need instead of the
        # blind full-span guess, and the reservation clears the moment
        # it lands on a replica (whose own _hol floor takes over).
        self._hol_handoff: dict = {}
        self.hol_handoffs = 0
        # version-pinned replay: rids currently HELD for a same-version
        # replica (event de-dup), and the canary machinery's id space —
        # negative, so canary requests can never collide with the
        # shared queue's monotonically increasing request ids
        self._version_holds: set = set()
        self._canary_ids = itertools.count(-1000, -1)
        self._canary_ref: dict = {}  # (version, k) -> token reference
        self._ctl_lock = threading.Lock()
        self._started = False
        self._ctl_thread: Optional[threading.Thread] = None
        self._ctl_stop = threading.Event()
        self._t_start: Optional[float] = None

        # no other thread exists yet, but _bring_up mutates set-level
        # counters (bringup_failures) that every later call site guards
        # with _ctl_lock — keep the discipline uniform from the start
        with self._ctl_lock:
            now = self.clock()
            for r in self.replicas:
                self._bring_up(r, now)

    # -- events -------------------------------------------------------------

    def _event(self, kind: str, **fields) -> None:
        if self.metrics is not None:
            try:
                self.metrics.event(**S.structured_event(kind, **fields))
            except Exception:   # noqa: BLE001 — observability must never
                pass            # take down supervision

    def _mark_replay(self, h: S.RequestHandle, reason: str,
                     replica: int) -> None:
        """Stamp the failover-replay link on a reclaimed handle's trace:
        the ``replayed_from`` span covers the fence gap (victim's last
        progress -> re-queue) under its own name — the timeline shows a
        labeled gap, never decode time that didn't happen — and opens
        the next attempt. The marker also lands in the set ring."""
        if h.trace is not None:
            self.flight.record(h.trace.replay(
                self.clock(), reason=reason, replica=replica))

    def _scale_error(self, op: str, **fields) -> ScaleError:
        """A typed reshape rejection with the set ring's tail embedded:
        the refusal record carries the recent lifecycle events that
        explain WHY (who is mid-upgrade, which bring-up failed), so the
        operator's 409 body is a diagnosis, not just a verdict."""
        return ScaleError(S.structured_event(
            "serve_scale_reject", op=op, **fields,
            flight=self.flight.tail(32)))

    def debug_events(self) -> dict:
        """The ``GET /debug/events`` body: the set-level ring, every
        live replica's ring (a process replica's parent-side mirror),
        and the last fence dump per fenced replica index."""
        out = {"server": self.flight.dump(), "replicas": {}, "loop": {},
               "fenced": {str(i): d for i, d in
                          self.fence_dumps.items()}}
        for r in self.replicas:
            fl = getattr(r.engine, "flight", None)
            if fl is not None:
                out["replicas"][str(r.index)] = fl.dump()
            # a thread replica's chunk ledger; a child's rows stay with
            # it, its stalls arrive in the mirror ring as events
            ledger = getattr(r.engine, "loop_ring", None)
            if ledger is not None:
                out["loop"][str(r.index)] = ledger.dump()
        return out

    def _device_for(self, i: int):
        """Placement for replica ``i`` — shared by the constructor and
        ``add_replica`` (a replica born at runtime places exactly like
        one born at startup)."""
        import jax
        devices = jax.devices()
        if self.devices_per_replica > 1 and self.isolation != "process":
            # replica = mesh SLICE: devices [i*m, (i+1)*m) (wrapped
            # like the single-chip i % n placement when the host
            # holds fewer slices than replicas). A mesh engine is
            # always pinned to its slice — unpinned, every replica
            # would shard over ALL devices and serialize against
            # the others. Process mode resolves the slice in the
            # WORKER from its own jax client (serve/worker.py): a
            # remote worker's devices live on its host, and the
            # parent — possibly a 0-accelerator head node — must
            # not gate construction on holding them locally.
            from dalle_pytorch_tpu.parallel import serve_specs as SS
            return SS.slice_devices(devices, i, self.devices_per_replica)
        return devices[i % len(devices)] if self._placed else None

    def _on_complete(self, handle: S.RequestHandle,
                     result: S.Result) -> None:
        """Every thread-mode engine's ``complete`` hook: canary handles
        (rolling upgrade's health-gate probes) are fulfilled directly —
        they must never reach the server's postprocess stage or latency
        accounting — everything else flows to the set's downstream
        ``complete`` exactly as before."""
        if getattr(handle, "canary", False) or self.complete is None:
            handle.fulfill(result)
        else:
            self.complete(handle, result)

    # -- bring-up / circuit breaker -----------------------------------------

    def _bring_up(self, r: _Replica, now: float) -> bool:
        """One bring-up attempt: fresh private queue + fresh Engine (the
        old pair, if any, was fenced and drained at reclaim — reusing
        the drained queue would cancel the NEW engine's evictions).
        Failure schedules the next attempt with exponential backoff;
        the replica stays circuit-broken (BROKEN) in between."""
        from dalle_pytorch_tpu.resilience import faults
        from dalle_pytorch_tpu.serve.engine import Engine

        attempt = r.bringups
        r.bringups += 1
        # per-replica weight resolution: a replica mid-upgrade carries
        # an override (new params pytree, or a new ckpt path for the
        # checkpoint-path attach shape); everyone else serves the
        # set-level weights. weights_version rides into the engine so
        # every Result it fulfils is stamped with the generation that
        # decoded it — and the same string keys the prefix cache
        # (model_version), so an upgraded replica can never serve a
        # previous generation's cached prompt KV.
        params = self.params if r.params_override is None \
            else r.params_override
        ckpt = self.worker_ckpt if r.ckpt_override is None \
            else r.ckpt_override
        versioned = dict(weights_version=r.version,
                         model_version=r.version)
        try:
            faults.on_replica_bringup(r.index, attempt)
            if r.born_scaled:
                # the scale-out fault row: a replica born from
                # add_replica killed mid-bring-up (circuit-breaks and
                # retries; the serving survivors must be untouched)
                faults.on_scale_add_bringup(r.index, attempt)
            if self.isolation == "process":
                from dalle_pytorch_tpu.serve import ipc
                if ckpt is not None:
                    np_params = None
                elif r.params_override is not None:
                    import jax
                    import numpy as np
                    np_params = jax.tree.map(np.asarray,
                                             r.params_override)
                else:
                    np_params = self._np_params
                client = ipc.ChildEngineClient(
                    np_params, self.cfg,
                    index=r.index,
                    engine_kwargs={**self._child_kwargs, **versioned},
                    device_index=r.index,
                    place=self._placed,
                    devices_per_replica=self.devices_per_replica,
                    ckpt_path=ckpt,
                    ckpt_use_ema=self.worker_use_ema,
                    ckpt_quantize=self.worker_quantize,
                    heartbeat_interval_s=min(
                        max(self.heartbeat_s / 5, 0.01), 0.25),
                    rss_limit_mb=self.child_rss_limit_mb,
                    # hard-fault plans cross the boundary ONCE per
                    # activation per replica (fire-once must outlive
                    # the child — see faults.child_plan_for)
                    fault_plan=faults.child_plan_for(r.index),
                    idle_sleep_s=self._idle_sleep_s,
                    clock=self.clock,
                    on_done=self._child_done,
                    transport=self.transport,
                    listener=self.listener,
                    worker_cmd=self.worker_cmd)
            else:
                queue = S.RequestQueue(
                    max_depth=4 * self._engine_kwargs["num_slots"] + 8,
                    clock=self.clock)
                if self.devices_per_replica > 1:
                    # replica = mesh slice: same Engine surface, params
                    # + KV sharded over this replica's device slice —
                    # which is why nothing else in this module changes
                    from dalle_pytorch_tpu.serve.mesh_engine import \
                        MeshEngine
                    engine = MeshEngine(
                        params, self.cfg, queue,
                        complete=self._on_complete, clock=self.clock,
                        devices=r.device,
                        **{**self._engine_kwargs, **versioned})
                else:
                    engine = Engine(params, self.cfg, queue,
                                    complete=self._on_complete,
                                    clock=self.clock,
                                    device=r.device,
                                    **{**self._engine_kwargs,
                                       **versioned})
                # every bring-up (initial, restart, scale-out) inherits
                # the set-level preview hook — a replica that replaced
                # a crashed one keeps streaming previews
                engine.on_preview = self.on_preview
        except Exception as e:  # noqa: BLE001 — circuit-break, don't die
            r.attempt += 1
            self.bringup_failures += 1
            delay = self.bringup_policy.backoff(min(r.attempt - 1, 20))
            r.next_bringup_t = now + delay
            r.last_error = repr(e)
            r.state = BROKEN
            self._event("serve_replica_bringup_fail", replica=r.index,
                        attempt=attempt, consecutive=r.attempt,
                        backoff_s=round(delay, 3), error=repr(e))
            return False
        if self.isolation == "process":
            # the spawn is async: the child is importing jax and
            # building its engine. RUNNING means "spawned"; routing is
            # gated on client.ready, and _check_replicas turns a child
            # that dies or stalls before READY into a bring-up failure
            # (with backoff), not a failover — there is nothing to
            # reclaim yet. r.attempt resets when READY lands.
            r.engine, r.queue = client, None
            r.dead = False
            r.await_ready = True
            r.stop = None
            r.state = RUNNING
            return True
        # an orphan is a handle the fenced engine popped but never
        # admitted (fence landed mid-step): back to the shared queue
        engine.on_fenced_orphan = \
            lambda h: self.queue.requeue(h)
        r.engine, r.queue = engine, queue
        r.attempt = 0
        r.dead = False
        r.last_error = ""
        r.stop = threading.Event()
        r.state = RUNNING
        self._event("serve_replica_up", replica=r.index,
                    bringups=r.bringups, device=str(r.device))
        if self._started:
            self._spawn(r)
        return True

    def _child_done(self, handle: S.RequestHandle,
                    result: S.Result) -> None:
        """Completion hand-off for process-mode results (the client's
        ``on_done``): same contract as ``Engine._finish`` — OK results
        flow downstream (postprocess), everything else fulfils the
        handle directly. Canary probes (rolling upgrade) never flow
        downstream: the health gate reads them, nobody else."""
        if result.status == S.OK and self.complete is not None \
                and not getattr(handle, "canary", False):
            self.complete(handle, result)
        else:
            handle.fulfill(result)

    # -- fencing and reclaim (failover / drain) -----------------------------

    def _fence_and_reclaim(self, r: _Replica, now: float,
                           reason: str) -> int:
        """Fence the replica's engine, then reclaim every request it
        held — private queue first (routed, never admitted), then the
        in-slot handles — back into the shared queue at their original
        arrival positions for deterministic replay. Fencing comes FIRST:
        from that point the old engine cannot fulfil, complete, or
        requeue anything, so the reclaim sweep is the single owner of
        these handles (a wedge waking later hits the fence, and
        ``fulfill`` being first-write-wins closes the last window).

        Process mode inverts one step on purpose: the child is KILLED
        first (SIGKILL — crashed, wedged, or lying, all three deserve
        -9), then the pipe is drained for frames written before death
        (salvaged results stand and are NOT replayed; the final
        snapshot is the last consistent counter state), and only then
        is the client fenced and the shadow reclaimed. Killing before
        salvaging is what makes the drain safe: a dead writer cannot
        extend the stream while we read it."""
        if self.isolation == "process":
            return self._fence_and_reclaim_child(r, now, reason)
        eng, q = r.engine, r.queue
        r.engine, r.queue, r.thread = None, None, None
        if r.stop is not None:
            r.stop.set()
        reclaimed = 0
        if eng is not None:
            eng.fence()
            # a crashed/exited loop left the lock free and the hang
            # fault sleeps outside it, so this normally succeeds; a
            # thread truly wedged INSIDE a step keeps the lock — the
            # snapshot below is host-side bookkeeping only, safe to
            # read anyway, and the fence already disarmed the wedge
            got = eng._lock.acquire(timeout=0.2)
            try:
                queued = q.drain() if q is not None else []
                slots = [s for s in list(eng.slots) if s is not None]
                # inflight covers the slots AND any mid-admission
                # handles a thread wedged inside the admission compile
                # holds in step locals (engine._admitting)
                inflight = eng.inflight_handles()
                # the engine's head-of-line page reservation must not
                # die with it: hand it back to the shared-queue level
                # (the router routes the waiting request with its EXACT
                # prefix-aware need, not the blind full-span guess)
                hol = (None if eng.kv != "paged"
                       or eng._hol_rid is None
                       else (eng._hol_rid, eng._hol_need))
            finally:
                if got:
                    eng._lock.release()
            # fold the dead engine's counters into the set's base,
            # un-crediting reclaimed requests' harvested prefixes: the
            # replay re-credits every token, and the aggregate must
            # keep counting DISTINCT delivered tokens (same discipline
            # as paged eviction's un-credit)
            retire = {k: getattr(eng, k, 0) for k in _COUNTERS}
            for s in slots:
                retire["tokens_decoded"] -= len(s.emitted)
                retire["occupancy_sum"] -= len(s.emitted)
            for k in _COUNTERS:
                self._retired[k] += retire[k]
            seen: set = set()
            for h in queued + inflight:
                rid = h.request.request_id
                if h.done() or rid in seen:
                    continue
                seen.add(rid)
                if getattr(h, "canary", False):
                    # an upgrade probe dying with its replica: cancel,
                    # never replay — a canary in the shared queue would
                    # decode as (and be billed like) real traffic
                    h.fulfill(S.Result(
                        status=S.CANCELLED, request_id=rid,
                        reason="canary cancelled (replica fenced)"))
                    continue
                # original arrival position: zero-loss AND no
                # queue-jumping — a replayed request neither loses
                # its place nor steals anyone else's
                self._mark_replay(h, reason, r.index)
                self.queue.requeue(h)
                reclaimed += 1
            if hol is not None and hol[0] in seen:
                self._hol_handoff[hol[0]] = hol[1]
                self.hol_handoffs += 1
                self._event("serve_hol_handoff", replica=r.index,
                            request_id=hol[0], pages_needed=hol[1])
        # the victim's flight recorder rides the fence event: the ring
        # was always on, so the post-mortem exists even when no JSONL
        # sink was ever configured
        dump = eng.flight.dump() if eng is not None \
            and getattr(eng, "flight", None) is not None else []
        self.fence_dumps[r.index] = dump
        self.reclaimed += reclaimed
        self._event("serve_replica_fenced", replica=r.index,
                    reason=reason, reclaimed=reclaimed, flight=dump)
        return reclaimed

    def _fence_and_reclaim_child(self, r: _Replica, now: float,
                                 reason: str) -> int:
        """The process-mode half of ``_fence_and_reclaim`` (see its
        docstring): kill -> salvage -> fence -> reclaim-from-shadow."""
        client = r.engine
        r.engine, r.queue, r.thread = None, None, None
        r.await_ready = False
        reclaimed = 0
        if client is not None:
            # how the child died, honestly: a child that was already
            # dead when we got here died on its own (signal/OOM/crash
            # — the decoded exit is the story); a child WE are killing
            # (drain, hang, protocol error) must not advertise
            # 'killed by SIGKILL' as if the OS had done it
            died_on_its_own = not client.alive_proc()
            client.hard_kill()
            r.last_exit = (client.exit_desc() if died_on_its_own
                           else f"hard-killed by supervisor ({reason})")
            client.salvage()
            client.fence()
            handles = client.reclaim()
            retire = client.retire_counters(handles)
            for k in _COUNTERS:
                self._retired[k] += retire.get(k, 0)
            rids = set()
            for h in handles:
                rid = h.request.request_id
                if getattr(h, "canary", False):
                    # same rule as the thread path: probes die with
                    # their replica, they never replay as traffic
                    h.fulfill(S.Result(
                        status=S.CANCELLED, request_id=rid,
                        reason="canary cancelled (replica fenced)"))
                    continue
                rids.add(rid)
                # original arrival position: zero-loss AND no
                # queue-jumping, same as the thread path
                self._mark_replay(h, reason, r.index)
                self.queue.requeue(h)
                reclaimed += 1
            # the child's last-frame HOL reservation (serve/ipc.py
            # snapshots mirror it) hands back exactly like a thread
            # engine's — the corpse can't be asked, the mirror can
            if client.hol is not None and client.hol[0] in rids:
                self._hol_handoff[client.hol[0]] = client.hol[1]
                self.hol_handoffs += 1
                self._event("serve_hol_handoff", replica=r.index,
                            request_id=client.hol[0],
                            pages_needed=client.hol[1])
        # the parent-side MIRROR ring (fed by the frames the child
        # shipped before dying) is what a SIGKILL cannot destroy: the
        # dump is whatever the victim managed to tell us, which the
        # frame protocol guarantees is a consistent prefix
        dump = client.flight.dump() if client is not None \
            and getattr(client, "flight", None) is not None else []
        self.fence_dumps[r.index] = dump
        self.reclaimed += reclaimed
        self._event("serve_replica_fenced", replica=r.index,
                    reason=reason, reclaimed=reclaimed,
                    exit=r.last_exit, flight=dump)
        return reclaimed

    def _failover(self, r: _Replica, now: float, reason: str) -> None:
        self.failovers += 1
        self._fence_and_reclaim(r, now, reason)
        r.state = BROKEN
        r.next_bringup_t = now          # first restart attempt is free;
        #                                 backoff only after it fails

    # -- live KV migration (drain / scale-in / upgrade / roles) -------------

    def _migrate_targets(self, src: _Replica,
                         pin: Optional[str],
                         exclude_prefill: bool = False) -> List[_Replica]:
        """Replicas that could take a migrated request RIGHT NOW:
        serving, not a canary, version-matched when the request is
        pinned, with slot capacity. Decode-capable targets sort first
        (a ``prefill`` replica is a landing spot of last resort — and
        never one at all for the prefill->decode handoff sweep, which
        would otherwise ping-pong work between prefill replicas)."""
        out = []
        for x in self.replicas:
            if x is src or x.state != RUNNING or x.engine is None \
                    or x.canary:
                continue
            if pin is not None and x.version != pin:
                continue
            if exclude_prefill and x.role == "prefill":
                continue
            if not self._replica_serving(x):
                continue
            if self._capacity(x) <= 0:
                continue
            out.append(x)
        out.sort(key=lambda x: (x.role == "prefill",
                                -self._capacity(x), x.index))
        return out

    def _inslot_requests(self, r: _Replica):
        """``(request_id, handle)`` for every request that may hold a
        live slot on ``r`` — exact for a thread engine (read off its
        slot table), the full shadow for a process child (the parent
        cannot see which shadow entries are in-slot; an export of a
        merely-queued one answers ``not_found`` and is skipped).
        Canary probes never migrate — they exist to gate ONE replica."""
        if self.isolation == "process":
            return [(rid, h) for rid, h in list(r.engine.shadow.items())
                    if not h.done() and not getattr(h, "canary", False)]
        eng = r.engine
        out = []
        with eng._lock:
            for s in eng.slots:
                if s is not None and s.shadow_of is None \
                        and not s.handle.done() \
                        and not getattr(s.handle, "canary", False):
                    out.append((s.handle.request.request_id, s.handle))
        return out

    def _migrate_fallback(self, src: _Replica, rid: int,
                          handle: Optional[S.RequestHandle],
                          reason: str, detail: str, now: float) -> None:
        """One migration attempt giving up: structured event + counter,
        and — when the export already VACATED the source slot (handle
        in hand) — the replay fallback itself: requeue at the original
        arrival position, exactly like a fence reclaim. With no handle
        the request never left the source, so the fence that follows a
        failed drain-migration replays it through the normal path."""
        self.migrate_fallbacks += 1
        self._event("serve_migrate_fallback", request_id=rid,
                    replica=src.index, reason=reason, error=detail)
        if handle is not None and not handle.done():
            self._mark_replay(handle, f"migration fallback ({reason})",
                              src.index)
            self.queue.requeue(handle)

    def _migrate_from(self, src: _Replica, now: float, reason: str,
                      pin_version: Optional[str] = None,
                      exclude_prefill: bool = False) -> int:
        """Move ``src``'s in-slot requests to live targets MID-STREAM
        — KV pages, decode cursor, RNG and all — instead of replaying
        them from token zero. The planned-downtime paths (operator
        drain, scale-in, rolling-upgrade drain, autoscaler scale-in)
        call this immediately before their fence; the prefill->decode
        role sweep calls it on a healthy source. Replay stays the
        automatic fallback at every rung: source dead or denies the
        export -> the fence's reclaim replays; export succeeded but no
        target can map it -> requeued for replay right here. Returns
        the number of requests migrated."""
        from dalle_pytorch_tpu.resilience import faults
        if self.kv != "paged" or src.engine is None:
            return 0    # dense KV has no transferable pages
        if not self._replica_serving(src):
            return 0    # a corpse answers nothing: replay handles it
        moved = 0
        for rid, pre in self._inslot_requests(src):
            pin = getattr(pre, "replay_version", None) or pin_version \
                or src.version
            targets = self._migrate_targets(src, pin, exclude_prefill)
            if not targets:
                break   # nowhere to land anything: fence will replay
            t0 = time.perf_counter()
            handle: Optional[S.RequestHandle] = None
            try:
                # the crash-mid-transfer fault row: SIGKILL the source
                # exactly as the snapshot is requested — the export
                # times out against a corpse and everything it held
                # falls back to fence-reclaim replay, zero loss
                faults.on_migrate_transfer(
                    src.index,
                    getattr(src.engine, "pid", None)
                    if self.isolation == "process" else None)
                if self.isolation == "process":
                    snap = src.engine.export_request(rid)
                    handle = src.engine.shadow.pop(rid, None)
                    if handle is None:
                        raise MigrationError(
                            "not_found", "no shadow handle for the "
                            "exported request")
                else:
                    snap, handle = src.engine.export_request(rid)
            except MigrationError as e:
                if e.reason == "not_found":
                    # queued / mid-admission / just completed: nothing
                    # mid-stream to move — not a fallback, the normal
                    # paths own it
                    continue
                self._migrate_fallback(src, rid, handle, e.reason,
                                       str(e), now)
                if not self._replica_serving(src):
                    break   # source died under us: fence replays rest
                continue
            except faults.FaultInjected as e:
                self._migrate_fallback(src, rid, handle, "source_dead",
                                       str(e), now)
                continue
            saved = len(snap.get("emitted") or ())
            dst = None
            err_reason, err_detail = "target_pages", ""
            for tgt in targets:
                try:
                    # the reject-target fault row: the target reports
                    # page exhaustion at import time
                    faults.on_migrate_import(tgt.index)
                    if self.isolation == "process":
                        tgt.engine.import_request(snap, handle)
                    else:
                        tgt.engine.import_slot(snap, handle)
                    dst = tgt
                    break
                except MigrationError as e:
                    err_reason, err_detail = e.reason, str(e)
                except faults.FaultInjected as e:
                    err_reason, err_detail = "target_pages", str(e)
            if dst is None:
                # the export vacated the source slot and credited its
                # prefix to the source's counters; the replay re-decodes
                # and re-credits every token, so un-credit here to keep
                # the aggregate counting DISTINCT delivered tokens (the
                # same discipline as eviction and fence reclaim)
                self._retired["tokens_decoded"] -= saved
                self._retired["occupancy_sum"] -= saved
                self._migrate_fallback(src, rid, handle, err_reason,
                                       err_detail, now)
                continue
            wall = time.perf_counter() - t0
            moved += 1
            self.migrations += 1
            self.migrated_tokens_saved += saved
            self.migration_seconds.append(wall)
            if handle.trace is not None:
                self.flight.record(handle.trace.span(
                    "migrate", now, src=src.index, dst=dst.index,
                    tokens_saved=saved))
            self._event("serve_migrated", request_id=rid,
                        src=src.index, dst=dst.index,
                        tokens_saved=saved, reason=reason,
                        wall_s=round(wall, 4))
        return moved

    def _role_handoff(self, now: float) -> bool:
        """The disaggregated-serving sweep: a ``prefill`` replica keeps
        admission + prefill and hands every warm (in-slot, decoding)
        request to a decode-capable replica the moment one has
        capacity. Paced — the sweep costs an export probe per in-slot
        request, so it runs at most every 50ms, and not at all in a
        homogeneous (all-``both``) fleet."""
        if self.kv != "paged" or self._upgrading:
            return False
        sources = [r for r in self.replicas
                   if r.state == RUNNING and r.role == "prefill"
                   and r.engine is not None]
        if not sources or now - self._role_sweep_t < 0.05:
            return False
        self._role_sweep_t = now
        did = False
        for r in sources:
            did = bool(self._migrate_from(
                r, now, reason="prefill_handoff",
                exclude_prefill=True)) or did
        return did

    # -- operator drain -----------------------------------------------------

    def drain_replica(self, index: int,
                      reason: str = "operator drain") -> int:
        """Planned maintenance: live-migrate the in-flight work to
        survivors mid-stream (each moved request keeps every token it
        already decoded), then fence + reclaim whatever could not move
        (replays on the survivors — zero requests lost either way) and
        hold the replica DOWN until ``undrain_replica``. Returns the
        number of requests handed to survivors (migrated + reclaimed)."""
        with self._ctl_lock:
            self._reject_mid_upgrade("drain")
            r = self._replica_or_reject("drain", index)
            now = self.clock()
            # racelint: disable=RL003 — deliberate: reshapes are
            # serialized by _ctl_lock end-to-end; migration transfers
            # (and the fault hooks that delay them in tests) run under
            # it so no second reshape can observe a half-moved slot.
            # The data plane (engine/queue locks) is not held here.
            moved = self._migrate_from(r, now, reason=reason)
            n = self._fence_and_reclaim(r, self.clock(), reason)
            r.state = DRAINED
            return moved + n

    def undrain_replica(self, index: int) -> bool:
        """Bring a drained replica back into routing (one bring-up
        attempt now; failure re-enters the circuit-breaker path)."""
        with self._ctl_lock:
            self._reject_mid_upgrade("undrain")
            r = self.replicas[index]
            if r.state != DRAINED:
                return False
            return self._bring_up(r, self.clock())

    # -- elastic fleet: runtime scale-out/in --------------------------------

    def _replica_or_reject(self, op: str, index: int) -> _Replica:
        """The slot an operator named, or a typed ``ScaleError`` — a
        retired tombstone or an out-of-range index must never be acted
        on half-way."""
        if not 0 <= index < len(self.replicas):
            raise self._scale_error(op, replica=index,
                                    reason="no_such_replica",
                                    replicas=len(self.replicas))
        r = self.replicas[index]
        if r.state == RETIRED:
            raise self._scale_error(op, replica=index,
                                    reason="replica_retired")
        return r

    def _reject_mid_upgrade(self, op: str) -> None:
        if self._upgrading:
            raise self._scale_error(op, reason="upgrade_in_progress")

    def add_replica(self, role: str = "both") -> int:
        """Runtime scale-out: append one new supervised slot — same
        isolation/transport/mesh shape as the rest of the set — and
        bring it up now. The replica joins routing ATOMICALLY once
        serving (thread engines immediately; process children at their
        READY frame — ``_route`` never offers work to a slot that
        cannot take it), and a bring-up failure circuit-breaks with
        backoff exactly like a failover restart: the survivors never
        notice. Growing past ``max_replicas`` is a typed ``ScaleError``
        — the cap exists because every replica allocates its own KV
        page pool, so fleet width is an HBM page budget, not a free
        integer. Returns the new replica's index."""
        with self._ctl_lock:
            self._reject_mid_upgrade("add")
            if role not in REPLICA_ROLES:
                raise self._scale_error("add", reason="unknown_role",
                                        role=str(role))
            if role != "both" and self.kv != "paged":
                raise self._scale_error(
                    "add", reason="roles_need_paged_kv", role=role)
            active = [r for r in self.replicas if r.state != RETIRED]
            if self.max_replicas and len(active) >= self.max_replicas:
                raise self._scale_error(
                    "add", reason="scale_out_past_cap",
                    replicas=len(active),
                    max_replicas=self.max_replicas)
            index = len(self.replicas)
            r = _Replica(index, device=self._device_for(index),
                         version=self.weights_version, role=role)
            r.born_scaled = True
            self.replicas.append(r)
            self.n_replicas = len(active) + 1
            self.scale_outs += 1
            self._event("serve_scale_out", replica=index,
                        replicas=self.n_replicas,
                        weights_version=self.weights_version)
            self._bring_up(r, self.clock())
            return index

    def remove_replica(self, index: int, drain: bool = True,
                       reason: str = "operator scale-in") -> int:
        """Runtime scale-in: drain ``index``'s in-flight work to the
        survivors — LIVE-MIGRATED mid-stream first (KV pages + decode
        cursor move; every already-decoded token is kept), with the
        fence→reclaim→replay of failover as the unconditional fallback
        for anything that could not move (zero-loss is not a flag;
        ``drain=False`` skips the migration pass and names the
        operator's replay-only intent in the event stream) — and
        RETIRE the slot for good. Removing the last live replica is a
        typed ``ScaleError``: a set with no slots is not a smaller
        fleet, it is an outage an operator almost certainly didn't
        mean. Returns the number of requests handed to survivors
        (migrated + reclaimed)."""
        with self._ctl_lock:
            self._reject_mid_upgrade("remove")
            r = self._replica_or_reject("remove", index)
            survivors = [x for x in self.replicas
                         if x is not r and x.state != RETIRED]
            if not survivors:
                raise self._scale_error("remove", replica=index,
                                        reason="remove_last_replica")
            now = self.clock()
            # racelint: disable=RL003 — deliberate: scale-in migrates
            # under _ctl_lock so the reshape is atomic against other
            # control-plane ops; the data plane stays unlocked
            moved = self._migrate_from(r, now, reason=reason) \
                if drain else 0
            n = self._fence_and_reclaim(r, self.clock(), reason)
            r.state = RETIRED
            r.params_override = None
            r.ckpt_override = None
            self.n_replicas = len(survivors)
            self.scale_ins += 1
            self._event("serve_scale_in", replica=index, drain=drain,
                        migrated=moved, reclaimed=n,
                        replicas=self.n_replicas)
            return moved + n

    # -- elastic fleet: rolling weight hot-swap -----------------------------

    def _drive_until(self, pred: Callable[[], bool],
                     timeout_s: float) -> bool:
        """Wait for ``pred`` while keeping the set moving: in threaded
        mode the control loop is already running, so just sleep; in
        single-threaded drive (tests, bench) the caller IS the loop,
        so step. Wall-clock bounded either way."""
        deadline = time.perf_counter() + timeout_s
        while time.perf_counter() < deadline:
            if pred():
                return True
            if self._started:
                time.sleep(0.005)
            else:
                self.step_once()
        return pred()

    def _replica_serving(self, r: _Replica) -> bool:
        """The replica's engine can decode a request RIGHT NOW (for a
        process child: READY landed and the process is believable)."""
        if r.state != RUNNING or r.engine is None:
            return False
        if self.isolation == "process":
            c = r.engine
            return c.ready and not c.crashed and not c.poisoned \
                and not c.fenced and c.alive_proc()
        return True

    def _submit_canaries(self, r: _Replica, version: str,
                         canary_codes, n: int) -> List[S.RequestHandle]:
        """Hand ``n`` canary requests DIRECTLY to replica ``r`` —
        never through the shared queue, where a survivor would answer
        them and the gate would prove nothing. Canary ids are negative
        (they can never collide with queue-assigned request ids) and
        the handles are marked so reclaim cancels rather than replays
        them and completions bypass the postprocess stage."""
        now = self.clock()
        handles = []
        for k in range(n):
            codes = tuple(canary_codes[k % len(canary_codes)])
            rid = next(self._canary_ids)
            req = S.Request(codes=codes, seed=10_000 + k,
                            request_id=rid, submit_t=now)
            h = S.RequestHandle(req)
            h.queue_seq = rid       # unique (negative), heap-safe
            h.canary = True
            h.replay_version = version
            handles.append(h)
        with self._ctl_lock:
            if self.isolation == "process":
                r.engine.route(handles)
            else:
                for h in handles:
                    r.queue.requeue(h, count=False)
        return handles

    def _abort_upgrade(self, r: _Replica, version: str,
                       old_version: str, error: str,
                       timeout_s: float) -> None:
        """Roll the WHOLE fleet back to the old weights and raise the
        typed ``UpgradeAborted`` — the failing replica ``r`` AND every
        replica upgraded earlier in this cycle re-cycle (drain →
        bring-up on the old weights), so the abort leaves the fleet
        fully serving ``old_version``, never a mixed-version state.
        Work reclaimed from a rolled-back replica was pinned to the NEW
        generation; once no replica of it remains, the router releases
        the pin (structured event) and the replay re-decodes on the old
        weights — zero requests lost either way."""
        self._event("serve_upgrade_abort", replica=r.index, to=version,
                    error=error)
        rollback = [x for x in self.replicas
                    if x.state != RETIRED and x.version == version]
        for x in rollback:
            with self._ctl_lock:
                self._fence_and_reclaim(x, self.clock(),
                                        reason="upgrade rollback")
                x.canary = False
                x.version = old_version
                x.params_override = None
                x.ckpt_override = None
                self._bring_up(x, self.clock())
            # bounded wait for the rollback engine; a replica that
            # cannot even serve the OLD weights re-enters the circuit
            # breaker, which is the failover path's problem, not the
            # upgrade's
            self._drive_until(lambda x=x: self._replica_serving(x),
                              timeout_s)
        # the aborted generation's canary references must not outlive
        # the abort: a RETRY of the same version name compares its
        # replica-0 canaries against a fresh reference, not the failed
        # attempt's tokens (which may have come from a bad checkpoint)
        for k in [k for k in self._canary_ref if k[0] == version]:
            del self._canary_ref[k]
        raise UpgradeAborted(S.structured_event(
            "serve_upgrade_aborted", replica=r.index, to=version,
            error=error, rolled_back=[x.index for x in rollback],
            fleet_version=old_version,
            # the set ring's tail: the drain/bring-up/canary events of
            # the failed cycle ride the abort record itself
            flight=self.flight.tail(64)))

    def rolling_upgrade(self, *, version: str, params=None,
                        ckpt: Optional[str] = None,
                        canary_codes=None, canaries: int = 2,
                        replica_timeout_s: float = 300.0) -> dict:
        """Hot-swap the fleet's weights replica-by-replica with ZERO
        dropped requests (docs/SERVING.md 'Elastic fleet'). Per
        replica, in index order:

          1. DRAIN — fence + reclaim: its in-flight work replays on
             survivors still serving the OLD weights (version-pinned
             routing guarantees the replay lands on the generation
             that started it, so the tokens stay byte-identical);
          2. RESTART on the new weights — ``params`` (a new pytree,
             thread/pipe shapes) or ``ckpt`` (a new ``--worker_ckpt``
             path for checkpoint-path attach: each worker loads +
             validates locally, weights never cross the wire);
          3. HEALTH-GATE — ``canaries`` requests decoded by the new
             engine ALONE, token-compared against the first upgraded
             replica's canary tokens (every replica of a generation
             must provably sample identical streams; replica 0 of the
             cycle sets the reference). A canary error, token
             divergence, bring-up failure/timeout, or the replica
             dying mid-canary ABORTS: the replica rolls back to the
             old weights and the typed ``UpgradeAborted`` reports the
             fleet whole on the old version;
          4. UNDRAIN — the gated replica rejoins routing; next slot.

        After the last replica, the set-level weights/version are
        promoted so future bring-ups, scale-outs, and /stats all speak
        the new generation. Returns the structured upgrade record."""
        import numpy as np

        from dalle_pytorch_tpu.resilience import faults

        with self._ctl_lock:
            self._reject_mid_upgrade("upgrade")
            if not version or version == self.weights_version:
                raise self._scale_error(
                    "upgrade", reason="version_unchanged",
                    weights_version=self.weights_version)
            if (params is None) == (ckpt is None):
                raise self._scale_error(
                    "upgrade",
                    reason="need_exactly_one_of_params_or_ckpt")
            if ckpt is not None and self.worker_ckpt is None:
                raise self._scale_error(
                    "upgrade",
                    reason="ckpt_upgrade_needs_worker_ckpt_set")
            if params is not None and self.worker_ckpt is not None:
                raise self._scale_error(
                    "upgrade",
                    reason="params_upgrade_on_worker_ckpt_set")
            self._upgrading = True
        # EVERYTHING past the flag runs under the finally that clears
        # it — an exception anywhere here (even a bad canaries value)
        # must never leave the fleet permanently rejecting reshapes
        try:
            old_version = self.weights_version
            if canary_codes is None:
                # smallest-bucket probe; any valid prompt does — the
                # gate compares determinism across replicas, not
                # quality
                canary_codes = [(1,) * min(2, self.cfg.text_seq_len)]
            record = {"from": old_version, "to": version,
                      "canaries": int(canaries), "replicas": []}
            self._event("serve_upgrade_begin", to=version,
                        from_version=old_version,
                        replicas=self.n_replicas)
            for r in list(self.replicas):
                if r.state == RETIRED:
                    continue
                if r.state == DRAINED:
                    # an operator-drained replica stays DOWN — the
                    # drain contract ('down until undrain_replica')
                    # outranks the rollout. Its version label moves
                    # with the fleet at promote time, so a later
                    # undrain brings it up on the promoted set-level
                    # weights, correctly stamped; the skip is an event
                    # an operator can see, not a silent hole.
                    self._event("serve_upgrade_skip_drained",
                                replica=r.index, to=version)
                    record["replicas"].append(
                        {"replica": r.index, "skipped": "drained"})
                    continue
                t0 = time.perf_counter()
                # the drain-race fault row: a real SIGKILL landing just
                # as the planned drain begins — reclaim-from-shadow
                # absorbs it identically (the fence kills a corpse)
                faults.on_upgrade_drain(
                    r.index,
                    getattr(r.engine, "pid", None)
                    if self.isolation == "process" else None)
                with self._ctl_lock:
                    # live-migrate first, version-pinned exactly like
                    # replay: only survivors still serving THIS
                    # replica's (old) generation may take its work
                    # mid-stream — same-seed tokens are byte-identical
                    # per weights_version, not across them
                    # racelint: disable=RL003 — deliberate: upgrade
                    # migration runs under _ctl_lock like every other
                    # reshape; see drain() for the full rationale
                    migrated = self._migrate_from(
                        r, self.clock(),
                        reason=f"rolling upgrade to {version}",
                        pin_version=r.version)
                    reclaimed = self._fence_and_reclaim(
                        r, self.clock(),
                        reason=f"rolling upgrade to {version}")
                    r.version = version
                    r.params_override = params
                    r.ckpt_override = ckpt
                    r.canary = True
                    self._bring_up(r, self.clock())
                if not self._drive_until(
                        lambda: self._replica_serving(r),
                        replica_timeout_s):
                    self._abort_upgrade(
                        r, version, old_version,
                        f"bring-up on new weights timed out "
                        f"(> {replica_timeout_s:g}s): {r.last_error}",
                        replica_timeout_s)
                # captured AFTER serving is confirmed: a circuit-breaker
                # retry DURING bring-up (flaky first spawn) is the
                # supervisor doing its job, not a death — only a
                # bring-up count moving while canaries are in flight
                # means the fresh engine died under the gate
                bringups0 = r.bringups
                handles = self._submit_canaries(r, version,
                                                canary_codes, canaries)
                self._drive_until(
                    lambda: all(h.done() for h in handles)
                    or r.bringups != bringups0
                    or not self._replica_serving(r),
                    replica_timeout_s)
                if r.bringups != bringups0 \
                        or not self._replica_serving(r):
                    self._abort_upgrade(
                        r, version, old_version,
                        f"replica died during canary: {r.last_error}",
                        replica_timeout_s)
                if not all(h.done() for h in handles):
                    self._abort_upgrade(
                        r, version, old_version,
                        f"canaries not answered within "
                        f"{replica_timeout_s:g}s", replica_timeout_s)
                try:
                    for k, h in enumerate(handles):
                        res = h.result(timeout=0)
                        if res.status != S.OK:
                            raise RuntimeError(
                                f"canary {k}: {res.status} "
                                f"({res.reason})")
                        if res.weights_version != version:
                            raise RuntimeError(
                                f"canary {k} stamped "
                                f"{res.weights_version!r}, expected "
                                f"{version!r}")
                        toks = np.asarray(res.tokens)
                        ref = self._canary_ref.setdefault(
                            (version, k), toks)
                        if not np.array_equal(toks, ref):
                            raise RuntimeError(
                                f"canary {k} tokens diverged from the "
                                f"generation reference — two replicas "
                                f"of {version!r} must sample "
                                f"byte-identical streams")
                    faults.on_canary_gate(r.index, version)
                except Exception as e:  # noqa: BLE001 — typed abort
                    self._abort_upgrade(r, version, old_version,
                                        f"canary gate failed: {e}",
                                        replica_timeout_s)
                r.canary = False
                self._event("serve_upgrade_replica", replica=r.index,
                            to=version, migrated=migrated,
                            reclaimed=reclaimed,
                            canaries=len(handles),
                            wall_s=round(time.perf_counter() - t0, 3))
                record["replicas"].append({
                    "replica": r.index, "migrated": migrated,
                    "reclaimed": reclaimed,
                    "wall_s": round(time.perf_counter() - t0, 3)})
            with self._ctl_lock:
                # promote: the new generation is now the set's truth —
                # future bring-ups, scale-outs, and stats all speak it
                self.weights_version = version
                if params is not None:
                    self.params = params
                    if self.isolation == "process" \
                            and self.worker_ckpt is None:
                        import jax
                        self._np_params = jax.tree.map(np.asarray,
                                                       params)
                if ckpt is not None:
                    self.worker_ckpt = ckpt
                for r in self.replicas:
                    r.params_override = None
                    r.ckpt_override = None
                    if r.state == DRAINED:
                        # skipped above; its next bring-up serves the
                        # promoted set-level weights, so the label
                        # must say so
                        r.version = version
                self.upgrades += 1
            self._event("serve_upgrade_done", to=version,
                        from_version=old_version,
                        replicas=len(record["replicas"]))
            return record
        finally:
            # the flag was SET under _ctl_lock; clearing it unguarded
            # would let a concurrent reshape read a half-written False
            # interleaved with its own admission check (every with-
            # block inside the try has unwound by here, so this cannot
            # self-deadlock)
            with self._ctl_lock:
                self._upgrading = False

    # -- supervision --------------------------------------------------------

    def _check_replicas(self, now: float) -> bool:
        """One supervision sweep: crashed loops and missed heartbeats
        are fenced + reclaimed; circuit-broken replicas past their
        backoff get a bring-up attempt. Hang detection applies only to
        replicas with a live loop THREAD — in single-threaded drive the
        driver itself is the loop, so a hang would block the driver,
        and crashes surface synchronously in ``step_once``."""
        did = False
        # a serve-side jax.profiler capture (POST /admin/profile) is
        # PROCESS-global: while one is RUNNING on any thread-mode
        # replica, every replica in this process runs slower (TraceMe
        # overhead, stop-time serialization, core contention) — exempt
        # them all from the hang deadline exactly like ``compiling``
        # (operator-initiated, bounded at K chunks, and fencing mid-
        # capture would both lose the replica and leak the global
        # trace open). Engine.capturing is a started trace only: an
        # armed-but-unconsumed request must NOT suppress fencing (a
        # wedged replica that never reaches its next dispatch would
        # otherwise evade the deadline forever)
        capturing = self.isolation != "process" and any(
            r.engine is not None
            and getattr(r.engine, "capturing", None) is not None
            and r.engine.capturing()
            for r in self.replicas if r.state == RUNNING)
        for r in self.replicas:
            if r.state == RUNNING and self.isolation == "process":
                did = self._check_child(r, now) or did
            elif r.state == RUNNING:
                if r.dead:
                    self._failover(r, now,
                                   reason=f"crash: {r.last_error}")
                    did = True
                elif r.thread is not None and not r.thread.is_alive():
                    self._failover(r, now, reason="loop thread died")
                    did = True
                elif r.thread is not None and r.engine is not None \
                        and not r.engine.compiling \
                        and not capturing \
                        and now - r.engine.last_heartbeat \
                        > self.heartbeat_s:
                    # ``compiling`` exempts a known first-call trace/
                    # compile (seconds on a cold cache) from the hang
                    # deadline — a healthy replica mid-compile must not
                    # be fenced for being slow to warm up
                    self._failover(
                        r, now,
                        reason=f"missed heartbeat "
                               f"(> {self.heartbeat_s:g}s: hang)")
                    did = True
            elif r.state == BROKEN and now >= r.next_bringup_t:
                did = self._bring_up(r, now) or did
        return did

    def _check_child(self, r: _Replica, now: float) -> bool:
        """One supervision check of a RUNNING process replica — the two
        liveness signals layered: PID liveness with exit decoding (a
        SIGKILL/SIGSEGV/OOM death answers at the OS level even though
        the child can say nothing), then the missed-heartbeat deadline
        over the frame stream (a process that is alive but silent is
        wedged — it gets hard-killed and fenced like a hang). A child
        that dies BEFORE its READY frame is a bring-up failure, not a
        failover: it never held work, so it re-enters the circuit-
        breaker backoff with nothing to reclaim."""
        c = r.engine
        if c is None:
            return False
        if not c.ready:
            if c.crashed or c.poisoned or not c.alive_proc():
                c.hard_kill()
                self._bringup_fail_async(
                    r, now, f"child died in bring-up: "
                            f"{c.last_error or c.exit_desc()}")
                return True
            if now - c.started_t > self.spawn_timeout_s \
                    and not c.awaiting_operator:
                # an operator-attached worker has no spawn to time out:
                # the slot waits (unroutable, harmless) until a worker
                # dials in, and the deadline starts at attach
                c.hard_kill()
                self._bringup_fail_async(
                    r, now, f"child bring-up exceeded "
                            f"{self.spawn_timeout_s:g}s")
                return True
            return False
        if c.crashed:
            r.last_error = f"crash: {c.last_error}"
            self._failover(r, now, reason=r.last_error)
        elif c.poisoned:
            r.last_error = c.last_error
            self._failover(r, now, reason=r.last_error)
        elif not c.alive_proc():
            r.last_error = f"child exited: {c.exit_desc()}"
            self._failover(r, now, reason=r.last_error)
        else:
            # compiling exempts a child from the tight deadline but not
            # forever: compile_grace_s caps how long "still compiling"
            # is believable without a single frame. The failover reason
            # names the deadline that actually expired.
            if c.compiling:
                deadline, which = (max(self.heartbeat_s,
                                       self.compile_grace_s),
                                   "compile grace")
            else:
                deadline, which = self.heartbeat_s, "heartbeat"
            if now - c.last_heartbeat <= deadline:
                return False
            self._failover(
                r, now,
                reason=f"missed {which} deadline (> {deadline:g}s: "
                       f"hang)")
        return True

    def _bringup_fail_async(self, r: _Replica, now: float,
                            msg: str) -> None:
        """A spawned child that died or stalled before READY: count it
        against the circuit breaker exactly like a synchronous
        constructor failure."""
        c = r.engine
        r.engine, r.queue = None, None
        r.await_ready = False
        if c is not None:
            r.last_exit = c.exit_desc()
            c.fence()               # releases the dead child's pipe
            # routing is gated on ready, so the shadow is normally
            # empty — but never drop a handle on principle
            for h in c.reclaim():
                self.queue.requeue(h)
        r.attempt += 1
        self.bringup_failures += 1
        delay = self.bringup_policy.backoff(min(r.attempt - 1, 20))
        r.next_bringup_t = now + delay
        r.last_error = msg
        r.state = BROKEN
        self._event("serve_replica_bringup_fail", replica=r.index,
                    attempt=r.bringups - 1, consecutive=r.attempt,
                    backoff_s=round(delay, 3), error=msg,
                    exit=r.last_exit)

    def _pump_children(self, now: float) -> bool:
        """Drain every live child's pipe: absorb heartbeats/snapshots,
        fulfil harvested results, notice READY transitions. The one
        place process-mode results enter the parent — called from the
        control loop (threaded) and ``step_once`` (sync drive)."""
        did = False
        for r in self.replicas:
            c = r.engine
            if r.state != RUNNING or c is None:
                continue
            did = c.pump() or did
            if r.await_ready and c.ready:
                announced = c.worker_weights_version
                if announced and announced != r.version:
                    # a worker serving the WRONG generation must never
                    # join routing: during a rolling upgrade a stale
                    # dialer (or an operator pointing an old worker at
                    # a reshaped fleet) would silently decode on old
                    # weights — fence it as a bring-up failure instead
                    self._bringup_fail_async(
                        r, now,
                        f"worker announced weights {announced!r}, "
                        f"replica expects {r.version!r}")
                    did = True
                    continue
                r.await_ready = False
                r.attempt = 0
                r.last_error = ""
                r.conns += 1
                self._event("serve_replica_up", replica=r.index,
                            bringups=r.bringups, pid=c.pid,
                            transport=c.transport_kind, peer=c.peer,
                            weights_version=r.version)
                did = True
        return did

    # -- routing ------------------------------------------------------------

    def _expire(self, h: S.RequestHandle, now: float) -> None:
        req = h.request
        self.expired += 1
        self._hol_handoff.pop(req.request_id, None)
        self._version_holds.discard(req.request_id)
        self._event("serve_deadline", request_id=req.request_id,
                    where="queued", deadline_s=req.deadline_s,
                    waited_s=round(now - req.submit_t, 4))
        h.fulfill(S.Result(
            status=S.DEADLINE_EXCEEDED, request_id=req.request_id,
            reason=f"deadline_s={req.deadline_s:g} exceeded (queued)",
            weights_version=self.weights_version,
            queued_s=round(now - req.submit_t, 6),
            total_s=round(now - req.submit_t, 6)))

    def _capacity(self, r: _Replica) -> int:
        if self.isolation == "process":
            # parent-authoritative: the shadow (routed, unresolved) is
            # the truth; the child's own reports lag a frame. Allow one
            # queued wave beyond the slot pool so the child can prefill
            # its next group while decoding the current one.
            return max(0, 2 * r.engine.num_slots - len(r.engine.shadow))
        return max(0, r.engine.num_slots - r.engine.active_slots()
                   - r.queue.depth())

    def _pick(self, cands: List[_Replica], caps: dict,
              h: S.RequestHandle) -> _Replica:
        """Least-loaded with page-awareness: most free slot capacity
        first; among paged replicas, one whose pool can map the
        request's prompt span NOW beats one that would defer it, and
        free pages break remaining ties."""
        from dalle_pytorch_tpu.serve import kv_pool as KV

        pin = h.replay_version
        # a fenced/drained replica's HOL reservation, handed back at
        # reclaim: the EXACT (prefix-aware) page need, which beats the
        # blind full-span guess below — the retiring replica's claim
        # follows the request instead of dying with the engine
        handoff = self._hol_handoff.get(h.request.request_id)

        def score(r: _Replica):
            if pin is not None and r.version != pin:
                # the route-level candidate filter makes this
                # unreachable; decoding a pinned replay on another
                # generation's weights must be impossible, not unlikely
                raise ReplayVersionMismatch(S.structured_event(
                    "serve_replay_version_mismatch",
                    request_id=h.request.request_id, pinned=pin,
                    replica=r.index, version=r.version))
            eng = r.engine
            fits, free_pages = True, 0
            if eng.kv == "paged":
                if self.isolation == "process":
                    # last-frame view: pages_free lags one heartbeat
                    # (-1 = no frame yet -> stay optimistic); the
                    # child's own admission gate is the authority
                    free_pages = eng.pages_free
                    buckets, page_size = self._buckets, self._page_size
                    if free_pages < 0:
                        return (True, caps[r.index], 0, -r.index)
                else:
                    free_pages = eng.alloc.free
                    buckets, page_size = eng.buckets, eng.page_size
                try:
                    need = handoff if handoff is not None \
                        else KV.pages_for(
                            S.bucket_for(len(h.request.codes), buckets),
                            page_size)
                    fits = free_pages >= need
                except ValueError:
                    # an over-long prompt buckets nowhere; the engine's
                    # admission turns it into a typed error result
                    fits = True
            return (fits, caps[r.index], free_pages, -r.index)

        return max(cands, key=score)

    def _route(self, now: float) -> bool:
        """Move ready requests from the shared queue into per-replica
        private queues (a hand-off: ``requeue(count=False)`` keeps the
        handle's shared-queue identity and arrival position). Queued
        deadline expiries are reaped here on EVERY sweep — even with
        zero live replicas, a dead entry must get its typed result."""
        live = [r for r in self.replicas
                if r.state == RUNNING and r.engine is not None
                and not r.canary]
        # (canary replicas are serving, but only the upgrade's health
        # gate may talk to them — routing rejoins at gate pass)
        if self.isolation == "process":
            # routable = READY and believable: not poisoned/crashed and
            # the PID is live RIGHT NOW — never route into a corpse in
            # the window before the next supervision sweep fences it
            live = [r for r in live
                    if r.engine.ready and not r.engine.poisoned
                    and not r.engine.crashed and not r.engine.fenced
                    and r.engine.alive_proc()]
        caps = {r.index: self._capacity(r) for r in live}
        total = sum(caps.values())
        ready, expired = self.queue.pop_ready(total, now)
        for h in expired:
            self._expire(h, now)
        assigned: dict = {}
        for h in ready:
            pin = h.replay_version
            cands = [r for r in live if caps[r.index] > 0
                     and (pin is None or r.version == pin)]
            # role preference: every admission (fresh or replay) needs
            # a prefill, so decode-specialized replicas are offered
            # work only when no prefill-capable candidate has capacity
            # — a PREFERENCE: zero-loss progress outranks the role
            # split, so the fallback to any candidate is automatic
            preferred = [r for r in cands if r.role != "decode"]
            cands = preferred or cands
            if not cands:
                # version-pinned replay with no same-generation
                # capacity right now: hold or release, never mis-route
                self._route_hold(h, pin)
                continue
            r = self._pick(cands, caps, h)
            if pin is None:
                # pin at first routing: from here on, failover replay
                # of this request goes only to this weights generation
                h.replay_version = r.version
            if h.trace is not None:
                # the shared-queue wait closes here; the zero-duration
                # route marker carries WHERE the request went (the
                # engine-side spans then tile from this instant)
                if not h.trace.has_in_attempt("queue_wait"):
                    self.flight.record(h.trace.span("queue_wait", now))
                self.flight.record(h.trace.span(
                    "route", now, replica=r.index,
                    weights_version=r.version))
            self._hol_handoff.pop(h.request.request_id, None)
            self._version_holds.discard(h.request.request_id)
            caps[r.index] -= 1
            if self.isolation == "process":
                assigned.setdefault(r.index, (r, []))[1].append(h)
            else:
                r.queue.requeue(h, count=False)
        for r, batch in assigned.values():
            r.engine.route(batch)       # one admit frame per replica
        return bool(ready or expired)

    def _route_hold(self, h: S.RequestHandle,
                    pin: Optional[str]) -> None:
        """A popped request the router cannot place THIS sweep. A
        version-pinned replay whose generation still exists somewhere
        in the fleet (busy, circuit-broken, draining — it may come
        back) is HELD at its original arrival position; one whose
        generation has left the fleet entirely (the upgrade completed
        under it) has its pin RELEASED — zero-loss outranks a stale
        pin, the request re-decodes from scratch on the current
        weights, and its Result is stamped with the version that
        actually produced the tokens. Both paths are structured
        events, fired once per request."""
        rid = h.request.request_id
        if pin is not None and not any(
                rr.version == pin and rr.state != RETIRED
                for rr in self.replicas):
            h.replay_version = None
            self._version_holds.discard(rid)
            self._event("serve_replay_version_released",
                        request_id=rid, pinned=pin,
                        fleet_version=self.weights_version)
        elif rid not in self._version_holds:
            self._version_holds.add(rid)
            self._event("serve_replay_version_hold", request_id=rid,
                        pinned=pin)
        self.queue.requeue(h, count=False)

    # -- the replica loop (threaded mode) -----------------------------------

    def _spawn(self, r: _Replica) -> None:
        r.thread = threading.Thread(
            target=self._run_replica, args=(r, r.engine, r.stop),
            daemon=True, name=f"serve-replica-{r.index}")
        r.thread.start()

    def _run_replica(self, r: _Replica, engine, stop) -> None:
        """One replica's serving loop. A step exception is a CRASH —
        recorded for the supervisor, loop exits (contrast the single-
        engine ``Engine.run``, which fails the in-slot requests in
        place: here the supervisor replays them instead, so the callers
        get their exact tokens, not typed errors). A fence (failover
        decided while this thread was wedged) ends the loop on the next
        iteration."""
        from dalle_pytorch_tpu.resilience import faults
        while not stop.is_set() and not engine.fenced:
            try:
                faults.on_replica_chunk(
                    r.index, engine.decode_steps // engine.chunk_steps)
                busy = engine.step_once()
            except Exception as e:  # noqa: BLE001 — supervised crash
                if engine.fenced or r.engine is not engine:
                    # a ZOMBIE crashing: this engine was already fenced
                    # and replaced (e.g. a wedge that finally errored
                    # out) — its requests were reclaimed long ago, and
                    # flagging r.dead now would fail over the healthy
                    # replacement that owns r
                    return
                r.last_error = repr(e)
                r.dead = True
                self._event("serve_replica_crash", replica=r.index,
                            error=repr(e))
                return
            if not busy and engine.idle():
                stop.wait(self._idle_sleep_s)

    def _run_control(self, stop: threading.Event) -> None:
        """Routing + supervision loop (threaded mode). In process mode
        this is the ONLY parent-side loop: the children drive their own
        engines, and this thread pumps their pipes, routes, and
        supervises."""
        while not stop.is_set():
            now = self.clock()
            with self._ctl_lock:
                busy = False
                if self.isolation == "process":
                    busy = self._pump_children(now)
                busy = self._check_replicas(now) or busy
                busy = self._route(now) or busy
                # racelint: disable=RL003 — deliberate: role handoff is
                # a reshape (warm prefill→decode migration) and runs
                # under _ctl_lock like drain/scale-in/upgrade
                busy = self._role_handoff(now) or busy
            stop.wait(0.0005 if busy else self._idle_sleep_s)

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "ReplicaSet":
        """Threaded mode: one loop thread per live replica plus the
        control thread (routing + supervision)."""
        self._started = True
        if self._t_start is None:       # threaded mode never steps
            self._t_start = self.clock()  # sync, so stamp elapsed here
        if self.isolation != "process":  # children ARE the loops
            for r in self.replicas:
                if r.state == RUNNING and r.thread is None:
                    self._spawn(r)
        self._ctl_stop = threading.Event()
        self._ctl_thread = threading.Thread(
            target=self._run_control, args=(self._ctl_stop,),
            daemon=True, name="serve-replica-control")
        self._ctl_thread.start()
        return self

    def close(self, timeout: float = 30.0) -> None:
        """Stop supervision, then every replica loop, joining each with
        its share of the deadline. A replica that OUTLIVES its join
        (wedged in a step) is fenced so it can never fulfil or requeue
        later; either way its private queue is drained and every
        still-open handle — queued or in-slot — is fulfilled
        ``cancelled`` lock-free (first-write-wins makes the late-waker
        race harmless). Callers are never stranded."""
        t0 = time.perf_counter()
        self._ctl_stop.set()
        if self._ctl_thread is not None:
            self._ctl_thread.join(timeout)
        if self.isolation == "process":
            with self._ctl_lock:
                for r in self.replicas:
                    c = r.engine
                    if c is None:
                        continue
                    left = max(0.5, timeout - (time.perf_counter() - t0))
                    # graceful SHUTDOWN -> join -> SIGKILL straggler;
                    # close() salvages the pipe and fences, so a child
                    # outliving its join can never fulfil anything late
                    c.close(left / max(self.n_replicas, 1))
                    for h in c.reclaim():
                        h.fulfill(S.Result(
                            status=S.CANCELLED,
                            request_id=h.request.request_id,
                            reason="server shutdown"))
                if self.listener is not None:
                    self.listener.close()
            return
        with self._ctl_lock:
            for r in self.replicas:
                if r.stop is not None:
                    r.stop.set()
            for r in self.replicas:
                if r.thread is not None:
                    left = max(0.1, timeout - (time.perf_counter() - t0))
                    r.thread.join(left / max(len(self.replicas), 1))
            for r in self.replicas:
                eng, q = r.engine, r.queue
                if r.thread is not None and r.thread.is_alive() \
                        and eng is not None:
                    eng.fence()
                handles = []
                if q is not None:
                    handles.extend(q.drain())
                if eng is not None:
                    handles.extend(eng.inflight_handles())
                for h in handles:
                    if not h.done():
                        h.fulfill(S.Result(
                            status=S.CANCELLED,
                            request_id=h.request.request_id,
                            reason="server shutdown"))

    # -- single-threaded drive (tests, bench) -------------------------------

    def step_once(self) -> bool:
        """One set iteration: supervise (bring-ups, crash cleanup),
        route, then step every live replica once. Crashes fail over
        INLINE — the same fence/reclaim/replay path the threaded
        supervisor takes, just synchronously."""
        from dalle_pytorch_tpu.resilience import faults
        now = self.clock()
        if self._t_start is None:
            self._t_start = now
        with self._ctl_lock:
            did = False
            if self.isolation == "process":
                did = self._pump_children(now)
            did = self._check_replicas(now) or did
            did = self._route(now) or did
            # racelint: disable=RL003 — deliberate: same reshape-under-
            # _ctl_lock discipline as the driver loop above
            did = self._role_handoff(now) or did
        if self.isolation == "process":
            # the children step themselves; the parent's "step" is the
            # pump/supervise/route above. Nap briefly when nothing
            # moved so run_until_idle doesn't hot-spin while children
            # decode at their own pace.
            if not did:
                time.sleep(0.001)
            return did
        for r in list(self.replicas):
            if r.state != RUNNING or r.engine is None:
                continue
            eng = r.engine
            try:
                faults.on_replica_chunk(
                    r.index, eng.decode_steps // eng.chunk_steps)
                did = eng.step_once() or did
            except Exception as e:  # noqa: BLE001 — supervised crash
                r.last_error = repr(e)
                self._event("serve_replica_crash", replica=r.index,
                            error=repr(e))
                with self._ctl_lock:
                    self._failover(r, self.clock(),
                                   reason=f"crash: {e!r}")
                did = True
        return did

    def idle(self) -> bool:
        if self.queue.depth() > 0:
            return False
        if self.isolation == "process":
            # the shadow is the parent-side truth: anything routed and
            # unresolved is still in flight somewhere
            return all(not r.engine.shadow for r in self.replicas
                       if r.engine is not None)
        for r in self.replicas:
            if r.queue is not None and r.queue.depth() > 0:
                return False
            if r.engine is not None and (r.engine.active_slots() > 0
                                         or r.engine._pending):
                return False
        return True

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        for _ in range(max_steps):
            busy = self.step_once()
            if not busy and self.idle():
                return
        raise RuntimeError(
            f"replica set did not go idle in {max_steps} steps")

    # -- aggregate counters (bench._serve_load_point's surface) -------------

    def _agg(self, name: str) -> int:
        return self._retired[name] + sum(
            getattr(r.engine, name, 0) for r in self.replicas
            if r.engine is not None)

    @property
    def tokens_decoded(self) -> int:
        return self._agg("tokens_decoded")

    @property
    def decode_steps(self) -> int:
        return self._agg("decode_steps")

    @property
    def harvests(self) -> int:
        return self._agg("harvests")

    @property
    def occupancy_sum(self) -> int:
        return self._agg("occupancy_sum")

    @property
    def completed(self) -> int:
        return self._agg("completed")

    # -- observability ------------------------------------------------------

    def alive(self) -> bool:
        """True while at least one replica serves (healthz contract:
        503 only when ALL are dead)."""
        for r in self.replicas:
            if r.state != RUNNING or r.engine is None:
                continue
            if self.isolation == "process":
                if r.engine.alive_proc():
                    return True
            elif r.thread is None or r.thread.is_alive():
                return True
        return False

    def replica_states(self) -> List[dict]:
        """Per-replica /healthz body. Process mode adds the supervised-
        child facts an operator triages with: the child PID, its
        restart count, the decoded last exit (signal name / OOM exit
        137 / plain code), and the child's reported RSS."""
        now = self.clock()
        out = []
        for r in self.replicas:
            if self.isolation == "process":
                alive = r.state == RUNNING and r.engine is not None \
                    and r.engine.alive_proc()
            else:
                alive = r.state == RUNNING and r.engine is not None and \
                    (r.thread is None or r.thread.is_alive())
            rec = {"replica": r.index, "state": r.state, "alive": alive,
                   "bringups": r.bringups,
                   "weights_version": r.version, "role": r.role}
            if r.device is not None:
                # thread mode: the chip (or mesh slice) this replica's
                # engine is pinned to — distinct per replica on a
                # multi-chip host
                rec["device"] = str(r.device)
            if r.canary:
                rec["canary"] = True    # upgrading: gate-only, unrouted
            if r.engine is not None:
                rec["heartbeat_age_s"] = round(
                    max(now - r.engine.last_heartbeat, 0.0), 4)
            if self.isolation == "process":
                rec["restarts"] = max(r.bringups - 1, 0)
                rec["reconnects"] = max(r.conns - 1, 0)
                if r.engine is not None:
                    rec["pid"] = r.engine.pid
                    rec["rss_mb"] = r.engine.rss_mb
                    rec["ready"] = r.engine.ready
                    rec.update(r.engine.transport_info(now))
                if r.last_exit:
                    rec["last_exit"] = r.last_exit
            if r.last_error:
                rec["last_error"] = r.last_error
            out.append(rec)
        return out

    def decode_compiles_per_replica(self) -> List[int]:
        """Each LIVE replica's decode-program trace count — the
        one-compile-per-replica contract the tests assert (a
        replaced engine is a fresh program, counted on its own)."""
        return [r.engine.decode_traces for r in self.replicas
                if r.engine is not None]

    def _kv_bytes_per_shard(self) -> int:
        """Per-shard KV residency — where one device of a replica's
        slice actually holds the pool (/stats mesh satellite). Read off
        a live thread-mode engine; MODELED from config for child-process
        engines, whose pools live in other interpreters."""
        if self.isolation != "process":
            for r in self.replicas:
                if r.engine is not None:
                    return r.engine._mesh_stats()[
                        "kv_hbm_bytes_per_shard"]
        from dalle_pytorch_tpu.serve import kv_pool as KV
        kw = self._engine_kwargs
        try:
            dtype_bytes = self.params["text_emb"]["w"].dtype.itemsize
        except (TypeError, KeyError, AttributeError):
            dtype_bytes = 4     # worker_ckpt mode may carry no params
        total = KV.modeled_kv_bytes(
            self.cfg.transformer, kv=self.kv,
            num_slots=kw["num_slots"], total_len=self.cfg.seq_len,
            page_size=kw["page_size"], num_pages=kw["num_pages"],
            quantized=kw["quantize_cache"], dtype_bytes=dtype_bytes)
        from dalle_pytorch_tpu.parallel.serve_specs import kv_heads_shard
        m = self.devices_per_replica
        if m > 1 and kv_heads_shard(self.cfg.transformer.heads, m):
            return total // m   # heads-sharded pool divides exactly
        return total

    def stats(self) -> dict:
        # lazy (the serve package's jax-free-import discipline):
        # serve_specs pulls jax, and by stats() time a backend exists
        from dalle_pytorch_tpu.parallel.serve_specs import \
            SERVE_AXIS as _SERVE_AXIS
        elapsed = None if self._t_start is None \
            else max(self.clock() - self._t_start, 1e-9)
        live = [r for r in self.replicas if r.engine is not None]
        proc = self.isolation == "process"
        per = []
        for r in self.replicas:
            rec = {"replica": r.index, "state": r.state,
                   "weights_version": r.version, "role": r.role}
            if r.engine is not None:
                e = r.engine
                rec.update({
                    "active_slots": e.active_slots(),
                    # routed-but-not-decoding: the shadow holds EVERY
                    # outstanding request (in-slot ones included), so
                    # subtract the active count rather than adding the
                    # child's own queue depth on top — same meaning as
                    # thread mode's private-queue depth
                    "queued": (max(len(e.shadow) - e.active_slots(), 0)
                               if proc
                               else (r.queue.depth() if r.queue else 0)),
                    "decode_compiles": e.decode_traces,
                    "prefill_compiles": e.prefill_traces,
                    "completed": e.completed,
                    "tokens_decoded": e.tokens_decoded,
                })
                # the engine loop's seconds by phase: a thread replica's
                # engine has them; a child's proxy does not ship them
                rec.update({k: getattr(e, k) for k in _LOOP_SECONDS
                            if hasattr(e, k)})
                if proc:
                    rec.update({"pid": e.pid, "rss_mb": e.rss_mb,
                                "restarts": max(r.bringups - 1, 0),
                                "reconnects": max(r.conns - 1, 0)})
                    rec.update(e.transport_info())
                    if r.last_exit:
                        rec["last_exit"] = r.last_exit
                    if e.kv == "paged" and e.pages_free >= 0:
                        rec["pages_free"] = e.pages_free
                elif e.kv == "paged":
                    rec["pages_free"] = e.alloc.free
            per.append(rec)
        tokens = self.tokens_decoded
        steps = self.decode_steps
        out = {
            "replicas": self.n_replicas,
            "isolation": self.isolation,
            # mesh observability (/stats satellite): how many devices
            # each replica's engine spans, and the mesh shape when > 1
            "devices_per_replica": self.devices_per_replica,
            "mesh_shape": (
                {_SERVE_AXIS: self.devices_per_replica}
                if self.devices_per_replica > 1 else None),
            "kv_hbm_bytes_per_shard": self._kv_bytes_per_shard(),
            "alive_replicas": sum(
                1 for r in self.replicas
                if r.state == RUNNING and r.engine is not None),
            "kv": self.kv,
            "queue_depth": self.queue.depth() + sum(
                r.queue.depth() for r in live if r.queue is not None),
            "num_slots": sum(r.engine.num_slots for r in live),
            "active_slots": sum(r.engine.active_slots() for r in live),
            "chunk_steps": self._engine_kwargs["chunk_steps"],
            "decode_steps": steps,
            "tokens_decoded": tokens,
            "tokens_per_s": (round(tokens / elapsed, 2)
                             if elapsed else 0.0),
            "mean_occupancy": round(self.occupancy_sum / max(steps, 1),
                                    3),
            "completed": self.completed,
            "expired": self._agg("expired") + self.expired,
            "rejected": self.queue.rejected,
            "requeued": self.queue.requeued,
            "decode_compiles": self._agg("decode_traces"),
            "prefill_compiles": self._agg("prefill_traces"),
            "harvests": self.harvests,
            "host_round_trips_per_token": round(
                self.harvests / max(tokens, 1), 6),
            "chunks_behind_admit": self._agg("chunks_behind_admit"),
            "loop_stalls": self._agg("loop_stalls"),
            "failovers": self.failovers,
            "reclaimed": self.reclaimed,
            "bringup_failures": self.bringup_failures,
            "evicted": self._agg("evicted"),
            # the cell-stats surface: fleet-wide prefix reuse for this
            # set, aggregated across replicas (retired ones included) —
            # what the gateway's affinity bench reads per CELL
            "prefix_hits": self._agg("prefix_hits"),
            "prefix_entries": sum(
                len(r.engine.prefix) for r in live
                if getattr(r.engine, "prefix", None) is not None),
            # the elastic surface: current generation, reshape
            # counters, and whether a rolling upgrade owns the fleet
            "weights_version": self.weights_version,
            "max_replicas": self.max_replicas,
            "scale_outs": self.scale_outs,
            "scale_ins": self.scale_ins,
            "upgrades": self.upgrades,
            "upgrading": self._upgrading,
            # live KV migration (drain/scale-in/upgrade/role handoff)
            "migrations": self.migrations,
            "migrate_fallbacks": self.migrate_fallbacks,
            "migrated_tokens_saved": self.migrated_tokens_saved,
            "hol_handoffs": self.hol_handoffs,
            "flight_events": len(self.flight),
            "per_replica": per,
        }
        if proc:
            out["transport"] = self.transport
            if self.listener is not None:
                # where a remote worker dials in, how many dialers the
                # HELLO gate turned away, and which replica indices are
                # currently open for attach (runtime-born slots
                # included — the registry is never startup-static)
                out["worker_endpoint"] = self.listener.endpoint
                out["attach_rejected"] = self.listener.rejected
                out["attach_expected"] = self.listener.expected_indices()
        return out
