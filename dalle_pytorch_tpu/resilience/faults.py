"""Deterministic fault injection — every failure mode the resilience
runtime defends against, reproducible on CPU in tier-1 tests.

A ``FaultPlan`` names the faults to fire; production code calls the narrow
hook functions below, which are no-ops unless a plan is active (activated
programmatically by tests, or via the ``DALLE_FAULTS`` env var — a JSON
FaultPlan — for subprocess/CLI runs). Hooks fire AT MOST ONCE per
activation: a preemption signal or a NaN batch is a point event, and
firing it every matching step would make recovery untestable.

Simulated faults (pytest -m faults exercises each):
  * hung / failing backend init        -> on_backend_init
  * mid-run SIGTERM (preemption)       -> maybe_signal
  * NaN gradients (poisoned batch)     -> corrupt_batch
  * crashing data iterator             -> crashing_iterator (test helper)
  * truncated / corrupt checkpoints    -> truncate_params / remove_manifest
                                          / simulate_interrupted_save
  * serving replica crash / hang       -> on_replica_chunk
  * flaky replica bring-up             -> on_replica_bringup
  * HARD replica kills (process mode)  -> on_worker_chunk
      real SIGKILL / SIGSEGV via os.kill on the child worker itself,
      memory exhaustion against the worker's RSS watchdog (exit 137,
      the container OOM-kill convention), and a corrupt IPC frame the
      parent must fence on — these need ``--isolation process`` (a
      thread cannot survive its own injected SIGKILL). Hard-fault
      plans cross the process boundary through ``child_plan_for``
      exactly once per activation per replica, so a restarted child
      never re-fires its own kill (fire-once is kept parent-side: the
      child's ``_fired`` set dies with it).
  * ELASTIC reshape faults              -> on_scale_add_bringup /
      on_upgrade_drain / on_canary_gate
      a replica killed mid-``add_replica`` bring-up (the scale-out slot
      circuit-breaks; survivors untouched), a real SIGKILL of the
      replica ``rolling_upgrade`` is draining (the planned drain races
      an unplanned death; reclaim-from-shadow still loses nothing), and
      a canary that fails the upgrade health gate (typed UpgradeAborted
      + rollback, fleet left on the old version).
  * LIVE-MIGRATION faults               -> on_migrate_transfer /
      on_migrate_import
      the source replica SIGKILLed at the instant its slot snapshot is
      requested (the export times out against the corpse and every
      request it held replays from the parent's shadow — zero loss),
      and the target rejecting the import with page exhaustion (the
      supervisor falls back to the next target or replay; the request
      completes byte-identically either way).
  * GATEWAY faults (serve/gateway.py)   -> on_gateway_dispatch /
      gateway_flood
      a whole cell (one ReplicaSet behind the gateway) dying the
      instant a request was routed to it — the gateway must fence the
      cell and re-route + replay every in-flight request it held on
      another cell, zero loss — and a synthetic abusive tenant
      (tenant_flood) whose burst the isolation bench drives while a
      victim tenant's p95 must stay within tolerance.
  * NETWORK faults (socket transport)   -> on_worker_chunk
      connection reset mid-frame (RST after half a frame), torn frame
      (half a frame then FIN), stalled socket (open but silent),
      duplicate and reordered frame delivery — the failure modes a
      pipe can never exhibit, each of which must fence the replica via
      typed errors and replay on a survivor (``--transport socket``
      for the stream-tearing ones; dup/reorder work on any transport).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import signal
import threading
import time
from typing import Iterator, Optional


class FaultInjected(RuntimeError):
    """Raised by hooks that simulate a hard failure."""


@dataclasses.dataclass
class FaultPlan:
    # backend bring-up: sleep (wedge) this long per init attempt, and/or
    # raise on the first N attempts (0-indexed attempts < fail_attempts)
    backend_init_hang_s: float = 0.0
    backend_init_fail_attempts: int = 0
    # training loop: deliver SIGTERM to this process just before this step
    sigterm_at_step: int = -1
    # training loop: replace the batch's float leaves with NaN at this step
    nan_at_step: int = -1
    # training loop: report the STEP LOSS as NaN at this step (corrupt_loss
    # in the supervisor's check path) — covers training paths whose batch
    # has no float leaves to poison (train_dalle/train_clip's integer
    # token ids), where nan_at_step raises instead of firing
    nan_loss_at_step: int = -1
    # serving replica set (serve/replica.py): which replica index the
    # serve-side faults below target, and the deterministic failure
    # points — crash (raise out of the serving loop) or hang (stall the
    # loop for replica_hang_s so the heartbeat deadline trips) once the
    # replica has dispatched this many fused decode chunks, and/or fail
    # its first replica_flaky_bringup bring-up attempts (the circuit-
    # breaker path). Mirrors the train-side style: -1/0 = off, hooks
    # no-ops without an active plan, crash/hang fire AT MOST ONCE.
    fault_replica: int = 0
    replica_crash_at_chunk: int = -1
    replica_hang_at_chunk: int = -1
    replica_hang_s: float = 30.0
    replica_flaky_bringup: int = 0
    # HARD serve faults (process-isolated replicas, serve/worker.py):
    # the child worker kills ITSELF with a real signal once it has
    # dispatched this many fused chunks — SIGKILL (what a host OOM
    # killer or an operator `kill -9` delivers) or SIGSEGV (what an XLA
    # bug delivers); replica_oom_at_chunk allocates real memory until
    # the worker's RSS watchdog trips (the child dies with exit 137,
    # the container OOM-kill convention — requires the replica set's
    # child_rss_limit_mb); replica_garbage_frame_at_chunk makes the
    # child emit one corrupt IPC frame (the parent must fence on the
    # protocol error, never deadlock). All -1 = off, fire at most once,
    # and target fault_replica only.
    replica_sigkill_at_chunk: int = -1
    replica_segv_at_chunk: int = -1
    replica_oom_at_chunk: int = -1
    replica_garbage_frame_at_chunk: int = -1
    # NETWORK faults (socket transport, serve/transport.py) — the
    # failure modes a duplex pipe can never exhibit, each of which the
    # parent must answer with a typed fence + replay, never a deadlock
    # or a double-delivery:
    #   * conn reset mid-frame: the worker writes HALF a valid frame,
    #     then aborts the connection with an RST (SO_LINGER 0) — what a
    #     dying NAT entry, a crashed host, or a yanked cable delivers;
    #   * torn frame: half a frame then a clean FIN — a peer that died
    #     between two writes of one frame;
    #   * stalled socket: the connection stays accepted and open but
    #     the worker goes silent for replica_hang_s — the parent must
    #     fence off the heartbeat deadline without any thread blocking
    #     on the unread socket;
    #   * duplicate / reordered frames: the worker re-sends a frame
    #     (same sequence number) or swaps two frames' wire order — the
    #     per-connection sequence check must fence, because replay
    #     correctness cannot survive double-absorbed or skipped frames.
    # The first two need --transport socket (a pipe has no RST/stream
    # tearing to inject); dup/reorder are transport-agnostic. All -1 =
    # off, fire at most once, target fault_replica only.
    replica_conn_reset_at_chunk: int = -1
    replica_torn_frame_at_chunk: int = -1
    replica_stall_socket_at_chunk: int = -1
    replica_dup_frame_at_chunk: int = -1
    replica_reorder_frames_at_chunk: int = -1
    # ELASTIC-fleet faults (runtime scale-out/in + rolling weight
    # hot-swap, serve/replica.py) — the reshape paths have their own
    # failure points, each of which must degrade typed and zero-loss:
    #   * scale_add_bringup_crash: kill the first N bring-up attempts
    #     of a replica born from ``add_replica`` (the scale-out path's
    #     own flaky-bring-up row — the new slot must circuit-break and
    #     retry WITHOUT disturbing the serving survivors, and the
    #     in-flight burst must lose nothing);
    #   * upgrade_drain_sigkill_replica: real SIGKILL of THIS replica's
    #     child just as ``rolling_upgrade`` starts draining it — the
    #     planned drain races an unplanned death, and the upgrade must
    #     absorb it (reclaim from the shadow, zero loss) and keep
    #     cycling (process isolation only: a thread cannot survive its
    #     own SIGKILL, the hook raises FaultInjected on a thread set);
    #   * upgrade_canary_fail_replica: fail the canary health gate on
    #     THIS replica's freshly upgraded engine — rolling_upgrade must
    #     abort typed (UpgradeAborted), roll the replica back to the
    #     old weights, and leave the WHOLE fleet serving the old
    #     version. All fire at most once; -1/0 = off.
    scale_add_bringup_crash: int = 0
    upgrade_drain_sigkill_replica: int = -1
    upgrade_canary_fail_replica: int = -1
    # LIVE-MIGRATION faults (serve/replica.py's _migrate_from): the two
    # rungs of the migrate->replay fallback ladder, each of which must
    # degrade to deterministic replay with zero requests lost:
    #   * migrate_crash_source_at_transfer: real SIGKILL of the SOURCE
    #     replica's child at the instant the supervisor requests its
    #     slot snapshot — the export times out against a corpse, the
    #     target never sees a frame (nothing partial to discard), and
    #     everything the source held replays from the parent's shadow
    #     (process isolation only: a thread cannot survive its own
    #     SIGKILL, the hook raises FaultInjected on a thread set, which
    #     the supervisor converts to the same fallback);
    #   * migrate_reject_target: the TARGET replica reports page
    #     exhaustion at import time — the supervisor must fall back to
    #     replay (or the next target) and the request must complete
    #     byte-identically anyway.
    # Both name the replica INDEX to target; -1 = off, fire at most
    # once per activation.
    migrate_crash_source_at_transfer: int = -1
    migrate_reject_target: int = -1
    # GATEWAY faults (serve/gateway.py, the multi-cell front door):
    #   * gateway_cell_down_at_request: once the gateway has ROUTED
    #     this many requests (cumulative across cells), the cell that
    #     received the latest one dies whole — every engine behind it —
    #     mid-stream; the gateway must fence the cell and re-route +
    #     replay everything it held on a surviving cell, zero loss;
    #   * tenant_flood / tenant_flood_requests: name a synthetic
    #     abusive tenant and its burst size — the isolation bench reads
    #     the spec via ``gateway_flood()`` (fire-once) and slams the
    #     gateway under that tenant's key while asserting the victim
    #     tenant's p95 and the typed 429 contract.
    # -1/"" = off; both fire at most once per activation.
    gateway_cell_down_at_request: int = -1
    tenant_flood: str = ""
    tenant_flood_requests: int = 0


_active: Optional[FaultPlan] = None
_fired: set = set()
# set when the plan is cleared: an injected replica hang waits on it, so
# the thread it holds is released with the plan and does not sleep on into
# whatever the process does next
_cleared = threading.Event()

ENV = "DALLE_FAULTS"


def activate(plan: FaultPlan) -> FaultPlan:
    global _active, _cleared
    _active = plan
    _fired.clear()
    _cleared = threading.Event()
    return plan


def deactivate() -> None:
    global _active
    _active = None
    _fired.clear()
    _cleared.set()


def active() -> Optional[FaultPlan]:
    return _active


def maybe_activate_from_env() -> Optional[FaultPlan]:
    """Activate a plan from the ``DALLE_FAULTS`` JSON env var (subprocess /
    CLI harness path). No-op when unset or a plan is already active."""
    if _active is not None:
        return _active
    raw = os.environ.get(ENV, "")
    if not raw:
        return None
    return activate(FaultPlan(**json.loads(raw)))


@contextlib.contextmanager
def injected(**kwargs):
    """``with faults.injected(nan_at_step=3): ...`` — scoped activation."""
    activate(FaultPlan(**kwargs))
    try:
        yield _active
    finally:
        deactivate()


def _once(key: str) -> bool:
    if key in _fired:
        return False
    _fired.add(key)
    return True


# ---------------------------------------------------------------------------
# hooks called from production code (all no-ops without an active plan)
# ---------------------------------------------------------------------------

def on_backend_init(attempt: int = 0) -> None:
    """Inside the deadline-bounded bring-up fn: wedge and/or fail."""
    p = _active
    if p is None:
        return
    if p.backend_init_hang_s > 0:
        time.sleep(p.backend_init_hang_s)
    if attempt < p.backend_init_fail_attempts:
        raise FaultInjected(
            f"injected backend init failure (attempt {attempt})")


def maybe_signal(step: int) -> None:
    """Deliver SIGTERM to this process before step ``sigterm_at_step`` —
    the supervisor's handler turns it into a preemption checkpoint."""
    p = _active
    if p is not None and step == p.sigterm_at_step and _once("sigterm"):
        os.kill(os.getpid(), signal.SIGTERM)


def corrupt_batch(batch, step: int):
    """NaN-poison every float leaf of ``batch`` at step ``nan_at_step`` —
    the downstream loss/grads go NaN exactly once, deterministically.

    A batch with NO float leaves (e.g. train_dalle's integer token ids)
    cannot be poisoned this way — raise instead of silently consuming the
    one-shot fire, so a fault test against such a CLI fails loudly rather
    than passing vacuously (that path needs a loss-level hook)."""
    p = _active
    if p is None or step != p.nan_at_step or not _once("nan"):
        return batch
    import jax
    import jax.numpy as jnp

    poisoned = []

    def poison(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            poisoned.append(True)
            return jnp.full_like(x, jnp.nan)
        return x

    out = jax.tree.map(poison, batch)
    if not poisoned:
        raise FaultInjected(
            f"nan_at_step={step} fired but the batch has no float leaves "
            "to poison (integer token ids?) — this fault cannot simulate "
            "a NaN loss on this training path; use nan_loss_at_step")
    return out


def corrupt_loss(loss: float, step: int) -> float:
    """Report NaN as the step loss at ``nan_loss_at_step`` — the loss-level
    injection point (TrainSupervisor.check_step calls it on every step's
    host-side loss). Unlike ``corrupt_batch`` this never touches device
    buffers, so it works for EVERY training path — including
    train_dalle/train_clip, whose integer-only batches have nothing to
    poison — and exercises exactly the same rollback machinery: the
    supervisor sees a non-finite loss and restores the newest anchor."""
    p = _active
    if p is None or step != p.nan_loss_at_step or not _once("nan_loss"):
        return loss
    return float("nan")


def on_replica_chunk(replica: int, chunk: int) -> None:
    """Inside a replica's serving loop, before each engine step, with the
    count of fused decode chunks the replica has dispatched so far.
    ``replica_crash_at_chunk=N`` raises (the loop dies and the supervisor
    must fence + reclaim + replay); ``replica_hang_at_chunk=N`` waits
    ``replica_hang_s``, or until the plan is cleared if that comes first,
    OUTSIDE the engine lock (the heartbeat stalls
    exactly as it would on a wedged device sync, and the supervisor must
    fence the replica without the wedged thread's cooperation). Both
    target ``fault_replica`` only and fire at most once."""
    p = _active
    if p is None or replica != p.fault_replica:
        return
    if p.replica_crash_at_chunk >= 0 \
            and chunk >= p.replica_crash_at_chunk \
            and _once("replica_crash"):
        raise FaultInjected(
            f"injected replica {replica} crash at chunk {chunk}")
    if p.replica_hang_at_chunk >= 0 \
            and chunk >= p.replica_hang_at_chunk \
            and _once("replica_hang"):
        _cleared.wait(p.replica_hang_s)


def child_plan_for(replica: int) -> Optional[dict]:
    """The active plan's dict form for ``replica``'s CHILD process spawn
    (serve/replica.py passes it into the worker spec; the child
    activates it instead of reading ``DALLE_FAULTS`` itself). Returns a
    plan AT MOST ONCE per activation per replica: the hard faults kill
    the child for real, and a restarted child re-activating the same
    plan would re-fire its own kill forever — fire-once must live in
    the parent, the only process that survives the fault."""
    p = _active
    if p is None or replica != p.fault_replica:
        return None
    if not _once(f"child_plan_{replica}"):
        return None
    return dataclasses.asdict(p)


# module-level on purpose: the injected-OOM allocations must stay
# referenced until the worker's RSS watchdog (or the kernel) kills the
# process — a local would be freed on return and the RSS would fall
# back under the limit before the check runs
_oom_ballast: list = []


def on_worker_chunk(replica: int, chunk: int, *,
                    emit_frame=None,
                    rss_limit_mb: int = 0,
                    rss_mb=None,
                    transport=None,
                    sender=None) -> None:
    """Inside a child-process worker's loop (serve/worker.py), before
    each engine step — the HARD half of the serve fault catalog, which
    only a process can survive being injected with:

      * ``replica_sigkill_at_chunk`` / ``replica_segv_at_chunk``: a
        real ``os.kill`` on the worker itself — no Python cleanup, no
        goodbye frame; the parent must detect the death from PID
        liveness + exit-signal decoding and replay from its own shadow
        bookkeeping;
      * ``replica_oom_at_chunk``: allocate-and-touch real memory in
        64 MiB steps until the worker's RSS (``rss_mb()``) crosses
        ``rss_limit_mb`` — the worker's own watchdog then dies with
        exit 137, exactly the kill a container memory limit delivers;
      * ``replica_garbage_frame_at_chunk``: ship one corrupt frame
        through ``emit_frame`` — the parent must fence this replica on
        the protocol error rather than deadlock on it.

    Like the soft hooks: no-op without an active plan, targets
    ``fault_replica`` only, each fault fires at most once."""
    p = _active
    if p is None or replica != p.fault_replica:
        return
    if p.replica_sigkill_at_chunk >= 0 \
            and chunk >= p.replica_sigkill_at_chunk \
            and _once("worker_sigkill"):
        os.kill(os.getpid(), signal.SIGKILL)
    if p.replica_segv_at_chunk >= 0 \
            and chunk >= p.replica_segv_at_chunk \
            and _once("worker_segv"):
        os.kill(os.getpid(), signal.SIGSEGV)
    if p.replica_oom_at_chunk >= 0 \
            and chunk >= p.replica_oom_at_chunk \
            and _once("worker_oom"):
        if not rss_limit_mb or rss_mb is None:
            raise FaultInjected(
                "replica_oom_at_chunk fired but the worker has no RSS "
                "limit to exhaust — run the replica set with "
                "child_rss_limit_mb set, or this fault proves nothing")
        import numpy as np
        for _ in range(256):            # hard cap: never OOM the host
            if rss_mb() > rss_limit_mb:
                return                  # watchdog kills on next check
            _oom_ballast.append(np.ones((64, 1024, 1024), np.uint8))
        raise FaultInjected(
            f"allocated {len(_oom_ballast) * 64} MiB without crossing "
            f"rss_limit_mb={rss_limit_mb} — limit too high to exercise")
    if p.replica_garbage_frame_at_chunk >= 0 \
            and chunk >= p.replica_garbage_frame_at_chunk \
            and emit_frame is not None and _once("worker_garbage"):
        # emit_frame checked BEFORE consuming the fire-once token: a
        # call without an emitter must not silently burn the fault
        emit_frame(b"\xde\xad\xbe\xef not a frame")

    # -- the network catalog (see the FaultPlan field comments) ------------
    def _heartbeat_frame(seq: int) -> bytes:
        from dalle_pytorch_tpu.serve import ipc as _ipc
        return _ipc.encode_frame(_ipc.HEARTBEAT, {"snap": None}, seq)

    def _need_socket(fault: str):
        if transport is None or getattr(transport, "kind", "") \
                != "socket":
            raise FaultInjected(
                f"{fault} fired but the worker is not on a socket "
                f"transport — a pipe has no stream tearing to inject; "
                f"run with --transport socket, or this fault proves "
                f"nothing")

    if p.replica_conn_reset_at_chunk >= 0 \
            and chunk >= p.replica_conn_reset_at_chunk \
            and sender is not None and _once("worker_conn_reset"):
        _need_socket("replica_conn_reset_at_chunk")
        # half a valid frame on the wire, then an RST: the parent must
        # surface a typed mid-frame error and fence, and this worker's
        # next transport touch dies (exit 3) like any orphan
        frame = _heartbeat_frame(sender.seq)
        transport.send_partial_frame(frame, len(frame) // 2)
        transport.reset_hard()
    if p.replica_torn_frame_at_chunk >= 0 \
            and chunk >= p.replica_torn_frame_at_chunk \
            and sender is not None and _once("worker_torn_frame"):
        _need_socket("replica_torn_frame_at_chunk")
        # half a frame then a clean FIN — died between two writes; the
        # split lands INSIDE the ipc header, the hardest spot to
        # mis-parse quietly
        frame = _heartbeat_frame(sender.seq)
        transport.send_partial_frame(frame, 3)
        transport.close()
    if p.replica_stall_socket_at_chunk >= 0 \
            and chunk >= p.replica_stall_socket_at_chunk \
            and _once("worker_stall"):
        # accepted, open, silent: no frames for replica_hang_s — only
        # the heartbeat deadline can notice, and no parent thread may
        # block on the unread socket while it does
        time.sleep(p.replica_hang_s)
    if p.replica_dup_frame_at_chunk >= 0 \
            and chunk >= p.replica_dup_frame_at_chunk \
            and emit_frame is not None and sender is not None \
            and _once("worker_dup"):
        # the same frame delivered twice (same sequence number): the
        # second copy must fence, never double-absorb
        frame = _heartbeat_frame(sender.seq)
        sender.seq += 1
        emit_frame(frame)
        emit_frame(frame)
    if p.replica_reorder_frames_at_chunk >= 0 \
            and chunk >= p.replica_reorder_frames_at_chunk \
            and emit_frame is not None and sender is not None \
            and _once("worker_reorder"):
        # two frames swapped on the wire: the gap at the first one
        # must fence — absorbing them out of order could interleave
        # results and the counters that explain them
        a = sender.seq
        sender.seq += 2
        emit_frame(_heartbeat_frame(a + 1))
        emit_frame(_heartbeat_frame(a))


def on_scale_add_bringup(replica: int, attempt: int) -> None:
    """Inside the supervisor's bring-up path, ONLY for a replica born
    from ``add_replica`` (runtime scale-out): fail its first
    ``scale_add_bringup_crash`` bring-up attempts — the replica 'killed
    mid-add_replica bring-up' row. The new slot must circuit-break with
    backoff and eventually join routing; the serving survivors and
    every in-flight request must be untouched throughout."""
    p = _active
    if p is None:
        return
    if attempt < p.scale_add_bringup_crash:
        raise FaultInjected(
            f"injected scale-out bring-up kill (replica {replica}, "
            f"attempt {attempt})")


def on_upgrade_drain(replica: int, pid: Optional[int]) -> None:
    """Called by ``rolling_upgrade`` just BEFORE it drains ``replica``:
    with ``upgrade_drain_sigkill_replica`` targeting it, deliver a REAL
    SIGKILL to the replica's child process — the planned drain races an
    unplanned death, and the upgrade must reclaim from the parent-side
    shadow (the corpse answers nothing), lose zero requests, and keep
    cycling. Needs process isolation: on a thread replica there is no
    process to kill, and silently skipping would make the test pass
    vacuously — raise instead."""
    p = _active
    if p is None or replica != p.upgrade_drain_sigkill_replica \
            or not _once("upgrade_drain_sigkill"):
        return
    if pid is None:
        raise FaultInjected(
            "upgrade_drain_sigkill_replica fired but the replica has no "
            "child process to kill — run with isolation='process', or "
            "this fault proves nothing")
    os.kill(pid, signal.SIGKILL)
    # let the death become OBSERVABLE before the drain proceeds: the
    # point of this row is that the upgrade finds a corpse where it
    # expected a live replica (died-on-its-own, decoded exit SIGKILL),
    # not that our kill races the supervisor's own fence kill
    time.sleep(0.3)


def on_migrate_transfer(replica: int, pid: Optional[int]) -> None:
    """Called by the supervisor's ``_migrate_from`` just BEFORE it asks
    ``replica`` (the migration SOURCE) for a slot snapshot: with
    ``migrate_crash_source_at_transfer`` targeting it, deliver a REAL
    SIGKILL to the source's child process — the export call then runs
    against a corpse, times out typed (``MigrationError
    'source_dead'``), and every request the source held must fall back
    to shadow-reclaim replay with zero loss. Needs process isolation;
    on a thread replica the hook raises ``FaultInjected`` instead of
    passing vacuously, which the supervisor converts into the same
    replay fallback."""
    p = _active
    if p is None or replica != p.migrate_crash_source_at_transfer \
            or not _once("migrate_crash_source"):
        return
    if pid is None:
        raise FaultInjected(
            "migrate_crash_source_at_transfer fired but the replica "
            "has no child process to kill — run with "
            "isolation='process', or this fault proves nothing")
    os.kill(pid, signal.SIGKILL)
    # as with on_upgrade_drain: let the death become observable, so
    # the export finds a corpse rather than racing the kill
    time.sleep(0.3)


def on_migrate_import(replica: int) -> None:
    """Inside ``_migrate_from``'s import step, just before the snapshot
    is offered to ``replica`` (the migration TARGET): with
    ``migrate_reject_target`` naming it, simulate the target reporting
    page-pool exhaustion — the supervisor must record the typed
    fallback and the request must complete byte-identically via the
    next target or deterministic replay."""
    p = _active
    if p is None or replica != p.migrate_reject_target \
            or not _once("migrate_reject_target"):
        return
    raise FaultInjected(
        f"injected migration target rejection (replica {replica}: "
        f"page pool exhausted)")


def on_canary_gate(replica: int, version: str) -> None:
    """Inside ``rolling_upgrade``'s health gate, after ``replica``'s
    fresh engine answered its canary requests: fail the gate for
    ``upgrade_canary_fail_replica`` — the upgrade must abort with the
    typed ``UpgradeAborted``, restore this replica to the OLD weights,
    and leave the whole fleet serving the old version."""
    p = _active
    if p is None or replica != p.upgrade_canary_fail_replica \
            or not _once("upgrade_canary_fail"):
        return
    raise FaultInjected(
        f"injected canary health-gate failure (replica {replica}, "
        f"version {version!r})")


def on_gateway_dispatch(dispatched: int) -> bool:
    """Called by the gateway AFTER each routing decision, with the
    cumulative count of requests routed so far. Returns True exactly
    once, when ``gateway_cell_down_at_request`` is reached — the
    gateway then kills the whole cell the latest request landed on
    (mid-stream for everything it holds) and must recover via fence +
    re-route + replay on a survivor, zero loss."""
    p = _active
    if p is None or p.gateway_cell_down_at_request < 0:
        return False
    return dispatched >= p.gateway_cell_down_at_request \
        and _once("gateway_cell_down")


def gateway_flood() -> Optional[dict]:
    """Fire-once spec for the synthetic abusive tenant: ``{"tenant":
    name, "requests": burst}`` when ``tenant_flood`` is set, else None.
    The isolation bench/test drives the flood itself (the gateway has
    no business submitting requests); the plan is the reproducible
    record of WHO flooded and HOW hard."""
    p = _active
    if p is None or not p.tenant_flood or not _once("tenant_flood"):
        return None
    return {"tenant": str(p.tenant_flood),
            "requests": int(p.tenant_flood_requests)}


def on_replica_bringup(replica: int, attempt: int) -> None:
    """Inside the replica supervisor's bring-up path: fail attempts
    ``< replica_flaky_bringup`` of ``fault_replica``'s lifetime bring-up
    count — the circuit-breaker exercise (repeated failure backs the
    replica off with exponential delays; the set degrades gracefully
    until the attempt that succeeds re-joins it to routing)."""
    p = _active
    if p is None or replica != p.fault_replica:
        return
    if attempt < p.replica_flaky_bringup:
        raise FaultInjected(
            f"injected replica {replica} bring-up failure "
            f"(attempt {attempt})")


# ---------------------------------------------------------------------------
# test-side helpers (no production hook needed)
# ---------------------------------------------------------------------------

def crashing_iterator(items, crash_at: int,
                      exc: Optional[BaseException] = None) -> Iterator:
    """Yield ``items`` until index ``crash_at``, then raise — the data-path
    fault ``data.prefetch`` must propagate (or, with ``max_bad_records``
    wrapping at the record level, skip)."""
    for i, item in enumerate(items):
        if i == crash_at:
            raise exc if exc is not None else FaultInjected(
                f"injected iterator crash at record {i}")
        yield item


def truncate_params(ckpt_dir: str, keep_bytes: int = 16) -> str:
    """Truncate a checkpoint's params.msgpack — the partial-write corruption
    ``checkpoint.validate`` must catch."""
    from dalle_pytorch_tpu import checkpoint as ckpt
    path = os.path.join(ckpt_dir, ckpt.PARAMS)
    with open(path, "rb") as f:
        data = f.read()
    with open(path, "wb") as f:
        f.write(data[:keep_bytes])
    return path


def remove_manifest(ckpt_dir: str) -> str:
    """Delete a checkpoint's manifest — e.g. a botched manual copy."""
    from dalle_pytorch_tpu import checkpoint as ckpt
    path = os.path.join(ckpt_dir, ckpt.MANIFEST)
    os.remove(path)
    return path


def simulate_interrupted_save(models_dir: str) -> str:
    """Leave a ``.ckpt-tmp-*`` staging dir behind, as if the writer died
    between the tmp write and the atomic rename. Resume discovery must
    ignore it (it never matches the name template) and GC must not trip."""
    import tempfile
    tmp = tempfile.mkdtemp(dir=models_dir, prefix=".ckpt-tmp-")
    with open(os.path.join(tmp, "params.msgpack"), "wb") as f:
        f.write(b"\x00" * 64)
    return tmp
