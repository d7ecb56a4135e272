"""Replica-set serving tests (ISSUE 7 acceptance criteria).

The load-bearing ones are the zero-loss failover contracts: a replica
KILLED or HUNG mid-decode costs zero requests, and every migrated
request's token stream is BYTE-IDENTICAL to the undisturbed
single-replica same-seed run (deterministic sampling makes in-flight
requests migratable — the same replay paged eviction uses, generalized
to replica death). Plus: hang detection fences within the heartbeat
deadline, a circuit-broken replica recovers and rejoins routing,
migration composes with paged eviction, operator drain, graceful
degradation (typed QueueFull, queued deadlines still reaped with zero
live replicas), the replica server end-to-end, and shutdown with a
replica outliving the join (callers never stranded).

Fault-injected tests are marked ``faults`` (the serve-side rows of the
fault catalog, docs/RESILIENCE.md); the rest of the file covers the
routing/observability surface. All CPU, tiny model (total_len 24).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.analysis import guards
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.models import vae as V
from dalle_pytorch_tpu.resilience import faults
from dalle_pytorch_tpu.resilience.retry import RetryPolicy
from dalle_pytorch_tpu.serve import (CANCELLED, DEADLINE_EXCEEDED, OK,
                                     QueueFull, Request, RequestQueue,
                                     SamplingParams)
from dalle_pytorch_tpu.serve.replica import (BROKEN, DRAINED, RETIRED,
                                             RUNNING, ReplicaSet,
                                             ReplayVersionMismatch,
                                             ScaleError, UpgradeAborted)

VCFG = V.VAEConfig(image_size=16, num_tokens=32, codebook_dim=16,
                   num_layers=2, hidden_dim=8)
CFG = D.DALLEConfig(dim=16, depth=2, vae=VCFG, num_text_tokens=50,
                    text_seq_len=8, heads=2, dim_head=8)

# short first-retry backoff so circuit-breaker tests run in milliseconds
FAST_BRINGUP = RetryPolicy(max_attempts=1, deadline_s=None,
                           base_backoff_s=0.01, backoff_multiplier=2.0,
                           max_backoff_s=0.1, jitter=0.0)


@pytest.fixture(scope="module")
def bundle():
    key = jax.random.PRNGKey(0)
    vae_params = V.vae_init(jax.random.fold_in(key, 1), VCFG)
    params = D.dalle_init(key, CFG, vae_params)
    return params, vae_params


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.deactivate()
    yield
    faults.deactivate()


_REF_CACHE: dict = {}


def reference_tokens(params, vae_params, req: Request) -> np.ndarray:
    """generate_images at batch 1 — the undisturbed single-replica
    same-seed run every migrated request must reproduce byte-for-byte
    (memoized: params are the module-scoped bundle everywhere)."""
    key = (req.codes, req.seed, req.sampling.temperature,
           req.sampling.filter_thres, req.sampling.top_p)
    if key not in _REF_CACHE:
        text = jnp.asarray([req.codes], jnp.int32)
        _, img_seq = D.generate_images(
            params, vae_params, text, cfg=CFG,
            rng=jax.random.PRNGKey(req.seed),
            filter_thres=req.sampling.filter_thres,
            top_p=req.sampling.top_p,
            temperature=req.sampling.temperature, return_img_seq=True)
        _REF_CACHE[key] = np.asarray(img_seq)[0]
    return _REF_CACHE[key]


REQS = [
    Request(codes=(3, 7, 9), seed=11),
    Request(codes=(5, 2, 8, 1, 4), seed=23,
            sampling=SamplingParams(temperature=0.7, filter_thres=0.8)),
    Request(codes=(6, 6), seed=5,
            sampling=SamplingParams(temperature=1.3, top_p=0.9)),
    Request(codes=(2, 4, 4), seed=7),
    Request(codes=(1, 5), seed=13),
    Request(codes=(4, 4, 4, 4), seed=17),
]


def assert_all_token_exact(params, vae_params, handles, reqs):
    for h, r in zip(handles, reqs):
        res = h.result(timeout=10)
        assert res.status == OK, (r, res.status, res.reason)
        np.testing.assert_array_equal(
            np.asarray(res.tokens),
            reference_tokens(params, vae_params, r))


def wait_all_ready(rs, timeout=180.0):
    """Drive the set until every process replica's worker reached READY.
    The chunk-keyed fault tests need this: children come up seconds
    apart (async spawn + jax import), and with an empty queue the
    first-ready replica's 2x-slot admission window can swallow a whole
    small burst — leaving the fault's target replica idle, its chunk
    counter at 0, and the injected fault never firing. Waiting costs
    nothing (no work queued = no chunks) and makes routing alternate
    deterministically at submit."""
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        rs.step_once()
        live = [r for r in rs.replicas if r.state == RUNNING
                and r.engine is not None]
        if len(live) == rs.n_replicas and all(
                getattr(r.engine, "ready", True) for r in live):
            return
        time.sleep(0.01)
    raise AssertionError("replicas never all became ready")


class TestCrashFailover:
    pytestmark = pytest.mark.faults

    def test_kill_replica_1_of_2_mid_decode_zero_loss_token_exact(
            self, bundle):
        """THE acceptance criterion: replica 1 of 2 crashes mid-decode
        (fault-injected after its 2nd fused chunk); every request —
        including the ones it held — completes with tokens
        byte-identical to the undisturbed single-replica run, and the
        failover is visible in the supervisor's counters."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS]
        with faults.injected(fault_replica=1, replica_crash_at_chunk=2):
            rs.run_until_idle()
        assert rs.failovers == 1
        assert rs.reclaimed >= 1, "the kill must have stranded work"
        assert_all_token_exact(params, vae_params, handles, REQS)
        stats = rs.stats()
        assert stats["completed"] == len(REQS)
        assert stats["failovers"] == 1
        # the replaced engine is a fresh program (own compile); every
        # LIVE replica still holds exactly one decode program
        assert all(c == 1 for c in rs.decode_compiles_per_replica())
        # distinct-delivered-tokens accounting survives the failover:
        # reclaimed prefixes were un-credited, replay re-credited them
        assert stats["tokens_decoded"] == sum(
            CFG.seq_len - len(r.codes) for r in REQS)

    def test_crash_with_single_replica_recovers_via_restart(self,
                                                            bundle):
        """replicas can be 1: the supervisor restarts the one engine and
        replays its work — slower than N>1, still zero-loss."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=8)
        rs = ReplicaSet(params, CFG, queue, replicas=1, num_slots=2,
                        chunk_steps=4, bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS[:2]]
        with faults.injected(fault_replica=0, replica_crash_at_chunk=1):
            rs.run_until_idle()
        assert rs.failovers == 1
        assert_all_token_exact(params, vae_params, handles, REQS[:2])


class TestHangFailover:
    pytestmark = pytest.mark.faults

    def test_hang_is_fenced_within_heartbeat_deadline(self, bundle):
        """A replica whose loop stalls (injected sleep where a wedged
        device sync would sit) must be fenced by the supervisor within
        the heartbeat deadline — WITHOUT the wedged thread's
        cooperation — and its requests must replay token-exact on the
        survivor while the hung thread is still asleep."""
        params, vae_params = bundle
        events = []

        class Sink:
            def event(self, **rec):
                events.append(rec)

        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, heartbeat_s=0.25, metrics=Sink(),
                        bringup_policy=FAST_BRINGUP)
        # warm both replicas' programs OUTSIDE the timed window (cold
        # compiles are seconds — the timing below must measure the
        # failover, not XLA)
        warm = [queue.submit(Request(codes=(1, 1), seed=90 + i))
                for i in range(4)]
        rs.run_until_idle()
        for h in warm:
            assert h.result(timeout=60).status == OK
        rs.start()
        try:
            hang_s = 20.0               # far past any load-induced slop
            with faults.injected(fault_replica=0,
                                 replica_hang_at_chunk=1,
                                 replica_hang_s=hang_s):
                handles = [queue.submit(r) for r in REQS[:4]]
                t0 = time.perf_counter()
                # the supervisor must fence the hung replica off its
                # stalled heartbeat — without the wedged thread's
                # cooperation, and LONG before the wedge clears (the
                # deadline is 0.25s; the bound leaves room for CI load)
                while rs.failovers < 1 \
                        and time.perf_counter() - t0 < hang_s:
                    time.sleep(0.01)
                t_fence = time.perf_counter() - t0
                assert rs.failovers >= 1, "hang never detected"
                assert t_fence < hang_s / 2, \
                    f"fence took {t_fence:.2f}s against a 0.25s deadline"
                fenced = [e for e in events
                          if e.get("kind") == "serve_replica_fenced"]
                assert fenced and "heartbeat" in fenced[0]["reason"]
                # and the reclaimed requests replay to completion while
                # the hung thread is STILL asleep
                for h in handles:
                    assert h.result(timeout=60).status == OK
                assert time.perf_counter() - t0 < hang_s, \
                    "completion waited out the hang"
            assert_all_token_exact(params, vae_params, handles, REQS[:4])
        finally:
            rs.close()

    def test_close_with_hung_replica_never_strands_callers(self, bundle):
        """The Server.close() ordering contract on the replica path: a
        replica thread that outlives the join deadline (hung) must not
        strand callers — its in-flight handles are fenced + fulfilled
        ``cancelled``, and the shared-queue drain catches the rest."""
        params, vae_params = bundle
        from dalle_pytorch_tpu.serve.server import InferenceServer
        server = InferenceServer(params, vae_params, CFG, num_slots=2,
                                 queue_depth=16, replicas=2,
                                 heartbeat_s=30.0,  # hang NOT detected:
                                 decode_images=False)  # close must cope
        server.start()
        with faults.injected(fault_replica=0, replica_hang_at_chunk=1,
                             replica_hang_s=4.0):
            handles = [server.submit(r.codes, seed=r.seed)
                       for r in REQS]
            time.sleep(0.5)             # replica 0 is asleep mid-loop
            t0 = time.perf_counter()
            server.close(timeout=1.0)
            assert time.perf_counter() - t0 < 3.0
            for h in handles:
                res = h.result(timeout=1)   # never strands: ok (done
                assert res.status in (OK, CANCELLED)  # before close)
                #                                 or typed cancelled


class TestCircuitBreaker:
    pytestmark = pytest.mark.faults

    def test_flaky_bringup_circuit_breaks_then_rejoins_routing(
            self, bundle):
        """A replica failing bring-up repeatedly is circuit-broken with
        exponential backoff while the set serves degraded; the attempt
        that succeeds re-joins it to routing (it completes real work
        afterwards)."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        with faults.injected(fault_replica=1, replica_flaky_bringup=2):
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=4, bringup_policy=FAST_BRINGUP)
            r1 = rs.replicas[1]
            assert r1.state == BROKEN       # attempt 0 failed at init
            assert rs.bringup_failures == 1
            assert rs.replicas[0].state == RUNNING
            # degraded but serving: work completes on replica 0 alone
            h = queue.submit(REQS[0])
            rs.run_until_idle()
            assert h.result(timeout=10).status == OK
            # wait out the backoff; attempt 1 fails too (flaky=2),
            # attempt 2 succeeds and the replica rejoins
            deadline = time.perf_counter() + 10
            while r1.state != RUNNING and time.perf_counter() < deadline:
                time.sleep(0.02)
                rs.step_once()
            assert r1.state == RUNNING
            assert rs.bringup_failures == 2
            assert r1.bringups == 3
            # rejoined ROUTING, not just alive: with both replicas'
            # slots needed for the burst, the recovered one completes
            # a share of it
            handles = [queue.submit(r) for r in REQS[:4]]
            rs.run_until_idle()
            assert_all_token_exact(params, vae_params, handles, REQS[:4])
            assert r1.engine.completed >= 1

    def test_all_replicas_down_degrades_to_typed_backpressure(self,
                                                              bundle):
        """Zero live replicas must never hang anyone: submits past the
        queue bound get typed QueueFull, and a queued request whose
        deadline passes gets its typed result from the ROUTER (no
        engine needed to reap it)."""
        params, _ = bundle
        queue = RequestQueue(max_depth=2)
        with faults.injected(fault_replica=0, replica_flaky_bringup=99):
            rs = ReplicaSet(params, CFG, queue, replicas=1, num_slots=2,
                            bringup_policy=FAST_BRINGUP)
            assert rs.replicas[0].state == BROKEN
            assert not rs.alive()
            h_dead = queue.submit(Request(codes=(1, 2), seed=0,
                                          deadline_s=0.0))
            queue.submit(Request(codes=(2, 2), seed=1))
            with pytest.raises(QueueFull):
                queue.submit(Request(codes=(3, 3), seed=2))
            time.sleep(0.01)
            rs.step_once()      # router reaps expired with 0 replicas
            assert h_dead.result(timeout=1).status == DEADLINE_EXCEEDED


class TestPagedMigration:
    pytestmark = pytest.mark.faults

    def test_migration_composes_with_paged_eviction(self, bundle):
        """The two replay mechanisms stack: on a pool that cannot hold
        two full sequences (page eviction guaranteed mid-decode), a
        replica crash reclaims BOTH the evicted-and-requeued victim and
        the in-flight survivor — and every request still lands
        token-exact after migrating to the other replica."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        # 6 usable pages at page_size 4 = exactly ONE full sequence:
        # two slots deep in decode MUST evict (same shape as
        # test_serve's eviction test, per replica)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, kv="paged", page_size=4,
                        num_pages=7, bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS]
        with faults.injected(fault_replica=0, replica_crash_at_chunk=4):
            rs.run_until_idle()
        stats = rs.stats()
        assert rs.failovers == 1
        assert stats["evicted"] >= 1, \
            "pool was sized to force eviction before the crash"
        assert_all_token_exact(params, vae_params, handles, REQS)
        # every live pool drained back to empty
        for r in rs.replicas:
            if r.engine is not None:
                assert r.engine.alloc.in_use == 0


class TestDrain:
    def test_operator_drain_migrates_inflight_and_undrain_rejoins(
            self, bundle):
        """Planned maintenance: drain fences the replica and replays
        its in-flight work on the survivor (zero loss, token-exact);
        undrain brings it back into routing."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS[:4]]
        for _ in range(2):              # both replicas mid-decode
            rs.step_once()
        assert rs.replicas[0].engine.active_slots() > 0
        reclaimed = rs.drain_replica(0)
        assert reclaimed >= 1
        assert rs.replicas[0].state == DRAINED
        rs.run_until_idle()             # survivor finishes everything
        assert_all_token_exact(params, vae_params, handles, REQS[:4])
        assert rs.replicas[0].state == DRAINED      # stays down
        assert rs.undrain_replica(0)
        assert rs.replicas[0].state == RUNNING
        h = queue.submit(REQS[4])
        rs.run_until_idle()
        assert h.result(timeout=10).status == OK


class TestProcessIsolation:
    """isolation='process': replicas are spawned child processes behind
    the typed IPC layer (serve/ipc.py + serve/worker.py). Base
    coverage: the set serves token-exact through the pipe, the operator
    surface reports child PIDs/RSS/restarts, and drain/undrain cycles a
    child process. Hard-kill failover lives in TestProcessHardKill."""

    def test_process_set_serves_token_exact_and_drain_cycles(
            self, bundle):
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, isolation="process",
                        bringup_policy=FAST_BRINGUP)
        try:
            # both READY before submitting: the [1, 1] compile assert
            # needs BOTH replicas to decode, and the first-ready
            # replica's 2x-slot admission window would otherwise
            # swallow the whole 4-request burst
            wait_all_ready(rs)
            handles = [queue.submit(r) for r in REQS[:4]]
            rs.run_until_idle(max_steps=500_000)
            assert_all_token_exact(params, vae_params, handles, REQS[:4])
            stats = rs.stats()
            assert stats["isolation"] == "process"
            assert stats["completed"] == 4
            assert stats["failovers"] == 0
            # distinct-delivered-token accounting across the pipe:
            # counters mirror the children's frames exactly
            assert stats["tokens_decoded"] == sum(
                CFG.seq_len - len(r.codes) for r in REQS[:4])
            assert rs.decode_compiles_per_replica() == [1, 1]
            pids = [p["pid"] for p in stats["per_replica"]]
            assert len(set(pids)) == 2
            assert all(isinstance(p, int) and p > 0 for p in pids)
            assert all(p["rss_mb"] > 0 for p in stats["per_replica"])
            # the transport observability block (PR 10) rides along in
            # pipe mode too: kind, peer, frame staleness, reconnects
            for p in stats["per_replica"]:
                assert p["transport"] == "pipe"
                assert p["peer"].startswith("pipe")
                assert p["last_frame_age_s"] >= 0.0
                assert p["reconnects"] == 0
            # operator drain kills the child; undrain spawns a fresh one
            old_pid = pids[0]
            rs.drain_replica(0)
            assert rs.replicas[0].state == DRAINED
            assert rs.undrain_replica(0)
            h = queue.submit(REQS[4])
            rs.run_until_idle(max_steps=500_000)
            assert h.result(timeout=10).status == OK
            new_pid = rs.replicas[0].engine.pid
            assert new_pid != old_pid, "undrain must be a fresh process"
        finally:
            rs.close()

    def test_process_server_end_to_end_health_and_stats(self, bundle):
        """The full threaded server over process replicas: /healthz
        carries the supervised-child fields (PID, restart count, last
        exit, child RSS) and 503 only when all replicas are dead."""
        params, vae_params = bundle
        from dalle_pytorch_tpu.serve.server import InferenceServer
        with pytest.raises(ValueError, match="replicas"):
            InferenceServer(params, vae_params, CFG, replicas=1,
                            isolation="process", decode_images=False)
        server = InferenceServer(params, vae_params, CFG, num_slots=2,
                                 queue_depth=16, replicas=2,
                                 isolation="process",
                                 decode_images=False).start()
        try:
            res = server.generate(REQS[0].codes, seed=REQS[0].seed,
                                  timeout=120)
            assert res.status == OK
            np.testing.assert_array_equal(
                np.asarray(res.tokens),
                reference_tokens(params, vae_params, REQS[0]))
            health = server.health()
            assert health["ok"] is True
            assert len(health["replicas"]) == 2
            for rep in health["replicas"]:
                assert rep["alive"]
                assert rep["pid"] > 0
                assert rep["restarts"] == 0
                assert rep["rss_mb"] > 0
            stats = server.stats()
            assert stats["isolation"] == "process"
            assert stats["completed"] == 1
        finally:
            server.close()


@pytest.mark.parametrize("transport", ["pipe", "socket"])
class TestProcessHardKill:
    """THE acceptance criterion of the process-isolation PR: a child
    replica killed for real — SIGKILL, SIGSEGV, a crash, an OOM kill,
    or a corrupted pipe — mid-decode loses ZERO requests; everything it
    held replays byte-identically on the survivor (reclaimed from the
    parent's shadow bookkeeping, never from the corpse), aggregate
    counters keep counting distinct delivered tokens, and the dead
    replica rejoins routing through the circuit-breaker backoff.

    Parameterized over BOTH frame transports (PR 10): the socket leg
    runs the identical suite over dial-back TCP workers, because the
    zero-loss contract must hold when the frames cross a network, not
    just a pipe. Socket-only failure modes (reset, torn frame, stalled
    link) live in TestSocketFaults."""

    pytestmark = pytest.mark.faults

    def _run_kill(self, bundle, plan_kwargs, expect_exit,
                  transport="pipe"):
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        with faults.injected(fault_replica=1, **plan_kwargs):
            # construct INSIDE the plan: hard-fault plans cross the
            # process boundary at spawn (faults.child_plan_for), once
            # per activation, so the restarted child comes up clean
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=4, isolation="process",
                            transport=transport,
                            bringup_policy=FAST_BRINGUP)
            try:
                wait_all_ready(rs)
                handles = [queue.submit(r) for r in REQS]
                rs.run_until_idle(max_steps=500_000)
                assert rs.failovers == 1
                assert rs.reclaimed >= 1, "the kill stranded no work?"
                assert_all_token_exact(params, vae_params, handles, REQS)
                stats = rs.stats()
                assert stats["completed"] == len(REQS)
                assert stats["tokens_decoded"] == sum(
                    CFG.seq_len - len(r.codes) for r in REQS), \
                    "distinct-token accounting broke across the kill"
                r1 = rs.replicas[1]
                assert expect_exit in r1.last_exit, \
                    (r1.last_exit, expect_exit)
                # rejoined routing after the circuit-breaker backoff
                assert r1.bringups >= 2
                assert r1.state == RUNNING
                assert rs.alive()
            finally:
                rs.close()

    def test_sigkill_mid_decode_zero_loss_token_exact(self, bundle,
                                                      transport):
        """kill -9 of a child replica mid-decode: the headline. The
        child dies with no goodbye; the parent decodes the exit signal,
        salvages the transport, replays the shadow."""
        self._run_kill(bundle, {"replica_sigkill_at_chunk": 2},
                       expect_exit="SIGKILL", transport=transport)

    def test_segv_mid_decode_zero_loss_token_exact(self, bundle,
                                                   transport):
        """SIGSEGV — the XLA-bug shape of death — decodes as its own
        signal and fails over identically."""
        self._run_kill(bundle, {"replica_segv_at_chunk": 2},
                       expect_exit="SIGSEGV", transport=transport)

    def test_child_crash_frame_zero_loss_token_exact(self, bundle,
                                                     transport):
        """A Python-level crash in the child ships a CRASH frame before
        exit 1 — the soft half of the catalog, process-drivable."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        with faults.injected(fault_replica=1, replica_crash_at_chunk=2):
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=4, isolation="process",
                            transport=transport,
                            bringup_policy=FAST_BRINGUP)
            try:
                wait_all_ready(rs)
                handles = [queue.submit(r) for r in REQS[:4]]
                rs.run_until_idle(max_steps=500_000)
                assert rs.failovers == 1
                assert_all_token_exact(params, vae_params, handles,
                                       REQS[:4])
            finally:
                rs.close()

    def test_oom_killed_child_fenced_and_replayed(self, bundle,
                                                  transport):
        """The child-side RSS limit: the injected OOM allocates real
        memory until the worker's watchdog crosses child_rss_limit_mb
        and dies with exit 137 (the container OOM-kill convention) —
        abruptly, no goodbye frame — and the failover replays its work
        token-exact."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        with faults.injected(fault_replica=1, replica_oom_at_chunk=1):
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=4, isolation="process",
                            transport=transport,
                            child_rss_limit_mb=1408,
                            # the ballast loop sends no frame while it
                            # allocates: the supervisor's hang deadline
                            # must not fire before the child's watchdog
                            heartbeat_s=90.0,
                            bringup_policy=FAST_BRINGUP)
            try:
                wait_all_ready(rs)
                handles = [queue.submit(r) for r in REQS[:4]]
                rs.run_until_idle(max_steps=500_000)
                assert rs.failovers == 1
                assert "oom" in rs.replicas[1].last_exit
                assert_all_token_exact(params, vae_params, handles,
                                       REQS[:4])
            finally:
                rs.close()

    def test_garbage_frame_fences_not_deadlocks(self, bundle,
                                                transport):
        """A child that corrupts its stream (injected garbage frame) is
        FENCED on the protocol error — hard-killed, salvaged, replayed
        — rather than deadlocking the parent or mis-parsing the lie."""
        params, vae_params = bundle
        events = []

        class Sink:
            def event(self, **rec):
                events.append(rec)

        queue = RequestQueue(max_depth=16)
        with faults.injected(fault_replica=1,
                             replica_garbage_frame_at_chunk=1):
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=4, isolation="process",
                            transport=transport,
                            metrics=Sink(), bringup_policy=FAST_BRINGUP)
            try:
                wait_all_ready(rs)
                handles = [queue.submit(r) for r in REQS[:4]]
                rs.run_until_idle(max_steps=500_000)
                assert rs.failovers == 1
                fenced = [e for e in events
                          if e.get("kind") == "serve_replica_fenced"]
                assert fenced and "protocol error" in \
                    fenced[0]["reason"], fenced
                assert_all_token_exact(params, vae_params, handles,
                                       REQS[:4])
            finally:
                rs.close()

    def test_hung_child_hard_killed_within_heartbeat_deadline(
            self, bundle, transport):
        """A child that is alive but silent (injected 20s stall where a
        wedged device sync would sit) is hard-killed off the missed-
        frame deadline — the hang detection working over the pipe, with
        known compiles exempted via the compiling-heartbeat — and its
        work replays long before the stall would have cleared."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        hang_s = 20.0
        with faults.injected(fault_replica=1, replica_hang_at_chunk=1,
                             replica_hang_s=hang_s):
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=4, isolation="process",
                            transport=transport, heartbeat_s=0.5,
                            bringup_policy=FAST_BRINGUP)
            try:
                wait_all_ready(rs)
                handles = [queue.submit(r) for r in REQS[:4]]
                t0 = time.perf_counter()
                rs.run_until_idle(max_steps=500_000)
                assert rs.failovers == 1
                assert time.perf_counter() - t0 < hang_s, \
                    "completion waited out the hang instead of fencing"
                # supervisor-initiated kill is labelled as such (and
                # names the deadline that expired), never dressed up
                # as an OS-delivered SIGKILL
                assert "hard-killed by supervisor" in \
                    rs.replicas[1].last_exit
                assert "heartbeat" in rs.replicas[1].last_exit
                assert_all_token_exact(params, vae_params, handles,
                                       REQS[:4])
            finally:
                rs.close()


class TestSocketFaults:
    """The NETWORK half of the fault catalog (PR 10) — the failure
    modes only a socket can exhibit, each of which must fence the
    replica via a TYPED error and replay its work byte-identically on
    a survivor, never deadlock, never double-deliver."""

    pytestmark = pytest.mark.faults

    def _run_socket_fault(self, bundle, plan_kwargs, **set_kwargs):
        params, vae_params = bundle
        events = []

        class Sink:
            def event(self, **rec):
                events.append(rec)

        queue = RequestQueue(max_depth=16)
        with faults.injected(fault_replica=1, **plan_kwargs):
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=4, isolation="process",
                            transport="socket", metrics=Sink(),
                            bringup_policy=FAST_BRINGUP, **set_kwargs)
            try:
                wait_all_ready(rs)
                handles = [queue.submit(r) for r in REQS[:4]]
                rs.run_until_idle(max_steps=500_000)
                assert rs.failovers == 1
                assert_all_token_exact(params, vae_params, handles,
                                       REQS[:4])
            finally:
                rs.close()
        return rs, events

    def test_conn_reset_mid_frame_zero_loss_token_exact(self, bundle):
        """A connection reset that tears a frame (half a heartbeat on
        the wire, then RST): the parent surfaces a typed mid-frame
        protocol error, fences, and replays — zero requests lost,
        tokens byte-identical."""
        rs, events = self._run_socket_fault(
            bundle, {"replica_conn_reset_at_chunk": 2})
        fenced = [e for e in events
                  if e.get("kind") == "serve_replica_fenced"]
        assert fenced, events
        assert "protocol error" in fenced[0]["reason"], fenced
        assert "mid-frame" in fenced[0]["reason"], fenced

    def test_torn_frame_at_byte_boundary_fences_typed(self, bundle):
        """Half a frame then a clean FIN (peer died between two writes
        of one frame): same typed fence + replay, distinguishable from
        a clean shutdown."""
        rs, events = self._run_socket_fault(
            bundle, {"replica_torn_frame_at_chunk": 2})
        fenced = [e for e in events
                  if e.get("kind") == "serve_replica_fenced"]
        assert fenced, events
        assert "protocol error" in fenced[0]["reason"], fenced

    def test_duplicate_frame_delivery_fences(self, bundle):
        """A transport that re-delivers a frame (same sequence number
        twice) is fenced on the duplicate — results and counters can
        never be silently double-absorbed."""
        rs, events = self._run_socket_fault(
            bundle, {"replica_dup_frame_at_chunk": 2})
        fenced = [e for e in events
                  if e.get("kind") == "serve_replica_fenced"]
        assert fenced and "duplicate or reordered" in \
            fenced[0]["reason"], fenced

    def test_reordered_frame_delivery_fences(self, bundle):
        """Two frames swapped on the wire: the sequence gap at the
        first fences the replica before anything is absorbed out of
        order."""
        rs, events = self._run_socket_fault(
            bundle, {"replica_reorder_frames_at_chunk": 2})
        fenced = [e for e in events
                  if e.get("kind") == "serve_replica_fenced"]
        assert fenced and "gap" in fenced[0]["reason"], fenced

    def test_stalled_socket_fenced_within_heartbeat_deadline(
            self, bundle):
        """The stalled-socket row: the connection stays accepted and
        OPEN but the worker goes silent (20s injected stall). The
        parent must fence off the missed-heartbeat deadline — with no
        thread ever blocking on the unread socket — and the stalled
        replica's work must replay long before the stall clears, with
        no caller stranded."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        hang_s = 20.0
        with faults.injected(fault_replica=1,
                             replica_stall_socket_at_chunk=1,
                             replica_hang_s=hang_s):
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=4, isolation="process",
                            transport="socket", heartbeat_s=0.5,
                            bringup_policy=FAST_BRINGUP)
            try:
                wait_all_ready(rs)
                handles = [queue.submit(r) for r in REQS[:4]]
                t0 = time.perf_counter()
                rs.run_until_idle(max_steps=500_000)
                assert rs.failovers == 1
                assert time.perf_counter() - t0 < hang_s, \
                    "completion waited out the stall instead of fencing"
                assert "hard-killed by supervisor" in \
                    rs.replicas[1].last_exit
                assert "heartbeat" in rs.replicas[1].last_exit
                assert_all_token_exact(params, vae_params, handles,
                                       REQS[:4])
            finally:
                rs.close()


class TestRemoteAttach:
    """Host-per-engine's defining move: a worker that is NOT a spawned
    child — launched by an operator command (``worker_cmd``) or started
    entirely by hand — dials the parent's endpoint, authenticates, and
    joins the replica set EXACTLY like a spawned child: same shadow
    bookkeeping, same heartbeat supervision, same fence→reclaim→replay
    on death. (The workers here run on localhost; the transport path is
    identical to a cross-host attach, minus the routing table.)"""

    def test_worker_cmd_launched_workers_serve_token_exact(self, bundle):
        """--worker_cmd as the launcher hook: every replica's worker is
        started by the command template (token via env, never argv) and
        the set serves token-exact with the transport fields visible in
        stats."""
        import os
        import sys
        params, vae_params = bundle
        env_before = os.environ.get("PYTHONPATH")
        os.environ["PYTHONPATH"] = os.pathsep.join(
            p for p in (os.getcwd(), env_before) if p)
        queue = RequestQueue(max_depth=16)
        try:
            rs = ReplicaSet(
                params, CFG, queue, replicas=2, num_slots=2,
                chunk_steps=4, isolation="process", transport="socket",
                # {token} pins the placeholder a remote (ssh) launcher
                # needs — a plain env var doesn't cross host boundaries
                worker_cmd=(f"{sys.executable} -m "
                            f"dalle_pytorch_tpu.serve.worker "
                            f"--connect {{endpoint}} --index {{index}} "
                            f"--token {{token}}"),
                bringup_policy=FAST_BRINGUP)
            try:
                handles = [queue.submit(r) for r in REQS[:4]]
                rs.run_until_idle(max_steps=500_000)
                assert_all_token_exact(params, vae_params, handles,
                                       REQS[:4])
                stats = rs.stats()
                assert stats["transport"] == "socket"
                assert stats["attach_rejected"] == 0
                for p in stats["per_replica"]:
                    assert p["transport"] == "socket"
                    assert ":" in p["peer"]
                    assert p["last_frame_age_s"] >= 0.0
            finally:
                rs.close()
        finally:
            if env_before is None:
                os.environ.pop("PYTHONPATH", None)
            else:
                os.environ["PYTHONPATH"] = env_before

    @pytest.mark.faults
    def test_hand_started_worker_attaches_dies_and_is_replaced(
            self, bundle):
        """The full remote-attach story: workers started BY HAND
        (worker_cmd='' — the set spawns nothing) dial in and serve; one
        self-SIGKILLs mid-decode (the fault plan rides the spec over
        the socket, so even a hand-started worker is fault-drivable);
        with no PID to probe, the parent declares it dead off the
        SOCKET, replays its work token-exact on the survivor, and a
        replacement worker started by hand attaches to the broken slot
        and rejoins routing."""
        import os
        import subprocess
        import sys
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (os.getcwd(), env.get("PYTHONPATH")) if p)

        def start_worker(listener, index):
            env2 = dict(env)
            from dalle_pytorch_tpu.serve import transport as T
            env2[T.TOKEN_ENV] = listener.token
            return subprocess.Popen(
                [sys.executable, "-m",
                 "dalle_pytorch_tpu.serve.worker",
                 "--connect", listener.endpoint,
                 "--index", str(index)], env=env2)

        with faults.injected(fault_replica=1,
                             replica_sigkill_at_chunk=2):
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=4, isolation="process",
                            transport="socket", worker_cmd="",
                            bringup_policy=FAST_BRINGUP)
            procs = []
            try:
                procs.append(start_worker(rs.listener, 0))
                procs.append(start_worker(rs.listener, 1))
                handles = [queue.submit(r) for r in REQS]
                # drive until the victim dies and the survivor finishes
                # everything; replica 1 stays BROKEN/awaiting because
                # nothing respawns a hand-started worker
                deadline = time.perf_counter() + 300
                while time.perf_counter() < deadline:
                    rs.step_once()
                    if rs.failovers >= 1 and all(h.done()
                                                 for h in handles):
                        break
                assert rs.failovers == 1, "worker death never fenced"
                assert_all_token_exact(params, vae_params, handles, REQS)
                # no PID was available: the death was declared off the
                # socket and labelled as the remote shape
                assert "remote worker" in rs.replicas[1].last_exit, \
                    rs.replicas[1].last_exit
                # the slot is waiting for a replacement, not circuit-
                # broken into oblivion: hand-start a new worker and it
                # must rejoin routing and complete fresh work
                deadline = time.perf_counter() + 60
                while time.perf_counter() < deadline:
                    rs.step_once()
                    r1 = rs.replicas[1]
                    if r1.state == RUNNING and r1.engine is not None \
                            and r1.engine.awaiting_operator:
                        break
                procs.append(start_worker(rs.listener, 1))
                h = queue.submit(REQS[0])
                deadline = time.perf_counter() + 300
                while time.perf_counter() < deadline:
                    rs.step_once()
                    if h.done() and rs.replicas[1].engine is not None \
                            and rs.replicas[1].engine.ready:
                        break
                assert h.result(timeout=10).status == OK
                assert rs.replicas[1].engine.ready, \
                    "replacement worker never rejoined"
            finally:
                rs.close()
                for p in procs:
                    if p.poll() is None:
                        p.kill()


class TestRoutingAndStats:
    def test_burst_routes_least_loaded_across_replicas(self, bundle):
        """A burst wider than one replica's slots spreads: both
        replicas complete a share, and the aggregate stats add up."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS[:4]]
        rs.step_once()
        assert all(r.engine.active_slots() == 2 for r in rs.replicas)
        rs.run_until_idle()
        assert_all_token_exact(params, vae_params, handles, REQS[:4])
        stats = rs.stats()
        assert stats["completed"] == 4
        assert all(p["completed"] == 2 for p in stats["per_replica"])
        assert stats["decode_compiles"] == 2        # one per replica
        assert stats["alive_replicas"] == 2
        assert stats["failovers"] == 0
        # the replicated steady state is transfer-clean: routing
        # hand-offs are host-side, and a harvest stays one explicit
        # device_get a chunk a replica
        again = [queue.submit(r) for r in REQS[:4]]
        with guards.no_transfers():
            rs.run_until_idle()
        assert_all_token_exact(params, vae_params, again, REQS[:4])
        assert rs.stats()["decode_compiles"] == 2

    def test_page_aware_routing_prefers_replica_with_free_pages(
            self, bundle):
        """With one paged replica's pool fully claimed, a new request
        routes to the replica that can map its prompt NOW."""
        params, _ = bundle
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=24, kv="paged", page_size=4,
                        num_pages=7, bringup_policy=FAST_BRINGUP)
        queue.submit(REQS[0])
        rs.step_once()      # lands on one replica, maps ALL its pages
        full = [r for r in rs.replicas if r.engine.alloc.free == 0]
        assert len(full) == 1
        queue.submit(REQS[1])
        rs.step_once()
        empty = [r for r in rs.replicas if r is not full[0]][0]
        assert empty.engine.active_slots() == 1, \
            "request routed to the page-starved replica"
        rs.run_until_idle()

    def test_replica_server_end_to_end_stats_and_health(self, bundle):
        """The full replica server: submit through the shared queue,
        aggregate /stats surface, per-replica /healthz body."""
        params, vae_params = bundle
        from dalle_pytorch_tpu.serve.server import InferenceServer
        server = InferenceServer(params, vae_params, CFG, num_slots=2,
                                 queue_depth=16, replicas=2,
                                 decode_images=False).start()
        try:
            res = server.generate(REQS[0].codes, seed=REQS[0].seed,
                                  timeout=60)
            assert res.status == OK
            np.testing.assert_array_equal(
                np.asarray(res.tokens),
                reference_tokens(params, vae_params, REQS[0]))
            stats = server.stats()
            assert stats["completed"] == 1
            assert stats["replicas"] == 2
            assert stats["requests_submitted"] == 1
            health = server.health()
            assert health["ok"] is True
            assert len(health["replicas"]) == 2
            assert all(r["alive"] for r in health["replicas"])
        finally:
            server.close()


# ---------------------------------------------------------------------------
# Elastic fleet (ISSUE 14): runtime scale-out/in, rolling weight hot-swap,
# version-pinned replay, the autoscaler policy loop, and the HOL hand-back
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bundle_v2(bundle):
    """A SECOND weights generation for upgrade tests: same config, a
    different init key — byte-distinct logits, so same-seed tokens
    differ between generations and 'byte-identical PER version' is a
    real assertion, not a tautology."""
    _, vae_params = bundle
    return D.dalle_init(jax.random.PRNGKey(42), CFG, vae_params), \
        vae_params


_VREF_CACHE: dict = {}


def versioned_reference(params, vae_params, req: Request) -> np.ndarray:
    """Like ``reference_tokens`` but keyed by the params object too —
    upgrade tests compare against the generation that STAMPED each
    result, and two generations must never share a cache row."""
    key = (id(params), req.codes, req.seed, req.sampling.temperature,
           req.sampling.filter_thres, req.sampling.top_p)
    if key not in _VREF_CACHE:
        text = jnp.asarray([req.codes], jnp.int32)
        _, img_seq = D.generate_images(
            params, vae_params, text, cfg=CFG,
            rng=jax.random.PRNGKey(req.seed),
            filter_thres=req.sampling.filter_thres,
            top_p=req.sampling.top_p,
            temperature=req.sampling.temperature, return_img_seq=True)
        _VREF_CACHE[key] = np.asarray(img_seq)[0]
    return _VREF_CACHE[key]


class _Sink:
    def __init__(self):
        self.events = []

    def event(self, **rec):
        self.events.append(rec)

    def of(self, kind):
        return [e for e in self.events if e.get("kind") == kind]


class TestElasticScale:
    def test_add_replica_joins_routing_and_caps_are_typed(self, bundle):
        """Scale-out under load: the new slot serves token-exact, the
        page-budget cap and the last-replica floor are typed
        ScaleErrors, and a retired slot stays retired."""
        params, vae_params = bundle
        sink = _Sink()
        queue = RequestQueue(max_depth=32)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, weights_version="v1",
                        max_replicas=3, metrics=sink,
                        bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS[:4]]
        for _ in range(2):              # both replicas mid-decode
            rs.step_once()
        index = rs.add_replica()
        assert index == 2 and rs.n_replicas == 3
        assert rs.replicas[2].state == RUNNING
        rs.run_until_idle()
        assert_all_token_exact(params, vae_params, handles, REQS[:4])
        # the new slot genuinely serves (route a fresh burst wide)
        more = [queue.submit(r) for r in REQS]
        rs.run_until_idle()
        assert_all_token_exact(params, vae_params, more, REQS)
        assert sink.of("serve_scale_out")
        with pytest.raises(ScaleError) as e:
            rs.add_replica()
        assert e.value.record["reason"] == "scale_out_past_cap"
        # scale-in retires; the tombstone is never resurrected
        assert rs.remove_replica(2) >= 0
        assert rs.replicas[2].state == RETIRED
        assert rs.n_replicas == 2
        with pytest.raises(ScaleError) as e:
            rs.remove_replica(2)
        assert e.value.record["reason"] == "replica_retired"
        with pytest.raises(ScaleError) as e:
            rs.drain_replica(2)
        assert e.value.record["reason"] == "replica_retired"
        rs.remove_replica(1)
        with pytest.raises(ScaleError) as e:
            rs.remove_replica(0)
        assert e.value.record["reason"] == "remove_last_replica"
        # the survivor still serves
        h = queue.submit(REQS[0])
        rs.run_until_idle()
        assert h.result(timeout=10).status == OK

    def test_remove_replica_drains_inflight_zero_loss(self, bundle):
        """Scale-in mid-decode: the retired replica's in-flight work
        replays on the survivor byte-identically — retirement is a
        fence+reclaim, never a drop."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS[:4]]
        for _ in range(2):
            rs.step_once()
        assert rs.replicas[0].engine.active_slots() > 0
        reclaimed = rs.remove_replica(0, reason="test scale-in")
        assert reclaimed >= 1
        rs.run_until_idle()
        assert_all_token_exact(params, vae_params, handles, REQS[:4])
        assert rs.stats()["scale_ins"] == 1

    @pytest.mark.faults
    def test_scale_out_bringup_kill_circuit_breaks_zero_loss(
            self, bundle):
        """The 'replica killed mid-add_replica bring-up' fault row: the
        scaled-out slot's first bring-up dies, it circuit-breaks and
        retries onto its feet, and the serving survivors (and every
        in-flight request) never notice."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=32)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, max_replicas=3,
                        bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS]
        rs.step_once()
        with faults.injected(scale_add_bringup_crash=1):
            index = rs.add_replica()
            assert rs.replicas[index].state == BROKEN, \
                "the injected bring-up kill never fired"
            assert rs.bringup_failures >= 1
            rs.run_until_idle()
            # the retry (attempt 1 >= the 1-attempt plan) must succeed
            deadline = time.perf_counter() + 30
            while rs.replicas[index].state != RUNNING \
                    and time.perf_counter() < deadline:
                rs.step_once()
                time.sleep(0.005)
        assert rs.replicas[index].state == RUNNING
        assert rs.failovers == 0, "survivors must be untouched"
        assert_all_token_exact(params, vae_params, handles, REQS)


class TestRollingUpgrade:
    def test_rolling_upgrade_zero_loss_byte_identical_per_version(
            self, bundle, bundle_v2):
        """THE elastic acceptance criterion: a rolling upgrade with
        traffic in flight loses zero requests, cycles every replica
        canary-gated, stamps every Result with the generation that
        decoded it, and same-seed tokens are byte-identical PER
        weights_version."""
        params, vae_params = bundle
        params2, _ = bundle_v2
        sink = _Sink()
        queue = RequestQueue(max_depth=32)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, weights_version="v1",
                        metrics=sink, bringup_policy=FAST_BRINGUP)
        pre = [queue.submit(r) for r in REQS[:2]]
        rs.run_until_idle()
        for h, r in zip(pre, REQS[:2]):
            res = h.result(timeout=10)
            assert res.status == OK and res.weights_version == "v1"
        mid = [queue.submit(r) for r in REQS]
        record = rs.rolling_upgrade(version="v2", params=params2,
                                    canary_codes=[(1, 2)], canaries=2,
                                    replica_timeout_s=180)
        assert len(record["replicas"]) == 2
        rs.run_until_idle()
        # zero loss through the reshape, and per-version byte-identity:
        # whichever generation answered each request, its tokens match
        # that generation's undisturbed single-engine run exactly
        for h, r in zip(mid, REQS):
            res = h.result(timeout=10)
            assert res.status == OK, (res.status, res.reason)
            assert res.weights_version in ("v1", "v2")
            p = params if res.weights_version == "v1" else params2
            np.testing.assert_array_equal(
                np.asarray(res.tokens),
                versioned_reference(p, vae_params, r))
        # the fleet is promoted: fresh traffic is v2, byte-identical
        post = queue.submit(REQS[0])
        rs.run_until_idle()
        res = post.result(timeout=10)
        assert res.weights_version == "v2"
        np.testing.assert_array_equal(
            np.asarray(res.tokens),
            versioned_reference(params2, vae_params, REQS[0]))
        stats = rs.stats()
        assert stats["weights_version"] == "v2"
        assert stats["upgrades"] == 1
        assert all(p["weights_version"] == "v2"
                   for p in stats["per_replica"])
        assert sink.of("serve_upgrade_begin")
        assert len(sink.of("serve_upgrade_replica")) == 2
        assert sink.of("serve_upgrade_done")
        # scaling mid-upgrade is an illegal transition — verify the
        # typed reject without racing a real upgrade: flip the flag
        rs._upgrading = True
        try:
            with pytest.raises(ScaleError) as e:
                rs.add_replica()
            assert e.value.record["reason"] == "upgrade_in_progress"
        finally:
            rs._upgrading = False

    def test_upgrade_skips_operator_drained_replica(self, bundle,
                                                    bundle_v2):
        """The drain contract outranks the rollout: a replica an
        operator drained stays DOWN through a rolling upgrade (skip
        recorded, structured event), its version label moves with the
        promote, and a later undrain brings it up on the promoted
        weights."""
        params, vae_params = bundle
        params2, _ = bundle_v2
        sink = _Sink()
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=3, num_slots=2,
                        chunk_steps=4, weights_version="v1",
                        metrics=sink, bringup_policy=FAST_BRINGUP)
        rs.drain_replica(2)
        record = rs.rolling_upgrade(version="v2", params=params2,
                                    canary_codes=[(1, 2)], canaries=1,
                                    replica_timeout_s=180)
        assert rs.replicas[2].state == DRAINED, \
            "the upgrade resurrected an operator-drained replica"
        assert {"replica": 2, "skipped": "drained"} \
            in record["replicas"]
        assert sink.of("serve_upgrade_skip_drained")
        assert rs.replicas[2].version == "v2"   # label moved at promote
        assert rs.undrain_replica(2)
        h = queue.submit(REQS[0])
        rs.run_until_idle()
        res = h.result(timeout=10)
        assert res.weights_version == "v2"
        np.testing.assert_array_equal(
            np.asarray(res.tokens),
            versioned_reference(params2, vae_params, REQS[0]))

    @pytest.mark.faults
    def test_canary_failure_aborts_and_rolls_back_whole_fleet(
            self, bundle, bundle_v2):
        """The injected canary health-gate failure: rolling_upgrade
        aborts typed at replica 1, AND replica 0 — already gated onto
        v2 — rolls back, so the whole fleet is left serving v1; live
        traffic survives both reshapes with zero loss."""
        params, vae_params = bundle
        params2, _ = bundle_v2
        sink = _Sink()
        queue = RequestQueue(max_depth=32)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, weights_version="v1",
                        metrics=sink, bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS[:4]]
        with faults.injected(upgrade_canary_fail_replica=1):
            with pytest.raises(UpgradeAborted) as e:
                rs.rolling_upgrade(version="v2", params=params2,
                                   canary_codes=[(1, 2)], canaries=1,
                                   replica_timeout_s=180)
        assert e.value.record["fleet_version"] == "v1"
        assert sorted(e.value.record["rolled_back"]) == [0, 1]
        assert all(r.version == "v1" for r in rs.replicas)
        assert all(not r.canary for r in rs.replicas)
        assert rs.weights_version == "v1" and rs.upgrades == 0
        rs.run_until_idle()
        for h in handles:
            assert h.result(timeout=10).status == OK
        # fresh traffic serves v1 byte-identically after the abort
        h = queue.submit(REQS[0])
        rs.run_until_idle()
        res = h.result(timeout=10)
        assert res.weights_version == "v1"
        np.testing.assert_array_equal(
            np.asarray(res.tokens),
            versioned_reference(params, vae_params, REQS[0]))
        assert sink.of("serve_upgrade_abort")
        assert not sink.of("serve_upgrade_done")
        # the abort must not wedge the fleet: a RETRY of the same
        # version (fault gone) succeeds — the aborted attempt's canary
        # reference was dropped with it, and the upgrade lock released
        record = rs.rolling_upgrade(version="v2", params=params2,
                                    canary_codes=[(1, 2)], canaries=1,
                                    replica_timeout_s=180)
        assert len(record["replicas"]) == 2
        assert rs.weights_version == "v2" and rs.upgrades == 1


class TestVersionPinnedReplay:
    def test_weights_version_survives_wire_roundtrip(self):
        """The Result wire satellite: weights_version round-trips
        through to_wire/from_wire exactly, and a frame from a
        pre-upgrade peer (no field) decodes as unversioned instead of
        failing the attach."""
        from dalle_pytorch_tpu.serve.scheduler import Result
        res = Result(status=OK, request_id=7,
                     tokens=np.asarray([1, 2, 3], np.int32),
                     weights_version="ckpt@99", decode_s=0.5)
        rt = Result.from_wire(res.to_wire())
        assert rt.weights_version == "ckpt@99"
        legacy = res.to_wire()
        del legacy["weights_version"]
        assert Result.from_wire(legacy).weights_version == ""

    def test_pick_refuses_cross_version_replay_typed(self, bundle):
        """The invariant guard: a handle pinned to one generation
        offered a replica on another raises the typed
        ReplayVersionMismatch (the router's filter makes this
        unreachable; the guard keeps it impossible, not unlikely)."""
        params, _ = bundle
        queue = RequestQueue(max_depth=8)
        rs = ReplicaSet(params, CFG, queue, replicas=1, num_slots=2,
                        chunk_steps=4, weights_version="v1",
                        bringup_policy=FAST_BRINGUP)
        h = queue.submit(REQS[0])
        (ready, _) = queue.pop_ready(1)
        assert ready == [h]
        h.replay_version = "v0-archaic"
        with pytest.raises(ReplayVersionMismatch):
            rs._pick([rs.replicas[0]], {0: 1}, h)

    @pytest.mark.faults
    def test_failover_replay_holds_for_same_version_replica(
            self, bundle, bundle_v2):
        """Failover replay mid-upgrade is version-pinned: with replica
        1 already on v2, replica 0's (v1) crash must NOT replay its
        work on the v2 survivor — the requests HOLD (structured event)
        until replica 0's circuit-breaker restart brings v1 capacity
        back, and the replayed tokens are byte-identical to v1."""
        params, vae_params = bundle
        params2, _ = bundle_v2
        sink = _Sink()
        queue = RequestQueue(max_depth=32)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, weights_version="v1",
                        metrics=sink, bringup_policy=FAST_BRINGUP)
        # hand-build the mixed-version fleet (replica 1 on v2) without
        # running a full upgrade: drain, override, undrain — exactly
        # what rolling_upgrade does, minus the canary gate. Draining
        # replica 1 FIRST funnels both requests onto replica 0, so
        # both are pinned to v1 before any v2 capacity exists.
        rs.drain_replica(1)
        handles = [queue.submit(r) for r in REQS[:2]]
        for _ in range(2):
            rs.step_once()          # both routed to replica 0 (v1)
        r1 = rs.replicas[1]
        r1.params_override = params2
        r1.version = "v2"
        assert rs.undrain_replica(1)
        # crash replica 0 mid-decode; the flaky restart keeps v1
        # capacity DOWN across routing sweeps, so the pinned replay
        # must visibly HOLD rather than ride the same-sweep restart
        # (replica 0's lifetime bring-up count is 1, so restart
        # attempts 1..2 fail and attempt 3 succeeds)
        with faults.injected(fault_replica=0, replica_crash_at_chunk=1,
                             replica_flaky_bringup=3):
            rs.run_until_idle()
        assert rs.failovers == 1
        holds = sink.of("serve_replay_version_hold")
        assert holds, "pinned replay never HELD for a v1 replica"
        for h, r in zip(handles, REQS[:2]):
            res = h.result(timeout=10)
            assert res.status == OK
            assert res.weights_version == "v1", \
                "pinned replay decoded on the wrong generation"
            np.testing.assert_array_equal(
                np.asarray(res.tokens),
                versioned_reference(params, vae_params, r))

    def test_pin_released_when_generation_leaves_fleet(self, bundle,
                                                       bundle_v2):
        """Zero-loss outranks a stale pin: reclaim work pinned to v1,
        retire every v1 replica, and the router must RELEASE the pin
        (structured event) and replay on v2 — completed, stamped v2,
        byte-identical to v2."""
        params, vae_params = bundle
        params2, _ = bundle_v2
        sink = _Sink()
        queue = RequestQueue(max_depth=32)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, weights_version="v1",
                        metrics=sink, bringup_policy=FAST_BRINGUP)
        rs.drain_replica(1)
        r1 = rs.replicas[1]
        r1.params_override = params2
        r1.version = "v2"
        assert rs.undrain_replica(1)
        handles = [queue.submit(r) for r in REQS[:2]]
        for _ in range(2):
            rs.step_once()          # replica 0 (v1) holds the work
        # retire the v1 replica: its work reclaims pinned v1, but no
        # v1 replica exists anymore (the tombstone doesn't count)
        rs.remove_replica(0, reason="retire the whole v1 generation")
        rs.run_until_idle()
        assert sink.of("serve_replay_version_released")
        for h, r in zip(handles, REQS[:2]):
            res = h.result(timeout=10)
            assert res.status == OK
            assert res.weights_version == "v2"
            np.testing.assert_array_equal(
                np.asarray(res.tokens),
                versioned_reference(params2, vae_params, r))


class TestAutoscaler:
    def test_policy_validation_is_typed(self):
        from dalle_pytorch_tpu.serve.autoscale import AutoscalePolicy
        with pytest.raises(ValueError, match="min_replicas"):
            AutoscalePolicy(min_replicas=0)
        with pytest.raises(ValueError, match="max_replicas"):
            AutoscalePolicy(min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError, match="occupancy"):
            AutoscalePolicy(low_occupancy=0.9, high_occupancy=0.8)

    def test_scale_out_in_with_hysteresis_cooldown_and_caps(
            self, bundle):
        """The policy loop end-to-end on a real set, sync-driven: idle
        ticks hold, a sustained burst scales out (after breach_ticks,
        once), saturation at max_replicas is a typed at_max decision,
        and sustained idleness scales back in — never below
        min_replicas."""
        from dalle_pytorch_tpu.serve.autoscale import (AutoscalePolicy,
                                                       Autoscaler)
        params, vae_params = bundle
        sink = _Sink()
        queue = RequestQueue(max_depth=64)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, max_replicas=3, metrics=sink,
                        bringup_policy=FAST_BRINGUP)
        clock = [0.0]
        scaler = Autoscaler(rs, AutoscalePolicy(
            min_replicas=2, max_replicas=3, high_occupancy=0.75,
            low_occupancy=0.10, queue_high=1, breach_ticks=2,
            cooldown_s=1.0), metrics=sink, clock=lambda: clock[0])
        # idle: no decisions, ever
        for _ in range(5):
            clock[0] += 10
            assert scaler.tick() is None
        # a deep queue breaches for breach_ticks consecutive ticks
        handles = [queue.submit(Request(codes=(1 + i % 7, 2), seed=i))
                   for i in range(16)]
        clock[0] += 10
        assert scaler.tick() is None        # breach 1 of 2: hysteresis
        clock[0] += 0.1
        dec = scaler.tick()
        assert dec is not None and dec["action"] == "scale_out"
        assert rs.n_replicas == 3
        # cooldown: still hot, but the scaler must hold its fire
        clock[0] += 0.1
        assert scaler.tick() is None
        # past cooldown and still saturated at the cap: typed at_max
        clock[0] += 2.0
        scaler.tick()                       # breach 1 (counters reset)
        clock[0] += 0.1
        dec = scaler.tick()
        assert dec is not None and dec["action"] == "at_max"
        rs.run_until_idle()
        for h in handles:
            assert h.result(timeout=30).status == OK
        # sustained idle: scale in once, then rest at the floor
        clock[0] += 2.0
        assert scaler.tick() is None        # breach 1 of 2
        clock[0] += 0.1
        dec = scaler.tick()
        assert dec is not None and dec["action"] == "scale_in"
        assert rs.n_replicas == 2
        assert rs.replicas[2].state == RETIRED
        clock[0] += 10
        for _ in range(4):
            clock[0] += 0.1
            assert scaler.tick() is None    # at the floor: quiet
        assert rs.n_replicas == 2
        auto = sink.of("autoscale_decision")
        assert [d["action"] for d in auto] == ["scale_out", "at_max",
                                               "scale_in"]
        # and the reshaped fleet still serves token-exact
        h = queue.submit(REQS[0])
        rs.run_until_idle()
        res = h.result(timeout=10)
        np.testing.assert_array_equal(
            np.asarray(res.tokens),
            reference_tokens(params, vae_params, REQS[0]))


class TestDrainHolHandoff:
    def test_drain_hands_hol_reservation_back_to_shared_queue(
            self, bundle):
        """The drain fix: retiring a replica whose private queue holds
        a page-deferred request must hand the head-of-line page
        reservation back to the shared-queue level (structured
        serve_hol_handoff event, exact pages_needed) instead of letting
        the _hol floor die with the fenced engine — and the deferred
        request completes token-exact on the survivor."""
        params, vae_params = bundle
        sink = _Sink()
        queue = RequestQueue(max_depth=32)
        # 6 usable pages at page_size 4 = ONE full sequence: a second
        # full-prompt request admitted late in the first one's decode
        # MUST defer on pages and become the engine's HOL reservation
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, kv="paged", page_size=4,
                        num_pages=7, metrics=sink,
                        bringup_policy=FAST_BRINGUP)
        first = [Request(codes=(1,) * 8, seed=0),
                 Request(codes=(2,) * 8, seed=1)]
        h1 = [queue.submit(r) for r in first]
        for _ in range(300):
            rs.step_once()
            e0 = rs.replicas[0].engine
            if e0 is not None and e0.alloc.free < 2 \
                    and e0.active_slots() > 0:
                break
        else:
            raise AssertionError("replica 0 never got page-tight")
        second = [Request(codes=(3,) * 8, seed=2),
                  Request(codes=(4,) * 8, seed=3)]
        h2 = [queue.submit(r) for r in second]
        hol = None
        for _ in range(300):
            rs.step_once()
            e0 = rs.replicas[0].engine
            if e0 is not None and e0._hol_rid is not None:
                hol = (e0._hol_rid, e0._hol_need)
                break
        assert hol is not None, "the defer window never produced a HOL"
        rs.drain_replica(0)
        events = sink.of("serve_hol_handoff")
        assert events and events[0]["request_id"] == hol[0] \
            and events[0]["pages_needed"] == hol[1]
        assert rs.hol_handoffs == 1
        rs.run_until_idle()
        assert not rs._hol_handoff, "reservation must clear on routing"
        assert_all_token_exact(params, vae_params, h1 + h2,
                               first + second)


class TestAdminScaleEndpoint:
    def test_admin_scale_http_auth_ops_and_typed_rejects(self, bundle):
        """POST /admin/scale end-to-end: 401 without the token, 200
        with structured bodies for add/remove/drain/undrain/status,
        409 with the typed record for illegal transitions — and the
        reshaped fleet keeps serving through the front door."""
        import http.client
        import json as json_mod

        from dalle_pytorch_tpu.serve.server import (InferenceServer,
                                                    make_http_server)
        params, vae_params = bundle
        server = InferenceServer(params, vae_params, CFG, num_slots=2,
                                 queue_depth=16, replicas=2,
                                 max_replicas=3, weights_version="v1",
                                 admin_token="tok-test",
                                 decode_images=False).start()
        httpd = make_http_server(server, port=0)
        port = httpd.server_address[1]
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()

        def post(path, body, token=None):
            c = http.client.HTTPConnection("127.0.0.1", port,
                                           timeout=60)
            hdrs = {"Content-Type": "application/json"}
            if token:
                hdrs["Authorization"] = f"Bearer {token}"
            c.request("POST", path, json_mod.dumps(body), hdrs)
            r = c.getresponse()
            return r.status, json_mod.loads(r.read())

        try:
            st, body = post("/admin/scale", {"op": "status"})
            assert st == 401
            st, body = post("/admin/scale", {"op": "status"},
                            "wrong-token")
            assert st == 401
            st, body = post("/admin/scale", {"op": "status"},
                            "tok-test")
            assert st == 200 and body["weights_version"] == "v1"
            assert len(body["replicas"]) == 2
            st, body = post("/admin/scale", {"op": "add"}, "tok-test")
            assert st == 200 and body["replicas"] == 3
            st, body = post("/admin/scale", {"op": "add"}, "tok-test")
            assert st == 409 \
                and body["reason"] == "scale_out_past_cap"
            st, body = post("/admin/scale",
                            {"op": "drain", "replica": 1}, "tok-test")
            assert st == 200
            st, body = post("/admin/scale",
                            {"op": "undrain", "replica": 1},
                            "tok-test")
            assert st == 200 and body["ok"] is True
            st, body = post("/admin/scale",
                            {"op": "remove", "replica": 2}, "tok-test")
            assert st == 200 and body["replicas"] == 2
            st, body = post("/admin/scale", {"op": "sideways"},
                            "tok-test")
            assert st == 409 and body["reason"] == "unknown_op"
            # a non-object JSON body is a 400, never a dropped
            # connection (the handler must answer every request)
            st, body = post("/admin/scale", "not-an-object",
                            "tok-test")
            assert st == 400 and "error" in body
            # the reshaped fleet still serves through the front door,
            # and the HTTP body carries the stamping generation
            st, body = post("/generate", {"codes": [3, 7, 9],
                                          "seed": 11})
            assert st == 200 and body["status"] == "ok"
            assert body["weights_version"] == "v1"
            assert server.health()["weights_version"] == "v1"
        finally:
            httpd.shutdown()
            server.close()


@pytest.mark.faults
class TestProcessElasticUpgrade:
    def test_upgrade_drain_sigkill_zero_loss_process(self, bundle,
                                                     bundle_v2):
        """The 'SIGKILL of the draining replica mid-upgrade' fault row
        (process isolation): a real -9 lands on replica 0's child just
        as rolling_upgrade starts draining it — the planned drain races
        an unplanned death, the shadow reclaim still loses nothing, the
        upgrade completes replica-by-replica, and every result is
        byte-identical per its stamped generation."""
        params, vae_params = bundle
        params2, _ = bundle_v2
        queue = RequestQueue(max_depth=32)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, isolation="process",
                        weights_version="v1",
                        bringup_policy=FAST_BRINGUP)
        try:
            wait_all_ready(rs)
            handles = [queue.submit(r) for r in REQS[:3]]
            for _ in range(20):
                rs.step_once()      # get work onto the children
            with faults.injected(upgrade_drain_sigkill_replica=0):
                record = rs.rolling_upgrade(
                    version="v2", params=params2,
                    canary_codes=[(1, 2)], canaries=1,
                    replica_timeout_s=240)
            assert len(record["replicas"]) == 2
            # the kill was real: the drained replica's decoded exit
            # says SIGKILL (it died on its own, before our fence)
            assert "SIGKILL" in rs.replicas[0].last_exit
            rs.run_until_idle(max_steps=500_000)
            for h, r in zip(handles, REQS[:3]):
                res = h.result(timeout=60)
                assert res.status == OK, (res.status, res.reason)
                p = params if res.weights_version == "v1" else params2
                np.testing.assert_array_equal(
                    np.asarray(res.tokens),
                    versioned_reference(p, vae_params, r))
            assert rs.weights_version == "v2"
            # and the upgraded fleet serves v2 byte-identically
            h = queue.submit(REQS[4])
            rs.run_until_idle(max_steps=500_000)
            res = h.result(timeout=60)
            assert res.weights_version == "v2"
            np.testing.assert_array_equal(
                np.asarray(res.tokens),
                versioned_reference(params2, vae_params, REQS[4]))
        finally:
            rs.close()
