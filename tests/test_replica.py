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

import numpy as np
import pytest

from dalle_pytorch_tpu.analysis import guards
from dalle_pytorch_tpu.resilience import faults
from dalle_pytorch_tpu.serve import (CANCELLED, DEADLINE_EXCEEDED, OK,
                                     QueueFull, Request, RequestQueue)
from dalle_pytorch_tpu.serve.replica import (BROKEN, DRAINED, RUNNING,
                                             ReplicaSet)
from replica_set import assert_all_token_exact
from tiny_model import (bundle, CFG, FAST_BRINGUP,  # noqa: F401
                        _no_leaked_plan, reference_tokens)
from tiny_model import MORE_REQS as REQS


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
            deadline = time.perf_counter() + 30.0
            while "replica_hang" not in faults._fired:  # replica 0 asleep
                assert time.perf_counter() < deadline   # mid-loop
                time.sleep(0.01)
            t0 = time.perf_counter()
            server.close(timeout=1.0)
            assert time.perf_counter() - t0 < 3.0
            for h in handles:
                res = h.result(timeout=1)   # never strands: ok (done
                assert res.status in (OK, CANCELLED)  # before close)
                #                                 or typed cancelled


    def test_clearing_the_plan_releases_the_hung_thread(self):
        """The injected hang lasts ``replica_hang_s`` under a set plan and
        ends with the plan: the thread it holds does not sleep on into
        whatever its worker runs next."""
        with faults.injected(fault_replica=0, replica_hang_at_chunk=0,
                             replica_hang_s=60.0):
            hung = threading.Thread(target=faults.on_replica_chunk,
                                    args=(0, 0), daemon=True)
            hung.start()
            hung.join(0.3)      # (the expected path: it must still hang)
            assert hung.is_alive()
        hung.join(5.0)
        assert not hung.is_alive()


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
