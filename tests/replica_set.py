"""What the replica set's test files share (tests/test_replica.py,
tests/test_replica_process.py, tests/test_replica_hard_kill_socket.py,
tests/test_replica_sockets.py, tests/test_replica_elastic.py): the tiny
configuration, the requests, the undisturbed single-replica reference,
the fixtures, and the hard-kill cases, which run once a frame transport
in two files. A helper module, not a test file. (One file held them all
and was a worker's whole chain, 420-430 s of a 1240 s run at PR 44: a
worker takes a file, and every process case starts child interpreters.)"""

import time

import numpy as np
import pytest

from dalle_pytorch_tpu.resilience import faults
from dalle_pytorch_tpu.serve import OK, RequestQueue
from dalle_pytorch_tpu.serve.replica import RUNNING, ReplicaSet
from tiny_model import CFG, FAST_BRINGUP, reference_tokens
from tiny_model import MORE_REQS as REQS


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


class ProcessHardKill:
    """THE acceptance criterion of the process-isolation PR: a child
    replica killed for real — SIGKILL, SIGSEGV, a crash, an OOM kill,
    or a corrupted pipe — mid-decode loses ZERO requests; everything it
    held replays byte-identically on the survivor (reclaimed from the
    parent's shadow bookkeeping, never from the corpse), aggregate
    counters keep counting distinct delivered tokens, and the dead
    replica rejoins routing through the circuit-breaker backoff.

    Run over BOTH frame transports (PR 10; ``transport`` is parametrized
    where this is subclassed, a file a transport): the socket leg runs
    the identical suite over dial-back TCP workers, because the zero-loss
    contract must hold when the frames cross a network, not just a pipe.
    Socket-only failure modes (reset, torn frame, stalled link) live in
    TestSocketFaults."""

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
