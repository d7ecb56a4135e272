"""The replica set over child processes (ISSUE 8, cut out of
tests/test_replica.py; tests/replica_set.py holds what the files share):
the process set serving token-exact, and a child killed for real over
the pipe transport (the socket leg: tests/test_replica_hard_kill_socket.py)."""

import time

import numpy as np
import pytest

from dalle_pytorch_tpu.serve import OK, RequestQueue
from dalle_pytorch_tpu.serve.replica import DRAINED, ReplicaSet
from replica_set import ProcessHardKill, assert_all_token_exact, wait_all_ready
from tiny_model import (bundle, CFG, FAST_BRINGUP,  # noqa: F401
                        _no_leaked_plan, reference_tokens)
from tiny_model import MORE_REQS as REQS


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
            # a child's RSS comes with its snapshots: wait for one from each
            deadline = time.perf_counter() + 60.0
            while True:
                health = server.health()
                if all(rep["rss_mb"] > 0 for rep in health["replicas"]):
                    break
                assert time.perf_counter() < deadline, health
                time.sleep(0.05)
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


@pytest.mark.parametrize("transport", ["pipe"])
class TestProcessHardKill(ProcessHardKill):
    """The pipe leg."""
