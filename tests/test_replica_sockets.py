"""The socket transport's own failure modes and remotely attached workers
(PR 10, cut out of tests/test_replica.py; tests/replica_set.py holds what
the files share): reset, torn, duplicated, reordered and stalled frames
fence typed and lose nothing; workers launched by a command or started by
hand attach, die and are replaced."""

import time

import pytest

from dalle_pytorch_tpu.resilience import faults
from dalle_pytorch_tpu.serve import OK, RequestQueue
from dalle_pytorch_tpu.serve.replica import RUNNING, ReplicaSet
from replica_set import assert_all_token_exact, wait_all_ready
from tiny_model import bundle, CFG, FAST_BRINGUP, _no_leaked_plan  # noqa: F401
from tiny_model import MORE_REQS as REQS


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
