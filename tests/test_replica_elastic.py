"""The elastic fleet (ISSUE 14, cut out of tests/test_replica.py;
tests/replica_set.py holds what the files share): runtime scale-out/in,
rolling weight hot-swap, version-pinned replay, the autoscaler policy
loop, the HOL hand-back, the admin endpoint, and an upgrade drain under
SIGKILL over child processes."""

import threading
import time

import jax
import numpy as np
import pytest

from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.resilience import faults
from dalle_pytorch_tpu.serve import OK, Request, RequestQueue
from dalle_pytorch_tpu.serve.replica import (BROKEN, DRAINED, RETIRED, RUNNING,
                                             ReplicaSet, ReplayVersionMismatch,
                                             ScaleError, UpgradeAborted)
from replica_set import assert_all_token_exact, wait_all_ready
from tiny_model import (bundle, CFG, FAST_BRINGUP,  # noqa: F401
                        _no_leaked_plan, reference_tokens)
from tiny_model import MORE_REQS as REQS


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
                reference_tokens(p, vae_params, r))
        # the fleet is promoted: fresh traffic is v2, byte-identical
        post = queue.submit(REQS[0])
        rs.run_until_idle()
        res = post.result(timeout=10)
        assert res.weights_version == "v2"
        np.testing.assert_array_equal(
            np.asarray(res.tokens),
            reference_tokens(params2, vae_params, REQS[0]))
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
            reference_tokens(params2, vae_params, REQS[0]))

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
            reference_tokens(params, vae_params, REQS[0]))
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
                reference_tokens(params, vae_params, r))

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
                reference_tokens(params2, vae_params, r))


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
                    reference_tokens(p, vae_params, r))
            assert rs.weights_version == "v2"
            # and the upgraded fleet serves v2 byte-identically
            h = queue.submit(REQS[4])
            rs.run_until_idle(max_steps=500_000)
            res = h.result(timeout=60)
            assert res.weights_version == "v2"
            np.testing.assert_array_equal(
                np.asarray(res.tokens),
                reference_tokens(params2, vae_params, REQS[4]))
        finally:
            rs.close()
