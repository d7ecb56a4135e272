"""Live KV page migration tests (ISSUE 16 acceptance criteria).

The load-bearing contract: a request moved MID-STREAM between engines
keeps every token it already decoded, and the tokens it emits on the
target are BYTE-IDENTICAL to the undisturbed single-engine run — the
deterministic (rng row, position) sampling makes the continuation
exact, so migration is replay minus the re-decode. Covered here:

  * export_slot -> import_slot byte-identity across the engine matrix
    (K in {1, 8} x gather/kernel paged attention x fp32/int8-KV), and
    a guided CFG pair whose cond+uncond slots move atomically;
  * every typed ``MigrationError`` precondition (dense KV, unknown
    request, page-size / quantization / weights-version mismatch, no
    free target slots) leaves both engines untouched, and a corrupt
    snapshot is discarded WHOLE by the target (pages released) with
    the intact payload still importable afterwards;
  * the replica-set surface: operator drain and scale-in migrate
    in-flight work to survivors (counters, ``serve_migrated`` events,
    flight-ring spans), prefill->decode role handoff, rolling-upgrade
    drains pinned to same-version targets, and the crash-mid-transfer
    / target-reject faults falling back to deterministic replay with
    zero requests lost;
  * THE acceptance drive: a process+socket 2-replica set where
    scale-in migrates a request >= 256 tokens into its decode and the
    survivor finishes it byte-identical.

Fault-injected tests are marked ``faults``. All CPU, tiny model
(total_len 72 — long enough to export mid-stream under K=8's
double-buffered pipeline; the acceptance drive uses total_len 408).
"""

import copy
import functools
import time

import jax
import numpy as np
import pytest

from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.models import vae as V
from dalle_pytorch_tpu.resilience import faults
from dalle_pytorch_tpu.serve import OK, Request, RequestQueue
from dalle_pytorch_tpu.serve.engine import Engine, MigrationError
from dalle_pytorch_tpu.serve.replica import (DRAINED, RUNNING,
                                             ReplicaSet, ScaleError)
import tiny_model
from tiny_model import (FAST_BRINGUP, _no_leaked_plan,  # noqa: F401
                        bundle)
from tiny_model import MORE_REQS as REQS

# 64 image tokens (total_len 72): wide enough that an export observed
# at >= 8 emitted tokens can never race the fused pipeline's in-flight
# chunks (at most 2 x K = 16 more) past completion
VCFG = V.VAEConfig(image_size=32, num_tokens=32, codebook_dim=16,
                   num_layers=2, hidden_dim=8)
CFG = D.DALLEConfig(dim=16, depth=2, vae=VCFG, num_text_tokens=50,
                    text_seq_len=8, heads=2, dim_head=8)
reference_tokens = functools.partial(tiny_model.reference_tokens, cfg=CFG)

def assert_all_token_exact(params, vae_params, handles, reqs):
    for h, r in zip(handles, reqs):
        res = h.result(timeout=30)
        assert res.status == OK, (r, res.status, res.reason)
        np.testing.assert_array_equal(
            np.asarray(res.tokens),
            reference_tokens(params, vae_params, r))


class _Sink:
    def __init__(self):
        self.events = []

    def event(self, **rec):
        self.events.append(rec)

    def of(self, kind):
        return [e for e in self.events if e.get("kind") == kind]


def wait_all_ready(rs, timeout=180.0):
    """Drive a process set until every worker reached READY — migration
    targets must be serving before work is submitted, or the first
    replica's admission window swallows the burst."""
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


def pump_until(stepper, pred, timeout=120.0, what="condition"):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        stepper.step_once()
        if pred():
            return
    raise AssertionError(f"timed out waiting for {what}")


# -- engine-level export/import ---------------------------------------------


def _decode_to(engine: Engine, rid: int, min_tokens: int,
               handle) -> None:
    """Step ``engine`` until ``rid`` has emitted >= min_tokens — and is
    still mid-stream (a request that finished first is a test-shape
    bug, not a migration result)."""
    deadline = time.perf_counter() + 120.0
    while time.perf_counter() < deadline:
        engine.step_once()
        if handle.done():
            raise AssertionError(
                "request finished before the export window")
        if engine.progress_snapshot().get(rid, 0) >= min_tokens:
            return
    raise AssertionError("request never reached the export window")


def _migrate_mid_stream(params, req: Request, *, chunk_steps: int,
                        paged_attn: str, page_size: int,
                        quantize_cache: bool, min_tokens: int = 8):
    """The tentpole drive at engine level: decode on A, export
    mid-stream, import on B, finish on B. Returns (tokens, saved)."""
    kw = dict(num_slots=2, chunk_steps=chunk_steps, kv="paged",
              page_size=page_size, paged_attn=paged_attn,
              quantize_cache=quantize_cache)
    src = Engine(params, CFG, RequestQueue(max_depth=4), **kw)
    dst = Engine(params, CFG, RequestQueue(max_depth=4), **kw)
    h = src.queue.submit(req)
    rid = h.request.request_id
    _decode_to(src, rid, min_tokens, h)
    payload, handle = src.export_request(rid)
    assert handle is h
    saved = len(payload["emitted"])
    assert saved >= min_tokens
    # the slot is VACATED: the source neither holds nor finishes it
    assert src.find_slot(rid) is None
    dst.import_slot(payload, handle)
    dst.run_until_idle()
    res = h.result(timeout=30)
    assert res.status == OK, (res.status, res.reason)
    return np.asarray(res.tokens), saved


class TestExportImportByteIdentity:
    @pytest.mark.parametrize("quantize_cache", [False, True],
                             ids=["fp32", "int8kv"])
    @pytest.mark.parametrize("paged_attn,page_size",
                             [("gather", 4), ("kernel", 8)],
                             ids=["gather", "kernel"])
    @pytest.mark.parametrize("chunk_steps", [1, 8], ids=["K1", "K8"])
    def test_matrix_token_exact(self, bundle, chunk_steps, paged_attn,
                                page_size, quantize_cache):
        """The acceptance matrix: the migrated continuation is
        byte-identical to the undisturbed run across chunk size,
        paged-attention implementation, and KV precision."""
        params, vae_params = bundle
        req = REQS[0]
        tokens, saved = _migrate_mid_stream(
            params, req, chunk_steps=chunk_steps, paged_attn=paged_attn,
            page_size=page_size, quantize_cache=quantize_cache)
        assert saved >= 8
        np.testing.assert_array_equal(
            tokens, reference_tokens(params, vae_params, req,
                                     quantize_cache=quantize_cache))

    def test_cfg_pair_migrates_atomically(self, bundle):
        """A guided request's cond+uncond slots export in ONE payload
        and land together: the guided mix stays exact across the
        move."""
        params, vae_params = bundle
        req = Request(codes=(3, 7, 9), seed=11, cfg_scale=2.0)
        kw = dict(num_slots=2, chunk_steps=4, kv="paged", page_size=4)
        src = Engine(params, CFG, RequestQueue(max_depth=4), **kw)
        dst = Engine(params, CFG, RequestQueue(max_depth=4), **kw)
        h = src.queue.submit(req)
        rid = h.request.request_id
        _decode_to(src, rid, 8, h)
        payload, handle = src.export_request(rid)
        assert payload["uncond"] is not None
        assert payload["uncond"]["cfg_scale"] == pytest.approx(2.0)
        # both halves vacated — no orphaned shadow decodes on
        assert src.active_slots() == 0
        dst.import_slot(payload, handle)
        dst.run_until_idle()
        res = h.result(timeout=30)
        assert res.status == OK
        np.testing.assert_array_equal(
            np.asarray(res.tokens),
            reference_tokens(params, vae_params, req))


class TestMigrationPreconditions:
    def test_dense_kv_export_is_typed(self, bundle):
        params, _ = bundle
        eng = Engine(params, CFG, RequestQueue(max_depth=4),
                     num_slots=2, chunk_steps=4)
        h = eng.queue.submit(REQS[0])
        rid = h.request.request_id
        pump_until(eng, lambda: eng.find_slot(rid) is not None,
                   what="admission")
        with pytest.raises(MigrationError) as ei:
            eng.export_request(rid)
        assert ei.value.reason == "kv_dense"

    def test_unknown_request_is_typed(self, bundle):
        params, _ = bundle
        eng = Engine(params, CFG, RequestQueue(max_depth=4),
                     num_slots=2, chunk_steps=4, kv="paged",
                     page_size=4)
        with pytest.raises(MigrationError) as ei:
            eng.export_request(999_999)
        assert ei.value.reason == "not_found"

    def test_import_mismatches_are_typed_and_leave_target_idle(
            self, bundle):
        """page-size, KV-precision, and weights-version mismatches are
        all typed rejections BEFORE any page is written — the target
        engine stays untouched for every one of them."""
        params, _ = bundle
        src = Engine(params, CFG, RequestQueue(max_depth=4),
                     num_slots=2, chunk_steps=4, kv="paged",
                     page_size=4, weights_version="v1")
        h = src.queue.submit(REQS[0])
        rid = h.request.request_id
        _decode_to(src, rid, 4, h)
        payload, _handle = src.export_request(rid)
        mismatched = [
            ("page_size", dict(page_size=8)),
            ("layout", dict(page_size=4, quantize_cache=True)),
            ("weights_version", dict(page_size=4,
                                     weights_version="v2")),
        ]
        for reason, kw in mismatched:
            dst = Engine(params, CFG, RequestQueue(max_depth=4),
                         num_slots=2, chunk_steps=4, kv="paged",
                         weights_version=kw.pop("weights_version",
                                                "v1"), **kw)
            free0 = dst.alloc.free
            with pytest.raises(MigrationError) as ei:
                dst.import_slot(copy.deepcopy(payload))
            assert ei.value.reason == reason
            assert dst.active_slots() == 0
            assert dst.alloc.free == free0

    def test_full_target_is_typed(self, bundle):
        params, _ = bundle
        src = Engine(params, CFG, RequestQueue(max_depth=4),
                     num_slots=2, chunk_steps=4, kv="paged",
                     page_size=4)
        h = src.queue.submit(REQS[0])
        rid = h.request.request_id
        _decode_to(src, rid, 4, h)
        payload, _handle = src.export_request(rid)
        dst = Engine(params, CFG, RequestQueue(max_depth=4),
                     num_slots=1, chunk_steps=4, kv="paged",
                     page_size=4)
        own = dst.queue.submit(REQS[1])
        pump_until(dst,
                   lambda: dst.find_slot(own.request.request_id)
                   is not None, what="target admission")
        with pytest.raises(MigrationError) as ei:
            dst.import_slot(copy.deepcopy(payload))
        assert ei.value.reason == "target_slots"

    def test_corrupt_snapshot_discarded_whole_then_intact_lands(
            self, bundle):
        """A torn page mid-install must not wedge the target: the
        partial import is discarded WHOLE (grants released, block
        table zeroed), and the intact payload still imports and
        finishes byte-identical afterwards."""
        params, vae_params = bundle
        kw = dict(num_slots=2, chunk_steps=4, kv="paged", page_size=4)
        src = Engine(params, CFG, RequestQueue(max_depth=4), **kw)
        dst = Engine(params, CFG, RequestQueue(max_depth=4), **kw)
        h = src.queue.submit(REQS[0])
        rid = h.request.request_id
        _decode_to(src, rid, 8, h)
        payload, handle = src.export_request(rid)
        torn = copy.deepcopy(payload)
        page0 = torn["cond"]["pages"][0]
        first = next(iter(page0))
        page0[first]["data"] = page0[first]["data"][: len(
            page0[first]["data"]) // 2]
        free0 = dst.alloc.free
        with pytest.raises(MigrationError) as ei:
            dst.import_slot(torn, handle)
        assert ei.value.reason == "transfer"
        assert dst.active_slots() == 0
        assert dst.alloc.free == free0
        dst.import_slot(payload, handle)
        dst.run_until_idle()
        res = h.result(timeout=30)
        assert res.status == OK
        np.testing.assert_array_equal(
            np.asarray(res.tokens),
            reference_tokens(params, vae_params, REQS[0]))


# -- replica-set surface ------------------------------------------------------


class TestSetMigration:
    def test_drain_migrates_in_flight_mid_stream(self, bundle):
        """Operator drain prefers the live move: the drained replica's
        in-flight request lands on the survivor with its decoded
        prefix intact — counted, evented, and token-exact."""
        params, vae_params = bundle
        sink = _Sink()
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, kv="paged", page_size=4,
                        metrics=sink, bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS[:2]]
        pump_until(
            rs, lambda: any(
                v >= 2 for v in
                rs.replicas[0].engine.progress_snapshot().values()),
            what="mid-stream work on replica 0")
        moved = rs.drain_replica(0)
        assert moved >= 1
        assert rs.replicas[0].state == DRAINED
        assert rs.migrations >= 1
        assert rs.migrated_tokens_saved >= 2
        assert rs.migrate_fallbacks == 0
        migrated = sink.of("serve_migrated")
        assert migrated and migrated[0]["src"] == 0
        assert migrated[0]["tokens_saved"] >= 2
        rs.run_until_idle()
        assert_all_token_exact(params, vae_params, handles, REQS[:2])
        stats = rs.stats()
        assert stats["migrations"] >= 1
        assert stats["migrated_tokens_saved"] >= 2
        assert all("role" in rec for rec in stats["per_replica"])
        # distinct-delivered-tokens accounting survives the move: the
        # prefix stays credited at the source, the continuation at the
        # target — no token counted twice, none dropped
        assert stats["tokens_decoded"] == sum(
            CFG.seq_len - len(r.codes) for r in REQS[:2])

    def test_scale_in_migrates_and_records_flight_span(self, bundle):
        """remove_replica(drain=True) live-migrates before the fence;
        the ``serve_scale_in`` event carries the migrated count and
        the set flight ring shows the migration."""
        params, vae_params = bundle
        sink = _Sink()
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, kv="paged", page_size=4,
                        metrics=sink, bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS[:2]]
        pump_until(
            rs, lambda: any(
                v >= 2 for v in
                rs.replicas[0].engine.progress_snapshot().values()),
            what="mid-stream work on replica 0")
        decoded = sum(
            rs.replicas[0].engine.progress_snapshot().values())
        rs.remove_replica(0, drain=True)
        scale_in = sink.of("serve_scale_in")
        assert scale_in and scale_in[0]["migrated"] >= 1
        assert rs.migrations >= 1
        # what the move is for: at least half of what a replay from
        # token zero would decode again arrives already decoded
        assert rs.migrated_tokens_saved >= max(1, decoded // 2)
        assert any(e.get("kind") == "serve_migrated"
                   for e in rs.flight.tail(64))
        rs.run_until_idle()
        assert_all_token_exact(params, vae_params, handles, REQS[:2])

    def test_replay_only_scale_in_skips_migration(self, bundle):
        """drain=False names the operator's replay-only intent: zero
        migrations, the fence's deterministic replay still loses
        nothing."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, kv="paged", page_size=4,
                        bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS[:2]]
        pump_until(
            rs, lambda: any(
                v >= 2 for v in
                rs.replicas[0].engine.progress_snapshot().values()),
            what="mid-stream work on replica 0")
        rs.remove_replica(0, drain=False)
        assert rs.migrations == 0
        rs.run_until_idle()
        assert_all_token_exact(params, vae_params, handles, REQS[:2])


class TestReplicaRoles:
    def test_role_validation_is_typed(self, bundle):
        params, _ = bundle
        with pytest.raises(ValueError, match="role"):
            ReplicaSet(params, CFG, RequestQueue(max_depth=4),
                       replicas=2, kv="paged", page_size=4,
                       roles=("prefill", "bogus"))
        with pytest.raises(ValueError, match="roles names"):
            ReplicaSet(params, CFG, RequestQueue(max_depth=4),
                       replicas=2, kv="paged", page_size=4,
                       roles=("prefill",))
        # disaggregated roles ship KV pages; dense has none to ship
        with pytest.raises(ValueError, match="paged"):
            ReplicaSet(params, CFG, RequestQueue(max_depth=4),
                       replicas=2, roles=("prefill", "decode"))

    def test_add_replica_role_rejections_are_typed(self, bundle):
        params, _ = bundle
        rs = ReplicaSet(params, CFG, RequestQueue(max_depth=4),
                        replicas=1, num_slots=2, chunk_steps=4,
                        bringup_policy=FAST_BRINGUP)
        with pytest.raises(ScaleError) as ei:
            rs.add_replica(role="bogus")
        assert ei.value.record["reason"] == "unknown_role"
        with pytest.raises(ScaleError) as ei:
            rs.add_replica(role="decode")
        assert ei.value.record["reason"] == "roles_need_paged_kv"

    def test_prefill_to_decode_handoff(self, bundle):
        """Disaggregated serving: the prefill replica admits + prefills
        and hands warm requests to the decode replica mid-stream; the
        decode replica finishes them token-exact."""
        params, vae_params = bundle
        sink = _Sink()
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, kv="paged", page_size=4,
                        roles=("prefill", "decode"), metrics=sink,
                        bringup_policy=FAST_BRINGUP)
        # a burst that FITS the prefill replica's slots: admission
        # prefers prefill, so both requests land there and the sweep
        # hands them to the (idle) decode replica (an overflow burst
        # would spill straight to the decode replica — the preference
        # is routing, not a wall)
        handles = [queue.submit(r) for r in REQS[:2]]
        pump_until(rs, lambda: rs.migrations >= 1, timeout=120.0,
                   what="a prefill->decode handoff")
        rs.run_until_idle()
        assert_all_token_exact(params, vae_params, handles, REQS[:2])
        moved = sink.of("serve_migrated")
        assert moved and all(e["reason"] == "prefill_handoff"
                             and e["dst"] == 1 for e in moved)
        # the decode replica actually finished migrated work
        assert rs.replicas[1].engine.completed >= 1
        roles = [rec["role"]
                 for rec in rs.stats()["per_replica"]]
        assert roles == ["prefill", "decode"]


class TestUpgradeMigration:
    def test_rolling_upgrade_drain_migrates_version_pinned(
            self, bundle):
        """The upgrade's drain live-migrates to SAME-version survivors
        (tokens are byte-identical per weight generation only); every
        request finishes token-exact against the generation that
        stamped its result."""
        params, vae_params = bundle
        params2 = D.dalle_init(jax.random.PRNGKey(42), CFG,
                               vae_params)
        by_version = {"v1": params, "v2": params2}
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, kv="paged", page_size=4,
                        weights_version="v1",
                        bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS[:2]]
        pump_until(
            rs, lambda: any(
                v >= 2 for v in
                rs.replicas[0].engine.progress_snapshot().values()),
            what="mid-stream work on replica 0")
        record = rs.rolling_upgrade(version="v2", params=params2,
                                    canary_codes=[(1, 2)], canaries=1,
                                    replica_timeout_s=120.0)
        assert sum(int(e.get("migrated", 0))
                   for e in record["replicas"]) >= 1
        assert rs.migrations >= 1
        rs.run_until_idle()
        for h, r in zip(handles, REQS[:2]):
            res = h.result(timeout=30)
            assert res.status == OK, (res.status, res.reason)
            np.testing.assert_array_equal(
                np.asarray(res.tokens),
                reference_tokens(by_version[res.weights_version],
                                 vae_params, r))


class TestMigrationFaults:
    pytestmark = pytest.mark.faults

    def test_target_reject_falls_back_to_replay(self, bundle):
        """The target refusing the import (fault: allocation failure)
        must cost nothing: typed fallback, deterministic replay on the
        survivor, zero loss, and the un-credit keeps distinct-token
        accounting exact."""
        params, vae_params = bundle
        sink = _Sink()
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, kv="paged", page_size=4,
                        metrics=sink, bringup_policy=FAST_BRINGUP)
        handles = [queue.submit(r) for r in REQS[:2]]
        pump_until(
            rs, lambda: any(
                v >= 2 for v in
                rs.replicas[0].engine.progress_snapshot().values()),
            what="mid-stream work on replica 0")
        with faults.injected(migrate_reject_target=1):
            rs.drain_replica(0)
        assert rs.migrations == 0
        assert rs.migrate_fallbacks >= 1
        fb = sink.of("serve_migrate_fallback")
        assert fb and fb[0]["reason"] == "target_pages"
        rs.run_until_idle()
        assert_all_token_exact(params, vae_params, handles, REQS[:2])
        stats = rs.stats()
        assert stats["completed"] == 2
        assert stats["tokens_decoded"] == sum(
            CFG.seq_len - len(r.codes) for r in REQS[:2])

    @pytest.mark.parametrize("transport", ["pipe", "socket"])
    def test_crash_source_mid_transfer_falls_back(self, bundle,
                                                  transport):
        """SIGKILL the source child exactly at the transfer point: the
        export dies, the fallback replays from the parent's shadow —
        zero requests lost, tokens byte-identical."""
        params, vae_params = bundle
        sink = _Sink()
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, kv="paged", page_size=4,
                        isolation="process", transport=transport,
                        metrics=sink, bringup_policy=FAST_BRINGUP)
        try:
            wait_all_ready(rs)
            handles = [queue.submit(r) for r in REQS]
            # in-flight work on child 0 (the parent's shadow is the
            # authority; the tiny model decodes faster than a heartbeat
            # interval, so the progress mirror may never show a
            # mid-stream value — the crash fires at the transfer point
            # regardless of depth)
            pump_until(
                rs, lambda: any(
                    not h.done() for h in
                    rs.replicas[0].engine.shadow.values()),
                what="in-flight work on child 0")
            with faults.injected(migrate_crash_source_at_transfer=0):
                rs.remove_replica(0, drain=True)
            assert rs.migrations == 0
            assert rs.migrate_fallbacks >= 1
            fb = sink.of("serve_migrate_fallback")
            assert fb and fb[0]["reason"] == "source_dead"
            rs.run_until_idle()
            assert_all_token_exact(params, vae_params, handles, REQS)
            assert rs.stats()["completed"] == len(REQS)
        finally:
            rs.close()


# -- THE acceptance drive -----------------------------------------------------

# 1024 image tokens (total_len 1032): deep enough that a request can
# be observed >= 256 tokens into decode with a wide window left before
# completion — the scale-in's migration must save >= 256 tokens
VCFG_BIG = V.VAEConfig(image_size=128, num_tokens=32, codebook_dim=16,
                       num_layers=2, hidden_dim=8)
CFG_BIG = D.DALLEConfig(dim=16, depth=2, vae=VCFG_BIG,
                        num_text_tokens=50, text_seq_len=8, heads=2,
                        dim_head=8)


@pytest.fixture(scope="module")
def bundle_big():
    key = jax.random.PRNGKey(0)
    vae_params = V.vae_init(jax.random.fold_in(key, 1), VCFG_BIG)
    params = D.dalle_init(key, CFG_BIG, vae_params)
    return params, vae_params


class TestAcceptanceDeepMigration:
    def test_socket_scale_in_migrates_256_deep_token_exact(
            self, bundle_big):
        """ISSUE 16 acceptance: a process+socket 2-replica set where
        ``remove_replica`` migrates a request >= 256 tokens into its
        decode; the survivor finishes it BYTE-IDENTICAL to the
        undisturbed run and the set counts >= 256 tokens saved."""
        params, vae_params = bundle_big
        reqs = [Request(codes=(3, 7, 9), seed=11),
                Request(codes=(5, 2), seed=23)]
        queue = RequestQueue(max_depth=8)
        rs = ReplicaSet(params, CFG_BIG, queue, replicas=2,
                        num_slots=2, chunk_steps=8, kv="paged",
                        page_size=8, isolation="process",
                        transport="socket",
                        bringup_policy=FAST_BRINGUP)
        try:
            wait_all_ready(rs)
            handles = [queue.submit(r) for r in reqs]
            pump_until(
                rs, lambda: any(
                    v >= 256 for v in
                    rs.replicas[0].engine.progress.values()),
                timeout=300.0,
                what="a request 256 tokens into decode on child 0")
            saved0 = rs.migrated_tokens_saved
            rs.remove_replica(0, drain=True)
            assert rs.migrations >= 1
            assert rs.migrated_tokens_saved - saved0 >= 256
            rs.run_until_idle()
            for h, r in zip(handles, reqs):
                res = h.result(timeout=60)
                assert res.status == OK, (res.status, res.reason)
                np.testing.assert_array_equal(
                    np.asarray(res.tokens),
                    reference_tokens(params, vae_params, r,
                                     cfg=CFG_BIG))
        finally:
            rs.close()
