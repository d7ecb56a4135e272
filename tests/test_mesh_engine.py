"""Mesh-sharded serving engine tests (ISSUE 11 acceptance criteria).

The load-bearing one is BYTE-IDENTITY: for the same params / prompts /
seeds / sampling knobs, a ``MeshEngine`` pjit-sharded over a multi-device
mesh emits tokens identical to the single-device ``Engine`` (itself
pinned token-identical to ``generate_images`` by tests/test_serve.py) —
across fused-chunk sizes K, dense AND paged KV, int8-KV, and a
mid-stream join under ``guards.no_transfers`` with ``decode_traces ==
1``. The serve partition rules (parallel/serve_specs.py) make this hold
BY CONSTRUCTION — no contracted dimension is ever sharded, so every
collective is data movement, never a float reassociation — and these
tests are the tripwire for anything (a GSPMD propagation change, a new
spec rule) that would break it.

Plus the composition contract: a ``ReplicaSet`` whose replicas are mesh
SLICES fails over with zero loss and byte-identical replay through the
unchanged supervision logic, and the checkpoint-path attach spec loads/
validates locally with typed failure.

Runs on the forced multi-device CPU platform tests/conftest.py sets up
(``--xla_force_host_platform_device_count=8`` — the standard JAX
substitute for a pod). Tiny model (total_len 24): depth 2 and heads 2
both divide the 2-device mesh, so params AND the KV store genuinely
shard.
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.analysis import guards
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.parallel import serve_specs as SS
from dalle_pytorch_tpu.resilience import faults
from dalle_pytorch_tpu.serve import OK, Request, RequestQueue
from dalle_pytorch_tpu.serve.engine import Engine
from tiny_model import (CFG, FAST_BRINGUP, REQS, _no_leaked_plan,  # noqa: F401
                        bundle)
from dalle_pytorch_tpu.serve.mesh_engine import (MeshEngine,
                                                 MeshPagedAttnError,
                                                 hbm_report)
from dalle_pytorch_tpu.serve.replica import ReplicaSet

def mesh_devices(n=2):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices (conftest forces 8 on CPU)")
    return tuple(devs[:n])


# single-device reference tokens, memoized per engine config: the mesh
# engine's contract is equality with the single-device ENGINE (itself
# pinned to generate_images by test_serve), so the reference is the
# cheap one-chip run, not a generate_images resample per test
_REF: dict = {}


def engine_tokens(params, engine_cls, *, K=8, reqs=REQS, **kw):
    queue = RequestQueue(max_depth=16)
    engine = engine_cls(params, CFG, queue, num_slots=2, chunk_steps=K,
                        **kw)
    handles = [queue.submit(r) for r in reqs]
    engine.run_until_idle()
    toks = []
    for h in handles:
        res = h.result(timeout=60)
        assert res.status == OK, (res.status, res.reason)
        toks.append(np.asarray(res.tokens))
    return engine, toks


def single_device_tokens(params, *, K=8, reqs=REQS, **kw):
    key = (K, len(reqs), tuple(sorted(kw.items())))
    if key not in _REF:
        _, toks = engine_tokens(params, Engine, K=K, reqs=reqs, **kw)
        _REF[key] = toks
    return _REF[key]


class TestMeshByteIdentity:
    @pytest.mark.parametrize("K", [1, 8])
    def test_dense_tokens_byte_identical(self, bundle, K):
        """THE acceptance criterion: same requests, same seeds — the
        2-device mesh engine's tokens equal the single-device engine's
        byte for byte, with the fused decode program compiled exactly
        once for the engine's life."""
        params, _ = bundle
        ref = single_device_tokens(params, K=K)
        engine, toks = engine_tokens(params, MeshEngine, K=K,
                                     devices=mesh_devices())
        assert engine.decode_traces == 1
        assert engine.params_sharded and engine.kv_sharded
        for a, b in zip(ref, toks):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("groups", [1, 2])
    @pytest.mark.parametrize("K", [1, 8])
    def test_paged_tokens_byte_identical(self, bundle, monkeypatch, K,
                                         groups):
        """Paged KV on the mesh: the page pool shards along heads, the
        block tables stay host-authoritative and replicated, and the
        gather oracle rides the per-shard slices — tokens unchanged,
        also where the layer's read runs a slot group at a time (the
        slices are along slots, ``out_sync`` sees the whole output)."""
        from dalle_pytorch_tpu.ops import decode as decode_ops
        params, _ = bundle
        kw = dict(kv="paged", page_size=8)
        ref = single_device_tokens(params, K=K, **kw)
        if groups > 1:      # the rule's floor: one slot a group
            monkeypatch.setattr(decode_ops, "_VIEW_VMEM_BYTES", 1)
        engine, toks = engine_tokens(params, MeshEngine, K=K,
                                     devices=mesh_devices(), **kw)
        assert engine.decode_traces == 1
        assert engine.kv_sharded
        # a page is whole rows (ps, heads * dh): a shard holds its heads'
        # columns of every row, and the read there is per head
        from jax.sharding import PartitionSpec as P
        tcfg = CFG.transformer
        for buf in engine.cache.values():
            assert buf.shape[2:] == (8, tcfg.heads * tcfg.dim_head)
            assert buf.sharding.spec == P(None, None, None, "mp")
            assert buf.sharding.shard_shape(buf.shape)[-1] == \
                tcfg.heads * tcfg.dim_head // len(mesh_devices())
        assert engine.stats()["kv_view_groups"] == groups
        for a, b in zip(ref, toks):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("kw", [dict(quantize_cache=True),
                                    dict(kv="paged", page_size=8,
                                         quantize_cache=True)])
    def test_int8_kv_tokens_byte_identical(self, bundle, kw):
        """int8-KV composes: the quantized rows and their f32 scale
        pages shard along heads together, and quantize/dequantize are
        per-row elementwise — still byte-identical."""
        params, _ = bundle
        ref = single_device_tokens(params, K=8, **kw)
        engine, toks = engine_tokens(params, MeshEngine, K=8,
                                     devices=mesh_devices(), **kw)
        assert engine.decode_traces == 1
        for a, b in zip(ref, toks):
            np.testing.assert_array_equal(a, b)

    def test_mid_stream_join_transfer_clean(self, bundle):
        """The steady-state transfer discipline survives sharding: full
        chunks, a mid-stream join (admission while another slot is
        mid-decode), and the emit-ring harvest all run under
        ``guards.no_transfers`` — GSPMD's collectives are device-side,
        and the only host traffic is the engine's explicit puts/gets.
        Tokens stay byte-identical through the join."""
        params, _ = bundle
        ref = single_device_tokens(params, K=8, kv="paged", page_size=8)
        queue = RequestQueue(max_depth=16)
        engine = MeshEngine(params, CFG, queue, num_slots=2,
                            chunk_steps=8, devices=mesh_devices(),
                            kv="paged", page_size=8)
        h0 = queue.submit(REQS[0])
        engine.step_once()              # admit + first chunk (compiles)
        engine.step_once()
        with guards.no_transfers():
            h2 = queue.submit(REQS[2])  # joins while slot 0 is mid-decode
            for _ in range(4):
                engine.step_once()
        engine.run_until_idle()
        assert engine.decode_traces == 1
        np.testing.assert_array_equal(
            np.asarray(h0.result(timeout=60).tokens), ref[0])
        np.testing.assert_array_equal(
            np.asarray(h2.result(timeout=60).tokens), ref[2])

    def test_prefix_cache_warm_hit_byte_identical(self, bundle):
        """The prefix cache composes with the head-sharded pool: a warm
        hit on the mesh — shared pages mapped into the replicated block
        tables, the COW boundary fork through the sharding-pinned pool
        update, first token from the cached (replicated) h_last row —
        emits tokens byte-identical to the single-device prefix-blind
        engine, with one decode trace and a guided pair riding along."""
        params, _ = bundle
        p8 = (4, 1, 2, 3, 5, 6, 7, 2)
        reqs = [Request(codes=p8, seed=31), Request(codes=p8, seed=37),
                Request(codes=p8, seed=41, cfg_scale=1.5)]
        kw = dict(kv="paged", page_size=8)
        _, ref = engine_tokens(params, Engine, reqs=reqs, **kw)
        engine, toks = engine_tokens(params, MeshEngine,
                                     devices=mesh_devices(),
                                     prefix_cache=True, reqs=reqs, **kw)
        assert engine.decode_traces == 1
        assert engine.kv_sharded
        assert engine.prefix_hits >= 1    # the same-prompt fan-out hit
        assert engine.cfg_pairs == 1
        for a, b in zip(ref, toks):
            np.testing.assert_array_equal(a, b)


class TestMeshSurfaceAndSpecs:
    def test_kernel_attn_gated_typed(self, bundle):
        """paged_attn='kernel' on a mesh is a typed init-time rejection
        (the Pallas custom call cannot be GSPMD-partitioned), never an
        opaque partitioner failure inside the first chunk."""
        params, _ = bundle
        queue = RequestQueue(max_depth=4)
        with pytest.raises(MeshPagedAttnError):
            MeshEngine(params, CFG, queue, devices=mesh_devices(),
                       kv="paged", page_size=8, paged_attn="kernel")
        with pytest.raises(MeshPagedAttnError):
            ReplicaSet(params, CFG, RequestQueue(max_depth=4),
                       replicas=2, devices_per_replica=2,
                       kv="paged", page_size=8, paged_attn="kernel")

    def test_stats_and_hbm_surface(self, bundle):
        """/stats mesh satellite: mesh_shape, devices_per_replica, and
        the per-shard residency — a 2-way heads-sharded pool's per-shard
        bytes are exactly half the global pool."""
        params, _ = bundle
        queue = RequestQueue(max_depth=4)
        engine = MeshEngine(params, CFG, queue, num_slots=2,
                            devices=mesh_devices(), kv="paged",
                            page_size=8)
        st = engine.stats()
        assert st["mesh_shape"] == {"mp": 2}
        assert st["devices_per_replica"] == 2
        assert st["kv_hbm_bytes_per_shard"] * 2 == st["kv_hbm_bytes"]
        rep = hbm_report(engine)
        assert rep["kv_hbm_bytes_per_shard"] * 2 == rep["kv_hbm_bytes"]
        # depth-sharded stacks + vocab-sharded tables: strictly under a
        # full replica, strictly over the impossible total/2 (some
        # leaves — layernorms, positional tables — stay replicated)
        assert rep["param_bytes"] / 2 < rep["param_bytes_per_shard"] \
            < rep["param_bytes"]
        # the baseline engine reports the degenerate surface
        st1 = Engine(params, CFG, RequestQueue(max_depth=4),
                     num_slots=2).stats()
        assert st1["devices_per_replica"] == 1
        assert st1["mesh_shape"] is None
        assert st1["kv_hbm_bytes_per_shard"] == st1["kv_hbm_bytes"]

    @pytest.mark.parametrize("kw", [
        dict(kv="dense"),
        dict(kv="paged", page_size=8),
        dict(kv="paged", page_size=8, quantize_cache=True)])
    def test_modeled_kv_bytes_matches_live_pool(self, bundle, kw):
        """The config-only model (replica-set /stats for child engines,
        bench HBM math) must equal what the live engine's arrays
        actually occupy — a drift here silently mis-budgets HBM."""
        from dalle_pytorch_tpu.serve import kv_pool as KV
        params, _ = bundle
        engine = Engine(params, CFG, RequestQueue(max_depth=4),
                        num_slots=2, **kw)
        assert KV.modeled_kv_bytes(
            CFG.transformer, kv=kw["kv"], num_slots=2,
            total_len=CFG.seq_len, page_size=kw.get("page_size", 0),
            quantized=kw.get("quantize_cache", False),
            dtype_bytes=4) == engine.kv_hbm_bytes()

    def test_remote_attach_mesh_needs_no_local_devices(self, bundle):
        """A mesh fleet whose engines live on WORKER hosts (socket
        remote attach) must construct on a parent that cannot hold even
        one slice locally — the workers slice their own jax clients'
        devices, and the head node may have zero accelerators."""
        params, _ = bundle
        rs = ReplicaSet(params, CFG, RequestQueue(max_depth=4),
                        replicas=2, isolation="process",
                        transport="socket", worker_cmd="",
                        devices_per_replica=16)   # > the 8 forced devs
        try:
            # no local SLICE was computed (the worker resolves its own);
            # the single-device bookkeeping placement may remain
            assert all(not isinstance(r.device, tuple)
                       for r in rs.replicas)
        finally:
            rs.close(timeout=2.0)

    def test_slice_devices_composition_rule(self):
        """replica=slice: non-overlapping slices, wrapping like the
        single-chip i %% n placement when replicas outnumber slices."""
        devs = list(range(8))
        assert SS.slice_devices(devs, 0, 2) == (0, 1)
        assert SS.slice_devices(devs, 3, 2) == (6, 7)
        assert SS.slice_devices(devs, 4, 2) == (0, 1)   # wraps
        assert SS.slice_devices(devs, 5, 1) == (5,)     # m=1 == i % n
        with pytest.raises(ValueError):
            SS.slice_devices(devs[:1], 0, 2)

    def test_param_specs_shard_only_uncontracted_dims(self, bundle):
        """The no-reassociation rule, structurally: transformer stacks
        shard dim 0 (depth), the logits head shards its OUTPUT dim,
        embedding tables their row dim — and nothing else shards."""
        params, _ = bundle
        mesh = SS.serve_mesh(mesh_devices())
        specs = SS.serve_param_specs(params, CFG, mesh)
        from jax.sharding import PartitionSpec as P
        qkv = specs["transformer"]["attn"]["qkv"]["w"]
        assert qkv.spec == P("mp")                      # depth axis
        assert specs["transformer"]["attn"]["ln"]["g"].spec == P("mp")
        # total_tokens is 83 here — odd, so the logits head exercises
        # the divisibility FALLBACK (replicated, never wrongly split);
        # the 50-row text table shards its vocab rows
        assert specs["to_logits"]["proj"]["w"].spec == P()
        assert specs["text_emb"]["w"].spec == P("mp")
        assert specs["image_emb"]["w"].spec == P("mp")
        assert specs["text_pos_emb"]["w"].spec == P()   # replicated
        # the dense cache carries its heads at dim 2
        kv_specs = SS.serve_kv_specs(
            {"k": jnp.zeros((2, 3, 2, 8, 8))}, mesh, heads=2, paged=False)
        assert kv_specs["k"].spec == P(None, None, "mp")
        # a page of the pool is whole rows (depth, pages, page_size,
        # heads * dh), head-major: a shard of the row is whole heads;
        # the int8 pool's scale pages (..., heads) the same
        kv_specs = SS.serve_kv_specs(
            {"k": jnp.zeros((2, 3, 8, 2 * 8), jnp.int8),
             "k_scale": jnp.zeros((2, 3, 8, 2))}, mesh, heads=2, paged=True)
        assert kv_specs["k"].spec == P(None, None, None, "mp")
        assert kv_specs["k_scale"].spec == P(None, None, None, "mp")
        # heads=3 does not divide 2: falls back replicated, not wrong
        for paged, shape in ((False, (2, 3, 3, 8, 8)), (True, (2, 3, 8, 24))):
            kv_specs = SS.serve_kv_specs({"k": jnp.zeros(shape)}, mesh,
                                         heads=3, paged=paged)
            assert kv_specs["k"].spec == P()


class TestMeshServer:
    def test_server_serves_mesh_engine_with_mesh_health(self, bundle):
        """InferenceServer(mesh_devices=2): the single-engine thread
        loop drives the mesh engine unchanged, and /healthz + /stats
        carry the mesh observability block."""
        params, vae_params = bundle
        from dalle_pytorch_tpu.serve.server import InferenceServer
        srv = InferenceServer(params, vae_params, CFG, num_slots=2,
                              chunk_steps=8, mesh_devices=2,
                              decode_images=False).start()
        try:
            res = srv.generate(REQS[0].codes, seed=REQS[0].seed,
                               timeout=120)
            assert res.status == OK
            np.testing.assert_array_equal(
                np.asarray(res.tokens),
                single_device_tokens(params, K=8)[0])
            health = srv.health()
            assert health["ok"]
            assert health["devices_per_replica"] == 2
            assert health["mesh_shape"] == {"mp": 2}
            st = srv.stats()
            assert st["mesh_shape"] == {"mp": 2}
            assert st["kv_hbm_bytes_per_shard"] * 2 == st["kv_hbm_bytes"]
        finally:
            srv.close()


class TestMeshReplicaSet:
    pytestmark = pytest.mark.faults

    def test_mesh_slice_failover_replay_byte_identical(self, bundle):
        """ReplicaSet-of-mesh-slices: replica 1 (devices 2-3) crashes
        mid-decode; its in-flight requests replay on replica 0 (devices
        0-1) with byte-identical tokens — the unchanged supervision
        logic, now over 2-device engines."""
        params, _ = bundle
        ref = single_device_tokens(params, K=4, reqs=REQS)
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, devices_per_replica=2,
                        bringup_policy=FAST_BRINGUP)
        assert [tuple(d.id for d in r.device) for r in rs.replicas] \
            == [(0, 1), (2, 3)]
        handles = [queue.submit(r) for r in REQS]
        with faults.injected(fault_replica=1, replica_crash_at_chunk=2):
            rs.run_until_idle()
        assert rs.failovers == 1
        assert rs.reclaimed >= 1, "the kill must have stranded work"
        for h, want in zip(handles, ref):
            res = h.result(timeout=10)
            assert res.status == OK, (res.status, res.reason)
            np.testing.assert_array_equal(np.asarray(res.tokens), want)
        stats = rs.stats()
        assert stats["completed"] == len(REQS)
        assert stats["devices_per_replica"] == 2
        assert stats["mesh_shape"] == {"mp": 2}
        assert all(c == 1 for c in rs.decode_compiles_per_replica())
        assert stats["tokens_decoded"] == sum(
            CFG.seq_len - len(r.codes) for r in REQS)


class TestWorkerCheckpointSpec:
    def test_load_ckpt_params_validates_and_restores(self, bundle):
        """The checkpoint-path attach loader: a valid checkpoint
        restores the exact params; the latest: form resolves through
        latest_valid; a torn checkpoint is a typed rejection naming the
        reason."""
        from dalle_pytorch_tpu import checkpoint as ckpt
        from dalle_pytorch_tpu.serve.worker import (WorkerCheckpointError,
                                                    load_ckpt_params)
        params, _ = bundle
        host = jax.tree.map(np.asarray, params)
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "w-3")
            ckpt.save(path, host)
            got = load_ckpt_params({"ckpt_path": path})
            np.testing.assert_array_equal(got["text_emb"]["w"],
                                          host["text_emb"]["w"])
            got = load_ckpt_params({"ckpt_path": f"latest:{d}:w"})
            np.testing.assert_array_equal(got["text_emb"]["w"],
                                          host["text_emb"]["w"])
            # torn payload: validate must refuse it, typed
            with open(os.path.join(path, "params.msgpack"), "r+b") as f:
                f.truncate(10)
            with pytest.raises(WorkerCheckpointError) as ei:
                load_ckpt_params({"ckpt_path": path})
            assert ei.value.record["kind"] == "serve_worker_ckpt_invalid"
            with pytest.raises(WorkerCheckpointError):
                load_ckpt_params({"ckpt_path": f"latest:{d}:w"})
        with pytest.raises(WorkerCheckpointError):
            load_ckpt_params({"ckpt_path": "/nonexistent/ckpt"})
        with pytest.raises(WorkerCheckpointError):
            load_ckpt_params({"ckpt_path": "latest:only-one-colon"})

    def test_worker_ckpt_requires_socket_transport(self, bundle):
        params, _ = bundle
        with pytest.raises(ValueError, match="socket"):
            ReplicaSet(params, CFG, RequestQueue(max_depth=4),
                       replicas=2, worker_ckpt="/tmp/x")

    def test_worker_transforms_require_worker_ckpt(self, bundle):
        """EMA/int8 transforms describe the worker's LOCAL load path;
        without a ckpt-path spec they would silently do nothing."""
        params, _ = bundle
        with pytest.raises(ValueError, match="worker_ckpt"):
            ReplicaSet(params, CFG, RequestQueue(max_depth=4),
                       replicas=2, isolation="process",
                       transport="socket", worker_use_ema=True)
        with pytest.raises(ValueError, match="worker_quantize"):
            ReplicaSet(params, CFG, RequestQueue(max_depth=4),
                       replicas=2, isolation="process",
                       transport="socket", worker_ckpt="/tmp/x",
                       worker_quantize="fp4")

    def test_load_ckpt_params_applies_worker_transforms(self, bundle):
        """The PR-11 follow-up: a checkpoint-path spec carries
        use_ema/quantize, and the worker applies them AFTER its local
        load in the in-process CLI's order — weight trees identical to
        ``ema_as``/``quantize_for_decode`` on the parent. A spec asking
        for EMA from an EMA-less checkpoint is the typed rejection
        (exit 5 downstream), not a KeyError."""
        from dalle_pytorch_tpu import checkpoint as ckpt
        from dalle_pytorch_tpu.cli.common import ema_as
        from dalle_pytorch_tpu.serve.worker import (WorkerCheckpointError,
                                                    load_ckpt_params)
        params, _ = bundle
        host = jax.tree.map(np.asarray, params)
        ema = jax.tree.map(
            lambda p: np.asarray(p, np.float32) * 1.25 + 0.01, host)
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "w-1")
            ckpt.save(path, host, ema=ema)
            got = load_ckpt_params({"ckpt_path": path,
                                    "ckpt_use_ema": True})
            want = ema_as(ema, host)
            jax.tree.map(np.testing.assert_array_equal, got, want)
            got_q = load_ckpt_params({"ckpt_path": path,
                                      "ckpt_quantize": "int8"})
            want_q = D.quantize_for_decode(host)
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)), got_q, want_q)
            with pytest.raises(WorkerCheckpointError, match="quantize"):
                load_ckpt_params({"ckpt_path": path,
                                  "ckpt_quantize": "fp4"})
            # EMA-less checkpoint + EMA spec: typed, names the cause
            path2 = os.path.join(d, "x-1")
            ckpt.save(path2, host)
            with pytest.raises(WorkerCheckpointError) as ei:
                load_ckpt_params({"ckpt_path": path2,
                                  "ckpt_use_ema": True})
            assert ei.value.record["kind"] == "serve_worker_ckpt_invalid"
            assert "EMA" in ei.value.record["reason"]

    @pytest.mark.slow
    def test_ckpt_attach_with_ema_serves_token_exact(self, bundle):
        """End-to-end (spawned children, socket transport): workers
        load the checkpoint locally AND apply the spec's EMA swap —
        tokens byte-identical to an in-process engine serving
        ``ema_as(ema, params)``."""
        from dalle_pytorch_tpu import checkpoint as ckpt
        from dalle_pytorch_tpu.cli.common import ema_as
        params, _ = bundle
        host = jax.tree.map(np.asarray, params)
        ema = jax.tree.map(
            lambda p: np.asarray(p, np.float32) * 1.25 + 0.01, host)
        ema_params = ema_as(ema, host)
        _, ref = engine_tokens(ema_params, Engine, K=8, reqs=REQS[:2])
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "w-0")
            ckpt.save(path, host, ema=ema)
            queue = RequestQueue(max_depth=16)
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=8, isolation="process",
                            transport="socket", worker_ckpt=path,
                            worker_use_ema=True,
                            heartbeat_s=60.0, spawn_timeout_s=240.0,
                            bringup_policy=FAST_BRINGUP)
            try:
                handles = [queue.submit(r) for r in REQS[:2]]
                rs.run_until_idle(max_steps=2_000_000)
                for h, want in zip(handles, ref):
                    res = h.result(timeout=10)
                    assert res.status == OK, (res.status, res.reason)
                    np.testing.assert_array_equal(
                        np.asarray(res.tokens), want)
            finally:
                rs.close()

    @pytest.mark.slow
    def test_ckpt_attach_serves_token_exact_and_bad_ckpt_is_typed(
            self, bundle):
        """End-to-end (spawned children, socket transport): workers
        load weights from the LOCAL checkpoint path — no params in the
        attach spec — and serve token-exact; a worker pointed at a
        missing checkpoint dies with the typed exit the parent decodes
        (exit 5: invalid checkpoint)."""
        from dalle_pytorch_tpu import checkpoint as ckpt
        params, _ = bundle
        ref = single_device_tokens(params, K=8, reqs=REQS[:2])
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "w-0")
            ckpt.save(path, jax.tree.map(np.asarray, params))
            queue = RequestQueue(max_depth=16)
            rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                            chunk_steps=8, isolation="process",
                            transport="socket", worker_ckpt=path,
                            heartbeat_s=60.0, spawn_timeout_s=240.0,
                            bringup_policy=FAST_BRINGUP)
            try:
                handles = [queue.submit(r) for r in REQS[:2]]
                rs.run_until_idle(max_steps=2_000_000)
                for h, want in zip(handles, ref):
                    res = h.result(timeout=10)
                    assert res.status == OK, (res.status, res.reason)
                    np.testing.assert_array_equal(
                        np.asarray(res.tokens), want)
            finally:
                rs.close()
