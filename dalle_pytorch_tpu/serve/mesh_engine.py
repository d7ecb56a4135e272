"""Mesh-sharded serving engine: ONE logical Engine pjit-sharded over an
ICI device mesh.

Every serving scale axis so far multiplied ENGINES — replicas (PR 7),
processes (PR 8), hosts (PR 10) — but each engine was still pinned to
one chip, so a DALLE config whose params + paged KV pool exceed a single
device's HBM could not be served at all. ``MeshEngine`` is the missing
axis: the SAME ``Engine`` (same prefill buckets, same fused-K emit-ring
``decode_loop_paged``, same paged KV lifecycle, same ``step_once`` /
``fence`` / ``counters`` / ``progress_snapshot`` supervision surface)
with its params and KV store sharded across a ``jax.sharding.Mesh`` by
the serve partition rules in ``parallel/serve_specs.py``:

  * transformer layer stacks shard DEPTH (ZeRO-style; params HBM 1/m),
  * the KV store — dense slot cache or paged page pool ``(depth,
    num_pages, heads, page_size, dim_head)``, int8 scale pages included
    — shards HEADS (KV HBM 1/m, the term that caps concurrency),
  * embedding/logits tables shard VOCAB,
  * everything the host protocol touches — per-slot decode state, block
    tables, the emit ring — is REPLICATED, so the host side of the
    engine (PageAllocator, admission device_puts, the one explicit
    emit-ring device_get per chunk) is bit-for-bit the single-device
    protocol.

The implementation is exactly the ``Engine`` placement hooks: this class
overrides ``_place_params`` / ``_place_kv`` (NamedShardings instead of a
device), pins the decode and prefill programs' output shardings so the
carried state's placement can never drift between calls (drift = a
silent retrace, which the ``decode_traces == 1`` contract would catch as
a correctness failure), and supplies the two constraint hooks that make
the math BYTE-IDENTICAL to the single-device engine rather than merely
close: ``_decode_out_sync`` re-replicates the per-head attention output
before the out projection, and ``_logits_sync`` re-replicates the
vocab-sharded logits before sampling. With those pinned, no contracted
dimension is ever sharded — every collective GSPMD inserts is an
all-gather / gather (pure data movement), never a psum (float
reassociation) — so token equality holds by construction, the same way
paged-vs-dense equality does (tests/test_mesh_engine.py pins it).

Because the surface is identical, everything above composes unchanged:
``ReplicaSet`` supervision treats a mesh engine exactly like a
single-chip one (a replica becomes a mesh SLICE — the engine factory
hands replica i devices ``[i*m, (i+1)*m)``, ``serve_specs
.slice_devices``), process isolation spawns a worker that builds its
MeshEngine from its own jax client's device slice, and socket transport
/ failover / deterministic replay carry over with zero changes to
``replica.py``'s supervision logic.

``paged_attn='kernel'`` is gated with the typed ``MeshPagedAttnError``:
the Pallas kernel is a custom call GSPMD cannot partition — riding the
per-shard pool slices needs a shard_map wrapper around the kernel entry,
the documented follow-up (docs/SERVING.md 'Mesh-sharded engine'). The
gather oracle rides the sharded pool today.
"""

from __future__ import annotations

from typing import Optional, Sequence

from dalle_pytorch_tpu.serve.engine import Engine
from dalle_pytorch_tpu.utils.metrics import structured_event


class MeshPagedAttnError(ValueError):
    """Typed rejection of ``paged_attn='kernel'`` on a mesh engine: the
    Pallas ragged paged-attention kernel is a custom call the GSPMD
    partitioner cannot split across shards — serving it on a mesh needs
    the shard_map wrapper (per-shard head slices of the pool), which is
    the documented follow-up. Raised HERE, at engine init, instead of an
    opaque partitioner failure inside the first fused chunk."""

    def __init__(self, record: dict):
        super().__init__(
            "paged_attn='kernel' is not yet supported on a mesh engine: "
            "the Pallas kernel is an opaque custom call GSPMD cannot "
            "partition across the KV pool's head shards. Use "
            "paged_attn='gather' (the parity oracle rides the sharded "
            "pool), or serve single-device replicas for the kernel path "
            "(docs/SERVING.md 'Mesh-sharded engine').")
        self.record = record


class MeshEngine(Engine):
    """``Engine`` over a device mesh. ``devices`` picks the slice (all
    visible devices when None); every other argument, counter, and
    method is the base engine's — the class is placement + program-
    sharding only, which is the entire point (see module docstring)."""

    def __init__(self, params: dict, cfg, queue, *,
                 devices: Optional[Sequence] = None,
                 **kwargs):
        import jax

        from dalle_pytorch_tpu.parallel import serve_specs as SS

        if kwargs.get("paged_attn", "gather") == "kernel":
            raise MeshPagedAttnError(structured_event(
                "serve_mesh_paged_attn_unsupported",
                paged_attn="kernel"))
        self.devices = tuple(devices) if devices is not None \
            else tuple(jax.devices())
        self.mesh = SS.serve_mesh(self.devices)
        self.n_shards = len(self.devices)
        self._rep = SS.replicated(self.mesh)
        self._sync = SS.replicate_sync(self.mesh)
        self._kv_shardings: Optional[dict] = None
        self.kv_sharded = False
        self.params_sharded = False
        # the base engine's ``device`` IS the placement every host-side
        # put flows through — handing it the replicated NamedSharding
        # makes admission tensors, block tables, kill masks, and the
        # per-slot state land replicated across the slice with zero
        # changes to the base code paths
        super().__init__(params, cfg, queue, device=self._rep, **kwargs)

    # -- placement hooks ----------------------------------------------------

    def _place_params(self, params):
        import jax

        from dalle_pytorch_tpu.parallel import serve_specs as SS
        from jax.sharding import PartitionSpec as P
        specs = SS.serve_param_specs(params, self.cfg, self.mesh)
        self.params_sharded = any(
            s.spec != P() for s in jax.tree_util.tree_leaves(specs))
        return jax.tree.map(jax.device_put, params, specs)

    def _place_kv(self, cache: dict) -> dict:
        import jax

        from dalle_pytorch_tpu.parallel import serve_specs as SS
        self._kv_shardings = SS.serve_kv_specs(
            cache, self.mesh, self.cfg.transformer.heads,
            paged=self.kv == "paged")
        self.kv_sharded = SS.kv_is_sharded(self._kv_shardings)
        return {k: jax.device_put(v, self._kv_shardings[k])
                for k, v in cache.items()}

    def _jit_decode(self, impl, donate):
        import jax
        # output shardings PINNED, not propagated: the decode outputs
        # are rebound as the next chunk's inputs, so a propagation
        # choice that drifted from the input NamedShardings would force
        # a retrace on the second call — the one-compile contract
        # (decode_traces == 1) turns that drift into a test failure
        # rather than a silent 2x compile. Order: (cur_tok, pos, active,
        # cache, emit_ring).
        rep = self._rep
        return jax.jit(impl, donate_argnums=donate,
                       out_shardings=(rep, rep, rep,
                                      dict(self._kv_shardings), rep))

    def _jit_prefill_program(self, pre):
        import jax
        # (cache, cur_tok, pos, active, rng, temp, topk_k, top_p,
        # h_last) — same drift-proofing as the decode program, once per
        # bucket; h_last (the prefix cache's insert payload) replicates
        # so a warm hit's first-token sample runs on whole rows
        rep = self._rep
        return jax.jit(pre, out_shardings=(
            dict(self._kv_shardings), rep, rep, rep, rep, rep, rep, rep,
            rep))

    def _jit_warm_program(self, warm):
        import jax
        # (cur_tok, pos, active, rng, temp, topk_k, top_p) — the warm
        # admission touches only replicated per-slot state
        rep = self._rep
        return jax.jit(warm, out_shardings=(rep,) * 7)

    def _jit_pool_update(self, fn):
        import jax
        # the COW boundary-page fork returns the UPDATED pool: pin its
        # shardings, or a propagation choice could drift the KV store's
        # placement and silently retrace the fused decode program
        return jax.jit(fn, out_shardings=dict(self._kv_shardings))

    # -- the byte-identity constraints --------------------------------------

    def _logits_sync(self, logits):
        # the logits head is vocab-sharded (column-parallel: every
        # element computed whole on one shard) — gather it back before
        # the sampler, whose softmax/cumsum reductions must never run
        # over a sharded axis (reassociation breaks byte-identity)
        return self._sync(logits)

    def _decode_out_sync(self):
        # ops.decode applies this to the per-head attention output
        # BEFORE the out projection: gathered heads (data movement)
        # instead of a partial-summed projection (reassociation)
        return self._sync

    # -- observability ------------------------------------------------------

    def _mesh_stats(self) -> dict:
        from dalle_pytorch_tpu.parallel import serve_specs as SS
        return {
            "devices_per_replica": self.n_shards,
            "mesh_shape": SS.mesh_shape_desc(self.mesh),
            "mesh_devices": SS.mesh_device_ids(self.mesh),
            "kv_sharded": self.kv_sharded,
            "params_sharded": self.params_sharded,
            # where the pool actually LIVES: resident bytes per shard
            # (== global/m only when the heads axis divided)
            "kv_hbm_bytes_per_shard": SS.per_shard_bytes(self.cache),
            "param_bytes_per_shard": SS.per_shard_bytes(self.params),
        }


def hbm_report(engine: Engine) -> dict:
    """Modeled HBM residency of an engine's two dominant terms — params
    and the KV store — global and per shard. Works on a plain ``Engine``
    (per-shard == global: one chip holds everything) and a ``MeshEngine``
    (per-shard is what one device of the slice actually stores). This is
    what operators read next to ``mesh_shape`` in /stats."""
    from dalle_pytorch_tpu.parallel import serve_specs as SS
    params_b = SS.param_bytes(engine.params)
    kv_b = engine.kv_hbm_bytes()
    params_ps = SS.per_shard_bytes(engine.params)
    kv_ps = SS.per_shard_bytes(engine.cache)
    return {
        "param_bytes": params_b,
        "kv_hbm_bytes": kv_b,
        "total_bytes": params_b + kv_b,
        "param_bytes_per_shard": params_ps,
        "kv_hbm_bytes_per_shard": kv_ps,
        "total_bytes_per_shard": params_ps + kv_ps,
        "devices": getattr(engine, "n_shards", 1),
    }
