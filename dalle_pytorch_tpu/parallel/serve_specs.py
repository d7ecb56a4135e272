"""Serve-side partition rules: sharding a DECODE program over an ICI mesh.

``parallel/train.py``'s specs shard for training throughput (Megatron
tp: column/row-parallel pairs whose row halves psum partial matmul
results). The serving engine cannot use those rules, because serving
carries a stricter contract than throughput: the mesh-sharded engine
(``serve/mesh_engine.py``) must emit tokens BYTE-IDENTICAL to the
single-device engine — the same equality the whole serving stack is
built on (paged-vs-dense, kernel-vs-gather, failover replay). A psum
reassociates a floating-point sum (partial products added in a
different order than the unsharded dot), which breaks bit-equality in
exactly the way a tolerance test hides and a token-equality test
catches.

So the serve rules shard only NON-CONTRACTED dimensions, making every
collective a data movement (all-gather / gather / dynamic-slice), never
an arithmetic reassociation:

  * transformer layer stacks ``(depth, ...)`` shard the DEPTH axis
    (ZeRO-style): the per-layer ``lax.scan`` slice all-gathers one
    layer's weights per step, and the math on the gathered values is
    the single-device math, bit for bit. Params HBM scales 1/m;
  * the KV store — the dense slot cache ``(depth, slots, heads, len,
    dh)`` or the paged page pool ``(depth, num_pages, heads,
    page_size, dh)`` and its int8 scale pages — shards the HEADS axis:
    per-head attention (scores, softmax, weighted sum) is data-
    independent across heads, so each shard computes its heads exactly
    as the single device would. KV HBM scales 1/m — the term that caps
    serving concurrency;
  * embedding tables and the logits head shard their VOCAB axis
    (gathers and column-parallel projection: elementwise-exact), and
    the engine re-replicates logits BEFORE sampling so softmax/cumsum
    reductions never run over a sharded axis;
  * everything the host touches — per-slot decode state, block tables,
    the emit ring — stays replicated, so the engine's host protocol
    (one explicit device_get per chunk, explicit device_puts at
    admission) is unchanged.

The one seam this needs inside the model math is ``ops.decode``'s
``out_sync`` hook: the per-head attention output is constrained back to
replicated BEFORE the output projection, forcing GSPMD to all-gather
the heads (data movement) instead of partial-summing the projection
(reassociation). ``head_sync``/``replicate_sync`` build that constraint.

Divisibility is checked per leaf: a dimension the mesh size does not
divide falls back to replicated for that leaf (same policy as
``train.dalle_param_specs``), so an odd config degrades in memory
footprint, never in correctness.
"""

from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dalle_pytorch_tpu.parallel.mesh import make_mesh

# the serving model-parallel mesh axis: one axis is enough because every
# sharded tensor shards exactly one dim over it (depth for params, heads
# for KV, vocab for the embedding/logits tables)
SERVE_AXIS = "mp"


def serve_mesh(devices: Sequence, axis: str = SERVE_AXIS) -> Mesh:
    """One-axis device mesh for a mesh-sharded serving engine. On a pod
    slice the devices should be ICI neighbours (a contiguous slice of
    ``jax.devices()`` — ``slice_devices`` below), so the per-layer
    all-gathers ride ICI, never DCN."""
    return make_mesh({axis: len(devices)}, devices)


def slice_devices(devices: Sequence, index: int,
                  per_replica: int) -> Tuple:
    """Replica ``index``'s device slice — the replica=slice composition
    rule (a ReplicaSet replica becomes a mesh SLICE instead of one
    chip). The host's devices divide into ``len(devices) // m``
    non-overlapping slices and replica ``index`` takes slice ``index %
    n_slices`` — the exact generalization of the single-chip placement
    ``devices[i % len(devices)]`` (``per_replica=1`` reproduces it), so
    more replicas than slices SHARE slices (slower, never wrong), and a
    remote worker serving replica 7 on a 2-chip host still gets a valid
    local slice. Raises only when the host cannot hold even one slice."""
    m = int(per_replica)
    if m < 1:
        raise ValueError(f"devices_per_replica must be >= 1, got {m}")
    n_slices = len(devices) // m
    if n_slices < 1:
        raise ValueError(
            f"a {m}-device mesh slice does not fit this host: only "
            f"{len(devices)} device(s) visible")
    lo = (index % n_slices) * m
    return tuple(devices[lo:lo + m])


def replicated(mesh: Mesh) -> NamedSharding:
    """The replicated placement every host-visible array gets."""
    return NamedSharding(mesh, P())


def _div(leaf_dim: int, mesh: Mesh, axis: str) -> bool:
    return leaf_dim % mesh.shape[axis] == 0


def serve_param_specs(params, cfg, mesh: Mesh, axis: str = SERVE_AXIS):
    """NamedSharding tree for a DALLE param tree under the serve rules
    (module docstring): transformer stacks depth-sharded, embedding /
    logits-head tables vocab-sharded, the rest replicated. ``cfg`` is
    the DALLEConfig (``cfg.transformer.depth`` identifies the stacked
    leaves; int8-quantized stacks keep their leading depth dim, so the
    shape test covers them too)."""
    depth = cfg.transformer.depth

    def rule(path, leaf):
        keys = [getattr(k, "key", None) for k in path]
        shape = getattr(leaf, "shape", ())
        if "transformer" in keys and len(shape) >= 1 \
                and shape[0] == depth and _div(depth, mesh, axis):
            return P(axis)
        if "proj" in keys and keys[-1] in ("w", "wq") \
                and len(shape) == 2 and _div(shape[1], mesh, axis):
            # logits head, column-parallel: the contraction (model dim)
            # stays replicated — elementwise-exact shards of the logits,
            # re-replicated by the engine's logits_sync before sampling
            return P(None, axis)
        if "proj" in keys and len(shape) == 1 \
                and _div(shape[0], mesh, axis):
            return P(axis)          # head bias / int8 scale, vocab-long
        if len(keys) >= 2 and keys[-2] in ("text_emb", "image_emb") \
                and keys[-1] == "w" and len(shape) == 2 \
                and _div(shape[0], mesh, axis):
            return P(axis)          # row-sharded table: gathers only
        return P()

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, rule(path, leaf)), params)


def kv_heads_shard(heads: int, mesh_size: int) -> bool:
    """THE policy predicate for sharding a KV store: heads shard iff the
    mesh size divides them. One definition shared by ``serve_kv_specs``
    (which places the live pool) and the replica set's config-only HBM
    model (``ReplicaSet._kv_bytes_per_shard`` — a parent fronting
    remote workers has no pool to measure), so the modeled per-shard
    bytes can never drift from what placement actually does."""
    return int(mesh_size) > 0 and heads % int(mesh_size) == 0


def serve_kv_specs(cache: dict, mesh: Mesh, heads: int, paged: bool,
                   axis: str = SERVE_AXIS) -> dict:
    """NamedSharding dict for a KV store — the dense slot cache or the
    paged page pool (``serve/kv_pool.py``), int8 scale pages included —
    sharded over the heads, the one axis whose shards attend
    independently. The dense cache carries them at dim 2 (``(depth, slots,
    heads, rows[, dh])``); a page of the pool is whole rows (``(depth,
    pages, page_size, heads * dh)``, scales ``... heads``), every head's
    numbers side by side in the row's last axis, head-major, so a shard
    of that axis is whole heads."""
    spec = P()
    if kv_heads_shard(heads, mesh.shape[axis]):
        spec = P(None, None, None, axis) if paged else P(None, None, axis)
    return {k: NamedSharding(mesh, spec) for k in cache}


def kv_is_sharded(specs: dict) -> bool:
    """True when the KV store actually sharded (heads divisible) — what
    per-shard HBM accounting divides by the mesh size on."""
    return any(s.spec != P() for s in specs.values())


def replicate_sync(mesh: Mesh) -> Callable:
    """A ``with_sharding_constraint`` closure pinning a value replicated
    — the engine applies it to logits before sampling (reductions over
    the vocab axis must never run sharded) and ``ops.decode`` applies it
    to the per-head attention output via the ``out_sync`` seam (the out
    projection must see gathered heads, not partial-sum them)."""
    sharding = NamedSharding(mesh, P())

    def sync(x):
        return jax.lax.with_sharding_constraint(x, sharding)

    return sync


def per_shard_bytes(tree) -> int:
    """Resident bytes ONE device of the mesh stores for ``tree`` —
    replicated leaves count whole, sharded leaves count their shard
    (``sharding.shard_shape``). Host/numpy leaves (no sharding) count
    whole: one copy somewhere is the honest model. The /stats
    ``*_per_shard`` fields and bench's ``mesh_compare`` HBM-budget
    assertion read this."""
    import numpy as np
    total = 0
    for x in jax.tree_util.tree_leaves(tree):
        sharding = getattr(x, "sharding", None)
        if sharding is None or not hasattr(sharding, "shard_shape"):
            total += int(getattr(x, "nbytes", 0))
        else:
            total += int(np.prod(sharding.shard_shape(x.shape))
                         * x.dtype.itemsize)
    return total


def param_bytes(params) -> int:
    """Total parameter bytes (the modeled-HBM term next to the KV pool
    in the mesh HBM budget math — bench's ``mesh_compare`` and the
    /stats surface read it)."""
    return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(params)))


def mesh_shape_desc(mesh: Mesh) -> dict:
    """``{axis: size}`` — the /stats ``mesh_shape`` field."""
    return {str(k): int(v) for k, v in mesh.shape.items()}


def mesh_device_ids(mesh: Mesh) -> List[int]:
    return [int(d.id) for d in mesh.devices.flat]
