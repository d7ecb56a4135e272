"""Mixture-of-Experts feed-forwards: two routings, one file.

**Dropless routing** (``dropless_apply``; the ``LatentMoEBlock`` of
ops/transformer.py, served): sigmoid scores in float32, the k largest of
``score + bias`` picked, their scores renormalised and scaled, and every
(token, pick) pair computed. The pairs are sorted by expert and the
experts run as grouped matrix products over the sorted rows
(``jax.lax.ragged_dot``, which the TPU compiler lowers to its own grouped
matmul kernel: it visits the groups that hold rows and reads no other
expert's weights); the results go back to token order and are summed
with their weights, shared experts added. No capacity exists and no
token is dropped, whatever the load: the same function serves a
prefill's thousands of tokens and a decode step's few dozen. It returns
its load (picks routed, experts touched, the fullest expert's picks, the
reads of an expert's weights) for the engine's counters. A block may HOLD
a share of its experts (one chip of an expert-parallel deployment:
``blk.first_expert``, ``blk.experts_held``): the router still scores and picks among all of
them, the pairs whose expert is held elsewhere lie in no group and add
nothing, and the load says how many picks were held. Nothing stands in
for the absent chips: their part of the sum is left out. What an absent
pair adds is nothing; what its row COSTS, handed to the grouped products,
is a row of every touched group's tile, since the kernel multiplies all
the rows it is given against each group that holds any. The absent pairs
are sorted behind the held ones, so a share's products are handed the
first R sorted rows alone: R the least step of a short static ladder
(``row_ladder``: from the shapes, twice the rows an even load brings,
that doubled, every pair) that holds the step's held pairs, by one
``lax.switch`` a layer, and the load says which step was taken. Where a
call has no ladder (every expert held, or few pairs) the same cost is paid
for the rows of OTHER groups, so its sorted rows are cut into static ROW
TILES of ``ROW_TILE`` rows, the ladder's floor, and each tile's products
run over the groups clipped to the tile (``row_tiles``, ``tile_sizes``): a
touched expert's weights meet the 64 sorted rows its picks lie in, an
expert whose rows straddle a boundary is read by both tiles, and the load
counts those reads. A call of no more rows than the chip's ridge
(``RIDGE_ROWS``: the operations hide beneath the weights' read), or of
more rows than the kernel's own tile (a prefill's thousands), runs as one.

**Capacity routing** (``moe_apply``; the trainable ``moe_experts`` option
of the classic block, expert-parallel over an ``ep`` axis): the standard
dense-dispatch top-k MoE (GShard/Switch pattern). Every routing decision
is an einsum over one-hot dispatch/combine tensors, so the layer is
static-shaped and shards with nothing but GSPMD annotations
(``moe_param_specs``: expert-stacked weights split over ``ep``, the
token->expert all-to-alls inserted by XLA). Softmax gates, capacity
C = ceil(T/E * k * cf) per expert and batch row; a token over capacity
is DROPPED from the expert and carried by the residual alone; the Switch
load-balancing auxiliary loss. It is kept for training under ``ep``;
serving a routed block uses the dropless path above.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dalle_pytorch_tpu.ops import core

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    dim: int
    num_experts: int = 8
    k: int = 2                       # experts per token
    ff_mult: int = 4
    capacity_factor: float = 1.25
    # NOTE: the aux-loss WEIGHT lives with the model objective
    # (DALLEConfig.moe_aux_coef) — moe_apply returns the raw aux loss

    def __post_init__(self):
        if self.k > self.num_experts:
            raise ValueError(
                f"k={self.k} experts per token exceeds num_experts="
                f"{self.num_experts}")


def moe_init(key: Array, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    """Router + expert-stacked GEGLU weights (leading axis = experts)."""
    k_r, k_w1, k_w2 = jax.random.split(key, 3)
    hidden = cfg.dim * cfg.ff_mult
    e = cfg.num_experts

    def stack(k, din, dout):
        keys = jax.random.split(k, e)
        return jax.vmap(
            lambda kk: core.linear_init(kk, din, dout, bias=False,
                                        dtype=dtype)["w"])(keys)

    return {
        "router": core.linear_init(k_r, cfg.dim, e, bias=False,
                                   dtype=dtype),
        "w1": stack(k_w1, cfg.dim, hidden * 2),     # (E, d, 2h) GEGLU in
        "w2": stack(k_w2, hidden, cfg.dim),         # (E, h, d)
    }


def moe_apply(params: dict, x: Array, *, cfg: MoEConfig
              ) -> Tuple[Array, Array]:
    """-> (out (b, n, d), aux load-balance loss scalar).

    Exact dense-dispatch computation, GROUPED per batch row (GShard's
    group semantics): each row routes its n tokens independently with
    capacity C = ceil(n*k/E * cf), so the one-hot dispatch/combine
    tensors are (b, n, E, C) — O(n^2 k cf) per row — instead of the
    O((bn)^2) a flat global queue would cost. Tokens over a row's
    capacity are DROPPED from the expert (they contribute zero here; the
    transformer's residual still carries them — Switch-style graceful
    overflow).
    """
    b, n, d = x.shape
    e, k = cfg.num_experts, cfg.k
    # floor the FINAL capacity at 1 — a 0-width queue would silently zero
    # the whole layer (every token overflows)
    cap = max(1, int(-(-n * k // e) * cfg.capacity_factor))
    cdt = x.dtype

    def group(xt):                                       # (n, d) one row
        logits = core.linear(params["router"], xt.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)          # (n, E)
        gate_vals, idx = lax.top_k(probs, k)             # (n, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(axis=-1, keepdims=True), 1e-9)

        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)     # (n, k, E)
        # queue position of each token within its expert (first-come)
        ranks = jnp.cumsum(onehot.sum(1), axis=0) - onehot.sum(1)  # (n, E)
        keep = (ranks < cap)[:, None, :] * onehot        # (n, k, E)

        # dispatch: binary (n, E, C); combine: gate-weighted dispatch
        # ranks are whole numbers carried in f32 (a cumsum of one-hots)
        pos = jax.nn.one_hot(ranks.astype(jnp.int32), cap,
                             dtype=jnp.float32)            # (n, E, C)
        dispatch = jnp.einsum("tke,tec->tec", keep, pos)
        combine = jnp.einsum("tke,tk,tec->tec", keep, gate_vals, pos)

        xin = jnp.einsum("tec,td->ecd", dispatch.astype(cdt), xt)
        h = jnp.einsum("ecd,edf->ecf", xin, params["w1"])      # (E, C, 2h)
        h, gates = jnp.split(h, 2, axis=-1)
        h = h * core.gelu(gates)
        eout = jnp.einsum("ecf,efd->ecd", h, params["w2"])     # (E, C, d)
        out = jnp.einsum("tec,ecd->td", combine.astype(cdt), eout)

        # Switch load-balance loss: E * sum_e mean_prob_e * token_frac_e
        aux = e * jnp.sum(onehot[:, 0].mean(axis=0) * probs.mean(axis=0))
        return out, aux

    out, aux = jax.vmap(group)(x)
    return out, jnp.mean(aux).astype(jnp.float32)


def moe_param_specs(axis: str = "ep") -> dict:
    """PartitionSpecs sharding the expert axis over ``axis`` (router
    replicated). Feed into a params-tree spec at the layer's position."""
    from jax.sharding import PartitionSpec as P
    return {"router": {"w": P()}, "w1": P(axis, None, None),
            "w2": P(axis, None, None)}


# ---------------------------------------------------------------------------
# dropless routing (served): sort by expert, grouped matrix products
# ---------------------------------------------------------------------------

def holds_all(blk) -> bool:
    """Whether every routed expert of the block is held here."""
    return blk.experts_held == blk.num_experts


def load_width(blk) -> int:
    """Entries of a routed layer's load: picks routed, experts touched,
    the fullest expert's picks, the reads of an expert's weights
    (``dropless_experts``: the (row tile, expert) pairs that hold a row);
    and, where a share is held, the picks that fell on it and the rows
    handed to the grouped products. None for a block without routed
    layers."""
    if not blk.num_experts:
        return 0
    return 4 if holds_all(blk) else 6


def dropless_init(key: Array, dim: int, blk, dtype=jnp.float32) -> dict:
    """Router (with its selection bias) over every expert, the HELD
    experts' stacked SiLU-gated units (gate and up side by side in
    ``w_in``) and the shared unit (none where ``shared_hidden`` is 0)."""
    k_r, k_in, k_out, k_s = jax.random.split(key, 4)
    e, he = blk.num_experts, blk.expert_hidden
    held = blk.experts_held
    out = {
        "router": {"w": core.uniform_fan_in(k_r, (dim, e), dim, dtype),
                   "bias": jnp.zeros((e,), jnp.float32)},
        "experts": {
            "w_in": core.uniform_fan_in(k_in, (held, dim, 2 * he), dim,
                                        dtype),
            "w_out": core.uniform_fan_in(k_out, (held, he, dim), he,
                                         dtype)},
    }
    if blk.shared_hidden:
        out["shared"] = core.swiglu_init(k_s, dim, blk.shared_hidden, dtype)
    return out


@jax.named_scope("moe.route")
def route(router: dict, x: Array, k: int, scale: float, eps: float = 0.0):
    """x (t, dim) -> (picks (t, k) expert ids, weights (t, k) f32).
    Scores are sigmoids in f32; the bias moves the SELECTION only, the
    weights are the picked scores over their sum (plus ``eps`` where a
    block's equations have one), times ``scale``."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                    router["w"].astype(jnp.float32)))
    _, picks = lax.top_k(scores + router["bias"].astype(jnp.float32), k)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    total = jnp.sum(picked, axis=-1, keepdims=True)
    if eps:
        total = total + eps
    return picks, scale * picked / total


# the fewest sorted rows the grouped products are handed at once: the
# ladder's least step and a row tile's height. At 64 rows handed the
# products run at 82% of the touched experts' read in two cells (PERF.md
# section 6, PRs 33 and 41); under it a tile's re-reads buy nothing
ROW_TILE = 64
# the rows handed at which a grouped product's operations (two a weight a
# row) take this chip as long as the read of the weights (two bytes a
# weight): 197 TFLOP/s over 819 GB/s (a v5e: utils/device.py CHIP_PEAKS).
# Under it the operations hide beneath the read, and cutting the rows buys
# nothing and costs a call a tile (measured at 192 rows: PERF.md section
# 6, PR 43)
RIDGE_ROWS = 240
# the compiler's grouped-product kernel cuts the rows it is handed into
# tiles of this many itself (its row operand in the compiled text) and
# passes a tile that holds no row of a group: over it, nothing is gained
# by cutting the rows here (a prefill's thousands: PERF.md section 6, PR 41)
KERNEL_ROWS = 512


def row_ladder(pairs: int, held: int, num_experts: int) -> Tuple[int, ...]:
    """The row counts at which a block that holds ``held`` of its
    ``num_experts`` experts runs the grouped products of ``pairs`` (token,
    pick) pairs, from shapes alone: twice the rows an even load brings the
    held experts, rounded up to a power of two and not under ``ROW_TILE``;
    that doubled; and every pair. Steps at or over ``pairs`` fall away, so
    the ladder of a call whose first step already holds every pair is
    ``(pairs,)``: no ladder."""
    expected = -(-2 * pairs * held // num_experts)
    step = max(ROW_TILE, 1 << (expected - 1).bit_length())
    return tuple(r for r in (step, 2 * step) if r < pairs) + (pairs,)


def row_tiles(rows: int) -> int:
    """The tiles into which the grouped products of ``rows`` sorted rows
    are cut, from the shape alone: ``ROW_TILE`` rows each (the last one
    what is left) where ``RIDGE_ROWS < rows <= KERNEL_ROWS``, the rows at
    which the products are bound by operations that a tile saves; else
    one."""
    return -(-rows // ROW_TILE) if RIDGE_ROWS < rows <= KERNEL_ROWS else 1


def tile_sizes(sizes: Array, rows: int) -> Array:
    """(tiles of ``ROW_TILE`` rows, E) int32: of each group's rows, those
    that lie in each tile of the first ``rows`` sorted rows. A group that
    no row of a tile lies in has size 0 there (its weights are not read
    for that tile); one that straddles a boundary has rows in both tiles
    (and is read for both)."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    lo = jnp.arange(0, rows, ROW_TILE, dtype=jnp.int32)[:, None]
    hi = jnp.minimum(lo + ROW_TILE, rows)
    return jnp.clip(ends, lo, hi) - jnp.clip(starts, lo, hi)


def dropless_experts(experts: dict, x: Array, picks: Array,
                     weights: Array, first: Optional[int] = None,
                     num_experts: Optional[int] = None):
    """Every (token, pick) pair through its expert, summed per token with
    its weight. x (t, dim), picks / weights (t, k) -> (out (t, dim),
    sizes (E,) int32: picks each HELD expert received, the rows handed to
    the grouped products, int32, the reads of an expert's weights: the
    (row tile, group) pairs that hold a row, int32).

    ``first`` is None where the E experts of ``experts`` are all that the
    picks name. Where they are a share, ``first`` .. ``first + E`` of
    ``num_experts``, a pair whose expert is held elsewhere is sorted
    behind every group and lies in none: it adds nothing. It is not free:
    the compiler's grouped-product kernel multiplies EVERY row it is
    handed against each touched group's weights and keeps the rows of the
    group, so a row in no group costs a row of every touched group's
    tile (at 512 pair rows of which 32 are held, sixteen times the
    products' work, and past what the weights' read takes). So a share's
    products are handed the first R sorted rows only, R the least step of
    ``row_ladder`` that holds ``sum(sizes)``, chosen by one ``lax.switch``
    around the products; the sort, the sizes and the way back to token
    order stay outside it. Where the ladder is one step (every expert
    held, or few pairs) there is no switch.

    A row that lies in ANOTHER group costs the same as one that lies in
    none: where every expert is held there is no dead row to leave out,
    and 256 pair rows against 63 touched groups are as many operations as
    the experts' read takes time (the products sit on the chip's ridge).
    So a call without a ladder runs its products a ROW TILE at a time
    (``row_tiles``): the sorted rows are cut into static tiles of
    ``ROW_TILE`` rows, and each tile's two products run over the groups
    clipped to it (``tile_sizes``). A touched group is then multiplied
    against the 64 rows its picks lie in, a group with no row in a tile
    is not read for it, and one that straddles a boundary is read by both
    tiles: the fourth output counts that. ``ROW_TILE`` is the ladder's
    floor, since 64 rows handed is where the records put the products at
    82% of the touched experts' read. Tiles do not engage at
    ``RIDGE_ROWS`` or fewer (the operations hide beneath the read there,
    and a tile costs a call: 192 rows over 99 of 128 smaller experts ran
    3% slower in tiles), over ``KERNEL_ROWS`` (the kernel cuts such rows
    itself and passes the tiles that hold no row of a group), or inside a
    ladder's steps, whose row count is the cut already: those calls trace
    as they did.

    ``experts`` holds ``w_in`` (E, dim, 2 * hidden) and ``w_out``; or, from
    a scanned stack (``ops.transformer.block_stack``), the WHOLE stack's
    (L, E, ...) with ``layer``, this layer's index in it. The grouped
    product then runs over all L * E groups with this layer's sizes laid at
    its offset and zero elsewhere: it reads the groups that hold rows, and
    the compiler is given no slice of a layer to copy first (a scan's
    slice of a layer's experts, handed to a kernel, is a copy of them:
    1.2 GB a layer a step at the published widths)."""
    t, k = picks.shape
    e = experts["w_in"].shape[-3]
    ladder = (t * k,) if first is None else row_ladder(t * k, e, num_experts)
    with jax.named_scope("moe.route"):
        flat = picks.reshape(-1)
        if first is not None:
            # an absent pair's key is E: behind the last group, and out
            # of range of ``sizes`` (a scatter drops such an index)
            here = (flat >= first) & (flat < first + e)
            flat = jnp.where(here, flat - first, e)
        order = jnp.argsort(flat, stable=True)      # pairs, by expert
        sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
        # (tiles, E): a call without a ladder, its rows several tiles
        tiled = tile_sizes(sizes, t * k) \
            if len(ladder) == 1 and row_tiles(t * k) > 1 else None

    def products(r: int):
        """The first ``r`` sorted pairs through their experts, a row tile
        at a time -> (t * k, dim) f32 in sorted order, the rows behind
        ``r`` zero."""
        w_in, w_out = experts["w_in"], experts["w_out"]
        with jax.named_scope("moe.route"):
            rows = jnp.take(x, order[:r] // k, axis=0)      # (r, dim)
            pair_weights = jnp.take(weights.reshape(-1), order[:r])
            groups = [sizes] if tiled is None else list(tiled)
            if "layer" in experts:
                w_in = w_in.reshape((-1,) + w_in.shape[2:])
                w_out = w_out.reshape((-1,) + w_out.shape[2:])
                groups = [lax.dynamic_update_slice(
                    jnp.zeros((w_in.shape[0],), jnp.int32), g,
                    (experts["layer"] * e,)) for g in groups]
        with jax.named_scope("moe.experts"):
            outs = []
            for j, g in enumerate(groups):
                tile = rows if tiled is None else \
                    rows[j * ROW_TILE:(j + 1) * ROW_TILE]
                gate, up = jnp.split(
                    lax.ragged_dot(tile, w_in.astype(x.dtype), g), 2,
                    axis=-1)
                outs.append(lax.ragged_dot(jax.nn.silu(gate) * up,
                                           w_out.astype(x.dtype), g))
            out = outs[0] if tiled is None else jnp.concatenate(outs)
            # each pair's weight, in f32, here: the compiler's
            # grouped-product kernel carries no scope of its own and takes
            # its first reader's
            out = out.astype(jnp.float32) * pair_weights[:, None]
            if first is not None:
                # rows behind the last group are no product's output
                out = jnp.where((jnp.take(flat, order[:r]) < e)[:, None],
                                out, 0.0)
            return out if r == t * k else jnp.pad(
                out, ((0, t * k - r), (0, 0)))

    if len(ladder) == 1:
        out, handed = products(t * k), jnp.int32(t * k)
    else:
        step = jnp.sum(jnp.sum(sizes) > jnp.asarray(ladder[:-1]))
        out = lax.switch(step, [functools.partial(products, r)
                                for r in ladder])
        handed = jnp.asarray(ladder, jnp.int32)[step]
    with jax.named_scope("moe.route"):
        # back to (token, pick) order, and the sum over a token's picks
        out = jnp.take(out, jnp.argsort(order), axis=0).reshape(t, k, -1)
        out = jnp.sum(out, axis=1)
        # one tile reads each touched group once
        reads = jnp.sum((sizes if tiled is None else tiled) > 0).astype(
            jnp.int32)
    return out.astype(x.dtype), sizes, handed, reads


def dropless_apply(params: dict, x: Array, blk):
    """x (..., dim) -> (out (..., dim), load (``load_width``,) int32:
    picks routed, held experts that received one, the fullest held
    expert's picks, the (row tile, expert) pairs that hold a row and,
    where a share is held, the picks that fell on it and the rows handed
    to the grouped products)."""
    lead = x.shape[:-1]
    xt = x.reshape(-1, x.shape[-1])
    picks, weights = route(params["router"], xt, blk.experts_per_token,
                           blk.routed_scale, blk.route_eps)
    whole = holds_all(blk)
    out, sizes, handed, reads = dropless_experts(
        params["experts"], xt, picks, weights,
        None if whole else blk.first_expert, blk.num_experts)
    if "shared" in params:
        with jax.named_scope("moe.shared"):
            out = out + core.swiglu(params["shared"], xt)
    held = jnp.sum(sizes)
    load = [held if whole else jnp.int32(picks.size),
            jnp.sum(sizes > 0).astype(jnp.int32), jnp.max(sizes), reads]
    if not whole:
        load += [held, handed]
    return out.reshape(lead + (-1,)), jnp.stack(load)
