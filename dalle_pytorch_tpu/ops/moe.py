"""Mixture-of-Experts feed-forwards: two routings, one file.

**Dropless routing** (``dropless_apply``; the ``LatentMoEBlock`` of
ops/transformer.py, served): sigmoid scores in float32, the k largest of
``score + bias`` picked, their scores renormalised and scaled, and every
(token, pick) pair computed. The pairs are sorted by expert and the
experts run as grouped matrix products over the sorted rows; the results
go back to token order and are summed with their weights, shared experts
added. No capacity exists and no token is dropped, whatever the load: the
same function serves a prefill's thousands of tokens and a decode step's
few dozen. It returns its load (picks routed, experts touched, the
fullest expert's picks, the reads of an expert's weights) for the
engine's counters. A block may HOLD a share of its experts (one chip of
an expert-parallel deployment: ``blk.first_expert``,
``blk.experts_held``): the router still scores and picks among all of
them, the pairs whose expert is held elsewhere lie in no group and add
nothing, and the load says how many picks were held. Nothing stands in
for the absent chips: their part of the sum is left out.

WHICH grouped products run, the shapes of a step of a call's ladder
decide (``kernel_hidden_tile``). A decode step's rows (at most ``KERNEL_ROWS``
pair rows, bfloat16, widths of whole lanes) run in the repo's Pallas
kernel (``expert_products``): its grid visits the (row tile of
``ROW_TILE``, group) pairs that share a row and multiplies a group's
weights against its own tiles only, both products in one pass over the
expert, the stack indexed in place. Every other call (a prefill's
thousands of rows, float32, toy widths) runs ``jax.lax.ragged_dot``, which
the TPU compiler lowers to its own grouped matmul kernel: it visits the
groups that hold rows and reads no other expert's weights, but
multiplies ALL the rows it is given against each group that holds any.
There, what an absent pair's row COSTS is a row of every touched group's
tile. The absent pairs are sorted behind the held ones, so a share's
products are handed the first R sorted rows alone: R the least step of a
short static ladder (``row_ladder``: from the shapes, twice the rows an
even load brings, that doubled, every pair) that holds the step's held
pairs, by one ``lax.switch`` a layer, and the load says which step was
taken. Where a call has no ladder (every expert held, or few pairs) the
compiler's products pay the same for the rows of OTHER groups, so its
sorted rows are cut into static ROW TILES of ``ROW_TILE`` rows, the
ladder's floor, and each tile's products run over the groups clipped to
the tile (``row_tiles``, ``tile_sizes``): a touched expert's weights meet
the 64 sorted rows its picks lie in, an expert whose rows straddle a
boundary is read by both tiles, and the load counts those reads. A call
of no more rows than the chip's ridge (``RIDGE_ROWS``: the operations hide
beneath the weights' read), or of more rows than the compiler's kernel's
own tile (a prefill's thousands), runs as one.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
    """Router (with its selection bias, where the block's scores are
    sigmoids) over every expert, the HELD experts' stacked SiLU-gated
    units (gate and up side by side in ``w_in``), the shared unit (none
    where ``shared_hidden`` is 0) and, where the block gates it, the row
    that scores its gate."""
    k_r, k_in, k_out, k_s = jax.random.split(key, 4)
    e, he = blk.num_experts, blk.expert_hidden
    held = blk.experts_held
    router = {"w": core.uniform_fan_in(k_r, (dim, e), dim, dtype)}
    if blk.route_scores == "sigmoid":
        router["bias"] = jnp.zeros((e,), jnp.float32)
    out = {
        "router": router,
        "experts": {
            "w_in": core.uniform_fan_in(k_in, (held, dim, 2 * he), dim,
                                        dtype),
            "w_out": core.uniform_fan_in(k_out, (held, he, dim), he,
                                         dtype)},
    }
    if blk.shared_hidden:
        out["shared"] = core.swiglu_init(k_s, dim, blk.shared_hidden, dtype)
        if blk.shared_gate:
            out["shared_gate"] = core.linear_init(
                jax.random.fold_in(k_s, 1), dim, 1, bias=False, dtype=dtype)
    return out


@jax.named_scope("moe.route")
def route(router: dict, x: Array, k: int, scale: float, eps: float = 0.0,
          scores: str = "sigmoid"):
    """x (t, dim) -> (picks (t, k) expert ids, weights (t, k) f32).
    Scores are sigmoids in f32; the bias moves the SELECTION only, the
    weights are the picked scores over their sum (plus ``eps`` where a
    block's equations have one), times ``scale``. With ``scores``
    ``"softmax"`` the scores are a softmax over all the experts in f32
    and the largest are picked as they are (such a router holds no
    bias)."""
    logits = jnp.dot(x.astype(jnp.float32), router["w"].astype(jnp.float32))
    if scores == "softmax":
        picked, picks = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    else:
        marks = jax.nn.sigmoid(logits)
        _, picks = lax.top_k(marks + router["bias"].astype(jnp.float32), k)
        picked = jnp.take_along_axis(marks, picks, axis=-1)
    total = jnp.sum(picked, axis=-1, keepdims=True)
    if eps:
        total = total + eps
    return picks, scale * picked / total


# the fewest sorted rows the grouped products are handed at once: the
# ladder's least step, a row tile's height, and the row tile of the
# experts' kernel (``expert_products``). At 64 rows handed the compiler's
# grouped product runs at 82% of the touched experts' read in two cells
# (PERF.md section 6, PRs 33 and 41); under it a tile's re-reads buy nothing
ROW_TILE = 64
# the rows handed at which a grouped product's operations (two a weight a
# row) take this chip as long as the read of the weights (two bytes a
# weight): 197 TFLOP/s over 819 GB/s (a v5e: utils/device.py CHIP_PEAKS).
# Under it the operations of the COMPILER'S grouped product hide beneath
# the read, and cutting the rows buys nothing and costs a call a tile
# (measured at 192 rows: PERF.md section 6, PR 43). It binds a call that
# the experts' kernel does not take; the kernel multiplies a group's own
# rows only and has no ridge to stay under
RIDGE_ROWS = 240
# the most rows a call hands the experts' kernel: a decode step's. The
# compiler's grouped product cuts the rows it is handed into tiles of this
# many itself (its row operand in the compiled text) and passes a tile
# that holds no row of a group: over it, nothing is gained by cutting the
# rows here, the products are bound by their operations, and they stay the
# compiler's (a prefill's thousands: PERF.md section 6, PR 41)
KERNEL_ROWS = 512
# the bytes of one expert's weights that a grid step of the experts'
# kernel brings in (a hidden tile's gate, up and down blocks); two such
# are in flight. 20 MB takes a whole expert of 2048 x 1536 (18.9 MB) a
# step: measured at four cells' decode shapes, whole experts run 2.4% and
# 0.6% faster than tiles of them (a group that straddles a row tile is
# then read once, not twice), and 50 MB experts lose under 1% to tiles of
# 6 MB (PERF.md section 6, PR 44)
KERNEL_BLOCK_BYTES = 20 << 20
NUM_LANES = 128


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
    """The tiles into which the COMPILER'S grouped products of ``rows``
    sorted rows are cut, from the shape alone: ``ROW_TILE`` rows each (the
    last one what is left) where ``RIDGE_ROWS < rows <= KERNEL_ROWS``, the
    rows at which the products are bound by operations that a tile saves;
    else one."""
    return -(-rows // ROW_TILE) if RIDGE_ROWS < rows <= KERNEL_ROWS else 1


def tile_sizes(sizes: Array, rows: int) -> Array:
    """(tiles of ``ROW_TILE`` rows, E) int32: of each group's rows, those
    that lie in each tile of the first ``rows`` sorted rows. A group that
    no row of a tile lies in has size 0 there (its weights are not read
    for that tile); one that straddles a boundary has rows in both tiles
    (and is visited for both)."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    lo = jnp.arange(0, rows, ROW_TILE, dtype=jnp.int32)[:, None]
    hi = jnp.minimum(lo + ROW_TILE, rows)
    return jnp.clip(ends, lo, hi) - jnp.clip(starts, lo, hi)


def kernel_hidden_tile(rows: int, dim: int, hidden: int, dtype
                       ) -> Optional[int]:
    """The hidden units that a grid step of the experts' kernel takes for a
    call that hands it ``rows`` sorted rows of ``dim`` numbers, or None
    where the call stays on the compiler's grouped product; from the
    shapes and the type alone. The kernel takes a decode step's rows (at
    most ``KERNEL_ROWS``, whole row tiles) in bfloat16, the type the served
    configurations compute in (a float32 call, a reference's or a test's,
    is not what it was measured on), at widths that fill the chip's lanes;
    its hidden tile is the largest whole number of lanes that divides
    ``hidden`` and whose three blocks are within ``KERNEL_BLOCK_BYTES``."""
    if rows > KERNEL_ROWS or rows % ROW_TILE or dim % NUM_LANES \
            or jnp.dtype(dtype) != jnp.bfloat16:
        return None
    fit = [h for h in range(NUM_LANES, hidden + 1, NUM_LANES)
           if hidden % h == 0 and 3 * dim * h * 2 <= KERNEL_BLOCK_BYTES]
    return max(fit, default=None)


def _experts_kernel(tile_ref, group_ref, start_ref, end_ref, offset_ref,
                    x_ref, gate_ref, up_ref, down_ref, out_ref, acc_ref):
    """One (row tile, group) pair and one hidden tile of the group's
    expert: the tile's rows through the gate and up columns, their gated
    product through the down rows, summed over the hidden tiles in f32;
    at the last of them the group's own rows are stored."""
    del offset_ref                                  # the index maps' alone
    pair, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    gate = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
    acc_ref[...] += jnp.dot((jax.nn.silu(gate) * up).astype(x.dtype),
                            down_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        tile, group = tile_ref[pair], group_ref[pair]
        row = tile * ROW_TILE + lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        own = (row >= start_ref[group]) & (row < end_ref[group])
        # the block of a tile's first visit holds no earlier group's rows
        first = (pair == 0) | (tile != tile_ref[jnp.maximum(pair - 1, 0)])
        kept = jnp.where(first, 0.0, out_ref[...].astype(jnp.float32))
        out_ref[...] = jnp.where(own, acc_ref[...], kept).astype(
            out_ref.dtype)


def expert_products(rows: Array, w_in: Array, w_out: Array, layer,
                    sizes: Array, tiled: Array, hidden_tile: int) -> Array:
    """The experts' two products in one Pallas kernel that multiplies each
    group's weights against the row tiles its own rows lie in, and against
    nothing else. rows (r, dim), sorted by group, group ``g`` holding
    ``sizes[g]`` of them, ``tiled`` (``tile_sizes``) of those in each row
    tile; w_in (L * E, dim, 2 * hidden) and w_out (L * E, hidden, dim), a
    whole scanned stack as its groups, indexed in place at ``layer * E +
    group`` through scalar prefetch: no slice, no copy. -> (r, dim) in the
    rows' type: each group's rows through its expert, the rows of a
    VISITED tile that lie in no group zero, an unvisited tile's unwritten.

    The grid is (visited pairs, hidden tiles): the (row tile of
    ``ROW_TILE``, group) pairs that share a row, tile by tile and group by
    group within a tile, so that a tile's output block is revisited by
    consecutive steps only; its first bound is the count of such pairs, a
    traced number: no step idles on an untouched expert or a dead tile. A
    step's operands are bfloat16, its three products accumulate in f32,
    and the gated hidden units pass to the down product in bfloat16, as
    between the compiler's two grouped products. A group whose rows
    straddle a tile boundary is visited by both tiles, one after the
    other: where a step takes a whole expert (one hidden tile) the second
    visit finds the blocks it needs in place and reads nothing, so each
    touched expert is read once; where it takes a hidden tile of several,
    each visit reads the expert again."""
    r, dim = rows.shape
    e, hidden = sizes.shape[0], w_out.shape[1]
    hidden_tiles = hidden // hidden_tile
    with jax.named_scope("moe.route"):
        visited = (tiled > 0).reshape(-1)                   # tile-major
        pairs = jnp.nonzero(visited, size=r // ROW_TILE + e - 1,
                            fill_value=0)[0].astype(jnp.int32)
        ends = jnp.cumsum(sizes)
        prefetch = (pairs // e, pairs % e, ends - sizes, ends,
                    jnp.asarray(layer * e, jnp.int32).reshape(1))
        # a share that no pair fell on still zeroes a tile
        visits = jnp.maximum(jnp.sum(visited), 1).astype(jnp.int32)

    def rows_map(pair, j, tile, group, start, end, offset):
        return tile[pair], 0

    def column_map(first):
        def index(pair, j, tile, group, start, end, offset):
            return offset[0] + group[pair], 0, first + j
        return index

    def down_map(pair, j, tile, group, start, end, offset):
        return offset[0] + group[pair], j, 0

    columns = (None, dim, hidden_tile)
    block_bytes = 3 * dim * hidden_tile * w_in.dtype.itemsize
    with jax.named_scope("moe.experts"):
        return pl.pallas_call(
            _experts_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(visits, hidden_tiles),
                in_specs=[
                    pl.BlockSpec((ROW_TILE, dim), rows_map),
                    pl.BlockSpec(columns, column_map(0)),           # gate
                    pl.BlockSpec(columns, column_map(hidden_tiles)),  # up
                    pl.BlockSpec((None, hidden_tile, dim), down_map)],
                out_specs=pl.BlockSpec((ROW_TILE, dim), rows_map),
                scratch_shapes=[pltpu.VMEM((ROW_TILE, dim), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((r, dim), rows.dtype),
            # an output block is revisited along the pairs and summed
            # along the hidden tiles: both run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=2 * block_bytes + (16 << 20)),
            interpret=core.pallas_interpret(),
            name="moe.experts",
        )(*prefetch, rows, w_in, w_in, w_out)


def dropless_experts(experts: dict, x: Array, picks: Array,
                     weights: Array, first: Optional[int] = None,
                     num_experts: Optional[int] = None):
    """Every (token, pick) pair through its expert, summed per token with
    its weight. x (t, dim), picks / weights (t, k) -> (out (t, dim),
    sizes (E,) int32: picks each HELD expert received, the rows handed to
    the grouped products, int32, the reads of an expert's weights, int32).

    ``first`` is None where the E experts of ``experts`` are all that the
    picks name. Where they are a share, ``first`` .. ``first + E`` of
    ``num_experts``, a pair whose expert is held elsewhere is sorted
    behind every group and lies in none: it adds nothing. Handed to the
    COMPILER'S grouped product it is not free: that kernel multiplies
    EVERY row it is handed against each touched group's weights and keeps
    the rows of the group, so a row in no group costs a row of every
    touched group's tile (at 512 pair rows of which 32 are held, sixteen
    times the products' work, and past what the weights' read takes). So
    a share's products are handed the first R sorted rows only, R the
    least step of ``row_ladder`` that holds ``sum(sizes)``, chosen by one
    ``lax.switch`` around the products; the sort, the sizes and the way
    back to token order stay outside it. Where the ladder is one step
    (every expert held, or few pairs) there is no switch.

    WHICH PRODUCTS RUN is decided a step of the call's ladder, from the
    rows that step hands them (``kernel_hidden_tile``; a call without a
    ladder is its one step, and every ladder of PRs 41-44 lies on one side
    of ``KERNEL_ROWS``; 640 pairs of which a quarter is held run their
    first step, 512 rows, in the kernel and the step of every pair on the
    compiler's product): a decode
    step's pair rows (at most ``KERNEL_ROWS``) in bfloat16 at widths that
    fill the lanes run in the repo's kernel (``expert_products``), which
    visits the (row tile, group) pairs that share a row and multiplies a
    group's weights against its own tiles only, both products in one pass
    over the expert: the four routed configurations' decode steps, in a
    scratch call 0.06-0.31 ms a layer under the compiler's products
    (PERF.md section 6, PR 44). There the load's fourth entry is each
    touched expert once
    where a grid step takes a whole expert, and the visited pairs where it
    takes a tile of one. The ladder stays around the kernel, though a dead
    tile costs the kernel nothing: the kernel over all 512 rows ran 0.6%
    faster than in the ladder's first step, not worth a second meaning of
    the load's last entry in this change (PERF.md Open questions).

    Every other call (a prefill's thousands of rows, which are bound by
    their operations and which the compiler's kernel cuts and passes
    itself; float32; widths under a lane) runs ``lax.ragged_dot``, where a
    row that lies in ANOTHER group costs the same as one that lies in
    none. So such a call without a ladder runs its products a ROW TILE at
    a time (``row_tiles``): the sorted rows are cut into static tiles of
    ``ROW_TILE`` rows, and each tile's two products run over the groups
    clipped to it (``tile_sizes``). A touched group is then multiplied
    against the 64 rows its picks lie in, a group with no row in a tile
    is not read for it, and one that straddles a boundary is read by both
    tiles: the fourth output counts that. Tiles do not engage at
    ``RIDGE_ROWS`` or fewer (the operations hide beneath the read there,
    and a tile costs a call: 192 rows over 99 of 128 smaller experts ran
    3% slower in tiles), over ``KERNEL_ROWS`` (the compiler's kernel cuts
    such rows itself and passes the tiles that hold no row of a group), or
    inside a ladder's steps, whose row count is the cut already.

    ``experts`` holds ``w_in`` (E, dim, 2 * hidden) and ``w_out``; or, from
    a scanned stack (``ops.transformer.block_stack``), the WHOLE stack's
    (L, E, ...) with ``layer``, this layer's index in it: the compiler is
    given no slice of a layer to copy first (a scan's slice of a layer's
    experts, handed to a kernel, is a copy of them: 1.2 GB a layer a step
    at the published widths). Both products take the stack as its L * E
    groups: the repo's kernel indexes it at ``layer * E + group``, the
    compiler's grouped product runs over all the groups with this layer's
    sizes laid at its offset and zero elsewhere, and reads the groups that
    hold rows."""
    t, k = picks.shape
    e = experts["w_in"].shape[-3]
    stacked = "layer" in experts
    ladder = (t * k,) if first is None else row_ladder(t * k, e, num_experts)
    # a step's shapes decide its products: the rows it hands them
    hidden = experts["w_out"].shape[-2]
    hidden_tiles = {r: kernel_hidden_tile(r, x.shape[-1], hidden, x.dtype)
                    if experts["w_in"].dtype == x.dtype else None
                    for r in ladder}
    # a call without a ladder, off the kernel, whose rows the compiler's
    # products take a tile at a time
    in_tiles = len(ladder) == 1 and not hidden_tiles[t * k] \
        and row_tiles(t * k) > 1
    with jax.named_scope("moe.route"):
        flat = picks.reshape(-1)
        if first is not None:
            # an absent pair's key is E: behind the last group, and out
            # of range of ``sizes`` (a scatter drops such an index)
            here = (flat >= first) & (flat < first + e)
            flat = jnp.where(here, flat - first, e)
        order = jnp.argsort(flat, stable=True)      # pairs, by expert
        sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
        # (tiles, E): the kernel's visits, or the compiler's tiles
        tiled = tile_sizes(sizes, t * k) \
            if in_tiles or any(hidden_tiles.values()) else None

    def compiler_products(rows):
        """``lax.ragged_dot`` twice over the rows, a row tile at a time."""
        w_in, w_out = experts["w_in"], experts["w_out"]
        with jax.named_scope("moe.route"):
            groups = list(tiled) if in_tiles else [sizes]
            if stacked:
                w_in = w_in.reshape((-1,) + w_in.shape[2:])
                w_out = w_out.reshape((-1,) + w_out.shape[2:])
                groups = [lax.dynamic_update_slice(
                    jnp.zeros((w_in.shape[0],), jnp.int32), g,
                    (experts["layer"] * e,)) for g in groups]
        with jax.named_scope("moe.experts"):
            outs = []
            for j, g in enumerate(groups):
                tile = rows[j * ROW_TILE:(j + 1) * ROW_TILE] if in_tiles \
                    else rows
                gate, up = jnp.split(
                    lax.ragged_dot(tile, w_in.astype(x.dtype), g), 2,
                    axis=-1)
                outs.append(lax.ragged_dot(jax.nn.silu(gate) * up,
                                           w_out.astype(x.dtype), g))
            return jnp.concatenate(outs) if in_tiles else outs[0]

    def products(r: int):
        """The first ``r`` sorted pairs through their experts -> (t * k,
        dim) f32 in sorted order, the rows behind ``r`` zero."""
        with jax.named_scope("moe.route"):
            rows = jnp.take(x, order[:r] // k, axis=0)      # (r, dim)
            pair_weights = jnp.take(weights.reshape(-1), order[:r])
        if hidden_tiles[r]:
            w_in, w_out = experts["w_in"], experts["w_out"]
            out = expert_products(
                rows, w_in.reshape((-1,) + w_in.shape[-2:]),
                w_out.reshape((-1,) + w_out.shape[-2:]),
                experts["layer"] if stacked else 0, sizes,
                tiled[:r // ROW_TILE], hidden_tiles[r])
        else:
            out = compiler_products(rows)
        with jax.named_scope("moe.experts"):
            # each pair's weight, in f32, here: the compiler's
            # grouped-product kernel carries no scope of its own and takes
            # its first reader's (the repo's kernel carries this scope's
            # name)
            out = out.astype(jnp.float32) * pair_weights[:, None]
            if first is not None:
                # rows behind the last group are no product's output (the
                # repo's kernel leaves a tile it did not visit unwritten)
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
        # one tile reads each touched group once; so does the kernel where
        # a grid step takes a whole expert. A step (of the ladder, or a
        # call in tiles) that takes less reads a group a tile it lies in
        once = jnp.sum(sizes > 0).astype(jnp.int32)
        again = [in_tiles or hidden_tiles[r] not in (None, hidden)
                 for r in ladder]
        if not any(again):
            reads = once
        else:
            visits = jnp.sum(tiled > 0).astype(jnp.int32)
            reads = visits if all(again) else jnp.where(
                jnp.asarray(again)[step], visits, once)
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
                           blk.routed_scale, blk.route_eps, blk.route_scores)
    whole = holds_all(blk)
    out, sizes, handed, reads = dropless_experts(
        params["experts"], xt, picks, weights,
        None if whole else blk.first_expert, blk.num_experts)
    if "shared" in params:
        with jax.named_scope("moe.shared"):
            shared = core.swiglu(params["shared"], xt)
            if "shared_gate" in params:
                shared = shared * jax.nn.sigmoid(
                    core.linear(params["shared_gate"], xt))
            out = out + shared
    held = jnp.sum(sizes)
    load = [held if whole else jnp.int32(picks.size),
            jnp.sum(sizes > 0).astype(jnp.int32), jnp.max(sizes), reads]
    if not whole:
        load += [held, handed]
    return out.reshape(lead + (-1,)), jnp.stack(load)
