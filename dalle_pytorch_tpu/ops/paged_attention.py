"""Ragged paged-attention decode — Pallas TPU kernel over the page pool.

The paged KV layout (serve/kv_pool.py) won HBM *residency*: the pool is
far smaller than ``num_slots × seq_len``. It did not win read traffic —
the gather path (``ops.decode.layer_pool_view``, per layer since PR 25)
reads every page of a slot's table on every step, live or not, so the
bytes moved per token are the dense layout's.
This module is the chip-side fix (PAPERS.md *Ragged Paged Attention*):
a kernel that consumes the block tables IN PLACE and walks only the
live pages.

Shape of the computation (one ``pl.pallas_call`` per layer, inside the
engine's fused K-step decode scan):

  * grid = ``(slots, heads // head_tile, trips)`` — one (slot,
    head-tile) pair per output block, walked page by page along the
    innermost (sequential) trip axis. A page is whole rows, ``(page_size,
    heads * dh)`` (``kv_pool.page_layout``), and a tile's heads are read
    in ONE product a page: the tile's queries block-diagonal against the
    tile's columns of the rows (``ops.attention._own_columns``, the
    gather read's form), so the scores of all its heads come out ``(head
    tile, page_size)`` and the softmax recurrence runs on them at once;
  * the per-slot walk state — ``pos``, the block-table row and, for a
    sparse layer, the visible-page list — is SCALAR-PREFETCHED into
    SMEM, and the K/V block specs' index maps chase the block table
    through it: trip ``p`` of slot ``i`` stages physical page
    ``block_tables[i, p]``. Pages stay in HBM and transit VMEM one at
    a time, double-buffered by the Pallas pipeline — page ``p+1``'s
    copy is in flight while page ``p`` is on the MXU (the pool never
    transits VMEM whole, which is what the dense-view gather
    effectively forces);
  * the walk is RAGGED per slot: a slot 10 tokens into a 1280-token
    sequence computes on 1 page, not 80. Trips past the slot's
    ``ceil(pos / page_size)`` re-address its last live page, so they
    copy nothing (the pipeline skips a block it already holds) and
    compute nothing; a dead slot parked at pos 0 holds the reserved
    trash page and never reads it;
  * attention is the online-softmax recurrence over pages
    (flash-attention's m/l bookkeeping) carried in the revisited output
    blocks, returning UNNORMALIZED partials ``(acc, m, l)`` over the
    cached rows only — the caller (``ops.decode._decode_step_math``)
    folds in the current token's self-logit with the standard
    two-estimate softmax merge, which is exactly
    ``softmax(concat([scores, self]))`` up to summation order;
  * the int8-KV pool dequantizes PER PAGE: int8 K/V pages stage as
    int8 (half the bytes — the point of int8-KV), and the f32 scale
    pages ``(page_size, heads)`` apply outside the contractions,
    mirroring the gather path's register-upcast trick.

Masking parity with the gather path (``_decode_step_math``): dead rows
(causal ``j >= pos``, pad, sparse-layout holes) are filled with the
same finite ``core.neg_inf`` fill; because the self-logit is always a
live finite score, those rows underflow to weight 0.0 exactly in both
implementations, so kernel-vs-gather agreement is limited only by
summation order (allclose; emitted tokens byte-identical in practice —
tests/test_paged_attention.py pins both). The gather path stays as the
parity ORACLE: it is token-equal to the dense cache by construction,
so any kernel regression surfaces as a diff against it rather than as
silently wrong images.

``interpret=None`` takes ``ops.core.pallas_interpret()`` (compiled on a
TPU, the Pallas interpreter elsewhere), so the same code path runs in
tier-1 on the CPU mesh — including the scalar-prefetch pipeline, which
the interpreter emulates.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_pytorch_tpu.ops import attention, core

# NOTE: this module deliberately has no module-level serve import (ops
# must not depend on serve at import time — the dependency runs the
# other way). The page-size gate lives in serve/kv_pool.py, next to the
# other typed pool errors and importable without jax; the kernel entry
# fetches it lazily.

Array = jax.Array

# finite mask fill, BY CONSTRUCTION the gather path's substitution
# constant (ops.core.neg_inf = -finfo(dtype).max): masked rows underflow
# to exactly 0 weight once any live score enters the running max, so
# degenerate rows agree exactly between the kernel and the oracle — the
# same -finfo max formula, spelled in dtype METADATA rather than through
# a jnp op, because this module may first be imported from inside a
# traced function (the decode scan's lazy import) where any jnp op
# would become an abstract tracer (tests pin the equality)
FILL = -float(jnp.finfo(jnp.float32).max)

NUM_LANES = 128        # f32 VREG lane width — m/l stats stored broadcast


def _walk(i, p, pos_ref, vis_refs, page_size: int):
    """(live, logical page) of trip ``p`` of slot ``i``'s walk, from the
    scalar-prefetched per-slot state. The prefix walk visits logical
    pages ``0..ceil(pos/ps)``; the sparsity-aware walk (``vis_refs`` =
    (visible, visible_cnt)) follows the slot's precomputed visible-page
    LIST instead. Trips past the slot's ragged count re-address its
    LAST live page (a dead slot parked at pos 0: its table's column 0,
    the trash page), so consecutive dead trips name the block already
    resident and the pipeline issues no copy for them. Shared by the
    K/V index maps and the kernel body so the page a trip computes on
    is by construction the page its block spec staged."""
    if vis_refs:
        vis_ref, cnt_ref = vis_refs
        n_pages = cnt_ref[i]
    else:
        n_pages = lax.div(pos_ref[i] + (page_size - 1), page_size)
    pc = jnp.minimum(p, jnp.maximum(n_pages - 1, 0))
    return p < n_pages, (vis_ref[i, pc] if vis_refs else pc)


def _by_head(scales):
    """An int8 pool's scale page (page_size, head tile) as the scores lie,
    (head tile, page_size): the product with an identity, exact in
    float32 at the highest precision, where Mosaic has no transpose of a
    tile this small."""
    ht = scales.shape[1]
    eye = (lax.broadcasted_iota(jnp.int32, (ht, ht), 0)
           == lax.broadcasted_iota(jnp.int32, (ht, ht), 1))
    return lax.dot_general(eye.astype(jnp.float32), scales,
                           (((1,), (1,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _kernel(*refs, scale: float, page_size: int, quantized: bool,
            visible: bool):
    """One (slot, head-tile, trip) grid step: fold the trip's staged
    K/V page into the slot's online softmax, all the tile's heads at once.

    The scalar-prefetch operands come first (whole arrays in SMEM):
    ``pos`` (b,), ``block_tables`` (b, max_pages) and, under
    ``visible=True``, the sparsity-aware walk's ``visible`` (b, W) page
    list and ``visible_cnt`` (b,). ``q_ref`` holds the tile's queries
    block-diagonal, (head tile, head tile * dh): head h's dh numbers in
    its own columns and zeros in the others'. The K/V (and scale) refs
    are the tile's columns of the ONE page this trip's index map selected
    through the block table, (page_size, head tile * dh); the output
    blocks are revisited across the trip axis and carry the running (acc,
    m, l), acc as whole-row sums (head tile, head tile * dh) of which the
    caller keeps each head's own columns. Under the visible walk, skipped
    pages carry exactly-zero softmax weight under the finite FILL, so the
    online recurrence over the remaining (still ascending) pages is
    bit-equal to the prefix walk: max(m, FILL)=m, l*exp(0)+0=l,
    acc*1+0=acc."""
    n_prefetch = 4 if visible else 2
    pos_ref, _bt_ref, *vis_refs = refs[:n_prefetch]
    q_ref, allowed_ref, k_ref, v_ref, *refs = refs[n_prefetch:]
    if quantized:
        ksc_ref, vsc_ref, acc_ref, m_ref, l_ref = refs
    else:
        acc_ref, m_ref, l_ref = refs
    i = pl.program_id(0)
    p = pl.program_id(2)
    live, lp = _walk(i, p, pos_ref, vis_refs, page_size)

    @pl.when(p == 0)
    def _init():
        # a slot that walks zero pages returns exactly (0, FILL, 0)
        acc_ref[0] = jnp.zeros(acc_ref.shape[1:], jnp.float32)
        m_ref[0] = jnp.full(m_ref.shape[1:], FILL, jnp.float32)
        l_ref[0] = jnp.zeros(l_ref.shape[1:], jnp.float32)

    @pl.when(live)
    def _page():
        q = q_ref[0]                                       # (ht, ht * dh)
        # the page's mask row: allowed is laid out (max_pages, ps), so
        # a page is one sublane row (a dynamic LANE slice of ps
        # elements is not addressable)
        ok = allowed_ref[0, pl.ds(lp, 1), :] != 0          # (1, ps)
        kb, vb = k_ref[...], v_ref[...]                    # (ps, ht * dh)
        if quantized:
            kb, vb = kb.astype(q.dtype), vb.astype(q.dtype)
        m = m_ref[0, :, :1]                                # (ht, 1)
        l = l_ref[0, :, :1]
        # block-diagonal queries x whole rows -> (ht, ps) scores in f32
        s = lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if quantized:
            # scales OUTSIDE the contraction — no dequantized page
            # copy materializes (ops/decode.py's int8 discipline)
            s = s * _by_head(ksc_ref[...])
        s = jnp.where(ok, s, FILL)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)                          # (ht, ps)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + pexp.sum(axis=-1, keepdims=True)
        wj = pexp
        if quantized:
            wj = wj * _by_head(vsc_ref[...])
        acc_ref[0] = acc_ref[0] * alpha + lax.dot_general(
            wj.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (ht, ht * dh)
        # lane-broadcast stats tiles (the flash_attention layout):
        # Mosaic wants the last dim to be a 128-lane tile, and the
        # caller reads lane 0
        m_ref[0] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(l, l_ref.shape[1:])


def paged_decode_attention(q: Array, k_pages: Array, v_pages: Array,
                           block_tables: Array, pos: Array,
                           allowed: Array, *, scale: float,
                           k_scales: Optional[Array] = None,
                           v_scales: Optional[Array] = None,
                           visible: Optional[Array] = None,
                           visible_cnt: Optional[Array] = None,
                           head_tile: int = 0,
                           interpret: Optional[bool] = None,
                           ) -> Tuple[Array, Array, Array]:
    """Online-softmax attention partials over one layer's paged K/V.

    q: (b, heads, dh) — the decode step's single query per slot.
    k_pages/v_pages: (P, page_size, heads * dh) page pool of whole rows
    (int8 when quantized, with k_scales/v_scales (P, page_size, heads)
    f32). ``head_tile`` heads a grid step (0: all of them); a tile's
    columns of a page, ``head_tile * dh``, must be whole 128-lane tiles
    unless the tile is every head.
    block_tables: (b, max_pages) int32; pos: (b,) int32 per-slot
    positions; allowed: (b, L) bool — the gather path's full row mask
    (causal & pad & sparse), True = attend.

    ``visible``/``visible_cnt`` (both or neither) select the
    sparsity-aware walk: visible (b, W) int32 lists each slot's
    visible LOGICAL page ids in ascending order (entries must index
    ``block_tables`` columns), visible_cnt (b,) int32 how many are
    live — the per-(slot, layer) trip list a sparse layer's statically
    precomputed page visibility produces (ops.sparse.visible_pages;
    the caller applies the token-causal trim so entries never start at
    or past ``pos``). The kernel then fetches ONLY those pages; every
    skipped page is fully masked in ``allowed`` so its softmax weight
    is exactly zero and the partials are bit-equal to the prefix walk.

    Returns f32 ``(acc, m, l)``: acc (b, heads, dh) the unnormalized
    exp-weighted V sum over cached rows, m (b, heads) the running max
    score, l (b, heads) the exp sum — the caller merges the self-logit
    (ops.decode._decode_step_math) to complete the softmax. Rows the
    mask kills carry exactly 0 weight (finite-FILL underflow), so a
    slot at pos 0 returns (0, FILL, 0) and degrades to pure
    self-attention, identical to the gather path.
    """
    from dalle_pytorch_tpu.serve import kv_pool as KV
    b, heads, dh = q.shape
    P, page_size, _ = k_pages.shape
    L = allowed.shape[1]
    KV.validate_page_size(page_size)
    quantized = k_scales is not None
    if (visible is None) != (visible_cnt is None):
        raise ValueError("visible and visible_cnt come together: the "
                         "visible-page list is meaningless without its "
                         "per-slot live count (and vice versa)")
    if interpret is None:
        interpret = core.pallas_interpret()
    ht = int(head_tile) or heads
    if heads % ht:
        raise ValueError(f"head_tile {ht} must divide heads {heads}")
    max_pages = block_tables.shape[1]
    if max_pages * page_size < L:
        raise ValueError(
            f"block tables map {max_pages} pages of {page_size} rows "
            f"< allowed length {L}")
    if visible is not None and visible.shape[1] > max_pages:
        raise ValueError(
            f"visible lists {visible.shape[1]} pages per slot > the "
            f"{max_pages}-column block tables they index")
    # pad the mask out to whole pages: the last page can span logical
    # rows past L, and pl.ds CLAMPS out-of-bounds starts (dynamic_slice
    # semantics) — an unpadded mask would alias the tail page onto the
    # wrong rows. Padding is False = never attended.
    L_pages = max_pages * page_size
    if L < L_pages:
        allowed = jnp.pad(allowed, ((0, 0), (0, L_pages - L)))

    kernel = functools.partial(
        _kernel, scale=float(scale), page_size=page_size,
        quantized=quantized, visible=visible is not None)
    # a tile's queries block-diagonal over the tile's columns of a row
    q_wide = attention._own_columns(
        q.reshape(b * (heads // ht), ht, 1, dh)).reshape(b, heads, ht * dh)

    # scalar prefetch: the per-slot walk state (positions, block tables,
    # visible-page lists) lands whole in SMEM before the body runs, so
    # the K/V index maps can chase the block table: trip p of slot i
    # stages physical page block_tables[i, logical(p)], and the
    # pipeline double-buffers — page p+1's copy is in flight while page
    # p is on the MXU
    prefetch = [pos.astype(jnp.int32), block_tables.astype(jnp.int32)]
    trips = max_pages
    if visible is not None:
        prefetch += [visible.astype(jnp.int32),
                     visible_cnt.astype(jnp.int32)]
        trips = visible.shape[1]

    def tile_map(i, t, p, *_):
        return i, t, 0

    def page_map(i, t, p, pos_ref, bt_ref, *vis_refs):
        _, lp = _walk(i, p, pos_ref, vis_refs, page_size)
        return bt_ref[i, lp], 0, t

    in_specs = [
        pl.BlockSpec((1, ht, ht * dh), tile_map),          # q tile
        pl.BlockSpec((1, max_pages, page_size),
                     lambda i, t, p, *_: (i, 0, 0)),       # allowed rows
        pl.BlockSpec((None, page_size, ht * dh), page_map),  # K page
        pl.BlockSpec((None, page_size, ht * dh), page_map),  # V page
    ]
    inputs = [q_wide,
              allowed.astype(jnp.int32).reshape(b, max_pages, page_size),
              k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((None, page_size, ht), page_map)] * 2
        inputs += [k_scales, v_scales]

    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, heads // ht, trips),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, ht, ht * dh), tile_map),
                       pl.BlockSpec((1, ht, NUM_LANES), tile_map),
                       pl.BlockSpec((1, ht, NUM_LANES), tile_map)]),
        out_shape=[
            jax.ShapeDtypeStruct((b, heads, ht * dh), jnp.float32),
            jax.ShapeDtypeStruct((b, heads, NUM_LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, heads, NUM_LANES), jnp.float32),
        ],
        # the (acc, m, l) output blocks are revisited along the trip
        # axis — it must run in order on one core; slots and head
        # tiles are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attn",
    )(*prefetch, *inputs)
    # of each whole-row sum, the head's own columns
    acc = jnp.einsum("btgjd,gj->btgd",
                     acc.reshape(b, heads // ht, ht, ht, dh),
                     jnp.eye(ht, dtype=acc.dtype))
    return acc.reshape(b, heads, dh), m[:, :, 0], l[:, :, 0]


def modeled_kv_read_bytes_per_token(*, depth: int, heads: int,
                                    dim_head: int, total_len: int,
                                    page_size: int, prompt_len: int,
                                    itemsize: int, impl: str,
                                    quantized: bool = False,
                                    sparse_reads: bool = False,
                                    sparse_pattern=None,
                                    sparse_block: int = 16,
                                    causal: bool = True) -> float:
    """Analytic KV-read bytes per decoded token for one slot, for
    either read (HBM counters are not observable from the host, and on CPU the
    kernel runs interpreted, so the comparison is a model: the gather
    path reads the FULL ``total_len`` view every step regardless of
    position, the kernel reads only the ``ceil(pos/page_size)`` mapped
    pages, averaged over the decode span ``[prompt_len, total_len)``).
    K + V both counted; the int8 pool adds one f32 scale per row per
    K and V.

    ``sparse_reads=True`` models the sparsity-aware read
    (``sparse_pattern`` required — the per-layer dense/sparse tuple):
    dense layers read as above, sparse layers read only their
    statically visible pages (``ops.sparse.visible_pages`` on the
    VariableSparsity layout) — the kernel walks the token-causal
    visible count per position, the gather reads the fixed trimmed
    width ``W`` (the fixed-shape program's static bound)."""
    row = 2 * dim_head * itemsize          # K + V
    if quantized:
        row += 2 * 4                        # per-row f32 scales
    span = range(int(prompt_len), int(total_len))
    if impl == "gather":
        rows = float(total_len)
    elif impl == "kernel":
        rows = (sum(-(-p // page_size) for p in span)   # ceil(pos/ps)
                * page_size / max(len(span), 1))
    else:
        raise ValueError(f"impl must be 'gather' or 'kernel', got "
                         f"{impl!r}")
    if not sparse_reads:
        return depth * heads * rows * row
    if sparse_pattern is None or len(sparse_pattern) != depth:
        raise ValueError("sparse_reads=True needs the per-layer "
                         "sparse_pattern (length == depth) to split "
                         "dense from sparse layer reads")
    # the CACHED shared precompute the decode step math itself walks
    # (ops.sparse.visible_pages_causal via decode._sparse_page_
    # visibility) — one source, so the model cannot drift from the read
    from dalle_pytorch_tpu.ops import sparse as sparse_ops
    vis, _cnt, cnt_causal = sparse_ops.visible_pages_causal(
        total_len, page_size, sparse_block, causal=causal)
    if impl == "gather":
        rows_sparse = float(vis.shape[1] * page_size)
    else:
        rows_sparse = (sum(int(cnt_causal[p]) for p in span)
                       * page_size / max(len(span), 1))
    n_sparse = sum(bool(s) for s in sparse_pattern)
    return heads * row * ((depth - n_sparse) * rows
                          + n_sparse * rows_sparse)
