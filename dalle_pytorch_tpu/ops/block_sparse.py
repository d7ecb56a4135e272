"""Block-sparse attention — Pallas TPU kernel for the VariableSparsity
layout.

The TPU-native replacement for the DeepSpeed/Triton ``SparseSelfAttention``
the reference delegates to (reference dalle_pytorch/transformer.py:91-135;
build recipe install_deepspeed.sh:1-3) — SURVEY.md §2a row 1.

The layout is the VariableSparsityConfig default the reference constructs
(block=16, local window of 4 blocks, global block 0, optional causal —
ops.sparse.variable_sparsity_layout is the oracle): fully PROCEDURAL, so the
kernel needs no mask tensors — a score tile at absolute (rows, cols) allows

    (rows//W == cols//W) | (cols//block ∈ global_blocks)   [& cols <= rows]

with W = num_local_blocks*block tokens. The kernel tiles at MXU size
(128×128 by default, vs the 16-token logical block) and SKIPS every tile
whose 128-window provably intersects no allowed block — at seq 1280 with the
default layout that is a 13.5× FLOP cut at depth-64's sparse layers
(per q-tile only the diagonal tile + the global tile survive).

Backward: the shared blockwise scan (ops.flash_attention.
blockwise_attention_bwd) with the layout as the structural mask. Pad-key
masking follows the reference SparseAttention contract: KEYS only, queries
unmasked (reference transformer.py:120-122).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from dalle_pytorch_tpu.ops import core
from dalle_pytorch_tpu.ops.flash_attention import (FILL, NUM_LANES,
                                                   NUM_SUBLANES,
                                                   blockwise_attention_bwd)

Array = jax.Array


def _structural(rows, cols, *, block, window, global_blocks, causal):
    """Layout mask at absolute positions; ``rows`` and ``cols`` are mutually
    broadcastable (e.g. (BQ, 1) x (1, BK)) — kept 2-D so the Pallas kernel
    never builds 1-D vectors Mosaic can't lower."""
    same_window = (rows // window) == (cols // window)
    allow = same_window
    for g in global_blocks:
        allow = allow | ((cols // block) == g)
    if causal:
        allow = allow & (cols <= rows)
    return allow


def _static_tile_schedule(block_q, block_k, block, window, global_blocks,
                          causal):
    """The default VariableSparsity layout admits a STATIC k-tile schedule:
    when q and k tiles are the same size and the local window divides the
    tile, every row of q-tile ``iq`` finds its whole local window inside
    k-tile ``iq``; if additionally each global block sits wholly inside
    one statically-known k-tile, the complete schedule is
    ``{global tiles} + {diagonal}`` — no scan over tiles, no per-tile
    ``lax.cond`` predication (the r4-measured loss vs the XLA oracle was
    exactly that loop overhead: 10 causal tiles scanned to execute 2).
    Returns the sorted global-tile list, or None when the layout doesn't
    admit the static schedule (fall back to the scanning kernel)."""
    if block_q != block_k or block_k % window != 0 or not causal:
        return None
    tiles = set()
    for g in global_blocks:
        lo, hi = g * block, g * block + block - 1
        if lo // block_k != hi // block_k:
            return None                   # global block straddles tiles
        tiles.add(lo // block_k)
    return sorted(tiles)


def _kernel(*refs, scale, causal, block_q, block_k, seq_len, has_mask, block,
            window, global_blocks):
    if has_mask:
        mk_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
    iq = pl.program_id(1)
    # input-dtype MXU operands + f32 accumulation (bf16 runs the systolic
    # array at full rate); the scale applies to the f32 scores
    q = q_ref[0]
    rows = iq * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)                       # (BQ, 1)

    def update(ik, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(ik * block_k, block_k), :]
        vb = v_ref[0, pl.ds(ik * block_k, block_k), :]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * scale
        cols = ik * block_k + lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)               # (1, BK)
        if has_mask:
            km = mk_ref[0, :1, pl.ds(ik * block_k, block_k)] != 0
            s = jnp.where(km, s, FILL)        # keys only (reference)
        struct = _structural(rows, cols, block=block, window=window,
                             global_blocks=global_blocks, causal=causal)
        if seq_len % block_k:             # ragged tail tile bounds
            struct = struct & (cols < seq_len)
        s = jnp.where(struct, s, -jnp.inf)

        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - shift), 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - shift), 0.0)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    carry0 = (m0, l0, a0)

    static_tiles = _static_tile_schedule(block_q, block_k, block, window,
                                         global_blocks, causal)
    if static_tiles is not None:
        # static schedule: the (python-unrolled) global tiles, then the
        # diagonal — exactly the tiles the layout allows, 2 MXU tiles per
        # grid step at the default layout instead of a 10-tile scan
        carry = carry0
        for gt in static_tiles:
            # causal: a global tile in the future of this q-tile is fully
            # masked; one cond per STATIC tile (len 1 by default)
            carry = lax.cond(jnp.int32(gt) <= iq,
                             functools.partial(update, jnp.int32(gt)),
                             lambda c: c, carry)
        dup = jnp.zeros((), bool)
        for gt in static_tiles:           # diagonal may BE a global tile
            dup = dup | (iq == gt)
        m, l, acc = lax.cond(dup, lambda c: c,
                             functools.partial(update, iq), carry)
    else:
        num_k = pl.cdiv(seq_len, block_k)
        if causal:
            num_k = jnp.minimum(num_k,
                                pl.cdiv((iq + 1) * block_q, block_k))

        w_lo_q = (iq * block_q) // window
        w_hi_q = (iq * block_q + block_q - 1) // window

        def tile_any(ik):
            w_lo_k = (ik * block_k) // window
            w_hi_k = (ik * block_k + block_k - 1) // window
            overlap = (w_lo_k <= w_hi_q) & (w_lo_q <= w_hi_k)
            for g in global_blocks:
                tok = g * block
                overlap = overlap | ((tok >= ik * block_k)
                                     & (tok < (ik + 1) * block_k))
            return overlap

        def body(ik, carry):
            return lax.cond(tile_any(ik), functools.partial(update, ik),
                            lambda c: c, carry)

        m, l, acc = lax.fori_loop(0, num_k, body, carry0)

    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # (m, l) saved separately — see ops.flash_attention on lse absorption;
    # lane-broadcast (BQ, 128) tiles to satisfy Mosaic tiling.
    m_fin = jnp.where(jnp.isfinite(m), m, 0.0)
    m_ref[0] = jnp.broadcast_to(m_fin, (block_q, NUM_LANES))
    l_ref[0] = jnp.broadcast_to(l_safe, (block_q, NUM_LANES))


@jax.named_scope("attn.sparse_fwd")
def _bs_fwd(q, k, v, mask, *static):
    """The forward kernel, one shard of batch and heads per device under
    a mesh (a Mosaic kernel cannot be auto-partitioned)."""
    return core.shard_over_batch_and_heads(
        lambda q, k, v, mask: _bs_fwd_local(q, k, v, mask, *static),
        (q, k, v, mask), ["bhnd", "bhnd", "bhnd", "bn"],
        ("bhnd", ("bhn", "bhn")))


def _bs_fwd_local(q, k, v, mask, scale, causal, block, num_local_blocks,
                  global_blocks, block_q, block_k, interpret):
    from dalle_pytorch_tpu.ops.flash_attention import _pad_seq
    b, h, n_orig, d = q.shape
    mult = max(block_q, block_k)
    q = _pad_seq(q, mult, 2)
    k = _pad_seq(k, mult, 2)
    v = _pad_seq(v, mult, 2)
    b, h, n, d = q.shape
    bh = b * h
    has_mask = mask is not None
    window = num_local_blocks * block

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_len=n_orig, has_mask=has_mask, block=block,
        window=window, global_blocks=global_blocks)

    in_specs = []
    inputs = []
    if has_mask:
        mask_in = _pad_seq(mask, mult, 1).astype(jnp.int32)
        # key-only pad mask (reference contract), sublane-broadcast
        mk = jnp.broadcast_to(mask_in[:, None, :], (b, NUM_SUBLANES, n))
        in_specs.append(
            pl.BlockSpec((1, NUM_SUBLANES, n), lambda ib, iq: (ib // h, 0, 0)))
        inputs.append(mk)
    in_specs += [
        pl.BlockSpec((1, block_q, d), lambda ib, iq: (ib, iq, 0)),
        pl.BlockSpec((1, n, d), lambda ib, iq: (ib, 0, 0)),
        pl.BlockSpec((1, n, d), lambda ib, iq: (ib, 0, 0)),
    ]
    inputs += [q.reshape(bh, n, d), k.reshape(bh, n, d), v.reshape(bh, n, d)]

    out, m, l = pl.pallas_call(
        kernel,
        grid=(bh, pl.cdiv(n, block_q)),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda ib, iq: (ib, iq, 0)),
            pl.BlockSpec((1, block_q, NUM_LANES), lambda ib, iq: (ib, iq, 0)),
            pl.BlockSpec((1, block_q, NUM_LANES), lambda ib, iq: (ib, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n, d), q.dtype),
            jax.ShapeDtypeStruct((bh, n, NUM_LANES), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, NUM_LANES), jnp.float32),
        ],
        interpret=interpret,
        name="attn.sparse_fwd",
    )(*inputs)
    out = out.reshape(b, h, n, d)[:, :, :n_orig]
    m = m[:, :, 0].reshape(b, h, n)[:, :, :n_orig]
    l = l[:, :, 0].reshape(b, h, n)[:, :, :n_orig]
    return out, (m, l)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(4, 11)))
def _bs(q, k, v, mask, scale, causal, block, num_local_blocks, global_blocks,
        blocks_qk, interpret):
    out, _ = _bs_fwd(q, k, v, mask, scale, causal, block, num_local_blocks,
                     global_blocks, *blocks_qk, interpret)
    return out


def _bs_fwd_rule(q, k, v, mask, scale, causal, block, num_local_blocks,
                 global_blocks, blocks_qk, interpret):
    out, stats = _bs_fwd(q, k, v, mask, scale, causal, block,
                         num_local_blocks, global_blocks, *blocks_qk,
                         interpret)
    return out, (q, k, v, mask, out, stats)


def _bs_bwd_static(q, k, v, mask, dout, out, stats, *, scale, block, window,
                   global_blocks, tile):
    """Backward specialized to the static tile schedule (global tile 0 +
    diagonal): instead of scanning every key tile at dense cost (the
    shared blockwise backward — the r4-measured reason the Pallas train
    path lost to its oracle), compute exactly the two structural pieces:

      * DIAGONAL — per-tile (tile x tile) attention blocks, one batched
        einsum over all tiles at once (no scan);
      * GLOBAL STRIP — rows of tiles 1.. against key tile 0 only.

    Work drops from num_tiles to 2 tiles per query row — the same
    schedule the forward kernel runs. Semantics mirror
    blockwise_attention_bwd exactly (pad keys FILLed with ds zeroed,
    structural -inf, f32 accumulation with input-dtype MXU operands)."""
    m_stat, l_stat = stats
    b, h, n, d = q.shape
    T = n // tile
    cdt = q.dtype
    inv_l = (1.0 / l_stat).astype(jnp.float32)
    D = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1)                                        # (b, h, n)
    ar = jnp.arange(n)

    def pieces(qi, ki, vi, doi, mi, li, Di, row_ids, col_ids, key_mask):
        """dq/dk/dv for one structural piece. Leading dims broadcast:
        qi (..., R, d), ki/vi (..., C, d), mi/li/Di (..., R), row_ids
        (..., R), col_ids (..., C), key_mask (..., C) or None."""
        s = jnp.einsum("...id,...jd->...ij", qi, ki,
                       preferred_element_type=jnp.float32) * scale
        live = None
        if key_mask is not None:
            live = key_mask[..., None, :]
            s = jnp.where(live, s, FILL)
        struct = _structural(row_ids[..., :, None], col_ids[..., None, :],
                             block=block, window=window,
                             global_blocks=global_blocks, causal=True)
        s = jnp.where(struct, s, -jnp.inf)
        p = jnp.exp(s - mi[..., None]) * li[..., None]
        dv = jnp.einsum("...ij,...id->...jd", p.astype(cdt),
                        doi.astype(cdt), preferred_element_type=jnp.float32)
        dp = jnp.einsum("...id,...jd->...ij", doi.astype(cdt),
                        vi.astype(cdt), preferred_element_type=jnp.float32)
        ds = p * (dp - Di[..., None]) * scale
        if live is not None:
            ds = jnp.where(live, ds, 0.0)
        ds_c = ds.astype(cdt)
        dk = jnp.einsum("...ij,...id->...jd", ds_c, qi.astype(cdt),
                        preferred_element_type=jnp.float32)
        dq = jnp.einsum("...ij,...jd->...id", ds_c, ki.astype(cdt),
                        preferred_element_type=jnp.float32)
        return dq, dk, dv

    def tiled(x):
        if x.ndim == 4:                       # (b, h, n, d) operands
            return x.reshape(b, h, T, tile, x.shape[-1])
        return x.reshape(b, h, T, tile)       # (b, h, n) stats

    # diagonal: every (tile x tile) block at once, batched over T
    km_d = None
    if mask is not None:
        km_d = mask.reshape(b, 1, T, tile)
    ids = ar.reshape(T, tile)
    dq_d, dk_d, dv_d = pieces(
        tiled(q), tiled(k), tiled(v), tiled(dout), tiled(m_stat),
        tiled(inv_l), tiled(D), ids, ids, km_d)

    # global strip: rows of tiles 1.. against key tile 0
    km_g = None
    if mask is not None:
        km_g = mask[:, None, :tile]
    dq_g, dk_g, dv_g = pieces(
        q[:, :, tile:], k[:, :, :tile], v[:, :, :tile], dout[:, :, tile:],
        m_stat[:, :, tile:], inv_l[:, :, tile:], D[:, :, tile:],
        ar[tile:], ar[:tile], km_g)

    dq = dq_d.reshape(b, h, n, d).at[:, :, tile:].add(dq_g)
    dk = dk_d.reshape(b, h, n, d).at[:, :, :tile].add(dk_g)
    dv = dv_d.reshape(b, h, n, d).at[:, :, :tile].add(dv_g)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@jax.named_scope("attn.sparse_bwd")
def _bs_bwd_rule(scale, causal, block, num_local_blocks, global_blocks,
                 blocks_qk, interpret, res, dout):
    q, k, v, mask, out, stats = res
    window = num_local_blocks * block
    n = q.shape[2]
    bq, bk = blocks_qk

    # the same layout factorization the forward kernel exploits: when the
    # schedule is static with global tile 0, the backward runs as two
    # batched einsum pieces instead of a dense-cost scan over key tiles
    schedule = _static_tile_schedule(bq, bk, block, window, global_blocks,
                                     causal)
    if schedule == [0] and n % bk == 0 and n > bk:
        dq, dk, dv = _bs_bwd_static(
            q, k, v, mask, dout, out, stats, scale=scale, block=block,
            window=window, global_blocks=global_blocks, tile=bk)
        return dq, dk, dv, None

    def structural(rows, cols):
        return _structural(rows[:, None], cols[None, :], block=block,
                           window=window, global_blocks=global_blocks,
                           causal=causal)

    dq, dk, dv = blockwise_attention_bwd(
        q, k, v, mask, dout, out, stats, scale=scale,
        block_k=min(bk, n), structural_mask_fn=structural,
        mask_queries=False)
    return dq, dk, dv, None


_bs.defvjp(_bs_fwd_rule, _bs_bwd_rule)


def block_sparse_attention(q: Array, k: Array, v: Array, *,
                           scale: Optional[float] = None,
                           causal: bool = True,
                           mask: Optional[Array] = None, block: int = 16,
                           num_local_blocks: int = 4,
                           global_blocks: Tuple[int, ...] = (0,),
                           block_q: int = 128, block_k: int = 128,
                           interpret: Optional[bool] = None) -> Array:
    """VariableSparsity block-sparse attention, Pallas forward + blockwise
    custom_vjp backward. q/k/v: (b, h, n, d) with n a multiple of ``block``
    (the transformer pads beforehand, reference transformer.py:112-115);
    mask: (b, n) key-padding mask.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = core.pallas_interpret()
    n = q.shape[2]
    bq, bk = min(block_q, n), min(block_k, n)
    return _bs(q, k, v, mask, float(scale), bool(causal), int(block),
               int(num_local_blocks), tuple(global_blocks), (bq, bk),
               bool(interpret))
