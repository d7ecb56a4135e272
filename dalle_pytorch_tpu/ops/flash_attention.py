"""Flash attention — Pallas TPU kernel, O(n) memory, exact numerics.

Replaces the XLA einsum reference path (ops.attention) for the hot dense
attention in DALLE/CLIP (the reference reaches dense attention through torch
CUDA kernels, reference dalle_pytorch/transformer.py:51-89; this is the
TPU-native equivalent demanded by SURVEY.md §2a).

Forward: a ``pl.pallas_call`` gridded over (batch*heads, query tiles); each
program streams key/value tiles through the MXU with the online-softmax
recurrence — no (n, n) score matrix ever exists. Also emits the per-row
log-sum-exp for the backward.

Backward (``jax.custom_vjp``): the standard flash backward as a blockwise
``lax.scan`` over key tiles in plain XLA — recomputes score tiles from
(q, k, lse), accumulates dq and emits per-tile dk/dv; memory stays
O(n · block).

Masking semantics (shared with ops.attention so the two impls agree
EXACTLY, including degenerate rows):

  * pad mask (query rows AND key columns) uses a finite -fmax fill — a
    fully-padded row degrades to a uniform average, torch masked_fill
    behavior;
  * the causal mask uses a true -inf fill, so that degenerate uniform
    average runs over the CAUSAL PREFIX only. (The reference's single
    finite fill lets fully-padded text rows attend uniformly to FUTURE
    image positions — a quirk this rebuild deliberately fixes; flagged per
    SURVEY.md §5 "deliberately fix" allowance. Valid rows are bit-identical
    either way.)
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_pytorch_tpu.ops import core

Array = jax.Array

FILL = -3.0e38           # finite pad fill (torch masked_fill -fmax behavior)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

# Mosaic tiling constants: the last two dims of every block must be
# (multiples of) the (8, 128) f32 VREG tile or equal the array dims — the
# layouts below mirror jax.experimental.pallas.ops.tpu.flash_attention
# (q-mask broadcast over NUM_LANES, k-mask over NUM_SUBLANES, (m, l) stats
# stored as (block_q, 128) lane-broadcast tiles).
NUM_LANES = 128
NUM_SUBLANES = 8


def _masked_scores(q_tile, k_tile, *, scale, rows, cols, qm, km, causal,
                   seq_len, block_k):
    """(scores, live) with the shared two-fill semantics: pad pairs get the
    finite FILL (``live`` marks the untouched entries — ds must be zeroed
    where not live), causal/ragged bounds get -inf. One definition for the
    forward and both backward kernels so the masking cannot drift."""
    s = jax.lax.dot_general(q_tile, k_tile, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    live = None
    if km is not None:
        live = km & qm
        s = jnp.where(live, s, FILL)
    if causal:
        s = jnp.where(cols <= rows, s, -jnp.inf)
    if seq_len % block_k:                     # ragged tail tile bounds
        s = jnp.where(cols < seq_len, s, -jnp.inf)
    return s, live


def _mask_views(mask_in, b, n):
    """(mq, mk): the (b, n) int mask as lane-broadcast (b, n, 128) for
    query-row views and sublane-broadcast (b, 8, n) for key-column views —
    the Mosaic-legal layouts every kernel slices 2-D tiles from."""
    mq = jnp.broadcast_to(mask_in[:, :, None], (b, n, NUM_LANES))
    mk = jnp.broadcast_to(mask_in[:, None, :], (b, NUM_SUBLANES, n))
    return mq, mk


def _fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, seq_len: int, has_mask: bool):
    if has_mask:
        mq_ref, mk_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
    iq = pl.program_id(1)
    # MXU operands stay in the INPUT dtype (bf16 in training — full-rate
    # systolic passes) with f32 ACCUMULATION via preferred_element_type;
    # the scale applies to the f32 scores. Mirrors the backward's policy.
    q = q_ref[0]                                           # (BQ, d)
    rows = iq * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols_base = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # (BQ, 1) bool: query-row pad mask (any lane of the broadcast tile)
    qm = (mq_ref[0][:, :1] != 0) if has_mask else None

    num_k = pl.cdiv(seq_len, block_k)
    if causal:
        num_k = jnp.minimum(num_k, pl.cdiv((iq + 1) * block_q, block_k))

    def body(ik, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(ik * block_k, block_k), :]
        vb = v_ref[0, pl.ds(ik * block_k, block_k), :]
        km = (mk_ref[0, :1, pl.ds(ik * block_k, block_k)] != 0) \
            if has_mask else None
        s, _ = _masked_scores(q, kb, scale=scale, rows=rows,
                              cols=ik * block_k + cols_base, qm=qm, km=km,
                              causal=causal, seq_len=seq_len,
                              block_k=block_k)

        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((block_q, 1), FILL, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    m, l, acc = lax.fori_loop(0, num_k, body, (m0, l0, a0))

    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # m and l are saved SEPARATELY: a single lse = m + log(l) loses the
    # log(l) term entirely when m is the huge finite FILL (float absorption),
    # corrupting the backward's softmax reconstruction at degenerate rows.
    # Stored lane-broadcast as (BQ, 128) tiles to satisfy Mosaic tiling.
    m_ref[0] = jnp.broadcast_to(m, (block_q, NUM_LANES))
    l_ref[0] = jnp.broadcast_to(l_safe, (block_q, NUM_LANES))


def _pad_seq(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@jax.named_scope("attn.flash_fwd")
def _flash_fwd(q, k, v, mask, *static):
    """The forward kernel, one shard of batch and heads per device under
    a mesh (a Mosaic kernel cannot be auto-partitioned)."""
    return core.shard_over_batch_and_heads(
        lambda q, k, v, mask: _flash_fwd_local(q, k, v, mask, *static),
        (q, k, v, mask), ["bhnd", "bhnd", "bhnd", "bn"],
        ("bhnd", ("bhn", "bhn")))


def _flash_fwd_local(q, k, v, mask, scale, causal, block_q, block_k,
                     interpret):
    b, h, n_orig, d = q.shape
    # pad to tile multiples — pl.ds CLAMPS out-of-bounds starts
    # (dynamic_slice semantics), so ragged tails must be padded, not read
    # past; the in-kernel seq_len bound masks the pad keys out.
    mult = max(block_q, block_k)
    q = _pad_seq(q, mult, 2)
    k = _pad_seq(k, mult, 2)
    v = _pad_seq(v, mult, 2)
    b, h, n, d = q.shape
    bh = b * h
    has_mask = mask is not None

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_len=n_orig, has_mask=has_mask)

    in_specs = []
    inputs = []
    if has_mask:
        # q-side: broadcast over lanes; k-side: broadcast over sublanes —
        # gives the kernel 2-D (BQ, 1) / (1, BK) views with no transposes.
        mq, mk = _mask_views(_pad_seq(mask, mult, 1).astype(jnp.int32), b, n)
        in_specs += [
            pl.BlockSpec((1, block_q, NUM_LANES),
                         lambda ib, iq: (ib // h, iq, 0)),
            pl.BlockSpec((1, NUM_SUBLANES, n), lambda ib, iq: (ib // h, 0, 0)),
        ]
        inputs += [mq, mk]
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
        name="attn.flash_fwd",
    )(*inputs)
    out = out.reshape(b, h, n, d)[:, :, :n_orig]
    m = m[:, :, 0].reshape(b, h, n)[:, :, :n_orig]
    l = l[:, :, 0].reshape(b, h, n)[:, :, :n_orig]
    return out, (m, l)


# ---------------------------------------------------------------------------
# blockwise backward (shared with ops.block_sparse)
# ---------------------------------------------------------------------------

def blockwise_attention_bwd(q, k, v, mask, dout, out, softmax_stats, *,
                            scale: float, block_k: int, structural_mask_fn,
                            mask_queries: bool = True):
    """Flash backward as a lax.scan over key tiles; never materializes (n,n).

    ``softmax_stats`` is the forward's (m, l) pair — kept separate rather
    than fused into lse = m + log(l) so degenerate rows (m == FILL)
    reconstruct exactly. ``structural_mask_fn(rows, cols) -> (n, BK) bool``
    gives the -inf structural mask (causal and/or sparsity layout); the pad
    ``mask`` (b, n) applies with the finite FILL to key columns (and query
    rows when ``mask_queries``) — exactly mirroring the forward.
    """
    m_stat, l_stat = softmax_stats
    b, h, n_orig, d = q.shape
    # ragged sequences: pad everything to a block_k multiple (mirroring the
    # forward's _pad_seq) and mask padded KEY columns structurally below;
    # padded QUERY rows contribute nothing because dout/out/D are zero there
    # and m=0/l=1 keep p finite. Gradients are sliced back to n_orig.
    ragged = n_orig % block_k != 0
    if ragged:
        q, k, v, dout, out = (_pad_seq(x, block_k, 2)
                              for x in (q, k, v, dout, out))
        m_stat = _pad_seq(m_stat, block_k, 2)
        l_stat = _pad_seq(l_stat, block_k, 2)
        l_stat = jnp.where(jnp.arange(l_stat.shape[-1]) < n_orig,
                           l_stat, 1.0)                  # keep 1/l finite
        if mask is not None:
            mask = _pad_seq(mask, block_k, 1)
    inv_l = 1.0 / l_stat
    b, h, n, d = q.shape
    # MXU operands stay in the INPUT dtype (bf16 in training — full-rate
    # systolic passes; f32 in exactness tests) with f32 ACCUMULATION via
    # preferred_element_type; softmax reconstruction and the ds chain stay
    # f32 throughout. An all-f32 bwd ran the MXU at 1/3 rate for nothing —
    # the probabilities are exp() outputs with bf16-scale information.
    cdt = q.dtype
    doutc = dout.astype(cdt)
    D = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1)                                         # (b, h, n)
    rows = jnp.arange(n)

    num_k = n // block_k

    def step(dq, ik):
        ks = lax.dynamic_slice_in_dim(k, ik * block_k, block_k, axis=2)
        vs = lax.dynamic_slice_in_dim(v, ik * block_k, block_k, axis=2)
        cols = ik * block_k + jnp.arange(block_k)

        s = jnp.einsum("bhid,bhjd->bhij", q, ks,
                       preferred_element_type=jnp.float32) * scale
        live = None                           # entries whose s is not a
        if mask is not None:                  # constant fill substitution
            km = lax.dynamic_slice_in_dim(mask, ik * block_k, block_k,
                                          axis=1)
            pad_ok = km[:, None, :]
            if mask_queries:
                pad_ok = pad_ok & mask[:, :, None]
            s = jnp.where(pad_ok[:, None], s, FILL)
            live = pad_ok[:, None]
        struct = structural_mask_fn(rows, cols)
        if ragged:
            bound = (cols < n_orig)[None, :]   # padded keys out, all rows
            struct = bound if struct is None else struct & bound
        if struct is not None:
            s = jnp.where(struct[None, None], s, -jnp.inf)

        p = jnp.exp(s - m_stat[..., None]) * inv_l[..., None]  # (b,h,n,BK)
        dv = jnp.einsum("bhij,bhid->bhjd", p.astype(cdt), doutc,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhid,bhjd->bhij", doutc, vs,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - D[..., None]) * scale
        # where s was REPLACED by the fill, no gradient reaches q·k (the
        # forward's jnp.where blocks it) — p still feeds dv, but ds is 0.
        if live is not None:
            ds = jnp.where(live, ds, 0.0)
        ds_c = ds.astype(cdt)
        dk = jnp.einsum("bhij,bhid->bhjd", ds_c, q,
                        preferred_element_type=jnp.float32)
        dq = dq + jnp.einsum("bhij,bhjd->bhid", ds_c, ks,
                             preferred_element_type=jnp.float32)
        return dq, (dk, dv)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, (dks, dvs) = lax.scan(step, dq0, jnp.arange(num_k))
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, n, d)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, n, d)
    if ragged:
        dq, dk, dv = (x[:, :, :n_orig] for x in (dq, dk, dv))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Pallas backward kernels (opt-in: flash_attention(bwd_impl="pallas"))
#
# Same recomputation math as blockwise_attention_bwd, but as two
# pallas_calls so (1) causal-dead tiles are SKIPPED (the XLA scan walks
# every (row, key-tile) pair and masks — ~2x waste on causal attention)
# and (2) the (n, block) probability/ds intermediates live in VMEM instead
# of round-tripping HBM. dq is gridded over query tiles (loop over key
# tiles <= diagonal); dk/dv are gridded over key tiles (loop over query
# tiles >= diagonal). Masking mirrors the forward exactly (pad FILL with
# zeroed ds, causal -inf, ragged bound).
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, seq_len,
                   has_mask):
    if has_mask:
        (mq_ref, mk_ref, q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, d_ref,
         dq_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, d_ref, dq_ref = refs
    iq = pl.program_id(1)
    q = q_ref[0]                                           # (BQ, d)
    do = do_ref[0]                                         # (BQ, d)
    m = m_ref[0][:, :1]                                    # (BQ, 1) f32
    inv_l = 1.0 / l_ref[0][:, :1]
    dstat = d_ref[0][:, :1]
    rows = iq * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols_base = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    qm = (mq_ref[0][:, :1] != 0) if has_mask else None

    num_k = pl.cdiv(seq_len, block_k)
    if causal:
        num_k = jnp.minimum(num_k, pl.cdiv((iq + 1) * block_q, block_k))

    def body(ik, dq):
        kb = k_ref[0, pl.ds(ik * block_k, block_k), :]
        vb = v_ref[0, pl.ds(ik * block_k, block_k), :]
        km = (mk_ref[0, :1, pl.ds(ik * block_k, block_k)] != 0) \
            if has_mask else None
        s, live = _masked_scores(q, kb, scale=scale, rows=rows,
                                 cols=ik * block_k + cols_base, qm=qm,
                                 km=km, causal=causal, seq_len=seq_len,
                                 block_k=block_k)
        p = jnp.exp(s - m) * inv_l
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dstat) * scale
        if live is not None:
            ds = jnp.where(live, ds, 0.0)
        return dq + jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    dq_ref[0] = lax.fori_loop(0, num_k, body, dq0).astype(dq_ref.dtype)


def _bwd_keygrid_kernel(*refs, scale, causal, block_q, block_k, seq_len,
                        has_mask, with_dq):
    """Key-tile-gridded backward body, shared by the split dkv kernel
    (``with_dq=False``) and the fused single-pass kernel
    (``with_dq=True``).

    Fused: dq, dk AND dv come from ONE score/probability computation per
    (query-tile, key-tile) pair — the split dq/dkv pair recomputes s, p,
    dp twice (7 MXU dots per pair vs 4 here). Grid is (bh, key-tile) with
    ik innermost; the full-length dq block's index map ignores ik, so on
    TPU's sequential grid the block stays resident in VMEM across all
    key tiles of one bh (output revisiting) and row tiles accumulate in
    f32 via read-modify-write. dk/dv are per-ik tile outputs either
    way."""
    if has_mask:
        mq_ref, mk_ref, *refs = refs
    else:
        mq_ref = mk_ref = None
    if with_dq:
        (q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, d_ref,
         dq_ref, dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, d_ref,
         dk_ref, dv_ref) = refs
    ik = pl.program_id(1)

    if with_dq:
        @pl.when(ik == 0)
        def _zero_dq():
            dq_ref[0] = jnp.zeros_like(dq_ref[0])

    kb = k_ref[0]                                          # (BK, d)
    vb = v_ref[0]                                          # (BK, d)
    cols = ik * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    rows_base = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    km = (mk_ref[0, :1, pl.ds(ik * block_k, block_k)] != 0) if has_mask \
        else None

    num_q = pl.cdiv(seq_len, block_q)
    # causal: query tiles strictly before this key tile see none of it
    iq0 = (ik * block_k) // block_q if causal else 0

    def body(iq, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(iq * block_q, block_q), :]
        do = do_ref[0, pl.ds(iq * block_q, block_q), :]
        m = m_ref[0, pl.ds(iq * block_q, block_q), :1]
        inv_l = 1.0 / l_ref[0, pl.ds(iq * block_q, block_q), :1]
        dstat = d_ref[0, pl.ds(iq * block_q, block_q), :1]
        qm = (mq_ref[0, pl.ds(iq * block_q, block_q), :1] != 0) \
            if has_mask else None
        s, live = _masked_scores(qb, kb, scale=scale,
                                 rows=iq * block_q + rows_base, cols=cols,
                                 qm=qm, km=km, causal=causal,
                                 seq_len=seq_len, block_k=block_k)
        p = jnp.exp(s - m) * inv_l
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dstat) * scale
        if live is not None:
            ds = jnp.where(live, ds, 0.0)
        ds_c = ds.astype(qb.dtype)
        dk = dk + jax.lax.dot_general(
            ds_c, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if with_dq:
            dq_rows = dq_ref[0, pl.ds(iq * block_q, block_q), :]
            dq_ref[0, pl.ds(iq * block_q, block_q), :] = dq_rows + \
                jax.lax.dot_general(ds_c, kb, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((block_k, q_ref.shape[-1]), jnp.float32)
    dv0 = jnp.zeros((block_k, q_ref.shape[-1]), jnp.float32)
    dk, dv = lax.fori_loop(iq0, num_q, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


_bwd_dkv_kernel = functools.partial(_bwd_keygrid_kernel, with_dq=False)
_bwd_fused_kernel = functools.partial(_bwd_keygrid_kernel, with_dq=True)


def _pallas_attention_bwd(q, k, v, mask, dout, out, softmax_stats, **kw):
    """The Pallas backward, sharded over batch and heads under a mesh
    like the forward."""
    return core.shard_over_batch_and_heads(
        lambda q, k, v, mask, dout, out, m, l: _pallas_attention_bwd_local(
            q, k, v, mask, dout, out, (m, l), **kw),
        (q, k, v, mask, dout, out, *softmax_stats),
        ["bhnd", "bhnd", "bhnd", "bn", "bhnd", "bhnd", "bhn", "bhn"],
        ("bhnd", "bhnd", "bhnd"))


def _pallas_attention_bwd_local(q, k, v, mask, dout, out, softmax_stats, *,
                                scale, causal, block_q, block_k, interpret,
                                fused: bool = False):
    """Pallas counterpart of ``blockwise_attention_bwd`` (dense/causal/pad
    only — the sparse layout keeps the XLA blockwise path). ``fused``
    selects the single-pass kernel (_bwd_fused_kernel) over the split
    dq/dkv pair."""
    m_stat, l_stat = softmax_stats
    b, h, n_orig, d = q.shape
    mult = max(block_q, block_k)
    q, k, v, dout, out = (_pad_seq(x, mult, 2)
                          for x in (q, k, v, dout, out))
    m_stat = _pad_seq(m_stat, mult, 2)
    l_stat = _pad_seq(l_stat, mult, 2)
    if l_stat.shape[-1] != n_orig:                  # keep 1/l finite on pad
        l_stat = jnp.where(jnp.arange(l_stat.shape[-1]) < n_orig,
                           l_stat, 1.0)
    b, h, n, d = q.shape
    bh = b * h
    has_mask = mask is not None
    D = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1)                                        # (b, h, n)

    def lanes(x):                     # (b, h, n) -> (bh, n, NUM_LANES) f32
        return jnp.broadcast_to(x.astype(jnp.float32).reshape(bh, n)[
            :, :, None], (bh, n, NUM_LANES))

    stats = [lanes(m_stat), lanes(l_stat), lanes(D)]
    qf, kf, vf, dof = (x.reshape(bh, n, d) for x in (q, k, v, dout))

    mask_inputs, mk_spec = [], None
    if has_mask:
        mask_inputs = list(_mask_views(
            _pad_seq(mask, mult, 1).astype(jnp.int32), b, n))
        mk_spec = pl.BlockSpec((1, NUM_SUBLANES, n),
                               lambda ib, i: (ib // h, 0, 0))

    full = lambda ib, i: (ib, 0, 0)                    # noqa: E731
    tile_q = lambda ib, i: (ib, i, 0)                  # noqa: E731
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, seq_len=n_orig, has_mask=has_mask)

    if fused:
        # one pass: grid over key tiles, dq as a full-length revisited
        # block (index map ignores ik -> stays VMEM-resident per bh on
        # the sequential TPU grid), f32 row-tile accumulation in-kernel
        tile_k2 = lambda ib, i: (ib, i, 0)             # noqa: E731
        in_specs = []
        if has_mask:
            in_specs += [pl.BlockSpec((1, n, NUM_LANES),
                                      lambda ib, i: (ib // h, 0, 0)),
                         mk_spec]
        in_specs += [
            pl.BlockSpec((1, n, d), full),             # q full
            pl.BlockSpec((1, block_k, d), tile_k2),    # k tile
            pl.BlockSpec((1, block_k, d), tile_k2),    # v tile
            pl.BlockSpec((1, n, d), full),             # dout full
            pl.BlockSpec((1, n, NUM_LANES), full),     # m
            pl.BlockSpec((1, n, NUM_LANES), full),     # l
            pl.BlockSpec((1, n, NUM_LANES), full),     # D
        ]
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, **common),
            grid=(bh, pl.cdiv(n, block_k)),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, n, d), full),
                       pl.BlockSpec((1, block_k, d), tile_k2),
                       pl.BlockSpec((1, block_k, d), tile_k2)],
            out_shape=[jax.ShapeDtypeStruct((bh, n, d), jnp.float32),
                       jax.ShapeDtypeStruct((bh, n, d), k.dtype),
                       jax.ShapeDtypeStruct((bh, n, d), v.dtype)],
            # the dq block is REVISITED along the key-tile axis (zeroed
            # at ik == 0, accumulated after): that axis must run in
            # order on one core
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="attn.flash_bwd",
        )(*mask_inputs, qf, kf, vf, dof, *stats)
        dq = dq.astype(q.dtype).reshape(b, h, n, d)[:, :, :n_orig]
        dk = dk.reshape(b, h, n, d)[:, :, :n_orig]
        dv = dv.reshape(b, h, n, d)[:, :, :n_orig]
        return dq, dk, dv

    # dq: grid over query tiles
    in_specs = []
    if has_mask:
        in_specs += [pl.BlockSpec((1, block_q, NUM_LANES),
                                  lambda ib, i: (ib // h, i, 0)), mk_spec]
    in_specs += [
        pl.BlockSpec((1, block_q, d), tile_q),         # q tile
        pl.BlockSpec((1, n, d), full),                 # k full
        pl.BlockSpec((1, n, d), full),                 # v full
        pl.BlockSpec((1, block_q, d), tile_q),         # dout tile
        pl.BlockSpec((1, block_q, NUM_LANES), tile_q),  # m
        pl.BlockSpec((1, block_q, NUM_LANES), tile_q),  # l
        pl.BlockSpec((1, block_q, NUM_LANES), tile_q),  # D
    ]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(bh, pl.cdiv(n, block_q)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), tile_q),
        out_shape=jax.ShapeDtypeStruct((bh, n, d), q.dtype),
        interpret=interpret,
        name="attn.flash_bwd",
    )(*mask_inputs, qf, kf, vf, dof, *stats)

    # dk/dv: grid over key tiles
    tile_k = lambda ib, i: (ib, i, 0)                  # noqa: E731
    in_specs = []
    if has_mask:
        in_specs += [pl.BlockSpec((1, n, NUM_LANES),
                                  lambda ib, i: (ib // h, 0, 0)), mk_spec]
    in_specs += [
        pl.BlockSpec((1, n, d), full),                 # q full
        pl.BlockSpec((1, block_k, d), tile_k),         # k tile
        pl.BlockSpec((1, block_k, d), tile_k),         # v tile
        pl.BlockSpec((1, n, d), full),                 # dout full
        pl.BlockSpec((1, n, NUM_LANES), full),         # m
        pl.BlockSpec((1, n, NUM_LANES), full),         # l
        pl.BlockSpec((1, n, NUM_LANES), full),         # D
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(bh, pl.cdiv(n, block_k)),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, block_k, d), tile_k),
                   pl.BlockSpec((1, block_k, d), tile_k)],
        out_shape=[jax.ShapeDtypeStruct((bh, n, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, n, d), v.dtype)],
        interpret=interpret,
        name="attn.flash_bwd",
    )(*mask_inputs, qf, kf, vf, dof, *stats)

    dq = dq.reshape(b, h, n, d)[:, :, :n_orig]
    dk = dk.reshape(b, h, n, d)[:, :, :n_orig]
    dv = dv.reshape(b, h, n, d)[:, :, :n_orig]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, mask, scale, causal, block_q, block_k, interpret,
           bwd_impl):
    out, _ = _flash_fwd(q, k, v, mask, scale, causal, block_q, block_k,
                        interpret)
    return out


def _flash_fwd_rule(q, k, v, mask, scale, causal, block_q, block_k,
                    interpret, bwd_impl):
    out, stats = _flash_fwd(q, k, v, mask, scale, causal, block_q, block_k,
                            interpret)
    return out, (q, k, v, mask, out, stats)


@jax.named_scope("attn.flash_bwd")
def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, bwd_impl,
                    res, dout):
    q, k, v, mask, out, stats = res

    if bwd_impl in ("pallas", "pallas_fused"):
        dq, dk, dv = _pallas_attention_bwd(
            q, k, v, mask, dout, out, stats, scale=scale, causal=causal,
            block_q=min(block_q, q.shape[2]),
            block_k=min(block_k, q.shape[2]), interpret=interpret,
            fused=bwd_impl == "pallas_fused")
        return dq, dk, dv, None

    def structural(rows, cols):
        if not causal:
            return None
        return cols[None, :] <= rows[:, None]

    dq, dk, dv = blockwise_attention_bwd(
        q, k, v, mask, dout, out, stats, scale=scale,
        block_k=min(block_k, q.shape[2]), structural_mask_fn=structural)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q: Array, k: Array, v: Array, *,
                    scale: Optional[float] = None, causal: bool = True,
                    mask: Optional[Array] = None, block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None,
                    bwd_impl: str = "xla") -> Array:
    """Exact attention, Pallas forward + blockwise custom_vjp backward.

    q/k/v: (b, h, n, d); mask: (b, n) True=keep. ``interpret=None``
    auto-selects the Pallas interpreter off-TPU so the same code path runs
    on the CPU test mesh. ``bwd_impl='pallas'`` swaps the XLA blockwise
    backward for the split dq/dkv Pallas kernels (causal-dead tiles
    skipped, VMEM intermediates); ``'pallas_fused'`` uses the
    single-pass kernel (one score computation per tile pair, dq
    accumulated in a VMEM-resident revisited block — 4 MXU dots per
    pair vs the split pair's 7). Both opt-in until compiled-mode
    numbers decide a default.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = core.pallas_interpret()
    if bwd_impl not in ("xla", "pallas", "pallas_fused"):
        raise ValueError(f"unknown bwd_impl {bwd_impl!r}")
    n = q.shape[2]
    return _flash(q, k, v, mask, float(scale), bool(causal),
                  min(block_q, n), min(block_k, n), bool(interpret),
                  bwd_impl)
