"""Sequence/context-parallel attention: ring (ppermute) and Ulysses
(all-to-all) kernels.

Long-context support the reference lacks entirely (SURVEY.md §5.7: no ring
attention, no context parallel — it scales sequence cost only by reversible
layers and block-sparse attention on ONE device). Here the sequence axis is
sharded over a mesh axis and attention runs as an SPMD program:

  * ``ring_attention`` — each device holds a sequence shard of q/k/v. K/V
    blocks rotate around the ring with ``lax.ppermute`` (ICI
    neighbor-to-neighbor, bandwidth-optimal) while each device folds one
    block per step into a numerically-stable online-softmax accumulator
    (the flash-attention recurrence, so no (n, n) matrix ever exists).
    Causal masking is block-aware: blocks wholly in the future contribute
    nothing (their weights underflow to exactly zero via the -inf mask).
  * ``ulysses_attention`` — all-to-all re-shards sequence -> heads, attends
    over the full sequence for the local head group, and all-to-alls back.
    One collective round-trip instead of a ring of size-1 hops; better when
    heads >= mesh axis size. At long context the local attention folds the
    key axis in chunks through the same online-softmax recurrence as the
    ring (``kv_chunks``), so no (n, n) score matrix ever materializes on
    either path.

Both are exact (same math as dense attention) — parity tests drive them on
the virtual CPU mesh against the single-device oracle.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _online_block(carry, kb, vb, q, scale, allow, pair_ok=None):
    """Fold one K/V block into the online-softmax state.

    carry: (m, l, acc) with m,l (b,h,nl,1) and acc (b,h,nl,d).
    allow: (nl_q, nl_k) bool — True where attention is permitted (causal).
    pair_ok: optional (b, nl_q, nl_k) pad mask — False entries fill with
    the FINITE -fmax (reference transformer.py:74-77), so a fully-padded
    row degrades to a uniform average over its causal prefix exactly like
    the dense path (ops.attention.dense_attention_weights).
    """
    m, l, acc = carry
    s = jnp.einsum("bhid,bhjd->bhij", q, kb) * scale
    if pair_ok is not None:
        fmax = jnp.asarray(-jnp.finfo(s.dtype).max, s.dtype)
        s = jnp.where(pair_ok[:, None], s, fmax)
    neg = jnp.asarray(-jnp.inf, s.dtype)
    s = jnp.where(allow[None, None], s, neg)

    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    # rows with no allowed key yet keep m=-inf; shift with 0 to avoid nans
    shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - shift)
    p = jnp.where(allow[None, None], p, 0.0)   # causal zeros only; pad rows
    #                                            keep their uniform exp(0)=1
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - shift), 0.0)
    l = l * alpha + p.sum(axis=-1, keepdims=True)
    acc = acc * alpha + jnp.einsum("bhij,bhjd->bhid", p, vb)
    return m_new, l, acc


def ring_attention_local(q, k, v, *, axis: str, size: int,
                         causal: bool = True,
                         scale: Optional[float] = None,
                         mask=None):
    """Per-shard ring attention body — call INSIDE a ``shard_map`` whose
    mesh has axis ``axis`` of ``size``; q, k, v are the LOCAL (b, h, n/size,
    d) sequence shards. Exposed separately so higher layers (the
    sequence-parallel transformer stack in parallel.sequence) can fuse the
    ring into their own shard_map instead of nesting one per attention.

    ``mask`` is this shard's (b, n/size) pad mask; its blocks rotate around
    the ring with k/v, and pad pairs fill with the finite -fmax so the
    semantics match the dense path bit-for-bit (reference
    transformer.py:74-77 pair mask)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    nl = q.shape[2]
    rank = lax.axis_index(axis)
    rows = rank * nl + jnp.arange(nl)

    # init the accumulators FROM q so they carry the same device-varying
    # type as the scan's rotating kb/vb under shard_map
    m = q[..., :1] * 0.0 - jnp.inf
    l = q[..., :1] * 0.0
    acc = q * 0.0
    perm = [(i, (i + 1) % size) for i in range(size)]
    q_mask = mask

    def step(s, state):
        m, l, acc, kb, vb, mb = state
        src = (rank - s) % size          # who produced the block we hold
        cols = src * nl + jnp.arange(nl)
        allow = (cols[None, :] <= rows[:, None]) if causal else \
            jnp.ones((nl, nl), bool)
        pair_ok = None
        if mb is not None:
            pair_ok = q_mask[:, :, None] & mb[:, None, :]   # (b, nl, nl)
        m, l, acc = _online_block((m, l, acc), kb, vb, q, scale, allow,
                                  pair_ok)
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        if mb is not None:
            mb = lax.ppermute(mb, axis, perm)
        return m, l, acc, kb, vb, mb

    if mask is None:
        # fori_loop needs a fixed-structure carry: run the maskless variant
        def step_nomask(s, state):
            m, l, acc, kb, vb = state
            m, l, acc, kb, vb, _ = step(s, (m, l, acc, kb, vb, None))
            return m, l, acc, kb, vb
        m, l, acc, _, _ = lax.fori_loop(
            0, size, step_nomask, (m, l, acc, k, v), unroll=True)
    else:
        m, l, acc, _, _, _ = lax.fori_loop(
            0, size, step, (m, l, acc, k, v, mask), unroll=True)
    return acc / jnp.where(l == 0.0, 1.0, l)


def ring_attention(q, k, v, *, mesh: Mesh, axis: str = "sp",
                   causal: bool = True, scale: Optional[float] = None,
                   batch_axis: Optional[str] = None, mask=None):
    """Exact attention with the sequence axis sharded over ``axis``.

    q, k, v: (b, h, n, d) GLOBAL shapes; n divides by the axis size.
    ``mask``: optional (b, n) global pad mask (True = keep), dense-path
    semantics. Returns (b, h, n, d) sharded the same way. ``batch_axis``
    optionally names a mesh axis the batch dim is sharded over (pure SPMD
    pass-through).
    """
    size = mesh.shape[axis]

    def local(q, k, v, *m):
        return ring_attention_local(q, k, v, axis=axis, size=size,
                                    causal=causal, scale=scale,
                                    mask=m[0] if m else None)

    return _sharded_attn(local, mesh, axis, batch_axis, q, k, v, mask)


def ulysses_attention(q, k, v, *, mesh: Mesh, axis: str = "sp",
                      causal: bool = True, scale: Optional[float] = None,
                      batch_axis: Optional[str] = None, mask=None,
                      kv_chunks: Optional[int] = None):
    """Exact attention via head<->sequence all-to-all re-sharding.

    q, k, v: (b, h, n, d) global; h divides by the axis size. Inside the
    shard_map each device swaps its sequence shard for a head shard
    (all_to_all over ICI), attends over the FULL sequence for its heads,
    then swaps back. ``kv_chunks`` as in ``ulysses_attention_local``.
    """
    size = mesh.shape[axis]
    if q.shape[1] % size != 0:
        raise ValueError(f"heads {q.shape[1]} not divisible by mesh axis "
                         f"{axis} ({size})")

    def local(q, k, v, *m):
        return ulysses_attention_local(q, k, v, axis=axis, causal=causal,
                                       scale=scale,
                                       mask=m[0] if m else None,
                                       kv_chunks=kv_chunks)

    return _sharded_attn(local, mesh, axis, batch_axis, q, k, v, mask)


def _sharded_attn(local, mesh: Mesh, axis: str, batch_axis, q, k, v, mask):
    """Shared shard_map plumbing for the standalone wrappers: q/k/v
    sequence-sharded over ``axis``, the optional (b, n) mask alongside."""
    spec = P(batch_axis, None, axis, None)
    in_specs = [spec, spec, spec]
    args = [q, k, v]
    if mask is not None:
        in_specs.append(P(batch_axis, axis))
        args.append(mask)
    return shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=spec)(*args)


# full-sequence length at/above which the Ulysses body switches from the
# one-einsum dense score matrix to the chunked online-softmax (the (n, n)
# buffer is fine at bench scale but contradicts the long-context purpose)
_ULYSSES_DENSE_MAX = 4096


def ulysses_attention_local(q, k, v, *, axis: str, causal: bool = True,
                            scale: Optional[float] = None, mask=None,
                            kv_chunks: Optional[int] = None):
    """Per-shard Ulysses body — call INSIDE a ``shard_map``; q, k, v are
    LOCAL (b, h, n/size, d) shards with h divisible by the axis size.
    ``mask`` is this shard's (b, n/size) pad mask; it is all-gathered to
    the full sequence (the heads are local here anyway) and applied with
    dense-path semantics.

    ``kv_chunks`` bounds score memory: the key/value axis is folded in that
    many chunks through the same online-softmax recurrence as the ring path
    (peak (b, h/size, n, n/kv_chunks) instead of (b, h/size, n, n)). None =
    auto: dense below ``_ULYSSES_DENSE_MAX`` total sequence, one chunk per
    ring rank at or above it. 1 = always dense."""
    if scale is None:
        scale = q.shape[-1] ** -0.5

    # local shapes: (b, h, nl, d) -> all_to_all -> (b, h/size, n, d)
    def seq_to_heads(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    n = qh.shape[2]
    size = n // q.shape[2]                       # static: n = nl * size
    full = (lax.all_gather(mask, axis, axis=1, tiled=True)
            if mask is not None else None)       # (b, n)
    if kv_chunks is None:
        kv_chunks = 1 if n < _ULYSSES_DENSE_MAX else size
    if kv_chunks > 1 and n % kv_chunks:
        raise ValueError(f"kv_chunks {kv_chunks} must divide the full "
                         f"sequence {n}")

    if kv_chunks == 1:
        s = jnp.einsum("bhid,bhjd->bhij", qh, kh) * scale
        if full is not None:
            pair = full[:, :, None] & full[:, None, :]
            fmax = jnp.asarray(-jnp.finfo(s.dtype).max, s.dtype)
            s = jnp.where(pair[:, None], s, fmax)
        if causal:
            tri = jnp.tril(jnp.ones((n, n), bool))
            s = jnp.where(tri[None, None], s, -jnp.inf)
        out = jnp.einsum("bhij,bhjd->bhid", jax.nn.softmax(s, axis=-1), vh)
        return heads_to_seq(out)

    ck = n // kv_chunks
    b, hl, _, d = qh.shape
    ks = jnp.moveaxis(kh.reshape(b, hl, kv_chunks, ck, d), 2, 0)
    vs = jnp.moveaxis(vh.reshape(b, hl, kv_chunks, ck, d), 2, 0)
    rows = jnp.arange(n)
    m0 = qh[..., :1] * 0.0 - jnp.inf
    l0 = qh[..., :1] * 0.0
    acc0 = qh * 0.0

    def fold(carry, xs):
        j, kb, vb = xs
        cols = j * ck + jnp.arange(ck)
        allow = (cols[None, :] <= rows[:, None]) if causal else \
            jnp.ones((n, ck), bool)
        pair_ok = None
        if full is not None:
            mb = lax.dynamic_slice_in_dim(full, j * ck, ck, axis=1)
            pair_ok = full[:, :, None] & mb[:, None, :]
        return _online_block(carry, kb, vb, qh, scale, allow, pair_ok), None

    (m, l, acc), _ = lax.scan(fold, (m0, l0, acc0),
                              (jnp.arange(kv_chunks), ks, vs))
    return heads_to_seq(acc / jnp.where(l == 0.0, 1.0, l))
