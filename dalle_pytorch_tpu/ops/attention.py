"""Multi-head attention — parameter layout + XLA reference implementation.

Semantics match the reference dense attention
(reference dalle_pytorch/transformer.py:51-89) exactly:

  * fused qkv projection, no bias (reference :60)
  * scale = ``dim ** -0.5`` — NOT ``dim_head ** -0.5`` (reference :57); a
    ``scale_mode='head'`` escape hatch provides the conventional scaling
  * pad mask applied as ``mask_i ⊗ mask_j`` with fill ``-finfo.max``
    (reference :74-77)
  * causal mask = strict upper triangle (reference :79-82)
  * output projection with bias + dropout (reference :61-64)

Implementation is selected by ``impl``:

  * ``"xla"``    — einsum reference path (this file); XLA fuses it well and it
                   is the numerics oracle for the kernel tests.
  * ``"flash"``  — Pallas flash-attention kernel (ops.flash_attention); tiled
                   online-softmax, O(n) memory, MXU-sized blocks.
  * ``"sparse"`` is expressed per-layer by the transformer via
    ops.block_sparse (VariableSparsityConfig-equivalent layout).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from dalle_pytorch_tpu.ops import core

Array = jax.Array


def attention_init(key: Array, dim: int, heads: int, dim_head: int,
                   dtype=jnp.float32) -> dict:
    """Fused qkv (no bias) + output projection, as in the reference."""
    inner = heads * dim_head
    k_qkv, k_out = jax.random.split(key)
    return {
        "qkv": core.linear_init(k_qkv, dim, inner * 3, bias=False, dtype=dtype),
        "out": core.linear_init(k_out, inner, dim, bias=True, dtype=dtype),
    }


def split_heads(x: Array, heads: int) -> Array:
    """(b, n, h*d) -> (b, h, n, d)"""
    b, n, hd = x.shape
    x = x.reshape(b, n, heads, hd // heads)
    return x.transpose(0, 2, 1, 3)


def merge_heads(x: Array) -> Array:
    """(b, h, n, d) -> (b, n, h*d)"""
    b, h, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, n, h * d)


@jax.named_scope("attn.proj")
def qkv_project(params: dict, x: Array, heads: int):
    qkv = core.linear(params["qkv"], x)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    return (split_heads(q, heads), split_heads(k, heads), split_heads(v, heads))


def dense_attention_weights(q: Array, k: Array, scale: float,
                            mask: Optional[Array], causal: bool,
                            offset: Optional[int] = None) -> Array:
    """Masked softmax attention weights, reference semantics.

    ``offset`` gives the absolute position of ``q``'s first row for decode
    steps where ``q`` holds positions ``[offset, offset + n_q)`` against keys
    ``[0, n_k)``. ``None`` (the default) end-aligns the queries against the
    keys — the common decode shape, and plain self-attention when
    ``n_q == n_k``.
    """
    dots = jnp.einsum("bhid,bhjd->bhij", q, k) * scale
    fill = core.neg_inf(dots.dtype)

    n_q, n_k = dots.shape[-2], dots.shape[-1]
    row0 = (n_k - n_q) if offset is None else offset   # abs pos of q row 0

    if mask is not None:
        # Query rows use the same absolute positions as the causal check.
        q_mask = lax.dynamic_slice_in_dim(mask, row0, n_q, axis=1) \
            if mask.shape[1] != n_q else mask
        pair = q_mask[:, None, :, None] & mask[:, None, None, :]
        dots = jnp.where(pair, dots, fill)

    if causal:
        # -inf (not the finite pad fill): a fully-padded row then degrades
        # to a uniform average over its CAUSAL PREFIX rather than leaking
        # future positions — shared semantics with ops.flash_attention
        # (deliberate fix of a reference quirk; see flash_attention module
        # docstring).
        rows = jnp.arange(n_q)[:, None] + row0
        cols = jnp.arange(n_k)[None, :]
        dots = jnp.where(cols <= rows, dots, -jnp.inf)

    return jax.nn.softmax(dots, axis=-1)


@jax.named_scope("attn.proj")
def output_tail(params: dict, out: Array, *, dropout_rate: float = 0.0,
                dropout_key: Optional[Array] = None,
                train: bool = False) -> Array:
    """Shared post-attention tail: merge heads -> out proj -> dropout
    (reference transformer.py:61-64). Used by both the dense and the
    per-layer sparse paths so they cannot drift."""
    out = merge_heads(out)
    out = core.linear(params["out"], out)
    return core.dropout(dropout_key, out, dropout_rate, train)


def attention_apply(params: dict, x: Array, *, heads: int, dim_head: int,
                    scale: float, causal: bool,
                    mask: Optional[Array] = None,
                    dropout_rate: float = 0.0,
                    dropout_key: Optional[Array] = None,
                    train: bool = False,
                    impl: str = "xla",
                    bwd_impl: str = "xla",
                    block_q: int = 128,
                    block_k: int = 128) -> Array:
    """Full attention block: qkv proj -> attention -> out proj (+dropout).
    ``bwd_impl`` selects the flash backward ('xla' blockwise | 'pallas'
    kernels); ``block_q``/``block_k`` the flash tile sizes. Both are
    ignored on the xla forward path."""
    if impl not in ("xla", "flash"):
        raise ValueError(f"unknown attention impl {impl!r}; "
                         f"expected 'xla' or 'flash'")
    q, k, v = qkv_project(params, x, heads)

    if impl == "flash":
        from dalle_pytorch_tpu.ops.flash_attention import flash_attention
        out = flash_attention(q, k, v, scale=scale, causal=causal, mask=mask,
                              bwd_impl=bwd_impl,
                              block_q=block_q, block_k=block_k)
    else:
        with jax.named_scope("attn.read"):
            attn = dense_attention_weights(q, k, scale, mask, causal)
            out = jnp.einsum("bhij,bhjd->bhid", attn, v)

    return output_tail(params, out, dropout_rate=dropout_rate,
                       dropout_key=dropout_key, train=train)


# ---------------------------------------------------------------------------
# latent attention (the ``LatentMoEBlock`` of ops/transformer.py)
# ---------------------------------------------------------------------------
#
# A token's cached state is ONE row a layer: the normed latent ``c``
# (``kv_rank`` wide) and the roped key part ``k_rope`` shared by every
# head, side by side and filled up with zeros to whole lanes (``row``,
# ``blk.row_width`` wide; the zeros add nothing to a contraction). The
# per-head keys and values are products of ``c`` with ``k_up`` / ``v_up``,
# and there are two reads that are one identity: the MATERIALISED read
# makes those products for the rows it attends (a whole prompt at once:
# prefill, the full forward), the ABSORBED read moves ``k_up`` onto the
# query and ``v_up`` behind the weighted sum, so that every head contracts
# the same cached rows as they lie (decode: one matrix product a slot).

def rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary positions over interleaved pairs: (x[2i], x[2i+1]) turned by
    ``positions * theta ** (-2i / d)``. ``positions`` broadcasts against
    ``x.shape[:-1]``. Angles and the rotation in f32."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def latent_init(key: Array, dim: int, heads: int, blk,
                dtype=jnp.float32) -> dict:
    """No biases anywhere; ``k_up`` / ``v_up`` are the two halves of the
    published ``kv_b_proj``, kept apart and per head so that neither read
    slices a weight."""
    ks = jax.random.split(key, 5)
    r, dn, dr, dv = (blk.kv_rank, blk.qk_nope_dim, blk.qk_rope_dim,
                     blk.v_head_dim)
    return {
        "q": core.linear_init(ks[0], dim, heads * (dn + dr), bias=False,
                              dtype=dtype),
        "kva": core.linear_init(ks[1], dim, r + dr, bias=False, dtype=dtype),
        "kv_ln": core.rmsnorm_init(r, dtype),
        "k_up": core.uniform_fan_in(ks[2], (r, heads, dn), r, dtype),
        "v_up": core.uniform_fan_in(ks[3], (r, heads, dv), r, dtype),
        "out": core.linear_init(ks[4], heads * dv, dim, bias=False,
                                dtype=dtype),
    }


def latent_project(params: dict, h: Array, positions: Array, heads: int,
                   blk):
    """h (..., dim) normed input, ``positions`` broadcastable to
    ``h.shape[:-1]`` -> (q_nope (..., heads, dn), q_rope (..., heads, dr)
    roped, row (..., row_width): the row to cache, [c | k_rope | 0])."""
    r, dn, dr = blk.kv_rank, blk.qk_nope_dim, blk.qk_rope_dim
    positions = jnp.asarray(positions)
    with jax.named_scope("attn.proj"):
        q = core.linear(params["q"], h).reshape(h.shape[:-1]
                                                + (heads, dn + dr))
        q_nope = q[..., :dn]
        q_rope = rope(q[..., dn:], positions[..., None], blk.rope_theta)
    with jax.named_scope("attn.latent"):
        kva = core.linear(params["kva"], h)
        c = core.rmsnorm(params["kv_ln"], kva[..., :r], eps=blk.norm_eps)
        k_rope = rope(kva[..., r:], positions, blk.rope_theta)
        fill = jnp.zeros(c.shape[:-1] + (blk.row_width - blk.entry_width,),
                         c.dtype)
        row = jnp.concatenate([c, k_rope, fill], axis=-1)
    return q_nope, q_rope, row


def latent_attend_materialised(params: dict, q_nope: Array, q_rope: Array,
                               entry: Array, allowed: Array, blk,
                               scale: float) -> Array:
    """The prefill read. q_* (b, n, heads, .), entry (b, m, row_width),
    allowed broadcastable to (b, 1, n, m) -> (b, n, heads, dv): keys and
    values are made from the latent for every row and attended per head."""
    r = blk.kv_rank
    with jax.named_scope("attn.latent"):
        c, k_rope = entry[..., :r], entry[..., r:blk.entry_width]
        k_nope = jnp.einsum("bjr,rhd->bjhd", c, params["k_up"].astype(c.dtype))
        v = jnp.einsum("bjr,rhd->bjhd", c, params["v_up"].astype(c.dtype))
    with jax.named_scope("attn.read"):
        dots = (jnp.einsum("bihd,bjhd->bhij", q_nope, k_nope,
                           preferred_element_type=jnp.float32)
                + jnp.einsum("bihd,bjd->bhij", q_rope, k_rope,
                             preferred_element_type=jnp.float32)) * scale
        dots = jnp.where(allowed, dots, core.neg_inf(dots.dtype))
        w = jax.nn.softmax(dots, axis=-1).astype(v.dtype)
        return jnp.einsum("bhij,bjhd->bihd", w, v)


def latent_attend_absorbed(params: dict, q_nope: Array, q_rope: Array,
                           rows: Array, allowed: Array, entry: Array, blk,
                           scale: float) -> Array:
    """The decode read. One query a slot: q_* (b, heads, .), ``rows``
    (b, m, row_width) cached rows with ``allowed`` (b, m), ``entry`` (b,
    row_width) the token's own row (always attended) -> (b, heads, dv).
    ``k_up`` is folded into the query and ``v_up`` applied to the weighted
    sum of rows, so the rows are contracted as they lie, whole, all heads
    at once: the query and ``v_up`` are given zeros for the part of a row
    that is not theirs."""
    fill = blk.row_width - blk.entry_width
    with jax.named_scope("attn.latent"):
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope,
                           params["k_up"].astype(q_nope.dtype))
        q = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros(q_rope.shape[:-1] + (fill,),
                                      q_rope.dtype)], axis=-1)
    with jax.named_scope("attn.read"):
        scores = jnp.einsum("bhc,bjc->bhj", q, rows,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(allowed[:, None, :], scores,
                           core.neg_inf(scores.dtype))
        own = jnp.einsum("bhc,bc->bh", q, entry,
                         preferred_element_type=jnp.float32) * scale
        w = jax.nn.softmax(
            jnp.concatenate([scores, own[..., None]], axis=-1), axis=-1)
        w = w.astype(rows.dtype)
        # the weighted sum of WHOLE rows: a slice of the rows (or of this
        # sum: the compiler moves it onto the rows) is a copy of them
        o_row = jnp.einsum("bhj,bjc->bhc", w[..., :-1], rows) \
            + w[..., -1:] * entry[:, None, :]
    with jax.named_scope("attn.latent"):
        v_up = jnp.pad(params["v_up"].astype(o_row.dtype),
                       ((0, blk.row_width - blk.kv_rank), (0, 0), (0, 0)))
        return jnp.einsum("bhc,chd->bhd", o_row, v_up)


@jax.named_scope("attn.proj")
def latent_out(params: dict, o: Array) -> Array:
    """(..., heads, dv) -> (..., dim): the output projection."""
    return core.linear(params["out"], o.reshape(o.shape[:-2] + (-1,)))
