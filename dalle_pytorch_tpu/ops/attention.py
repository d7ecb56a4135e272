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


# ---------------------------------------------------------------------------
# grouped-query, gated attention over a window or the whole sequence (the
# ``WindowGQABlock`` of ops/transformer.py)
# ---------------------------------------------------------------------------
#
# ``heads`` query heads read ``kv_heads`` key/value heads: query head i
# reads head ``i // (heads / kv_heads)``, so the query heads of one group
# lie side by side and (..., heads, d) reshapes to (..., kv_heads, group,
# d) as it lies. A token caches one K and one V row a layer, every
# key/value head's numbers side by side in it. The reads differ in where
# the rows lie (a sequence at once: prefill and the full forward, a whole
# group against its head's rows in one product; gathered pages: decode,
# whole rows against block-diagonal queries) and share the mask semantics:
# ``allowed`` says which rows, and a sliding layer's says the window too.

def rope_half(x: Array, positions: Array, theta: float) -> Array:
    """Rotary positions with the rotate-half pairing: (x[i], x[i + d/2])
    turned by ``positions * theta ** (-2i / d)``. ``positions``
    broadcasts against ``x.shape[:-1]``. Angles and the rotation in f32."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., :d // 2], xf[..., d // 2:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.astype(x.dtype)


def gqa_init(key: Array, dim: int, heads: int, blk,
             dtype=jnp.float32, full: bool = False) -> dict:
    """No biases; the norms over a query and a key head have one gain
    vector for all heads. A layer type's key/value heads, and what else
    the block's fields leave out or add (``WindowGQABlock``): the gate,
    the two norms, a window layer's sink logits (float32, a query head)."""
    ks = jax.random.split(key, 5)
    dh, kvh = blk.head_dim, blk.kv_heads_of(full)
    dv = blk.v_head_dim or dh
    out = {
        "q": core.linear_init(ks[0], dim, heads * dh, bias=False,
                              dtype=dtype),
        "k": core.linear_init(ks[1], dim, kvh * dh, bias=False, dtype=dtype),
        "v": core.linear_init(ks[2], dim, kvh * dv, bias=False, dtype=dtype),
        "out": core.linear_init(ks[4], heads * dv, dim, bias=False,
                                dtype=dtype),
    }
    if blk.out_gate:
        out["gate"] = core.linear_init(ks[3], dim, heads * dh, bias=False,
                                       dtype=dtype)
    if blk.qk_norm:
        out["q_ln"] = core.rmsnorm_init(dh, dtype)
        out["k_ln"] = core.rmsnorm_init(dh, dtype)
    if blk.sink and not full:
        out["sink"] = jnp.zeros((heads,), jnp.float32)
    return out


def gqa_project(params: dict, h: Array, positions: Array, heads: int, blk,
                full: bool):
    """h (..., dim) normed input, ``positions`` broadcastable to
    ``h.shape[:-1]`` -> (q (..., heads, dh), gate (..., heads * dh) or
    None, (k (..., kv_heads, dh), v (..., kv_heads, dv)): the rows to
    cache), the key/value heads those of the layer's type (``full`` or
    window). Query and key heads are normed where the block has such
    norms and turned by position at their layer type's rotary base
    (``blk.rope_theta_of``; None: a layer that carries no position), on
    their first ``rotary_dim`` numbers where the block turns a part; the
    values are multiplied by ``value_scale``."""
    dh, kvh = blk.head_dim, blk.kv_heads_of(full)
    lead = h.shape[:-1]
    with jax.named_scope("attn.proj"):
        q = core.linear(params["q"], h).reshape(lead + (heads, dh))
        k = core.linear(params["k"], h).reshape(lead + (kvh, dh))
        v = core.linear(params["v"], h).reshape(lead + (kvh, -1))
        gate = core.linear(params["gate"], h) if "gate" in params else None
        if blk.value_scale != 1.0:
            v = v * jnp.asarray(blk.value_scale, v.dtype)
    if "q_ln" in params:
        q = core.rmsnorm(params["q_ln"], q, eps=blk.norm_eps)
        k = core.rmsnorm(params["k_ln"], k, eps=blk.norm_eps)
    theta = blk.rope_theta_of(full)
    if theta is not None:
        with jax.named_scope("attn.proj"):
            at = jnp.asarray(positions)[..., None]
            q = rope_part(q, at, theta, blk.rotary_dim)
            k = rope_part(k, at, theta, blk.rotary_dim)
    return q, gate, (k, v)


def rope_part(x: Array, positions: Array, theta: float,
              turned: Optional[int]) -> Array:
    """``rope_half`` on the first ``turned`` numbers of the last axis (the
    pairs lie inside them), the others left as they are; None: on all."""
    if turned is None or turned == x.shape[-1]:
        return rope_half(x, positions, theta)
    return jnp.concatenate([rope_half(x[..., :turned], positions, theta),
                            x[..., turned:]], axis=-1)


def _read_scope(window: bool):
    """A read's name in a trace: a window layer's or a full layer's."""
    return jax.named_scope("attn.window") if window \
        else jax.named_scope("attn.read")


def gqa_attend_materialised(q: Array, k: Array, v: Array, allowed: Array,
                            scale: float, window: bool,
                            diff_lam: Optional[Array] = None,
                            sink: Optional[Array] = None):
    """The prefill read. q (b, n, heads, dh), k (b, m, kv_heads, dh), v
    (b, m, kv_heads, dv), allowed broadcastable to (b, 1, n, m) -> (b, n,
    heads, dv). ``window`` names the read in a trace (``_read_scope``);
    the window itself is in ``allowed``. With ``diff_lam`` the read is the
    differential one (``_pair_weights``) -> (b, n, heads / 2, 2 dh). With
    ``sink`` (heads,), a learned logit a query head, the softmax's
    denominator holds ``exp(sink)`` beside the rows' terms and no value
    answers it -> (the output, the sink's weight summed over the heads
    (b, n) float32)."""
    b, n, heads, dh = q.shape
    kvh = k.shape[2]
    with _read_scope(window):
        qg = q.reshape(b, n, kvh, heads // kvh, dh)
        dots = jnp.einsum("bikgd,bjkd->bkgij", qg, k,
                          preferred_element_type=jnp.float32) * scale
        dots = jnp.where(allowed[:, :, None], dots,
                         core.neg_inf(dots.dtype))
        if sink is not None:
            dots = jnp.concatenate([dots, jnp.broadcast_to(
                sink.astype(dots.dtype).reshape(kvh, -1, 1, 1),
                dots.shape[:-1] + (1,))], axis=-1)
        w = jax.nn.softmax(dots, axis=-1)
        if sink is not None:
            w, mass = w[..., :-1], jnp.sum(w[..., -1], axis=(1, 2))
        if diff_lam is not None:
            w = _pair_weights(w, diff_lam)
            v = v.reshape(v.shape[:2] + (kvh // 2, 2 * dh))
        o = jnp.einsum("bkgij,bjkd->bikgd", w.astype(v.dtype), v)
        o = o.reshape((b, n, -1, v.shape[-1]))
        return o if sink is None else (o, mass)


def gqa_attend_rows(q: Array, k: Array, v: Array, rows_k: Array,
                    gather_v, allowed: Array, scale: float,
                    window: bool, diff_lam: Optional[Array] = None,
                    k_scale: Optional[Array] = None, gather_v_scale=None,
                    per_head: bool = False,
                    sink: Optional[Array] = None):
    """The decode read, one query a slot: q (b, heads, dh); k (b,
    kv_heads, dh) / v (b, kv_heads, dv) the token's own rows (always
    attended); ``rows_k`` (b, m, kv_heads * dh) the cached K rows as they
    lie in the gathered pages, every key/value head's dh numbers side by
    side in a row, and ``gather_v(wts)`` the V rows (b, m, kv_heads * dv)
    the same way, asked for once K's readers are done (the budget of a
    slot group is ONE gathered buffer: ops/decode.py
    ``view_slot_groups``); ``allowed`` (b, m) -> (b, heads, dv). The
    classic block's read is this one at ``kv_heads == heads``; a value
    head may be narrower than a key head (``dv`` is ``v``'s).

    The rows are contracted whole, all query heads at once, as the latent
    block's absorbed read contracts its rows: a query head is given zeros
    for the columns of the key/value heads it does not read (block
    diagonal, ``_own_columns``), and of the weighted sum of whole V rows it
    keeps its own head's columns. A contraction of one head's columns
    alone (a batch dimension between page and row) is what the compiler
    turned into a relayout of every gathered page (PERF.md section 6,
    PR 33); the zeros cost matrix-unit passes that have no other use here.
    ``per_head`` asks for exactly that contraction, the row viewed as (m,
    kv_heads, dh): under a mesh that shards the heads a whole-row
    contraction would sum partial scores across chips, and a head's own
    columns lie on one chip. The mask, the softmax and the self logit are
    the same in both forms.

    An int8 pool hands its float32 scale pages beside the rows, ``k_scale``
    (b, m, kv_heads) and ``gather_v_scale()`` the same: they multiply the
    float32 scores and the weights, outside the contractions, so that no
    dequantised copy of a page is made. ``window`` names the read in a
    trace. With ``diff_lam`` the read is the differential one: the same
    products and softmaxes, the pairing (``_pair_weights``) after them
    -> (b, heads / 2, 2 dh); it has no int8 form. With ``sink`` (heads,),
    a learned logit a query head, the softmax holds one term more, which
    no value answers -> (the output, the sink's weight summed over the
    heads (b,) float32)."""
    b, heads, dh = q.shape
    kvh = k.shape[1]
    dv = v.shape[-1]
    g = heads // kvh
    qg = q.reshape(b, kvh, g, dh)

    def by_head(sc):        # (b, m, kv_heads) -> (b, heads, m)
        return jnp.repeat(jnp.moveaxis(sc, 1, 2), g, axis=1)

    with _read_scope(window):
        if k_scale is not None:
            rows_k = rows_k.astype(q.dtype)
        if per_head:
            scores = jnp.einsum(
                "bkgd,bjkd->bkgj", qg, rows_k.reshape(b, -1, kvh, dh),
                preferred_element_type=jnp.float32).reshape(b, heads, -1)
        else:
            scores = jnp.einsum("bhc,bjc->bhj", _own_columns(qg), rows_k,
                                preferred_element_type=jnp.float32)
        scores = scores * scale
        if k_scale is not None:
            scores = scores * by_head(k_scale)
        scores = jnp.where(allowed[:, None, :], scores,
                           core.neg_inf(scores.dtype))
        own = jnp.einsum("bkgd,bkd->bkg", qg, k,
                         preferred_element_type=jnp.float32) * scale
        logits = [scores, own.reshape(b, heads, 1)]
        if sink is not None:
            logits.append(jnp.broadcast_to(
                sink.astype(scores.dtype)[None, :, None], (b, heads, 1)))
        wts = jax.nn.softmax(jnp.concatenate(logits, axis=-1), axis=-1)
        if sink is not None:
            wts, mass = wts[..., :-1], jnp.sum(wts[..., -1], axis=-1)
        if diff_lam is not None:
            # from here on a pair of heads is one head of twice the width
            # over a pair of key/value heads
            wts = _pair_weights(
                wts.reshape(b, kvh, g, -1), diff_lam
            ).reshape(b, heads // 2, -1)
            heads, kvh, dv = heads // 2, kvh // 2, 2 * dv
            v = v.reshape(b, kvh, dv)
        wts = wts.astype(v.dtype)
    rows_v = gather_v(wts)
    with _read_scope(window):
        wj = wts[..., :-1]
        if gather_v_scale is not None:
            wj = wj * by_head(gather_v_scale()).astype(wj.dtype)
            rows_v = rows_v.astype(wj.dtype)
        if per_head:
            o = jnp.einsum("bkgj,bjkd->bkgd", wj.reshape(b, kvh, g, -1),
                           rows_v.reshape(b, -1, kvh, dv))
        else:
            o_rows = jnp.einsum("bhj,bjc->bhc", wj, rows_v)
            # of each whole-row sum, the columns of the head's own kv head
            o = jnp.einsum("bkgjd,kj->bkgd",
                           o_rows.reshape(b, kvh, g, kvh, dv),
                           jnp.eye(kvh, dtype=o_rows.dtype))
        o = o + wts[..., -1].reshape(b, kvh, g, 1) * v[:, :, None, :]
        o = o.reshape(b, heads, dv)
        return o if sink is None else (o, mass)


def _pair_weights(w: Array, lam: Array) -> Array:
    """The differential read's pairing, after the softmaxes: ``w`` (b,
    kv_heads, group, ...) float32 softmax weights, head (k, g) reading
    key/value head k. Key/value heads pair up (2p, 2p + 1); the heads that
    read 2p + 1 are subtracted, times ``lam``, from the heads that read 2p
    -> (b, kv_heads / 2, group, ...): the weights of ``group`` double
    heads a PAIR of key/value heads, over whose values side by side (2 dh
    wide) they are summed."""
    paired = w.reshape((w.shape[0], w.shape[1] // 2, 2) + w.shape[2:])
    return paired[:, :, 0] - lam.astype(w.dtype) * paired[:, :, 1]


def _own_columns(qg: Array) -> Array:
    """(b, kv_heads, group, dh) -> (b, heads, kv_heads * dh): each query
    head's dh numbers in the columns of its own key/value head, zeros in
    the others'."""
    b, kvh, g, dh = qg.shape
    wide = jnp.einsum("bkgd,kj->bkgjd", qg, jnp.eye(kvh, dtype=qg.dtype))
    return wide.reshape(b, kvh * g, kvh * dh)


@jax.named_scope("attn.proj")
def gqa_out(params: dict, o: Array, gate: Optional[Array]) -> Array:
    """(..., heads, dv), gate (..., heads * dv) or None -> (..., dim): the
    output gate, where the block has one, and the output projection."""
    o = o.reshape(o.shape[:-2] + (-1,))
    if gate is not None:
        o = o * jax.nn.sigmoid(gate)
    return core.linear(params["out"], o)


# ---------------------------------------------------------------------------
# differential attention (the attention layers of the ``SSMHybridBlock`` of
# ops/transformer.py; arXiv:2410.05258)
# ---------------------------------------------------------------------------
#
# The grouped-query reads above with one step more: heads come in pairs,
# each softmax over its own key head of a PAIR of key/value heads, and the
# second map is subtracted, times a learned scalar a layer, from the first
# before the pair's values (side by side, twice the width) are summed
# (``_pair_weights``); the pair's output is RMS-normed and scaled by 1 -
# lam_init. The query heads lie grouped by the key head they read, as the
# grouped-query reads take them: head 4p + 2s + j is the s-th head (0: the
# map that stays, 1: the map that is subtracted) of the j-th pair over the
# key/value heads (2p, 2p + 1) and reads key head 2p + s. Biases on every
# projection. No position enters.

def diff_init(key: Array, dim: int, heads: int, blk, lam_init: float,
              dtype=jnp.float32, own_kv: bool = True) -> dict:
    """``own_kv`` False: a layer that reads another layer's keys and
    values and projects none. ``lam`` holds the four vectors of the
    lambda, (q1, k1, q2, k2), N(0, 0.1); ``lam_init`` the layer's own
    constant."""
    ks = jax.random.split(key, 5)
    dh, kvh = blk.head_dim, blk.kv_heads
    out = {
        "q": core.linear_init(ks[0], dim, heads * dh, dtype=dtype),
        "lam": core.normal_init(ks[3], (4, dh), 0.1, jnp.float32),
        "lam_init": jnp.asarray(lam_init, jnp.float32),
        "sub_ln": core.rmsnorm_init(2 * dh, dtype),
        "out": core.linear_init(ks[4], heads * dh, dim, dtype=dtype),
    }
    if own_kv:
        out["k"] = core.linear_init(ks[1], dim, kvh * dh, dtype=dtype)
        out["v"] = core.linear_init(ks[2], dim, kvh * dh, dtype=dtype)
    return out


@jax.named_scope("attn.proj")
def diff_project(params: dict, h: Array, heads: int, blk):
    """h (..., dim) normed input -> (q (..., heads, dh), (k, v) each (...,
    kv_heads, dh): the rows to cache, or None for a layer that projects
    none)."""
    dh, kvh = blk.head_dim, blk.kv_heads
    lead = h.shape[:-1]
    q = core.linear(params["q"], h).reshape(lead + (heads, dh))
    if "k" not in params:
        return q, None
    return q, (core.linear(params["k"], h).reshape(lead + (kvh, dh)),
               core.linear(params["v"], h).reshape(lead + (kvh, dh)))


def diff_lambda(params: dict) -> Array:
    """exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init, a float32 scalar."""
    lam = params["lam"].astype(jnp.float32)
    return jnp.exp(jnp.sum(lam[0] * lam[1])) \
        - jnp.exp(jnp.sum(lam[2] * lam[3])) + params["lam_init"]


def diff_out(params: dict, o: Array, eps: float) -> Array:
    """o (..., heads / 2, 2 dh), a pair's output -> (..., dim): the norm
    over each pair's output, the scale 1 - lam_init, the output
    projection."""
    o = core.rmsnorm(params["sub_ln"], o, eps=eps)
    with jax.named_scope("attn.proj"):
        o = o * (1.0 - params["lam_init"]).astype(o.dtype)
        return core.linear(params["out"], o.reshape(o.shape[:-2] + (-1,)))
