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
