"""A gated short convolution: the recurrent mixer of the short-convolution
hybrid block (``ops.transformer.ShortConvGQABlock``).

With ``x`` the layer's normed input at position t (``dim`` wide; ``taps``
the block's ``conv_taps``):

    [B; C; u] = W_in x                               (dim -> 3 dim)
    g_t  = B_t * u_t
    c_t  = sum_j w_j * g_{t-(taps-1)+j}              depthwise, causal,
                                                     g = 0 before position 0
    out  = W_out (C_t * c_t)

No bias and no activation anywhere: the two gates are plain products.

The layer has two forms that are one identity: ``shortconv_sequence`` over
a whole sequence from an empty tail (the full forward, prefill) and
``shortconv_step``, one token against a carried tail (decode). What a slot
carries from one token to the next is the convolution's TAIL, the last
``taps - 1`` gated inputs ``g`` in the activations' type: fixed in size
whatever the position, and no row is ever cached. The two matrix products
run in the activations' type (``conv.proj`` in a trace); the gates and the
taps are float32 over gated inputs rounded to the activations' type, the
type in which the tail holds them (``conv.mix``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.ops import core

Array = jax.Array


def shortconv_init(key: Array, dim: int, blk, dtype=jnp.float32) -> dict:
    k_in, k_conv, k_out = jax.random.split(key, 3)
    taps = blk.conv_taps
    return {
        "in": core.linear_init(k_in, dim, 3 * dim, bias=False, dtype=dtype),
        "conv": {"w": core.uniform_fan_in(k_conv, (taps, dim), taps, dtype)},
        "out": core.linear_init(k_out, dim, dim, bias=False, dtype=dtype),
    }


@jax.named_scope("conv.proj")
def _in_proj(params: dict, x: Array):
    """-> the three streams B, C, u."""
    return jnp.split(core.linear(params["in"], x), 3, axis=-1)


@jax.named_scope("conv.mix")
def _gate_in(b: Array, u: Array) -> Array:
    """g = B * u, rounded to the type the tail holds it in."""
    return (b.astype(jnp.float32) * u.astype(jnp.float32)).astype(u.dtype)


@jax.named_scope("conv.mix")
def _mix(params: dict, taps, c: Array) -> Array:
    """``taps``: each position's last ``taps`` gated inputs as that many
    arrays (..., dim), oldest first -> C * conv (..., dim) in the inputs'
    type. One order of summation for both forms of the layer."""
    w = params["conv"]["w"].astype(jnp.float32)
    acc = taps[0].astype(jnp.float32) * w[0]
    for j, tap in enumerate(taps[1:], 1):
        acc = acc + tap.astype(jnp.float32) * w[j]
    return (c.astype(jnp.float32) * acc).astype(c.dtype)


@jax.named_scope("conv.proj")
def _out_proj(params: dict, y: Array) -> Array:
    return core.linear(params["out"], y)


def shortconv_step(params: dict, x: Array, tail: Array):
    """One token a row against its carried tail: x (rows, dim), tail
    (rows, taps - 1, dim), zeros before a row's first token -> (out (rows,
    dim), the new tail). The caller keeps the old tail for a row that is
    not to advance."""
    b, c, u = _in_proj(params, x)
    g = _gate_in(b, u)
    y = _mix(params, [tail[:, j] for j in range(tail.shape[1])] + [g], c)
    with jax.named_scope("conv.mix"):
        tail = jnp.concatenate([tail[:, 1:], g[:, None, :]], axis=1)
    return _out_proj(params, y), tail


def shortconv_sequence(params: dict, x: Array, mask: Optional[Array]):
    """Whole sequences from an empty tail: x (rows, n, dim), ``mask``
    (rows, n) bool or None -> (out (rows, n, dim), the tail each row
    carries on: its last ``taps - 1`` gated inputs, zeros before the
    sequence's start). A position whose ``mask`` is False is not among
    the tail's inputs: a row padded on the right to a longer bucket
    carries what its own length gives (the convolution reads its
    neighbours as they lie, so a hole INSIDE a sequence is still an input
    of the positions after it)."""
    rows, n, _ = x.shape
    taps = params["conv"]["w"].shape[-2]
    b, c, u = _in_proj(params, x)
    g = _gate_in(b, u)
    with jax.named_scope("conv.mix"):
        padded = jnp.pad(g, ((0, 0), (taps - 1, 0), (0, 0)))
    y = _mix(params, [padded[:, j:j + n] for j in range(taps)], c)
    with jax.named_scope("conv.mix"):
        # the last taps - 1 gated inputs of each row's own length: input
        # t lies at t + taps - 1
        lens = jnp.full((rows,), n) if mask is None \
            else jnp.sum(mask, axis=1)
        at = lens[:, None] + jnp.arange(taps - 1)[None, :]
        tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    return _out_proj(params, y), tail
