"""A selective state-space layer (S6, "Mamba-1") and the gated memory unit
that reuses its scan output: the two recurrent mixers of the state-space
hybrid block (``ops.transformer.SSMHybridBlock``).

With ``x`` the layer's normed input at position t (``d_inner``, ``d_state``,
``d_conv``, ``dt_rank`` from the block):

    [u; z] = W_in x                                  (dim -> 2 d_inner)
    c_t    = silu(sum_j w_j * u_{t-(d_conv-1)+j} + b_conv)   depthwise, causal
    [r; B_t; C_t] = W_x c_t                          (d_inner -> dt_rank + 2 d_state)
    D_t    = softplus(W_dt r + b_dt)                 (dt_rank -> d_inner)
    s_t    = exp(D_t * A) * s_{t-1} + (D_t * c_t) (x) B_t,   A = -exp(A_log)
    m_t    = s_t C_t + D_skip * c_t
    out    = W_out(m_t * silu(z_t))

The layer has two forms that are one identity: ``ssm_sequence`` over a whole
sequence from a zero state (the full forward, prefill) and ``ssm_step``, one
token against a carried state (decode). What a slot carries from one token
to the next is FIXED in size whatever its position: the state ``s`` in
float32, held (d_state, d_inner) so that its minor dimension is whole
lanes (a d_state of 16 there would be filled up to 128 in device memory),
and the convolution's tail, the last ``d_conv - 1`` inputs ``u``. The four matrix products run in the activations' type
(``ssm.proj`` in a trace); the convolution, the step size ``D_t``, the
state's update and its readout are float32 (``ssm.scan``).

The gated memory unit has no state of its own: ``W_2(silu(W_1 x) * m_t)``
with ``m_t`` the scan output of an earlier state-space layer at the same
token, before that layer's own gate (``gmu`` in a trace).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dalle_pytorch_tpu.ops import core

Array = jax.Array

# positions of the sequence scan that one iteration of its loop holds: the
# state (rows x d_state x d_inner float32: 10 MB at 32 x 16 x 5120) crosses
# HBM once an iteration and not once a position
SCAN_UNROLL = 8


def ssm_init(key: Array, dim: int, blk, dtype=jnp.float32) -> dict:
    """Mamba's published initialisers: ``A_log = log(1..d_state)`` a
    channel, ``D_skip = 1``, ``b_dt`` such that ``softplus(b_dt)`` is
    log-uniform in [1e-3, 0.1] (a state that neither dies nor overflows
    over thousands of steps). ``a_log`` and ``d_skip`` stay float32."""
    ks = jax.random.split(key, 7)
    di, ds, dc, dr = blk.d_inner, blk.d_state, blk.d_conv, blk.dt_rank
    dt = jnp.exp(jax.random.uniform(ks[5], (di,), jnp.float32)
                 * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    return {
        "in": core.linear_init(ks[0], dim, 2 * di, bias=False, dtype=dtype),
        "conv": {"w": core.uniform_fan_in(ks[1], (dc, di), dc, dtype),
                 "b": core.uniform_fan_in(ks[2], (di,), dc, dtype)},
        "x": core.linear_init(ks[3], di, dr + 2 * ds, bias=False,
                              dtype=dtype),
        "dt": {"w": core.uniform_fan_in(ks[4], (dr, di), dr, dtype),
               # the inverse of softplus
               "b": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)},
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, ds + 1, dtype=jnp.float32)), (di, ds)),
        "d_skip": jnp.ones((di,), jnp.float32),
        "out": core.linear_init(ks[6], di, dim, bias=False, dtype=dtype),
    }


def gmu_init(key: Array, dim: int, blk, dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    return {"w1": core.linear_init(k1, dim, blk.d_inner, bias=False,
                                   dtype=dtype),
            "w2": core.linear_init(k2, blk.d_inner, dim, bias=False,
                                   dtype=dtype)}


@jax.named_scope("gmu")
def gmu(params: dict, x: Array, m: Array) -> Array:
    """x (..., dim), m (..., d_inner) the shared scan output of the same
    token -> (..., dim)."""
    return core.linear(params["w2"],
                       jax.nn.silu(core.linear(params["w1"], x)) * m)


def zero_state(params: dict, rows: int, dtype) -> Tuple[Array, Array]:
    """What a row carries before its first token: (state (rows, d_state,
    d_inner) float32, tail (rows, d_conv - 1, d_inner)). The sizes are
    the parameters' own."""
    d_conv, d_inner = params["conv"]["w"].shape[-2:]
    d_state = params["a_log"].shape[-1]
    return (jnp.zeros((rows, d_state, d_inner), jnp.float32),
            jnp.zeros((rows, d_conv - 1, d_inner), dtype))


@jax.named_scope("ssm.proj")
def _in_proj(params: dict, x: Array) -> Tuple[Array, Array]:
    u, z = jnp.split(core.linear(params["in"], x), 2, axis=-1)
    return u, z


@jax.named_scope("ssm.scan")
def _conv(params: dict, taps) -> Array:
    """``taps``: each position's last ``d_conv`` inputs as ``d_conv``
    arrays (..., d_inner), oldest first -> c (..., d_inner) in the inputs'
    type. One order of summation for both forms of the layer."""
    w = params["conv"]["w"].astype(jnp.float32)
    acc = params["conv"]["b"].astype(jnp.float32)
    for j, tap in enumerate(taps):
        acc = acc + tap.astype(jnp.float32) * w[j]
    return jax.nn.silu(acc).astype(taps[-1].dtype)


@jax.named_scope("ssm.proj")
def _step_size_and_bc(params: dict, c: Array):
    """c (..., d_inner) -> (D (..., d_inner) float32, B, C (..., d_state)
    float32)."""
    dr, ds = params["dt"]["w"].shape[-2], params["a_log"].shape[-1]
    rbc = core.linear(params["x"], c)
    delta = jax.nn.softplus(
        core.linear(params["dt"], rbc[..., :dr]).astype(jnp.float32))
    return (delta, rbc[..., dr:dr + ds].astype(jnp.float32),
            rbc[..., dr + ds:].astype(jnp.float32))


def _advance(params: dict, state: Array, delta: Array, c: Array, b: Array,
             cc: Array) -> Tuple[Array, Array]:
    """One position of the recurrence, float32: state (rows, d_state,
    d_inner), delta / c (rows, d_inner), b / cc (rows, d_state) -> (the new
    state, m (rows, d_inner))."""
    a = -jnp.exp(params["a_log"].astype(jnp.float32)).T
    cf = c.astype(jnp.float32)
    state = jnp.exp(delta[:, None, :] * a) * state \
        + (delta * cf)[:, None, :] * b[:, :, None]
    m = jnp.sum(state * cc[:, :, None], axis=1) \
        + params["d_skip"].astype(jnp.float32) * cf
    return state, m


@jax.named_scope("ssm.proj")
def _out_proj(params: dict, m: Array, z: Array) -> Array:
    return core.linear(params["out"], m * jax.nn.silu(z))


def ssm_step(params: dict, x: Array, state: Tuple[Array, Array]):
    """One token a row against its carried state: x (rows, dim), state
    (``zero_state``'s pair) -> (out (rows, dim), m (rows, d_inner) in x's
    type, the new pair). The caller keeps the old pair for a row that is
    not to advance."""
    s, tail = state
    u, z = _in_proj(params, x)
    c = _conv(params, [tail[:, j] for j in range(tail.shape[1])] + [u])
    delta, b, cc = _step_size_and_bc(params, c)
    with jax.named_scope("ssm.scan"):
        s, m = _advance(params, s, delta, c, b, cc)
        m = m.astype(x.dtype)
        tail = jnp.concatenate([tail[:, 1:], u[:, None, :]], axis=1)
    return _out_proj(params, m, z), m, (s, tail)


def ssm_sequence(params: dict, x: Array, mask: Optional[Array]):
    """Whole sequences from a zero state: x (rows, n, dim), ``mask``
    (rows, n) bool or None -> (out (rows, n, dim), m (rows, n, d_inner),
    the pair each row carries on). A position whose ``mask`` is False
    leaves the state as it was and is not among the tail's inputs: a row
    padded on the right to a longer bucket carries what its own length
    gives (the convolution reads its neighbours as they lie, so a hole
    INSIDE a sequence is still an input of the positions after it).

    The products and the convolution are made for all positions at once;
    the recurrence is a sequential scan over the positions,
    ``SCAN_UNROLL`` of them an iteration. It never holds the states of all
    positions (rows x n x d_inner x d_state float32: 2.7 GB a layer at 32
    x 256 x 5120 x 16), and a log-depth scan over such an array would
    cross HBM with it at every level, where the sequential one crosses
    with one state a group of positions."""
    rows, n, _ = x.shape
    dc = params["conv"]["w"].shape[-2]
    u, z = _in_proj(params, x)
    with jax.named_scope("ssm.scan"):
        padded = jnp.pad(u, ((0, 0), (dc - 1, 0), (0, 0)))
    c = _conv(params, [padded[:, j:j + n] for j in range(dc)])
    delta, b, cc = _step_size_and_bc(params, c)
    with jax.named_scope("ssm.scan"):
        keep = jnp.ones((rows, n), bool) if mask is None else mask

        def one(s, at):
            d_t, c_t, b_t, cc_t, keep_t = at
            new, m_t = _advance(params, s, d_t, c_t, b_t, cc_t)
            return jnp.where(keep_t[:, None, None], new, s), m_t

        s0, _ = zero_state(params, rows, x.dtype)
        s, m = lax.scan(one, s0, tuple(
            jnp.moveaxis(a, 1, 0) for a in (delta, c, b, cc, keep)),
            unroll=min(SCAN_UNROLL, n))
        m = jnp.moveaxis(m, 0, 1).astype(x.dtype)
        # the last d_conv - 1 inputs of each row's own length (zeros
        # before a sequence's start): input t lies at t + d_conv - 1
        lens = jnp.sum(keep, axis=1)
        at = lens[:, None] + jnp.arange(dc - 1)[None, :]
        tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    return _out_proj(params, m, z), m, (s, tail)
