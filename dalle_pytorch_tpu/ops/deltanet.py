"""A gated delta-rule layer ("Gated DeltaNet"): the recurrent mixer of the
delta-rule hybrid block (``ops.transformer.DeltaGQABlock``).

With ``x`` the layer's normed input at position t, ``nk`` key heads of
``dk`` numbers and ``nv`` value heads of ``dv`` (value head h reads key
head ``h // (nv / nk)``), ``taps`` the block's ``conv_taps``:

    [q; k; v; z] = W_in x            (dim -> 2 nk dk + 2 nv dv)
    [b; a]       = W_ba x            (dim -> 2 nv)
    [q; k; v]_t  = silu(sum_j w_j * [q; k; v]_{t-(taps-1)+j})
                                     depthwise, causal, no bias, zeros
                                     before position 0
    q = l2norm(q) / sqrt(dk),  k = l2norm(k)       a head
    beta = sigmoid(b),  g = -exp(A_log) * softplus(a + dt_bias)
                                     a value head each, float32
    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
    o_t = S^T q_t                    S (dk, dv) a value head, zero at first
    out = W_out(w * o / sqrt(mean(o^2) + eps) * silu(z))   the norm a head

The layer has two forms that are one identity: ``delta_sequence`` over a
whole sequence from a zero state (the full forward, prefill) and
``delta_step``, one token against a carried state (decode). What a slot
carries from one token to the next is FIXED in size whatever its position:
the matrix state ``S`` of every value head in float32, held (nv, dk, dv)
(the minor dimension whole lanes), and the convolution's tail, the last
``taps - 1`` projected ``[q; k; v]`` in the activations' type. The three
matrix products run in the activations' type (``delta.proj`` in a trace);
the convolution, the norms and gates, the rule and the gated norm are
float32 whatever the parameters' type (``delta.rule``).

``delta_sequence`` is the CHUNKED form of the rule (arXiv:2412.06464,
section 3.3): inside a chunk of ``CHUNK`` positions the rule's dependence
of each ``d_t`` on the earlier ones is a unit lower-triangular system,
solved once a chunk for all value heads, and the state crosses memory
once a chunk and not once a position; the step form is the rule as it is
written above. The sizes are the parameters' own: ``a_log`` and
``dt_bias`` lie (key heads, value heads a key head), the grouping in
their shape.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dalle_pytorch_tpu.ops import core

Array = jax.Array

# positions of one chunk of the sequence form (the published
# implementation's): the triangular system is CHUNK x CHUNK a value head
CHUNK = 64
L2_EPS = 1e-6
# the gated norm's epsilon: the block's ``norm_eps``, which
# ``ops.transformer.DeltaGQABlock`` holds to this value (the two forms
# are called with the parameters and the input alone)
NORM_EPS = 1e-6
F32 = jnp.float32
HI = lax.Precision.HIGHEST


def delta_init(key: Array, dim: int, blk, dtype=jnp.float32) -> dict:
    """The published initialisers: ``A`` uniform in (0, 16) a value head
    (``a_log`` its logarithm), ``dt_bias`` 1, the gated norm's gain 1.
    ``a_log`` and ``dt_bias`` stay float32."""
    ks = jax.random.split(key, 5)
    nk, nv = blk.key_heads, blk.value_heads
    dk, dv, taps = blk.key_head_dim, blk.value_head_dim, blk.conv_taps
    conv_dim = 2 * nk * dk + nv * dv
    return {
        "in": core.linear_init(ks[0], dim, conv_dim + nv * dv, bias=False,
                               dtype=dtype),
        "ba": core.linear_init(ks[1], dim, 2 * nv, bias=False, dtype=dtype),
        "conv": {"w": core.uniform_fan_in(ks[2], (taps, conv_dim), taps,
                                          dtype)},
        "a_log": jnp.log(jax.random.uniform(
            ks[3], (nk, nv // nk), F32, 1e-3, 16.0)),
        "dt_bias": jnp.ones((nk, nv // nk), F32),
        "norm": core.rmsnorm_init(dv, dtype),
        "out": core.linear_init(ks[4], nv * dv, dim, bias=False,
                                dtype=dtype),
    }


def _sizes(params: dict) -> Tuple[int, int, int, int]:
    """(key heads, value heads a key head, dk, dv), the parameters' own."""
    nk, group = params["a_log"].shape[-2:]
    dv = params["norm"]["g"].shape[-1]
    conv_dim = params["conv"]["w"].shape[-1]
    return nk, group, (conv_dim - nk * group * dv) // (2 * nk), dv


def zero_state(params: dict, rows: int, dtype) -> Tuple[Array, Array]:
    """What a row carries before its first token: (S (rows, nv, dk, dv)
    float32, tail (rows, taps - 1, conv_dim))."""
    nk, group, dk, dv = _sizes(params)
    taps, conv_dim = params["conv"]["w"].shape[-2:]
    return (jnp.zeros((rows, nk * group, dk, dv), F32),
            jnp.zeros((rows, taps - 1, conv_dim), dtype))


@jax.named_scope("delta.proj")
def _in_proj(params: dict, x: Array):
    """-> (the convolution's input [q; k; v], z, b, a)."""
    conv_dim = params["conv"]["w"].shape[-1]
    qkvz = core.linear(params["in"], x)
    b, a = jnp.split(core.linear(params["ba"], x), 2, axis=-1)
    return qkvz[..., :conv_dim], qkvz[..., conv_dim:], b, a


def _conv(params: dict, taps) -> Array:
    """``taps``: each position's last ``taps`` inputs as that many arrays
    (..., conv_dim), oldest first -> silu(conv) (..., conv_dim) float32.
    One order of summation for both forms of the layer."""
    w = params["conv"]["w"].astype(F32)
    acc = taps[0].astype(F32) * w[0]
    for j, tap in enumerate(taps[1:], 1):
        acc = acc + tap.astype(F32) * w[j]
    return jax.nn.silu(acc)


def _l2norm(x: Array) -> Array:
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + L2_EPS)


def _rule_inputs(params: dict, c: Array, b: Array, a: Array):
    """c (..., conv_dim) float32, b / a (..., nv) -> (q, k (..., nk, dk),
    v (..., nk, group, dv), beta, g (..., nk, group)), all float32."""
    nk, group, dk, dv = _sizes(params)
    lead = c.shape[:-1]
    q = c[..., :nk * dk].reshape(lead + (nk, dk))
    k = c[..., nk * dk:2 * nk * dk].reshape(lead + (nk, dk))
    v = c[..., 2 * nk * dk:].reshape(lead + (nk, group, dv))
    beta = jax.nn.sigmoid(b.astype(F32)).reshape(lead + (nk, group))
    g = -jnp.exp(params["a_log"].astype(F32)) * jax.nn.softplus(
        a.astype(F32).reshape(lead + (nk, group))
        + params["dt_bias"].astype(F32))
    return _l2norm(q) * dk ** -0.5, _l2norm(k), v, beta, g


def _gated_norm(params: dict, o: Array, z: Array, dtype):
    """o (..., nk, group, dv) float32, z (..., nv * dv) -> (..., nv * dv)
    in ``dtype``: the norm over each head's numbers, its gain, the gate."""
    lead = o.shape[:-3]
    o = o.reshape(lead + (-1, o.shape[-1]))
    y = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                      + NORM_EPS)
    y = y * params["norm"]["g"].astype(F32)
    y = y * jax.nn.silu(z.astype(F32).reshape(y.shape))
    return y.reshape(lead + (-1,)).astype(dtype)


@jax.named_scope("delta.proj")
def _out_proj(params: dict, y: Array) -> Array:
    return core.linear(params["out"], y)


def delta_step(params: dict, x: Array, state: Tuple[Array, Array]):
    """One token a row against its carried state: x (rows, dim), state
    (``zero_state``'s pair) -> (out (rows, dim), the new pair). The
    caller keeps the old pair for a row that is not to advance.

    The old state is read twice and written once: ``S^T k`` and ``S^T q``
    come of ONE pass over it (``o = exp(g) S^T q + (k . q) d`` is the
    readout of the updated state without a pass over that), the update is
    the other."""
    s, tail = state
    nk, group, dk, dv = _sizes(params)
    rows = x.shape[0]
    mixed, z, b, a = _in_proj(params, x)
    with jax.named_scope("delta.rule"):
        c = _conv(params, [tail[:, j] for j in range(tail.shape[1])]
                  + [mixed])
        q, k, v, beta, g = _rule_inputs(params, c, b, a)
        s = s.reshape(rows, nk, group, dk, dv)
        decay = jnp.exp(g)
        kq = jnp.stack([k, q], axis=2)                  # (rows, nk, 2, dk)
        read = jnp.sum(s[:, :, :, None] * kq[:, :, None, :, :, None],
                       axis=-2)                 # (rows, nk, group, 2, dv)
        d = beta[..., None] * (v - decay[..., None] * read[..., 0, :])
        s = decay[..., None, None] * s \
            + k[:, :, None, :, None] * d[..., None, :]
        o = decay[..., None] * read[..., 1, :] \
            + jnp.sum(k * q, axis=-1)[:, :, None, None] * d
        y = _gated_norm(params, o, z, x.dtype)
        s = s.reshape(rows, nk * group, dk, dv)
        tail = jnp.concatenate([tail[:, 1:], mixed[:, None, :]], axis=1)
    return _out_proj(params, y), (s, tail)


def _chunked_rule(q: Array, k: Array, v: Array, beta: Array, g: Array):
    """The rule over whole sequences from a zero state, a chunk at a
    time. q, k (R, H, N, C, K); v (R, H, G, N, C, V); beta, g (R, H, G, N,
    C): R rows, H key heads, G value heads a key head, N chunks of C
    positions -> (o (R, H, G, N, C, V), the state after the last position
    (R, H, G, K, V)). A position whose ``beta`` and ``g`` are 0 leaves the
    state as it was."""
    c = q.shape[-2]
    lower = jnp.tril(jnp.ones((c, c), bool))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    gc = jnp.cumsum(g, axis=-1)
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))               # (R, H, G, N, C, C)
    kk = jnp.einsum("rhnik,rhnjk->rhnij", k, k, precision=HI)
    system = jnp.where(strict, kk[:, :, None] * beta[..., :, None] * decay,
                       0.0) + jnp.eye(c, dtype=F32)
    # each d_t in terms of the chunk's inputs alone: (I + A)^-1 applied
    # to beta v and to beta exp(gc) k
    rhs = jnp.concatenate(
        [v * beta[..., None],
         k[:, :, None] * (beta * jnp.exp(gc))[..., None]], axis=-1)
    solved = lax.linalg.triangular_solve(
        system, rhs, left_side=True, lower=True, unit_diagonal=True)
    u, w = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    qk = jnp.where(lower, jnp.einsum("rhnik,rhnjk->rhnij", q, k,
                                     precision=HI)[:, :, None] * decay, 0.0)

    def one(s, chunk):
        q_n, k_n, u_n, w_n, qk_n, gc_n = chunk
        new = u_n - jnp.einsum("rhgck,rhgkv->rhgcv", w_n, s, precision=HI)
        o_n = jnp.einsum("rhck,rhgkv->rhgcv", q_n, s, precision=HI) \
            * jnp.exp(gc_n)[..., None] \
            + jnp.einsum("rhgij,rhgjv->rhgiv", qk_n, new, precision=HI)
        last = gc_n[..., -1:]
        s = s * jnp.exp(last)[..., None] + jnp.einsum(
            "rhgck,rhgcv->rhgkv",
            k_n[:, :, None] * jnp.exp(last - gc_n)[..., None], new,
            precision=HI)
        return s, o_n

    r, h, n, _, dk = q.shape
    s0 = jnp.zeros((r, h, v.shape[2], dk, v.shape[-1]), F32)
    s, o = lax.scan(one, s0, (
        jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(u, 3, 0),
        jnp.moveaxis(w, 3, 0), jnp.moveaxis(qk, 3, 0),
        jnp.moveaxis(gc, 3, 0)))
    return jnp.moveaxis(o, 0, 3), s


def delta_sequence(params: dict, x: Array, mask: Optional[Array]):
    """Whole sequences from a zero state: x (rows, n, dim), ``mask``
    (rows, n) bool or None -> (out (rows, n, dim), the pair each row
    carries on). A position whose ``mask`` is False leaves the state as it
    was and is not among the tail's inputs: a row padded on the right to a
    longer bucket carries what its own length gives (the convolution reads
    its neighbours as they lie, so a hole INSIDE a sequence is still an
    input of the positions after it).

    The products and the convolution are made for all positions at once;
    the rule runs chunk by chunk (``_chunked_rule``), the sequence filled
    up to whole chunks with positions that leave the state alone."""
    rows, n, _ = x.shape
    nk, group, dk, dv = _sizes(params)
    taps = params["conv"]["w"].shape[-2]
    mixed, z, b, a = _in_proj(params, x)
    with jax.named_scope("delta.rule"):
        padded = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
        c = _conv(params, [padded[:, j:j + n] for j in range(taps)])
        q, k, v, beta, g = _rule_inputs(params, c, b, a)
        keep = jnp.ones((rows, n), bool) if mask is None else mask
        beta = jnp.where(keep[:, :, None, None], beta, 0.0)
        g = jnp.where(keep[:, :, None, None], g, 0.0)
        chunk = min(CHUNK, n)
        fill = -n % chunk

        def chunks(a, heads: int):
            """(rows, n, heads..., ...) -> (rows, heads..., N, C, ...)."""
            a = jnp.pad(a, ((0, 0), (0, fill)) + ((0, 0),) * (a.ndim - 2))
            a = a.reshape((rows, -1, chunk) + a.shape[2:])
            return jnp.moveaxis(a, (1, 2), (1 + heads, 2 + heads))

        o, s = _chunked_rule(chunks(q, 1), chunks(k, 1), chunks(v, 2),
                             chunks(beta, 2), chunks(g, 2))
        # (rows, nk, group, N, C, dv) -> (rows, n, nk, group, dv)
        o = jnp.moveaxis(o, (3, 4), (1, 2)).reshape(
            (rows, -1, nk, group, dv))[:, :n]
        y = _gated_norm(params, o, z, x.dtype)
        s = s.reshape(rows, nk * group, dk, dv)
        # the last taps - 1 inputs of each row's own length (zeros before
        # a sequence's start): input t lies at t + taps - 1
        lens = jnp.sum(keep, axis=1)
        at = lens[:, None] + jnp.arange(taps - 1)[None, :]
        tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    return _out_proj(params, y), (s, tail)
