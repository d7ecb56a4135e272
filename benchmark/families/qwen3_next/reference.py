"""The plain reference of the ``qwen3_next`` family's configurations.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``precision=HIGHEST``: the equations of a ``qwen3_next`` layer over whole
sequences, the delta rule as its TOKEN-BY-TOKEN recurrence from a zero
state (no chunking, no carried tail: the convolution runs over the whole
sequence with zeros before its first position), the attention computed for
every row against every row, a plain loop over the held experts with each
token's weight for an expert it did not pick at zero, no cache, no pages,
no kernels, no grouped products, nothing imported from the program under
test or from another family. Weights come from this family's ``weights.py``
layer by layer, cast up from the stored type. Long sequences are computed
a block of query rows at a time, so that one head's scores fit whatever
the length.

With ``n(x) = x / sqrt(mean(x^2) + eps) * g`` (``g = 1 + w``: the
zero-centred norm's gain, stored whole), a layer with input ``h`` at
position ``t``:

    a = n_in(h)
    delta layer:  [q; k; v; z] = W_in a;  [b; a'] = W_ba a;
        [q; k; v]_t = silu(sum_j w_j * [q; k; v]_{t - (taps-1) + j}) (a
        weight a channel a tap, zeros before position 0, no bias);
        q = l2norm(q) / sqrt(dk), k = l2norm(k) a key head (eps 1e-6),
        value head i reads key head i // (nv / nk);  beta = sigmoid(b);
        g = -exp(A_log) * softplus(a' + dt_bias);  a value head's S (dk x
        dv), zero at first:  S <- exp(g_t) S;  d = beta_t (v_t - S^T
        k_t);  S <- S + k_t d^T;  o_t = S^T q_t;
        h = h + W_out(n_head(o_t) * silu(z_t)), n_head over a head's dv
    full layer:  q = W_q a, gate = W_g a (heads x dh);  k = W_k a, v =
        W_v a (kv x dh);  q, k = n_q(q), n_k(k) a head;  q, k = RoPE(q,
        k; t) on a head's first ``rotary_dim`` numbers, rotate-half pairs
        (x[i], x[i + rotary_dim/2]) at rope_theta;  query head i reads
        key/value head i // (heads / kv);  scores q_t . k_j / sqrt(dh)
        over j <= t, softmax;  h = h + W_o (o * sigmoid(gate))
    m = n_mlp(h)
    p = softmax(W_r m) over all the experts in float32;  the k largest
        are picked;  w_i = p_i / sum_picked p;
        h = h + sum over the picked HELD experts of w_i E_i(m)
              + sigmoid(w_s . m) * Shared(m)

Token embeddings enter as they are; after the last layer a final norm,
then the untied head over the held rows.

``lower`` names the control's precision: ``"fp8"`` rounds both operands
of every matrix product to float8_e4m3fn first (the step below bfloat16),
the router's included; the rule's state and its arithmetic stay float32,
as the configuration states them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import seeds

from . import weights as W

F32 = jnp.float32
HI = lax.Precision.HIGHEST
QUERY_BLOCK = 1024      # query rows of one head scored at a time
L2_EPS = 1e-6


def _round_operand(x, lower):
    if lower is None:
        return x
    if lower == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(F32)
    raise ValueError(f"unknown lower precision {lower!r}")


def _ein(spec, a, b, lower=None):
    return jnp.einsum(spec, _round_operand(a.astype(F32), lower),
                      _round_operand(b.astype(F32), lower), precision=HI)


def _rms(p, x, eps):
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y * p["g"].astype(F32)


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + L2_EPS)


def rope(x, positions, theta, turned):
    """The first ``turned`` numbers of every head of x (n, heads, d) as
    rotate-half pairs (x[i], x[i + turned/2]), turned by pos * theta^(-2i
    / turned); the others as they are."""
    inv_freq = theta ** (-jnp.arange(0, turned, 2, dtype=F32) / turned)
    ang = positions.astype(F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :turned // 2], x[..., turned // 2:turned]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., turned:]], axis=-1)


def _unit(p, h, lower):
    """W_down(silu(W_gate h) * (W_up h)); gate | up lie side by side."""
    hidden = p["w_out"].shape[-2]
    gate = _ein("nd,df->nf", h, p["w_in"][..., :hidden], lower)
    up = _ein("nd,df->nf", h, p["w_in"][..., hidden:], lower)
    return _ein("nf,fd->nd", jax.nn.silu(gate) * up, p["w_out"], lower)


def delta_inputs(p, x, d: W.Dims, lower=None):
    """What the rule reads over one sequence x (n, dim): q, k (n, nv,
    dk) a VALUE head (its key head's), v (n, nv, dv), beta, g (n, nv) and
    the gate z (n, nv, dv)."""
    n = x.shape[0]
    a = _rms(p["ln"], x, d.norm_eps)
    qkvz = _ein("nd,df->nf", a, p["in"]["w"], lower)
    ba = _ein("nd,df->nf", a, p["ba"]["w"], lower)
    mixed = jnp.pad(qkvz[:, :d.conv_dim], ((d.conv_taps - 1, 0), (0, 0)))
    w = p["conv"]["w"].astype(F32)
    c = jax.nn.silu(sum(w[j] * mixed[j:j + n] for j in range(d.conv_taps)))
    group = d.value_heads // d.key_heads
    q = c[:, :d.key_dim].reshape(n, d.key_heads, d.key_head_dim)
    k = c[:, d.key_dim:2 * d.key_dim].reshape(n, d.key_heads,
                                              d.key_head_dim)
    q = jnp.repeat(_l2norm(q) * d.key_head_dim ** -0.5, group, axis=1)
    k = jnp.repeat(_l2norm(k), group, axis=1)
    v = c[:, 2 * d.key_dim:].reshape(n, d.value_heads, d.value_head_dim)
    z = qkvz[:, d.conv_dim:].reshape(n, d.value_heads, d.value_head_dim)
    beta = jax.nn.sigmoid(ba[:, :d.value_heads])
    g = -jnp.exp(p["a_log"].astype(F32).reshape(-1)) * jax.nn.softplus(
        ba[:, d.value_heads:] + p["dt_bias"].astype(F32).reshape(-1))
    return q, k, v, beta, g, z


def delta_rule(q, k, v, beta, g):
    """The recurrence, a token at a time from a zero state -> o (n, nv,
    dv). q, k (n, nv, dk), v (n, nv, dv), beta, g (n, nv)."""
    def one(s, at):
        q_t, k_t, v_t, beta_t, g_t = at
        s = s * jnp.exp(g_t)[:, None, None]
        d_t = beta_t[:, None] * (v_t - jnp.einsum(
            "hkv,hk->hv", s, k_t, precision=HI))
        s = s + k_t[:, :, None] * d_t[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HI)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return lax.scan(one, s0, (q, k, v, beta, g))[1]


def delta_net(p, x, d: W.Dims, lower=None):
    """The delta-rule branch's output over one sequence x (n, dim)."""
    q, k, v, beta, g, z = delta_inputs(p, x, d, lower)
    o = delta_rule(q, k, v, beta, g)
    y = _rms(p["norm"], o, d.norm_eps) * jax.nn.silu(z)
    return _ein("nf,fd->nd", y.reshape(x.shape[0], -1), p["out"]["w"],
                lower)


def attention(p, x, d: W.Dims, lower=None):
    """The attention branch's output over one sequence x (n, dim)."""
    n = x.shape[0]
    pos = jnp.arange(n)
    a = _rms(p["ln"], x, d.norm_eps)
    q = _ein("nd,df->nf", a, p["q"]["w"], lower).reshape(
        n, d.heads, d.head_dim)
    k = _ein("nd,df->nf", a, p["k"]["w"], lower).reshape(
        n, d.kv_heads, d.head_dim)
    v = _ein("nd,df->nf", a, p["v"]["w"], lower).reshape(
        n, d.kv_heads, d.head_dim)
    gate = _ein("nd,df->nf", a, p["gate"]["w"], lower)
    q = rope(_rms(p["q_ln"], q, d.norm_eps), pos, d.rope_theta,
             d.rotary_dim)
    k = rope(_rms(p["k_ln"], k, d.norm_eps), pos, d.rope_theta,
             d.rotary_dim)
    scale = d.head_dim ** -0.5
    block = min(QUERY_BLOCK, n)
    blocks = -(-n // block)
    fill = blocks * block - n

    def one_head(args):                 # a query head against its kv head
        qh, kh, vh = args

        def one_block(rows):            # (block, dh) query rows at ``at``
            qb, at = rows
            score = _ein("id,jd->ij", qb, kh, lower) * scale
            ok = pos[None, :] <= at + jnp.arange(block)[:, None]
            attn = jax.nn.softmax(jnp.where(ok, score, -jnp.inf), axis=-1)
            return _ein("ij,jd->id", attn, vh, lower)

        qh = jnp.pad(qh, ((0, fill), (0, 0))).reshape(blocks, block, -1)
        out = lax.map(one_block, (qh, jnp.arange(blocks) * block))
        return out.reshape(blocks * block, -1)[:n]

    reads = jnp.arange(d.heads) // (d.heads // d.kv_heads)
    o = lax.map(one_head, (q.transpose(1, 0, 2),
                           k.transpose(1, 0, 2)[reads],
                           v.transpose(1, 0, 2)[reads]))
    o = o.transpose(1, 0, 2).reshape(n, d.heads * d.head_dim)
    return _ein("nf,fd->nd", o * jax.nn.sigmoid(gate), p["out"]["w"], lower)


def route(p, m, d: W.Dims, lower=None):
    """-> (n, experts) float32: each token's weight for each expert, zero
    for the experts it did not pick."""
    s = jax.nn.softmax(_ein("nd,de->ne", m, p["router"]["w"], lower),
                       axis=-1)
    _, picks = lax.top_k(s, d.experts_per_token)
    picked = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], picks].set(1.0)
    return s * picked / jnp.sum(s * picked, axis=-1, keepdims=True)


def routed(p, m, weights, lower=None):
    """sum_i w_i E_i(m) over the experts of ``p`` (stacked), ``weights``
    (n, their number): a plain loop over them."""
    def one_expert(acc, xs):
        expert, w = xs
        return acc + w[:, None] * _unit(expert, m, lower), None

    out, _ = lax.scan(one_expert, jnp.zeros_like(m), (p, weights.T))
    return out


def shared(p, m, lower=None):
    """sigmoid(w_s . m) * Shared(m)."""
    gate = jax.nn.sigmoid(_ein("nd,df->nf", m, p["shared_gate"]["w"], lower))
    return gate * _unit(p["shared"], m, lower)


def feed_forward(p, x, d: W.Dims, lower=None, first=None):
    """The feed-forward branch's output: the experts of ``p`` (the
    published experts ``first`` on; this chip's unless told) and the
    shared unit."""
    m = _rms(p["ln"], x, d.norm_eps)
    first = d.first_expert if first is None else first
    held = p["experts"]["w_out"].shape[0]
    weights = route(p, m, d, lower)[:, first:first + held]
    return routed(p["experts"], m, weights, lower) + shared(p, m, lower)


def block(p: dict, x, d: W.Dims, full: bool, lower=None):
    """One layer on one sequence ``x`` of shape (n, dim)."""
    mix = attention if full else delta_net
    x = x + mix(p["attn"], x, d, lower)
    return x + feed_forward(p["ff"], x, d, lower)


def embed(po: dict, tokens, d: W.Dims):
    """``tokens`` (n,) int: text ids on the first ``text_seq_len``
    positions, image ids (no text offset) after them. Positions enter in
    the full layers (RoPE) and through the delta rule's order, not
    here."""
    pos = jnp.arange(tokens.shape[0])
    t_ids = jnp.clip(tokens, 0, d.num_text_tokens - 1)
    i_ids = jnp.clip(tokens, 0, d.num_image_tokens - 1)
    return jnp.where((pos < d.text_seq_len)[:, None],
                     po["text_emb"]["w"][t_ids].astype(F32),
                     po["image_emb"]["w"][i_ids].astype(F32))


def logits_of(po: dict, x, d: W.Dims, lower=None):
    """Masked logits (n, total_tokens): row i scores token i + 1."""
    n = x.shape[0]
    h = _rms(po["to_logits"]["ln"], x, d.norm_eps)
    lg = _ein("nd,dv->nv", h, po["to_logits"]["proj"]["w"], lower)
    row = jnp.arange(n)[:, None]
    col = jnp.arange(d.total_tokens)[None, :]
    boundary = d.text_seq_len - 1
    forbidden = (((row >= boundary) & (col < d.num_text_tokens))
                 | ((row < boundary) & (col >= d.num_text_tokens))
                 | ((row != d.seq_len - 1) & (col >= d.total_tokens - 1)))
    return jnp.where(forbidden, -jnp.inf, lg)


# ---------------------------------------------------------------------------
# serving: teacher-forced logits over what was served
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("d", "dtype", "full", "lower"))
def _serve_layer(halves, index, xs, *, d, dtype, full, lower):
    key = seeds.layer_key(seeds.seed_key_traced(halves), index)
    p = W.layer(key, d, dtype, full)
    return lax.map(lambda x: block(p, x, d, full, lower), xs)


@functools.partial(jax.jit, static_argnames=("d", "dtype"))
def _serve_embed(halves, tokens, *, d, dtype):
    po = W.outer(seeds.seed_key_traced(halves), d, dtype)
    return jax.vmap(lambda t: embed(po, t, d))(tokens)


@functools.partial(jax.jit, static_argnames=("d", "dtype", "lower"))
def _serve_gaps(halves, xs, cols, *, d, dtype, lower):
    """Per row and position: the reference's best logit minus its logit
    of the token in ``cols`` (>= 0; 0 where that token is best), and the
    column the reference puts first. A sequence at a time: the logits of
    all of them do not fit beside each other at a real vocabulary."""
    po = W.outer(seeds.seed_key_traced(halves), d, dtype)

    def one(args):
        x, col = args
        lg = logits_of(po, x, d, lower)
        got = jnp.take_along_axis(lg, col[:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - got, jnp.argmax(lg, axis=-1)

    return lax.map(one, (xs, cols))


def served_hidden(seed: int, d: W.Dims, dtype, sequences, lower=None):
    """The last layer's output (rows, seq_len - 1, dim) of the reference
    run once over each whole served sequence (prompt then served tokens),
    made layer by layer so that one block's weights live at a time."""
    halves = seeds.split_seed(seed)
    tokens = jnp.asarray(sequences, jnp.int32)[:, :-1]
    xs = _serve_embed(halves, tokens, d=d, dtype=dtype)
    for i in range(d.depth):
        xs = _serve_layer(halves, jnp.int32(d.first_layer + i), xs, d=d,
                          dtype=dtype, full=d.layer_is_full(i), lower=lower)
    return xs


def served_logits(seed: int, d: W.Dims, dtype, sequences, lower=None):
    """Logits (rows, seq_len - 1, total_tokens): for the tests, at toy
    widths (``served_gaps`` never holds them all at once)."""
    xs = served_hidden(seed, d, dtype, sequences, lower)
    po = W.outer(seeds.seed_key(seed), d, dtype)
    return jnp.stack([logits_of(po, x, d, lower) for x in xs])


def token_columns(sequences, d: W.Dims):
    """Vocabulary column of each served token at rows 0..seq_len-2."""
    seq = jnp.asarray(sequences, jnp.int32)[:, 1:]
    pos = jnp.arange(1, d.seq_len)[None, :]
    return jnp.where(pos >= d.text_seq_len, seq + d.num_text_tokens, seq)


def served_gaps(seed: int, d: W.Dims, dtype, sequences, prompt_lens,
                lower=None):
    """The gap by which each served token's reference logit lies below
    the reference's best, at every served position of every sequence.
    With ``lower`` set, the gap of the token that the lower precision
    puts first at the same position (the control; it decodes nothing).

    -> (gaps (rows, seq_len - 1) float32, served (rows, seq_len - 1) bool)
    """
    halves = seeds.split_seed(seed)
    cols = token_columns(sequences, d)
    if lower is not None:
        _, cols = _serve_gaps(
            halves, served_hidden(seed, d, dtype, sequences, lower), cols,
            d=d, dtype=dtype, lower=lower)
    gaps, _ = _serve_gaps(halves, served_hidden(seed, d, dtype, sequences),
                          cols, d=d, dtype=dtype, lower=None)
    # row i scores token i + 1, which was served iff i + 1 >= prompt_len
    row = jnp.arange(d.seq_len - 1)[None, :]
    served = row + 1 >= jnp.asarray(prompt_lens)[:, None]
    return gaps, served


def train_two_steps(seed, d, dtype, batches, lr, b1=0.9, b2=0.999,
                    eps=1e-8, lower=None):
    """The family is served and not trained (the program refuses
    ``train=True`` for this block): no training cell can name it."""
    raise NotImplementedError(
        "the qwen3_next family has no training reference")
