"""The plain reference of the ``mimo_v2`` family's configurations.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``precision=HIGHEST``: the equations of a ``mimo_v2`` layer over whole
sequences, the attention computed for every row against every row with
the window as a mask and the sink as one more term of the denominator, a
plain loop over the experts with each token's weight for an expert it did
not pick at zero, no cache, no pages, no kernels, no grouped products,
nothing imported from the program under test or from another family.
Weights come from this family's ``weights.py`` layer by layer, cast up
from the stored type. Long sequences are computed a block of query rows
at a time, so that one head's scores fit whatever the length.

With ``n(x) = x / sqrt(mean(x^2) + eps) * g``, a layer with input ``h`` at
position ``p``; ``kv`` is ``num_key_value_heads`` in a full layer and
``swa_num_key_value_heads`` in a window layer:

    a = n_in(h);  q = W_q a (heads x dh);  k = W_k a (kv x dh);
        v = value_scale * W_v a (kv x dv)
    q, k = RoPE(q, k; p) on the FIRST rotary_dim numbers of every head
        (rotate-half pairs inside them), the others as they are; the base
        is rope_theta in a full layer, swa_rope_theta in a window layer
    query head i reads key/value head i // (heads / kv); scores a_j =
        q_p . k_j / sqrt(dh), allowed j <= p and, in a window layer,
        p - j < window
    full layer:    w_j = softmax_j(a_j)
    window layer:  w_j = exp(a_j - m) / (exp(s_i - m) + sum_j' exp(a_j' -
        m)), s_i the query head's learned sink logit: it takes weight and
        gives no value, so the w_j sum to less than 1
    h = h + W_o (sum_j w_j v_j)
    m = n_mlp(h)
    dense layer:   h = h + W_down(silu(W_gate m) * (W_up m))
    routed layer:  s = sigmoid(W_r m) in float32 over ALL published
        experts; the k largest of s + b are picked; w_i = s_i / sum_picked
        s (no further scale, no shared expert);  h = h + sum over the
        picked experts THAT ARE HELD HERE of w_i E_i(m): what the experts
        held elsewhere would add is left out, as in the program

Token embeddings enter as they are; after the last layer a final norm,
then the head over the held rows of the vocabulary.

``lower`` names the control's precision: ``"fp8"`` rounds both operands
of every matrix product to float8_e4m3fn first (the step below bfloat16),
the router's included.
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


def rope(x, positions, theta, turned):
    """The first ``turned`` numbers of every head of x (n, heads, d), as
    rotate-half pairs (x[i], x[i + turned/2]), turned by pos * theta^(-2i
    / turned); the other d - turned numbers pass."""
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


def attention_weights(score, ok, sink):
    """One query head's weights over its rows: score (i, j), ok (i, j)
    the rows a query may attend, ``sink`` the head's logit or None ->
    (i, j), a row summing to 1, or to less where a sink took its share."""
    score = jnp.where(ok, score, -jnp.inf)
    if sink is None:
        return jax.nn.softmax(score, axis=-1)
    with_sink = jnp.concatenate(
        [score, jnp.full(score.shape[:-1] + (1,), sink, F32)], axis=-1)
    return jax.nn.softmax(with_sink, axis=-1)[..., :-1]


def attention(p, x, d: W.Dims, full: bool, lower=None):
    """The attention branch's output over one sequence x (n, dim)."""
    n = x.shape[0]
    pos = jnp.arange(n)
    kv = d.kv_heads_of(full)
    a = _rms(p["ln"], x, d.norm_eps)
    q = _ein("nd,df->nf", a, p["q"]["w"], lower).reshape(
        n, d.heads, d.head_dim)
    k = _ein("nd,df->nf", a, p["k"]["w"], lower).reshape(n, kv, d.head_dim)
    v = d.value_scale * _ein("nd,df->nf", a, p["v"]["w"], lower).reshape(
        n, kv, d.v_head_dim)
    theta = d.full_rope_theta if full else d.rope_theta
    q, k = rope(q, pos, theta, d.rotary_dim), rope(k, pos, theta,
                                                   d.rotary_dim)
    scale = d.head_dim ** -0.5
    block = min(QUERY_BLOCK, n)
    blocks = -(-n // block)
    fill = blocks * block - n

    def one_head(args):                 # a query head against its kv head
        qh, kh, vh, sink = args

        def one_block(rows):            # (block, dh) query rows at ``at``
            qb, at = rows
            score = _ein("id,jd->ij", qb, kh, lower) * scale
            i = at + jnp.arange(block)[:, None]
            ok = pos[None, :] <= i
            if not full:
                ok = ok & (i - pos[None, :] < d.window)
            attn = attention_weights(score, ok, None if full else sink)
            return _ein("ij,jd->id", attn, vh, lower)

        qh = jnp.pad(qh, ((0, fill), (0, 0))).reshape(blocks, block, -1)
        out = lax.map(one_block, (qh, jnp.arange(blocks) * block))
        return out.reshape(blocks * block, -1)[:n]

    reads = jnp.arange(d.heads) // (d.heads // kv)
    sinks = jnp.zeros((d.heads,), F32) if full else p["sink"].astype(F32)
    o = lax.map(one_head, (q.transpose(1, 0, 2),
                           k.transpose(1, 0, 2)[reads],
                           v.transpose(1, 0, 2)[reads], sinks))
    o = o.transpose(1, 0, 2).reshape(n, d.heads * d.v_head_dim)
    return _ein("nf,fd->nd", o, p["out"]["w"], lower)


def route(p, m, d: W.Dims, lower=None):
    """-> (n, experts) float32 over ALL published experts: each token's
    weight for each expert, zero for the experts it did not pick; a
    token's weights sum to 1."""
    s = jax.nn.sigmoid(_ein("nd,de->ne", m, p["router"]["w"], lower))
    _, picks = lax.top_k(s + p["router"]["bias"].astype(F32),
                         d.experts_per_token)
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


def feed_forward(p, x, d: W.Dims, moe: bool, lower=None):
    """The feed-forward branch's output."""
    m = _rms(p["ln"], x, d.norm_eps)
    if not moe:
        return _unit(p, m, lower)
    weights = route(p, m, d, lower)
    held = weights[:, d.first_expert:d.first_expert + d.experts_held]
    return routed(p["experts"], m, held, lower)


def block(p: dict, x, d: W.Dims, moe: bool, full: bool, lower=None):
    """One layer on one sequence ``x`` of shape (n, dim)."""
    x = x + attention(p["attn"], x, d, full, lower)
    return x + feed_forward(p["ff"], x, d, moe, lower)


def embed(po: dict, tokens, d: W.Dims):
    """``tokens`` (n,) int: text ids on the first ``text_seq_len``
    positions, image ids (no text offset) after them. Positions enter in
    the layers (RoPE), not here."""
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

@functools.partial(jax.jit, static_argnames=("d", "dtype", "moe", "full",
                                             "lower"))
def _serve_layer(halves, index, xs, *, d, dtype, moe, full, lower):
    key = seeds.layer_key(seeds.seed_key_traced(halves), index)
    p = W.layer(key, d, dtype, moe, full)
    return lax.map(lambda x: block(p, x, d, moe, full, lower), xs)


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
                          dtype=dtype, moe=d.layer_is_moe(i),
                          full=d.layer_is_full(i), lower=lower)
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
    raise NotImplementedError("the mimo_v2 family has no training reference")
