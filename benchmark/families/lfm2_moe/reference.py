"""The plain reference of the ``lfm2_moe`` family's configurations.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``precision=HIGHEST``: the equations of an ``lfm2_moe`` layer over whole
sequences, the short convolution over the whole sequence with zeros before
its first position (no carried tail), the attention computed for every row
against every row, a plain loop over all the experts with each token's
weight for an expert it did not pick at zero, no cache, no pages, no
kernels, no grouped products, nothing imported from the program under test
or from another family. Weights come from this family's ``weights.py``
layer by layer, cast up from the stored type. Long sequences are computed
a block of query rows at a time, so that one head's scores fit whatever
the length.

With ``n(x) = x / sqrt(mean(x^2) + eps) * g``, a layer with input ``h`` at
position ``t``:

    a = n_in(h)
    conv layer:  [B; C; u] = W_in a (three streams of the width, in that
        order);  g_t = B_t * u_t;  c_t = sum_j w_j * g_{t - (taps-1) + j}
        (a weight a channel a tap, g = 0 before position 0, no bias, no
        activation);  h = h + W_out (C_t * c_t)
    full layer:  q = W_q a (heads x dh);  k = W_k a, v = W_v a (kv x dh);
        q, k = n_q(q), n_k(k) a head (one gain vector for all query
        heads, one for all key heads);  q, k = RoPE(q, k; t), rotate-half
        pairs (x[i], x[i + dh/2]) over the whole head at rope_theta;
        query head i reads key/value head i // (heads / kv);  scores
        q_t . k_j / sqrt(dh) over j <= t, softmax;  h = h + W_o o
    m = n_mlp(h)
    dense layer:   h = h + W_down(silu(W_gate m) * (W_up m))
    routed layer:  s = sigmoid(W_r m) in float32;  the k largest of s + b
        are picked;  w_i = routed_scale * s_i / (sum_picked s + route_eps);
        h = h + sum over the picked experts of w_i E_i(m) (all held; no
        shared expert)

Token embeddings enter as they are; after the last layer a final norm,
then the head against the embedding rows themselves (tied).

Departure of the program that the reference does NOT follow: the program
rounds ``g`` to its activations' type (the type in which a slot's tail
holds it) before the taps; here ``g`` stays float32 like everything else.

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


def rope(x, positions, theta):
    """Every head of x (n, heads, d) as rotate-half pairs (x[i], x[i +
    d/2]), turned by pos * theta^(-2i / d)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _unit(p, h, lower):
    """W_down(silu(W_gate h) * (W_up h)); gate | up lie side by side."""
    hidden = p["w_out"].shape[-2]
    gate = _ein("nd,df->nf", h, p["w_in"][..., :hidden], lower)
    up = _ein("nd,df->nf", h, p["w_in"][..., hidden:], lower)
    return _ein("nf,fd->nd", jax.nn.silu(gate) * up, p["w_out"], lower)


def short_conv(p, x, d: W.Dims, lower=None):
    """The gated short convolution's output over one sequence x (n, dim):
    the convolution over the whole sequence, zeros before its start."""
    n = x.shape[0]
    a = _rms(p["ln"], x, d.norm_eps)
    bcu = _ein("nd,df->nf", a, p["in"]["w"], lower)
    b, c, u = (bcu[:, i * d.dim:(i + 1) * d.dim] for i in range(3))
    g = jnp.pad(b * u, ((d.conv_taps - 1, 0), (0, 0)))
    w = p["conv"]["w"].astype(F32)
    conv = sum(w[j] * g[j:j + n] for j in range(d.conv_taps))
    return _ein("nf,fd->nd", c * conv, p["out"]["w"], lower)


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
    q = rope(_rms(p["q_ln"], q, d.norm_eps), pos, d.rope_theta)
    k = rope(_rms(p["k_ln"], k, d.norm_eps), pos, d.rope_theta)
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
    return _ein("nf,fd->nd", o, p["out"]["w"], lower)


def route(p, m, d: W.Dims, lower=None):
    """-> (n, experts) float32: each token's weight for each expert, zero
    for the experts it did not pick."""
    s = jax.nn.sigmoid(_ein("nd,de->ne", m, p["router"]["w"], lower))
    _, picks = lax.top_k(s + p["router"]["bias"].astype(F32),
                         d.experts_per_token)
    picked = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], picks].set(1.0)
    return d.routed_scale * s * picked / (
        jnp.sum(s * picked, axis=-1, keepdims=True) + d.route_eps)


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
    return routed(p["experts"], m, route(p, m, d, lower), lower)


def block(p: dict, x, d: W.Dims, moe: bool, full: bool, lower=None):
    """One layer on one sequence ``x`` of shape (n, dim)."""
    mix = attention if full else short_conv
    x = x + mix(p["attn"], x, d, lower)
    return x + feed_forward(p["ff"], x, d, moe, lower)


def embed(po: dict, tokens, d: W.Dims):
    """``tokens`` (n,) int: text ids on the first ``text_seq_len``
    positions, image ids (no text offset) after them. Positions enter in
    the full layers (RoPE), not here."""
    pos = jnp.arange(tokens.shape[0])
    t_ids = jnp.clip(tokens, 0, d.num_text_tokens - 1)
    i_ids = jnp.clip(tokens, 0, d.num_image_tokens - 1)
    return jnp.where((pos < d.text_seq_len)[:, None],
                     po["text_emb"]["w"][t_ids].astype(F32),
                     po["image_emb"]["w"][i_ids].astype(F32))


def logits_of(po: dict, x, d: W.Dims, lower=None):
    """Masked logits (n, total_tokens): row i scores token i + 1, against
    the embedding rows themselves."""
    n = x.shape[0]
    h = _rms(po["to_logits"]["ln"], x, d.norm_eps)
    rows = jnp.concatenate([po[name]["w"] for name in
                            ("text_emb", "image_emb", "eos_emb")])
    lg = _ein("nd,vd->nv", h, rows, lower)
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
    raise NotImplementedError("the lfm2_moe family has no training reference")
