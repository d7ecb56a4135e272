"""The plain reference of the ``mla_moe`` family's configurations.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``precision=HIGHEST``: the published equations of a ``deepseek_v3`` layer
with no query compression, the MATERIALISED attention read (keys and
values made from the latent for every row), a plain loop over the experts
with each token's weight for an expert it did not pick at zero, the full
forward over whole sequences, no cache, no kernels, no grouped products,
nothing imported from the program under test or from another family.
Weights come from this family's ``weights.py`` layer by layer, cast up
from the stored type.

A layer, for a token's residual ``x``:

    h = RMSNorm(x);  q = h W_q -> heads x (nope | rope)
    [c_raw | k_rope_raw] = h W_kva;  c = RMSNorm_kv(c_raw)
    q_rope, k_rope = RoPE(.) at the token's position (interleaved pairs,
        k_rope one vector shared by all heads)
    k_nope = c W_k_up, v = c W_v_up  (the two halves of kv_b_proj)
    score = (q_nope . k_nope + q_rope . k_rope) * (nope + rope) ** -0.5
    causal softmax, o = sum p v, x += o W_o
    h = RMSNorm(x)
    dense layer:   x += W_down(silu(W_gate h) * (W_up h))
    expert layer:  s = sigmoid(h W_r) in float32; the k largest of s + b
        are picked; w_i = scale * s_i / sum_picked s;
        x += sum_i w_i E_i(h) + S(h)

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


def _rope(x, positions, theta):
    """Interleaved pairs (x[2i], x[2i+1]) turned by pos * theta^(-2i/d);
    x (n, ..., d) with ``positions`` (n,)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32).reshape((-1,) + (1,) * (x.ndim - 2) + (1,)) \
        * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def _unit(p, h, lower):
    """W_down(silu(W_gate h) * (W_up h)); gate | up lie side by side."""
    hidden = p["w_out"].shape[-2]
    gate = _ein("nd,df->nf", h, p["w_in"][..., :hidden], lower)
    up = _ein("nd,df->nf", h, p["w_in"][..., hidden:], lower)
    return _ein("nf,fd->nd", jax.nn.silu(gate) * up, p["w_out"], lower)


def attention(p, x, d: W.Dims, lower=None):
    """Latent attention over one sequence x (n, dim), materialised."""
    n = x.shape[0]
    pos = jnp.arange(n)
    h = _rms(p["ln"], x, d.norm_eps)
    q = _ein("nd,df->nf", h, p["q"]["w"], lower).reshape(
        n, d.heads, d.qk_nope + d.qk_rope)
    q_nope, q_rope = q[..., :d.qk_nope], _rope(q[..., d.qk_nope:], pos,
                                               d.rope_theta)
    kva = _ein("nd,df->nf", h, p["kva"]["w"], lower)
    c = _rms(p["kv_ln"], kva[:, :d.kv_rank], d.norm_eps)
    k_rope = _rope(kva[:, d.kv_rank:], pos, d.rope_theta)
    k_nope = _ein("jr,rhd->hjd", c, p["k_up"], lower)
    v = _ein("jr,rhd->hjd", c, p["v_up"], lower)
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    scale = (d.qk_nope + d.qk_rope) ** -0.5

    def one_head(args):                 # a head at a time: (n, n) scores
        qn, qr, kn, vh = args
        score = (_ein("id,jd->ij", qn, kn, lower)
                 + _ein("id,jd->ij", qr, k_rope, lower)) * scale
        attn = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), axis=-1)
        return _ein("ij,jd->id", attn, vh, lower)

    o = lax.map(one_head, (q_nope.transpose(1, 0, 2),
                           q_rope.transpose(1, 0, 2), k_nope, v))
    o = o.transpose(1, 0, 2).reshape(n, d.heads * d.v_head)
    return _ein("nf,fd->nd", o, p["out"]["w"], lower)


def route(p, h, d: W.Dims, lower=None):
    """-> (n, experts) float32: each token's weight for each expert, zero
    for the experts it did not pick."""
    s = jax.nn.sigmoid(_ein("nd,de->ne", h, p["router"]["w"], lower))
    _, picks = lax.top_k(s + p["router"]["bias"].astype(F32),
                         d.experts_per_token)
    picked = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], picks].set(1.0)
    return d.routed_scale * s * picked \
        / jnp.sum(s * picked, axis=-1, keepdims=True)


def feed_forward(p, x, d: W.Dims, moe: bool, lower=None):
    h = _rms(p["ln"], x, d.norm_eps)
    if not moe:
        return _unit(p, h, lower)
    weights = route(p, h, d, lower)

    def one_expert(acc, xs):            # a plain loop over the experts
        expert, w = xs
        return acc + w[:, None] * _unit(expert, h, lower), None

    routed, _ = lax.scan(one_expert, jnp.zeros_like(x),
                         (p["experts"], weights.T))
    return routed + _unit(p["shared"], h, lower)


def block(p: dict, x, d: W.Dims, moe: bool, lower=None):
    """One layer on one sequence ``x`` of shape (n, dim)."""
    x = x + attention(p["attn"], x, d, lower)
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

@functools.partial(jax.jit, static_argnames=("d", "dtype", "moe", "lower"))
def _serve_layer(halves, index, xs, *, d, dtype, moe, lower):
    key = seeds.layer_key(seeds.seed_key_traced(halves), index)
    p = W.layer(key, d, dtype, moe)
    return lax.map(lambda x: block(p, x, d, moe, lower), xs)


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
        xs = _serve_layer(halves, jnp.int32(i), xs, d=d, dtype=dtype,
                          moe=d.layer_is_moe(i), lower=lower)
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
    raise NotImplementedError("the mla_moe family has no training reference")
