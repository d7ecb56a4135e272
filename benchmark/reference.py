"""The plain reference of the benchmark's configurations.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``precision=HIGHEST``: no kernels, no cache, no batching tricks, nothing
imported from the program under test. Weights come from ``weights.py``
(the same seeded functions the harness hands the program), cast up from
the stored type, so the reference and the program disagree only by what
the program's arithmetic loses.

The block is the repository's block, not the published one (see each
configuration's ``departures``): PreNorm LayerNorm, fused qkv without
bias, softmax attention scaled by ``dim ** -0.5``, causal, with the
repository's block-sparse layout on "sparse" layers (windows of 4 blocks
of 16 tokens plus the first block as a global one), GEGLU feed-forward
(exact GELU) of 4 x dim.

``lower`` names the control's precision: ``"fp8"`` rounds both operands
of every matrix product to float8_e4m3fn first (the step below bfloat16),
and with them the gradients that flow back through those products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import weights as W

F32 = jnp.float32
HI = lax.Precision.HIGHEST


def _round_operand(x, lower):
    if lower is None:
        return x
    if lower == "fp8":
        # forward and backward alike: the cast's derivative rounds the
        # tangent too, as a step computed in fp8 without scaling would
        return x.astype(jnp.float8_e4m3fn).astype(F32)
    raise ValueError(f"unknown lower precision {lower!r}")


def _mm(x, w, lower=None):
    x = _round_operand(x.astype(F32), lower)
    w = _round_operand(w.astype(F32), lower)
    return jnp.matmul(x, w, precision=HI)


def _ln(p, x, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    return y * p["g"].astype(F32) + p["b"].astype(F32)


def attention_mask(n: int, d: W.Dims, sparse: bool):
    """(n, n) bool, True where query row i may read key column j."""
    i = jnp.arange(n)[:, None]
    j = jnp.arange(n)[None, :]
    keep = j <= i
    if sparse:
        bi, bj = i // d.sparse_block, j // d.sparse_block
        window = (bi // d.sparse_local_blocks) == (bj // d.sparse_local_blocks)
        keep = keep & (window | (bj == 0))
    return keep


def block(p: dict, x, d: W.Dims, sparse: bool, lower=None):
    """One transformer block on one sequence ``x`` of shape (n, dim)."""
    n = x.shape[0]
    h = _ln(p["attn"]["ln"], x)
    qkv = _mm(h, p["attn"]["qkv"]["w"], lower)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(n, d.heads, d.dim_head).transpose(1, 0, 2)

    q, k, v = heads(q), heads(k), heads(v)
    dots = jnp.einsum("hid,hjd->hij", _round_operand(q, lower),
                      _round_operand(k, lower), precision=HI)
    dots = dots * (d.dim ** -0.5)
    dots = jnp.where(attention_mask(n, d, sparse)[None], dots, -jnp.inf)
    attn = jax.nn.softmax(dots, axis=-1)
    out = jnp.einsum("hij,hjd->hid", _round_operand(attn, lower),
                     _round_operand(v, lower), precision=HI)
    out = out.transpose(1, 0, 2).reshape(n, d.inner)
    x = x + _mm(out, p["attn"]["out"]["w"], lower) \
        + p["attn"]["out"]["b"].astype(F32)

    h = _ln(p["ff"]["ln"], x)
    h = _mm(h, p["ff"]["w1"]["w"], lower) + p["ff"]["w1"]["b"].astype(F32)
    a, gates = jnp.split(h, 2, axis=-1)
    h = a * jax.nn.gelu(gates, approximate=False)
    return x + _mm(h, p["ff"]["w2"]["w"], lower) \
        + p["ff"]["w2"]["b"].astype(F32)


def embed(po: dict, tokens, d: W.Dims):
    """``tokens`` (n,) int: text ids on the first ``text_seq_len``
    positions, image ids (no text offset) after them."""
    n = tokens.shape[0]
    pos = jnp.arange(n)
    is_text = pos < d.text_seq_len
    t_ids = jnp.clip(tokens, 0, d.num_text_tokens - 1)
    i_ids = jnp.clip(tokens, 0, d.num_image_tokens - 1)
    ipos = jnp.clip(pos - d.text_seq_len, 0, d.image_seq_len - 1)
    text = po["text_emb"]["w"][t_ids].astype(F32) \
        + po["text_pos_emb"]["w"][jnp.clip(pos, 0, d.text_seq_len - 1)] \
        .astype(F32)
    image = po["image_emb"]["w"][i_ids].astype(F32) \
        + po["image_pos_emb"]["rows"][ipos // d.image_grid].astype(F32) \
        + po["image_pos_emb"]["cols"][ipos % d.image_grid].astype(F32)
    return jnp.where(is_text[:, None], text, image)


def logits_of(po: dict, x, d: W.Dims, lower=None):
    """Masked logits (n, total_tokens): row i scores token i + 1."""
    n = x.shape[0]
    h = _ln(po["to_logits"]["ln"], x)
    lg = _mm(h, po["to_logits"]["proj"]["w"], lower) \
        + po["to_logits"]["proj"]["b"].astype(F32)
    row = jnp.arange(n)[:, None]
    col = jnp.arange(d.total_tokens)[None, :]
    boundary = d.text_seq_len - 1
    forbidden = (((row >= boundary) & (col < d.num_text_tokens))
                 | ((row < boundary) & (col >= d.num_text_tokens))
                 | ((row != d.seq_len - 1) & (col >= d.total_tokens - 1)))
    return jnp.where(forbidden, -jnp.inf, lg)


def labels_of(text, image, d: W.Dims):
    """Targets of one training row: [text, image + offset, EOS][1:]."""
    full = jnp.concatenate([text, image + d.num_text_tokens,
                            jnp.full((1,), d.total_tokens - 1, text.dtype)])
    return full[1:]


# ---------------------------------------------------------------------------
# serving: teacher-forced logits over what was served
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("d", "dtype", "sparse", "lower"))
def _serve_layer(halves, index, xs, *, d, dtype, sparse, lower):
    p = W.layer(W.layer_key(W.seed_key_traced(halves), index), d, dtype)
    return lax.map(lambda x: block(p, x, d, sparse, lower), xs)


@functools.partial(jax.jit, static_argnames=("d", "dtype"))
def _serve_embed(halves, tokens, *, d, dtype):
    po = W.outer(W.seed_key_traced(halves), d, dtype)
    return jax.vmap(lambda t: embed(po, t, d))(tokens)


@functools.partial(jax.jit, static_argnames=("d", "dtype", "lower"))
def _serve_logits(halves, xs, *, d, dtype, lower):
    po = W.outer(W.seed_key_traced(halves), d, dtype)
    return lax.map(lambda x: logits_of(po, x, d, lower), xs)


def served_logits(seed: int, d: W.Dims, dtype, sequences, lower=None):
    """Logits (rows, seq_len - 1, total_tokens) of the reference run once
    over each whole served sequence (prompt then served tokens), made
    layer by layer so that one block's weights live at a time."""
    halves = W.split_seed(seed)
    tokens = jnp.asarray(sequences, jnp.int32)[:, :-1]
    xs = _serve_embed(halves, tokens, d=d, dtype=dtype)
    for i in range(d.depth):
        xs = _serve_layer(halves, jnp.int32(i), xs, d=d, dtype=dtype,
                          sparse=d.layer_is_sparse(i), lower=lower)
    return _serve_logits(halves, xs, d=d, dtype=dtype, lower=lower)


@jax.jit
def _gap_of(ref_logits, token_cols):
    """Per row and position: the reference's best logit minus its logit
    of the token in ``token_cols`` (>= 0; 0 where that token is best)."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, token_cols[..., None],
                              axis=-1)[..., 0]
    return best - got


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
    ref = served_logits(seed, d, dtype, sequences)
    if lower is None:
        cols = token_columns(sequences, d)
    else:
        cols = jnp.argmax(
            served_logits(seed, d, dtype, sequences, lower=lower), axis=-1)
    gaps = _gap_of(ref, cols)
    # row i scores token i + 1, which was served iff i + 1 >= prompt_len
    row = jnp.arange(d.seq_len - 1)[None, :]
    served = row + 1 >= jnp.asarray(prompt_lens)[:, None]
    return gaps, served


# ---------------------------------------------------------------------------
# training: loss, gradient and the first update
# ---------------------------------------------------------------------------

def _row_loss(params, text, image, d: W.Dims, lower):
    tokens = jnp.concatenate([text, image])
    x = embed(params, tokens, d)
    for i, p in enumerate(params["layers"]):
        x = jax.checkpoint(
            functools.partial(block, d=d, sparse=d.layer_is_sparse(i),
                              lower=lower))(p, x)
    lg = logits_of(params, x, d, lower)
    tgt = labels_of(text, image, d)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    got = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - got)


def batch_loss(params, text, image, d: W.Dims, lower=None):
    """Mean cross-entropy over the batch, one row at a time."""
    per_row = lax.map(lambda r: _row_loss(params, r[0], r[1], d, lower),
                      (text, image))
    return jnp.mean(per_row)


@functools.partial(jax.jit, static_argnames=("d", "dtype"))
def _f32_tree(halves, *, d, dtype):
    """Float32 parameters with the blocks as a list (one entry a layer),
    so that a layer's gradient is that layer's size."""
    key = W.seed_key_traced(halves)
    out = W.outer(key, d, dtype)
    out["layers"] = [W.layer(W.layer_key(key, i), d, dtype)
                     for i in range(d.depth)]
    return jax.tree.map(lambda a: a.astype(F32), out)


def stacked_norms(norms: dict) -> dict:
    """Per-leaf norms in the program's layout: the blocks' leaves are
    stacked over depth there, so their norms combine in quadrature."""
    out = {k: v for k, v in norms.items() if k != "layers"}
    out["transformer"] = jax.tree.map(
        lambda *ls: float(sum(x * x for x in ls) ** 0.5), *norms["layers"])
    return jax.tree.map(float, out)


@functools.partial(jax.jit, static_argnames=("d", "lower"))
def _loss_and_grad(params, text, image, *, d, lower):
    return jax.value_and_grad(batch_loss)(params, text, image, d, lower)


@functools.partial(jax.jit, static_argnames=("d", "lower"))
def _loss_only(params, text, image, *, d, lower):
    return batch_loss(params, text, image, d, lower)


@functools.partial(jax.jit, donate_argnums=(1,), static_argnames=("store",))
def _adam_first_step(params, grads, lr, b1, b2, eps, *, store):
    """The first Adam update (optax.adam's arithmetic at count 0 -> 1)
    and the per-leaf norms of the gradient and of the change. The
    configuration stores its parameters in ``store``: the updated values
    are rounded to it, so an update below that type's resolution is lost
    here as it is in any trainer that keeps no float32 copy."""
    def upd(g):
        mu_hat = ((1 - b1) * g) / (1 - b1)
        nu_hat = ((1 - b2) * jnp.square(g)) / (1 - b2)
        return -lr * mu_hat / (jnp.sqrt(nu_hat) + eps)

    updates = jax.tree.map(upd, grads)
    norm = lambda t: jnp.sqrt(jnp.sum(jnp.square(t)))  # noqa: E731
    gnorm = jax.tree.map(norm, grads)
    info = jnp.finfo(store)

    def stored(x):      # never elided, unlike a convert there and back
        return lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)

    new = jax.tree.map(lambda p, u: stored(p + u), params, updates)
    dnorm = jax.tree.map(lambda n, p: norm(n - p), new, params)
    return new, gnorm, dnorm


def train_two_steps(seed: int, d: W.Dims, dtype, batches, lr: float,
                    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                    lower=None):
    """Follow the trainer through its first two steps on ``batches``
    (a list of two ``{"text", "image"}`` integer arrays): the loss of
    each, the norm of every leaf of the first gradient, and the norm of
    every leaf's change by the first update. Float32 state for more steps
    than that does not fit beside one chip's memory at the cells' sizes
    (parameters, gradient and two moments, 16 bytes a parameter)."""
    params = _f32_tree(W.split_seed(seed), d=d, dtype=dtype)
    t0, i0 = (jnp.asarray(batches[0][k], jnp.int32) for k in ("text", "image"))
    loss0, grads = _loss_and_grad(params, t0, i0, d=d, lower=lower)
    params, gnorm, dnorm = _adam_first_step(params, grads, lr, b1, b2, eps,
                                            store=dtype)
    t1, i1 = (jnp.asarray(batches[1][k], jnp.int32) for k in ("text", "image"))
    loss1 = _loss_only(params, t1, i1, d=d, lower=lower)
    out = {"loss": [float(loss0), float(loss1)],
           "grad_norm": stacked_norms(gnorm),
           "change_norm": stacked_norms(dnorm)}
    del params
    return out
