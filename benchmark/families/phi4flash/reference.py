"""The plain reference of the ``phi4flash`` family's configurations.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``precision=HIGHEST``: the equations of ``README.md`` over whole
sequences, one sequence at a time: the state-space layers as a sequential
scan over the positions from a zero state, attention computed for every
row against every row with the window as a mask, one published pair of
heads at a time, no cache, no pages, no state carried between calls, no
grouped products, nothing imported from the program under test or from
another family. Weights come from this family's ``weights.py`` layer by
layer, cast up from the stored type. Long sequences are attended a block
of query rows at a time and scored against the vocabulary a block of
positions at a time, so that neither a head's scores nor the logits have
to fit whole.

With LN(x) = (x - mean) / sqrt(var + eps) * g + b, a layer l with input x
at position t: ``h = x + Mixer_l(LN_a(x))``, ``y = h + W_down(silu(g) *
u)``, ``[g; u] = W_gate_up LN_b(h)``. The mixers (``weights.mixers_of``):

    ssm     [u; z] = W_in a;  c_t = silu(sum_j w_j u_{t-3+j} + b_conv);
            [r; B_t; C_t] = W_x c_t;  D_t = softplus(W_dt r + b_dt);
            s_t = exp(D_t * A) s_{t-1} + (D_t c_t) (x) B_t, A = -exp(A_log);
            m_t = s_t C_t + D_skip c_t;  out = W_out(m_t * silu(z_t))
    window  q, k, v = W a + b; pair j of query heads (2j, 2j + 1) over the
            key/value heads (2g, 2g + 1), g = j // 2:
            A1 = softmax(q_2j K_2g^T / sqrt(dh)), A2 = softmax(q_2j+1
            K_2g+1^T / sqrt(dh)) over the rows i with t - window < i <= t;
            o_j = RMSNorm((A1 - lam A2) [V_2g, V_2g+1]) * (1 - lam_init);
            lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init;
            out = W_o [o_0 .. o_pairs-1] + b_o
    full    the same over every row i <= t; its K and V are kept
    cross   q = W_q a + b_q; the kept K and V; the rest the same
    gmu     out = W_2(silu(W_1 a) * m_t), m_t the last state-space layer's

After the last layer a final LayerNorm, then logits against the embedding
rows (text, image, EOS).

``lower`` names the control's precision: ``"fp8"`` rounds both operands
of every matrix product to float8_e4m3fn first (the step below bfloat16).
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
LOGIT_BLOCK = 512       # positions scored against the vocabulary at a time


def _round_operand(x, lower):
    if lower is None:
        return x
    if lower == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(F32)
    raise ValueError(f"unknown lower precision {lower!r}")


def _ein(spec, a, b, lower=None):
    return jnp.einsum(spec, _round_operand(a.astype(F32), lower),
                      _round_operand(b.astype(F32), lower), precision=HI)


def _linear(p, x, lower):
    y = _ein("nd,df->nf", x, p["w"], lower)
    return y + p["b"].astype(F32) if "b" in p else y


def _layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["g"].astype(F32) \
        + p["b"].astype(F32)


def _blocks(n, size):
    block = min(size, n)
    count = -(-n // block)
    return block, count, count * block - n


def state_space(p, a, lower=None):
    """One sequence a (n, dim) -> (out (n, dim), m (n, d_inner))."""
    n = a.shape[0]
    uz = _linear(p["in"], a, lower)
    u, z = jnp.split(uz, 2, axis=-1)
    taps = p["conv"]["w"].astype(F32)
    d_conv = taps.shape[0]
    padded = jnp.pad(u, ((d_conv - 1, 0), (0, 0)))
    c = jax.nn.silu(sum(padded[j:j + n] * taps[j] for j in range(d_conv))
                    + p["conv"]["b"].astype(F32))
    d_state = p["a_log"].shape[-1]
    rbc = _linear(p["x"], c, lower)
    rank = rbc.shape[-1] - 2 * d_state
    step = jax.nn.softplus(_linear(p["dt"], rbc[:, :rank], lower))
    b, cc = rbc[:, rank:rank + d_state], rbc[:, rank + d_state:]
    neg_a = -jnp.exp(p["a_log"].astype(F32))

    def one(s, at):
        step_t, c_t, b_t, cc_t = at
        s = jnp.exp(step_t[:, None] * neg_a) * s \
            + (step_t * c_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * cc_t[None, :], axis=-1)

    _, m = lax.scan(one, jnp.zeros(neg_a.shape, F32), (step, c, b, cc))
    m = m + p["d_skip"].astype(F32) * c
    return _linear(p["out"], m * jax.nn.silu(z), lower), m


def memory_unit(p, a, m, lower=None):
    return _linear(p["w2"], jax.nn.silu(_linear(p["w1"], a, lower)) * m,
                   lower)


def keys_values(p, a, d: W.Dims, lower=None):
    """(k, v) each (n, kv_heads, dh)."""
    n = a.shape[0]
    return (_linear(p["k"], a, lower).reshape(n, d.kv_heads, d.head_dim),
            _linear(p["v"], a, lower).reshape(n, d.kv_heads, d.head_dim))


def attention(p, a, k, v, d: W.Dims, window, lower=None):
    """Differential attention of one sequence: a (n, dim) the normed
    input, k / v (n, kv_heads, dh) the rows attended; ``window`` None for
    every earlier row."""
    n = a.shape[0]
    pos = jnp.arange(n)
    q = _linear(p["q"], a, lower).reshape(n, d.heads, d.head_dim)
    # the published head h lies where the program's layout puts it
    q = q[:, jnp.argsort(W.published_query_heads(d))]
    lam_vec = p["lam"].astype(F32)
    lam = jnp.exp(jnp.sum(lam_vec[0] * lam_vec[1])) \
        - jnp.exp(jnp.sum(lam_vec[2] * lam_vec[3])) + p["lam_init"]
    scale = d.head_dim ** -0.5
    block, blocks, fill = _blocks(n, QUERY_BLOCK)

    def one_pair(args):
        q1, q2, k1, k2, vg = args           # (n, dh) x 4, (n, 2 dh)

        def one_block(rows):
            qa, qb, at = rows
            i = at + jnp.arange(block)[:, None]
            ok = pos[None, :] <= i
            if window is not None:
                ok = ok & (i - pos[None, :] < window)

            def soft(qx, kx):
                score = _ein("id,jd->ij", qx, kx, lower) * scale
                return jax.nn.softmax(jnp.where(ok, score, -jnp.inf),
                                      axis=-1)
            return _ein("ij,jd->id", soft(qa, k1) - lam * soft(qb, k2), vg,
                        lower)

        def cut(x):
            return jnp.pad(x, ((0, fill), (0, 0))).reshape(blocks, block,
                                                           -1)
        out = lax.map(one_block, (cut(q1), cut(q2),
                                  jnp.arange(blocks) * block))
        return out.reshape(blocks * block, -1)[:n]

    pairs = d.heads // 2
    g = jnp.arange(pairs) // 2
    qh, kh, vh = (x.transpose(1, 0, 2) for x in (q, k, v))
    o = lax.map(one_pair, (
        qh[0::2], qh[1::2], kh[2 * g], kh[2 * g + 1],
        jnp.concatenate([vh[2 * g], vh[2 * g + 1]], axis=-1)))
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                      + d.norm_eps) * p["sub_ln"]["g"].astype(F32)
    o = o * (1.0 - p["lam_init"])
    return _linear(p["out"], o.transpose(1, 0, 2).reshape(n, -1), lower)


def feed_forward(p, x, d: W.Dims, lower=None):
    a = _layer_norm(p["ln"], x, d.norm_eps)
    hidden = p["w_out"].shape[-2]
    gate = _ein("nd,df->nf", a, p["w_in"][..., :hidden], lower)
    up = _ein("nd,df->nf", a, p["w_in"][..., hidden:], lower)
    return _ein("nf,fd->nd", jax.nn.silu(gate) * up, p["w_out"], lower)


def block(p: dict, x, kept, d: W.Dims, mixer: str, lower=None):
    """One layer on one sequence ``x`` (n, dim). ``kept`` is what earlier
    layers handed on: ``{"m": the last state-space layer's scan output,
    "k", "v": the full layer's rows}``. -> (y, kept)."""
    pa = p["attn"]
    a = _layer_norm(pa["ln"], x, d.norm_eps)
    if mixer == "ssm":
        out, m = state_space(pa, a, lower)
        kept = dict(kept, m=m)
    elif mixer == "gmu":
        out = memory_unit(pa, a, kept["m"], lower)
    elif mixer == "cross":
        out = attention(pa, a, kept["k"], kept["v"], d, None, lower)
    else:
        k, v = keys_values(pa, a, d, lower)
        out = attention(pa, a, k, v, d,
                        d.window if mixer == "window" else None, lower)
        if mixer == "full":
            kept = dict(kept, k=k, v=v)
    x = x + out
    return x + feed_forward(p["ff"], x, d, lower), kept


def embed(po: dict, tokens, d: W.Dims):
    """``tokens`` (n,) int: text ids on the first ``text_seq_len``
    positions, image ids (no text offset) after them. No position."""
    pos = jnp.arange(tokens.shape[0])
    t_ids = jnp.clip(tokens, 0, d.num_text_tokens - 1)
    i_ids = jnp.clip(tokens, 0, d.num_image_tokens - 1)
    return jnp.where((pos < d.text_seq_len)[:, None],
                     po["text_emb"]["w"][t_ids].astype(F32),
                     po["image_emb"]["w"][i_ids].astype(F32))


def logits_of(po: dict, x, d: W.Dims, lower=None, first_row=0):
    """Masked logits (n, total_tokens) of the rows ``first_row`` ..: row i
    scores token i + 1, against the embedding rows themselves."""
    n = x.shape[0]
    h = _layer_norm(po["to_logits"]["ln"], x, d.norm_eps)
    rows = jnp.concatenate([po[name]["w"] for name in
                            ("text_emb", "image_emb", "eos_emb")])
    lg = _ein("nd,vd->nv", h, rows, lower)
    row = first_row + jnp.arange(n)[:, None]
    col = jnp.arange(d.total_tokens)[None, :]
    boundary = d.text_seq_len - 1
    forbidden = (((row >= boundary) & (col < d.num_text_tokens))
                 | ((row < boundary) & (col >= d.num_text_tokens))
                 | ((row != d.seq_len - 1) & (col >= d.total_tokens - 1)))
    return jnp.where(forbidden, -jnp.inf, lg)


# ---------------------------------------------------------------------------
# serving: teacher-forced logits over what was served
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("d", "dtype", "mixer", "lower"))
def _serve_layer(halves, index, xs, kept, *, d, dtype, mixer, lower):
    key = seeds.layer_key(seeds.seed_key_traced(halves), index)
    p = W.layer(key, d, dtype, mixer, index)
    return lax.map(lambda a: block(p, a[0], a[1], d, mixer, lower),
                   (xs, kept))


@functools.partial(jax.jit, static_argnames=("d", "dtype"))
def _serve_embed(halves, tokens, *, d, dtype):
    po = W.outer(seeds.seed_key_traced(halves), d, dtype)
    return jax.vmap(lambda t: embed(po, t, d))(tokens)


@functools.partial(jax.jit, static_argnames=("d", "dtype", "lower"))
def _serve_gaps(halves, xs, cols, *, d, dtype, lower):
    """Per row and position: the reference's best logit minus its logit
    of the token in ``cols`` (>= 0; 0 where that token is best), and the
    column the reference puts first. A block of positions of one sequence
    at a time: neither all sequences' logits nor one's fit beside each
    other at a real vocabulary."""
    po = W.outer(seeds.seed_key_traced(halves), d, dtype)
    n = xs.shape[1]
    block, blocks, fill = _blocks(n, LOGIT_BLOCK)

    def one(args):
        x, col = args

        def part(rows):
            xb, cb, at = rows
            lg = logits_of(po, xb, d, lower, first_row=at)
            got = jnp.take_along_axis(lg, cb[:, None], axis=-1)[:, 0]
            return jnp.max(lg, axis=-1) - got, jnp.argmax(lg, axis=-1)

        gap, best = lax.map(part, (
            jnp.pad(x, ((0, fill), (0, 0))).reshape(blocks, block, -1),
            jnp.pad(col, (0, fill)).reshape(blocks, block),
            jnp.arange(blocks) * block))
        return gap.reshape(-1)[:n], best.reshape(-1)[:n]

    return lax.map(one, (xs, cols))


def served_hidden(seed: int, d: W.Dims, dtype, sequences, lower=None):
    """The last layer's output (rows, seq_len - 1, dim) of the reference
    run once over each whole served sequence (prompt then served tokens),
    made layer by layer so that one layer's weights live at a time."""
    halves = seeds.split_seed(seed)
    tokens = jnp.asarray(sequences, jnp.int32)[:, :-1]
    xs = _serve_embed(halves, tokens, d=d, dtype=dtype)
    rows, n = tokens.shape
    kept = {"m": jnp.zeros((rows, n, d.d_inner), F32),
            "k": jnp.zeros((rows, n, d.kv_heads, d.head_dim), F32),
            "v": jnp.zeros((rows, n, d.kv_heads, d.head_dim), F32)}
    for i, mixer in enumerate(d.mixers):
        xs, kept = _serve_layer(halves, jnp.int32(i), xs, kept, d=d,
                                dtype=dtype, mixer=mixer, lower=lower)
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
    raise NotImplementedError("the phi4flash family has no training "
                              "reference")
