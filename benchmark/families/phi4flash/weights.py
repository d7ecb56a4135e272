"""Seeded weights of the ``phi4flash`` family's configurations, in plain jax.

The family is the block that ``model_type phi4flash`` configurations
publish (Microsoft's Phi-4-mini-flash-reasoning; the family's description
is arXiv:2507.06607): ``num_hidden_layers`` layers, each ``h = x +
Mixer(LN(x))``, ``y = h + MLP(LN(h))`` with LayerNorms (gain and bias), a
SiLU-gated feed-forward without biases, and five kinds of mixer in a
strictly alternating stack (``mixers_of``): state-space layers (Mamba-1)
and differential attention over a window of ``sliding_window`` rows in the
first half, one full differential layer whose cached rows every later
attention layer reads through a query projection of its own (cross), and
gated memory units over the last state-space layer's scan output. No
positional signal; a final LayerNorm; logits against the embedding rows
(``tie_word_embeddings``). ``README.md`` beside this file has the
equations and where each assumed size comes from.

One jitted call on the device makes the whole tree from ``--seed`` in the
program's parameter layout (``tree``): a stack a kind of mixer (window and
full layers alike in one), each over its own layers in the published
order. The plain reference (``reference.py``) calls ``layer`` and
``outer`` layer by layer and never sees an array the program has held.
Nothing here imports the program.

Distributions (``assumed`` in the configuration file): uniform
+-1/sqrt(fan_in) for every linear and its bias, the query and key
projections' times ``qk_init_gain``; embeddings N(0, ``embedding_std``^2)
(layer 0's LayerNorm brings them to unit scale; the configuration file
says why they are small); LayerNorm gains and the pair norm's 1 + N(0,
0.05^2), LayerNorm biases N(0, 0.05^2): near their defaults and not at
them, so that one left out would show; the lambdas' four vectors N(0,
0.1) in float32; Mamba's published initialisers for the state-space
layers: ``A_log = log(1..d_state)``, ``D_skip = 1``, ``b_dt`` such that
``softplus(b_dt)`` is log-uniform in [1e-3, 0.1], ``W_dt`` uniform
+-dt_rank^-0.5, the convolution uniform +-1/sqrt(d_conv).

Layout choices of the program that the reference follows by slicing: the
gate and up projections of a feed-forward lie side by side in ``w_in``
(dim, 2 x hidden); the query heads lie grouped by the key head they read:
the published head 4p + 2j + s (s = 0 the map that stays, 1 the one
subtracted, of the pair 2p + j over the key/value heads 2p, 2p + 1) lies
at 4p + 2s + j (``published_query_heads``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmark.seeds import (layer_key, seed_key_traced, stored as _stored,
                             uniform as _uniform)

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration as one cell runs it."""
    dim: int
    depth: int
    mixers: tuple               # "ssm" | "window" | "full" | "cross" | "gmu"
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    hidden: int
    norm_eps: float
    qk_init_gain: float
    embedding_std: float
    text_seq_len: int
    image_grid: int
    num_text_tokens: int
    num_image_tokens: int

    @property
    def image_seq_len(self) -> int:
        return self.image_grid * self.image_grid

    @property
    def seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    @property
    def total_tokens(self) -> int:
        return self.num_text_tokens + self.num_image_tokens + 1

    def layers_of(self, *mixers) -> tuple:
        return tuple(i for i, m in enumerate(self.mixers) if m in mixers)

    @property
    def ssm_source(self) -> int:
        """The state-space layer whose scan output the memory units gate."""
        return self.layers_of("ssm")[-1]

    @property
    def kv_source(self) -> int:
        """The full layer whose rows the cross layers read."""
        return self.layers_of("full")[0]


def mixers_of(layers: int) -> tuple:
    """Each layer's mixer, from the published count alone (``mb_per_layer``
    2): with ``half = layers / 2``, state-space on the even layers up to
    ``half``, window attention on the odd ones before it, full attention
    on ``half + 1``, then memory units on the even layers and cross
    attention on the odd ones."""
    half = layers // 2

    def one(l):
        if l % 2 == 0:
            return "ssm" if l <= half else "gmu"
        return "window" if l < half else "full" if l == half + 1 \
            else "cross"
    return tuple(one(l) for l in range(layers))


def dims_of(config: dict, depth: int | None = None) -> Dims:
    """``Dims`` from a configuration file's object: the published keys
    under their published names, what the config has no key for from
    ``assumed``'s keys at the top level. Nothing is cut: ``depth`` is the
    published ``num_hidden_layers`` or an error."""
    layers = int(config["num_hidden_layers"])
    if int(depth or layers) != layers or layers % 4:
        raise ValueError(f"the phi4flash family runs every one of its "
                         f"{layers} layers (a multiple of 4), not {depth}")
    for key, want in (("hidden_act", "silu"), ("mb_per_layer", 2),
                      ("tie_word_embeddings", True), ("mlp_bias", False),
                      ("lm_head_bias", False)):
        if config[key] != want:
            raise ValueError(f"the phi4flash family's equations hold for "
                             f"{key} = {want!r}, not {config[key]!r}")
    dim, heads = config["hidden_size"], config["num_attention_heads"]
    d = Dims(dim=dim, depth=layers, mixers=mixers_of(layers), heads=heads,
             kv_heads=config["num_key_value_heads"], head_dim=dim // heads,
             window=config["sliding_window"],
             d_inner=config["mamba_expand"] * dim,
             d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
             dt_rank=config["mamba_dt_rank"],
             hidden=config["intermediate_size"],
             norm_eps=float(config["layer_norm_eps"]),
             qk_init_gain=float(config["qk_init_gain"]),
             embedding_std=float(config["embedding_std"]),
             text_seq_len=config["text_seq_len"],
             image_grid=config["image_grid"],
             num_text_tokens=config["num_text_tokens"],
             num_image_tokens=config["num_image_tokens"])
    if d.total_tokens != config["vocab_size"]:
        raise ValueError(f"text ids, image ids and EOS are {d.total_tokens} "
                         f"rows, the vocabulary {config['vocab_size']}")
    if dim % heads or heads % d.kv_heads or d.kv_heads % 2 \
            or heads != 2 * d.kv_heads:
        raise ValueError("differential attention pairs up the query heads "
                         "and the key/value heads, two query heads a "
                         "key/value head")
    return d


def published_query_heads(d: Dims):
    """For each query head as the program lays them (grouped by the key
    head they read), the published head that lies there."""
    at = jnp.arange(d.heads)
    p, s, j = at // 4, at // 2 % 2, at % 2
    return 4 * p + 2 * j + s


def lam_init(layer):
    """Differential attention's constant of the published layer
    ``layer`` (arXiv:2410.05258)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))


def _normal(key, shape, std, dtype, mean=0.0):
    return _stored(mean + std * jax.random.normal(key, shape, F32), dtype)


def _layer_norm(key, dim, dtype):
    kg, kb = jax.random.split(key)
    return {"g": _normal(kg, (dim,), 0.05, dtype, 1.0),
            "b": _normal(kb, (dim,), 0.05, dtype)}


def _linear(key, fan_in, fan_out, dtype, bias=False, gain=1.0):
    kw, kb = jax.random.split(key)
    out = {"w": _stored(gain * _uniform(kw, (fan_in, fan_out), fan_in,
                                        F32), dtype)}
    if bias:
        out["b"] = _uniform(kb, (fan_out,), fan_in, dtype)
    return out


def _ssm(key, d: Dims, dtype) -> dict:
    k = jax.random.split(key, 8)
    di, ds, dc, dr = d.d_inner, d.d_state, d.d_conv, d.dt_rank
    lo, hi = jnp.log(1e-3), jnp.log(0.1)
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(k[6], (di,), F32))
    return {
        "in": _linear(k[0], d.dim, 2 * di, dtype),
        "conv": {"w": _uniform(k[1], (dc, di), dc, dtype),
                 "b": _uniform(k[2], (di,), dc, dtype)},
        "x": _linear(k[3], di, dr + 2 * ds, dtype),
        "dt": {"w": _uniform(k[4], (dr, di), dr, dtype),
               # the inverse of softplus
               "b": _stored(dt + jnp.log(-jnp.expm1(-dt)), dtype)},
        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, ds + 1, dtype=F32)),
                                  (di, ds)),
        "d_skip": jnp.ones((di,), F32),
        "out": _linear(k[5], di, d.dim, dtype),
    }


def _attention(key, d: Dims, dtype, index, own_kv: bool) -> dict:
    k = jax.random.split(key, 6)
    h, kv, dh = d.heads, d.kv_heads, d.head_dim
    out = {
        "q": _linear(k[0], d.dim, h * dh, dtype, True, d.qk_init_gain),
        "lam": 0.1 * jax.random.normal(k[3], (4, dh), F32),
        "lam_init": lam_init(index),
        "sub_ln": {"g": _normal(k[4], (2 * dh,), 0.05, dtype, 1.0)},
        "out": _linear(k[5], h * dh, d.dim, dtype, True),
    }
    if own_kv:
        out["k"] = _linear(k[1], d.dim, kv * dh, dtype, True,
                           d.qk_init_gain)
        out["v"] = _linear(k[2], d.dim, kv * dh, dtype, True)
    return out


def layer(key, d: Dims, dtype, mixer: str, index) -> dict:
    """One layer of the published index ``index`` (it may be traced; the
    mixer is static): the mixer under ``"attn"``, the feed-forward under
    ``"ff"``, each with its LayerNorm."""
    k = jax.random.split(key, 6)
    if mixer == "ssm":
        mix = _ssm(k[0], d, dtype)
    elif mixer == "gmu":
        mix = {"w1": _linear(k[0], d.dim, d.d_inner, dtype),
               "w2": _linear(k[1], d.d_inner, d.dim, dtype)}
    else:
        mix = _attention(k[0], d, dtype, index, own_kv=mixer != "cross")
    return {"attn": {"ln": _layer_norm(k[2], d.dim, dtype), **mix},
            "ff": {"ln": _layer_norm(k[3], d.dim, dtype),
                   "w_in": _uniform(k[4], (d.dim, 2 * d.hidden), d.dim,
                                    dtype),
                   "w_out": _uniform(k[5], (d.hidden, d.dim), d.hidden,
                                     dtype)}}


def outer(key, d: Dims, dtype) -> dict:
    """The embedding rows, text first, image after, EOS last (never an
    input), which are the head's rows too, and the final LayerNorm."""
    k = jax.random.split(jax.random.fold_in(key, 3), 4)
    std = d.embedding_std
    return {
        "text_emb": {"w": _normal(k[0], (d.num_text_tokens, d.dim), std,
                                  dtype)},
        "image_emb": {"w": _normal(k[1], (d.num_image_tokens, d.dim), std,
                                   dtype)},
        "eos_emb": {"w": _normal(k[2], (1, d.dim), std, dtype)},
        "to_logits": {"ln": _layer_norm(k[3], d.dim, dtype)},
    }


# the program's parameter stacks and the mixers each holds
STACKS = {"ssm": ("ssm",), "attn": ("window", "full"), "cross": ("cross",),
          "gmu": ("gmu",)}


def tree(seed, d: Dims, dtype) -> dict:
    """The whole parameter tree in the program's layout. ``seed`` may be
    traced (``split_seed``'s pair). A layer's key is that of its published
    index. Layers are made one after the other (``lax.map``), so that the
    float32 draws of one layer are all that lives beside the tree."""
    key = seed_key_traced(seed)
    out = outer(key, d, dtype)
    out["transformer"] = {}
    for stack, mixers in STACKS.items():
        layers = jnp.asarray(d.layers_of(*mixers), jnp.int32)
        out["transformer"][stack] = jax.lax.map(
            lambda i: layer(layer_key(key, i), d, dtype, mixers[0], i),
            layers)
    return out
