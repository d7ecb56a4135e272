"""Seeded weights of the ``afmoe`` family's configurations, in plain jax.

The family is the block that ``model_type afmoe`` configurations publish
(Arcee's Trinity models): grouped-query attention (``num_attention_heads``
query heads over ``num_key_value_heads`` key/value heads of ``head_dim``)
behind RMSNorms over each query and key head, an output gate, a window of
``sliding_window`` rows with rotary positions on the ``sliding_attention``
layers of ``layer_types`` and the whole sequence with no position on the
``full_attention`` ones, four RMSNorms a layer (before and after each
branch), SiLU-gated feed-forwards without biases: ``num_dense_layers``
leading dense layers, then layers of ``num_experts`` routed experts with
``num_experts_per_tok`` picked by sigmoid scores plus a selection bias and
``num_shared_experts`` shared ones, token embeddings times the square root
of the width (``mup_enabled``), an untied head behind a final norm.

A configuration runs ONE CHIP'S SHARE of a stated deployment: ``depth``
layers from the published layer ``first_layer`` on, ``experts_held`` routed
experts from ``first_expert`` on (the router stays ``num_experts`` wide)
and ``vocab_held`` rows of the vocabulary. An expert's weights are drawn
from its PUBLISHED index, so the shares of one seed are parts of one
model: ``tests/benchmark_suite/test_benchmark_afmoe.py`` adds them up.

One jitted call on the device makes the whole tree from ``--seed`` in the
program's parameter layout (``tree``); the plain reference
(``reference.py``) calls ``layer`` and ``outer`` layer by layer and never
sees an array the program has held. Nothing here imports the program.

Distributions: uniform +-1/sqrt(fan_in) for linears; embeddings N(0,
1/hidden_size), so that they enter the first layer at unit scale after
the published multiplication by sqrt(hidden_size) (``assumed`` in the
configuration file); every norm's gain 1 + N(0, 0.05^2), near 1 and not
1, so that a gain left out would show; the gains of the norms over a
query and a key head besides times ``qk_norm_gain`` (``assumed``, 2.5 in
the published configuration's file): at gains near 1 the scores of random
queries and keys are N(0, 1), the softmax is all but uniform over its
thousands of rows, the attention output is their average scaled back up to
unit size by the branch's output norm, one direction shared by a slot's
tokens, so every token of a slot picks much the same experts for
thousands of steps, how many of them this chip holds wanders, and the step
time with it (PERF.md section 6, PR 33); a trained model's attention is
peaked, and at 2.5 x 2.5 so is this one's; the router's selection
bias N(0, ``router_bias_std``).

Layout choices of the program that the reference follows by slicing: the
gate and up projections of a feed-forward lie side by side in ``w_in``
(dim, 2 x hidden).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmark.seeds import (layer_key, seed_key_traced, stored as _stored,
                             uniform as _uniform)


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration as one cell runs it."""
    dim: int
    depth: int                  # layers run here
    first_layer: int            # published index of the first of them
    dense_layers: int           # of the layers run here, leading
    layer_types: tuple          # "sliding" | "full", a layer run here
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    dense_hidden: int
    expert_hidden: int
    experts: int                # published: the router's width
    experts_held: int
    first_expert: int
    experts_per_token: int
    shared_experts: int
    routed_scale: float
    rope_theta: float
    norm_eps: float
    router_bias_std: float
    qk_norm_gain: float         # what the q/k norms' gains are drawn around
    text_seq_len: int
    image_grid: int
    num_text_tokens: int
    num_image_tokens: int

    @property
    def moe_layers(self) -> int:
        return self.depth - self.dense_layers

    @property
    def full_layers(self) -> int:
        return sum(t == "full" for t in self.layer_types)

    @property
    def window_layers(self) -> int:
        return self.depth - self.full_layers

    @property
    def shared_hidden(self) -> int:
        return self.shared_experts * self.expert_hidden

    @property
    def embed_scale(self) -> float:
        return float(self.dim) ** 0.5

    @property
    def image_seq_len(self) -> int:
        return self.image_grid * self.image_grid

    @property
    def seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    @property
    def total_tokens(self) -> int:
        return self.num_text_tokens + self.num_image_tokens + 1

    def layer_is_moe(self, layer: int) -> bool:
        return layer >= self.dense_layers

    def layer_is_full(self, layer: int) -> bool:
        return self.layer_types[layer] == "full"


_TYPES = {"sliding_attention": "sliding", "full_attention": "full"}


def dims_of(config: dict, depth: int | None = None) -> Dims:
    """``Dims`` from a configuration file's object (the published keys
    under their published names, the cut under ``depth``, ``first_layer``,
    ``experts_held``, ``first_expert``, ``vocab_held``); ``depth`` is the
    cell's cut: that many published layers from ``first_layer`` on."""
    depth = int(depth or config["depth"])
    first = int(config["first_layer"])
    published = config["num_hidden_layers"]
    if not 0 <= first < first + depth <= published \
            or len(config["layer_types"]) != published:
        raise ValueError(f"layers {first}..{first + depth} are not of the "
                         f"published {published}")
    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("num_expert_groups", 1), ("num_limited_groups", 1),
                      ("score_func", "sigmoid"), ("route_norm", True),
                      ("rope_scaling", None), ("hidden_act", "silu"),
                      ("mup_enabled", True),
                      ("tie_word_embeddings", False)):
        if config[key] != want:
            raise ValueError(f"the afmoe family's equations hold for "
                             f"{key} = {want!r}, not {config[key]!r}")
    dense = min(max(int(config["num_dense_layers"]) - first, 0), depth)
    d = Dims(dim=config["hidden_size"], depth=depth, first_layer=first,
             dense_layers=dense,
             layer_types=tuple(_TYPES[t] for t in
                               config["layer_types"][first:first + depth]),
             heads=config["num_attention_heads"],
             kv_heads=config["num_key_value_heads"],
             head_dim=config["head_dim"], window=config["sliding_window"],
             dense_hidden=config["intermediate_size"],
             expert_hidden=config["moe_intermediate_size"],
             experts=config["num_experts"],
             experts_held=config["experts_held"],
             first_expert=config["first_expert"],
             experts_per_token=config["num_experts_per_tok"],
             shared_experts=config["num_shared_experts"],
             routed_scale=float(config["route_scale"]),
             rope_theta=float(config["rope_theta"]),
             norm_eps=float(config["rms_norm_eps"]),
             router_bias_std=float(config["router_bias_std"]),
             qk_norm_gain=float(config["qk_norm_gain"]),
             text_seq_len=config["text_seq_len"],
             image_grid=config["image_grid"],
             num_text_tokens=config["num_text_tokens"],
             num_image_tokens=config["num_image_tokens"])
    if d.total_tokens != config["vocab_held"] \
            or config["vocab_held"] > config["vocab_size"]:
        raise ValueError(f"text ids, image ids and EOS are {d.total_tokens} "
                         f"rows, the vocabulary's share held here "
                         f"{config['vocab_held']} of {config['vocab_size']}")
    if not 0 <= d.first_expert <= d.first_expert + d.experts_held \
            <= d.experts or d.heads % d.kv_heads:
        raise ValueError("the held experts are no share of the published "
                         "ones, or the query heads no multiple of the "
                         "key/value heads")
    return d


def _gain(key, dim, dtype, around: float = 1.0):
    return {"g": _stored(around * (1.0 + 0.05 * jax.random.normal(
        key, (dim,), jnp.float32)), dtype)}


def _unit(key, d: Dims, hidden: int, dtype) -> dict:
    """A SiLU-gated unit: gate | up side by side, then down."""
    k_in, k_out = jax.random.split(key)
    return {"w_in": _uniform(k_in, (d.dim, 2 * hidden), d.dim, dtype),
            "w_out": _uniform(k_out, (hidden, d.dim), hidden, dtype)}


def experts(key, d: Dims, dtype, first: int, count: int) -> dict:
    """The routed experts ``first`` .. ``first + count`` of a layer, each
    drawn from its published index, stacked."""
    return jax.lax.map(
        lambda e: _unit(jax.random.fold_in(key, e), d, d.expert_hidden,
                        dtype), first + jnp.arange(count))


def layer(key, d: Dims, dtype, moe: bool) -> dict:
    """One block (its attention type changes no parameter's shape):
    sandwich-normed gated grouped-query attention, then a sandwich-normed
    dense or routed-and-shared feed-forward holding this chip's experts."""
    k = jax.random.split(key, 16)
    h, kv, dh = d.heads, d.kv_heads, d.head_dim
    attn = {
        "ln": _gain(k[0], d.dim, dtype),
        "post_ln": _gain(k[1], d.dim, dtype),
        "q": {"w": _uniform(k[2], (d.dim, h * dh), d.dim, dtype)},
        "k": {"w": _uniform(k[3], (d.dim, kv * dh), d.dim, dtype)},
        "v": {"w": _uniform(k[4], (d.dim, kv * dh), d.dim, dtype)},
        "gate": {"w": _uniform(k[5], (d.dim, h * dh), d.dim, dtype)},
        "q_ln": _gain(k[6], dh, dtype, d.qk_norm_gain),
        "k_ln": _gain(k[7], dh, dtype, d.qk_norm_gain),
        "out": {"w": _uniform(k[8], (h * dh, d.dim), h * dh, dtype)},
    }
    if not moe:
        ff = _unit(k[9], d, d.dense_hidden, dtype)
    else:
        ff = {
            "router": {
                "w": _uniform(k[10], (d.dim, d.experts), d.dim, dtype),
                "bias": d.router_bias_std * jax.random.normal(
                    k[11], (d.experts,), jnp.float32)},
            "experts": experts(k[12], d, dtype, d.first_expert,
                               d.experts_held),
            "shared": _unit(k[13], d, d.shared_hidden, dtype),
        }
    return {"attn": attn, "ff": {"ln": _gain(k[14], d.dim, dtype),
                                 "post_ln": _gain(k[15], d.dim, dtype),
                                 **ff}}


def outer(key, d: Dims, dtype) -> dict:
    """The held rows of the vocabulary's embedding, divided into the text
    rows and the image rows (EOS, the last row, is never an input), and
    the untied head over the same rows behind its norm."""
    k = jax.random.split(jax.random.fold_in(key, 3), 4)

    def normal(kk, shape):
        return _stored(jax.random.normal(kk, shape, jnp.float32)
                       / d.embed_scale, dtype)

    return {
        "text_emb": {"w": normal(k[0], (d.num_text_tokens, d.dim))},
        "image_emb": {"w": normal(k[1], (d.num_image_tokens, d.dim))},
        "to_logits": {
            "ln": _gain(k[3], d.dim, dtype),
            "proj": {"w": _uniform(k[2], (d.dim, d.total_tokens), d.dim,
                                   dtype)},
        },
    }


def tree(seed, d: Dims, dtype) -> dict:
    """The whole parameter tree in the program's layout: the dense layers
    and the expert layers as two subtrees, each stacked on a leading axis
    of its own layers, in the published order. ``seed`` may be traced
    (``split_seed``'s pair). A layer's key is that of its PUBLISHED index.
    Layers are made one after the other (``lax.map``), so that the float32
    draws of one layer's experts are all that lives beside the tree."""
    key = seed_key_traced(seed)
    out = outer(key, d, dtype)

    def stack(first, n, moe):
        keys = jax.vmap(lambda i: layer_key(key, i))(
            d.first_layer + first + jnp.arange(n))
        return jax.lax.map(lambda kk: layer(kk, d, dtype, moe), keys)

    out["transformer"] = {"dense": stack(0, d.dense_layers, False),
                          "moe": stack(d.dense_layers, d.moe_layers, True)}
    return out
