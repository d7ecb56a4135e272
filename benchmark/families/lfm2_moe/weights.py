"""Seeded weights of the ``lfm2_moe`` family's configurations, in plain jax.

The family is the block that ``model_type lfm2_moe`` configurations publish
(Liquid AI's LFM2 mixture-of-experts models): two pre-norms a layer
(RMSNorm) and nothing else around the branches; ``layer_types`` says, layer
by layer, whether the mixer is a gated short convolution (``conv``: an
input projection to three streams ``B, C, u``, ``g = B * u``, a depthwise
causal convolution of ``conv_L_cache`` taps over ``g`` with no bias and no
activation, the output projection of ``C * conv``) or grouped-query
attention over every earlier row (``full_attention``:
``num_attention_heads`` query heads over ``num_key_value_heads`` key/value
heads of ``hidden_size / num_attention_heads``, an RMSNorm over each query
and key head, rotate-half RoPE on the whole head at ``rope_theta``, no
gate, no bias); SiLU-gated feed-forwards without biases: the first
``num_dense_layers`` layers dense (``intermediate_size``), the others
``num_experts`` routed experts of ``moe_intermediate_size`` with
``num_experts_per_tok`` picked by sigmoid scores plus a selection bias
(``use_expert_bias``), the picked scores over their sum + 1e-6
(``norm_topk_prob``) times ``routed_scaling_factor``, no shared expert; a
final norm and a head that is the embedding rows (tied).

A configuration runs ``depth`` layers from the published layer
``first_layer`` on, with every expert and every row of the vocabulary. A
layer's and an expert's weights are drawn from their PUBLISHED indices, so
a deeper cut of one seed is more of one model.

One jitted call on the device makes the whole tree from ``--seed`` in the
program's parameter layout (``tree``); the plain reference
(``reference.py``) calls ``layer`` and ``outer`` layer by layer and never
sees an array the program has held. Nothing here imports the program.

Distributions (``assumed`` in the configuration file says why each):
uniform +-1/sqrt(fan_in) for linears and for the convolution's taps (fan-in
``conv_L_cache``: three taps of one scale, so the two earlier ones carry
two thirds of ``c_t``); embeddings N(0, ``embedding_std``^2); every norm's
gain 1 + N(0, 0.05^2), near 1 and not 1, so that a gain left out would
show, the gains of the norms over a query and a key head besides times
``qk_norm_gain``; the router's selection bias N(0, ``router_bias_std``^2)
in float32.

Layout choices of the program that the reference follows by slicing: the
three streams of a convolution's input projection lie side by side in
``in`` (dim, 3 x dim) in the order B, C, u; the gate and up projections of
a feed-forward side by side in ``w_in`` (dim, 2 x hidden).
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
    layer_types: tuple          # "conv" | "full", a layer run here
    heads: int
    kv_heads: int
    head_dim: int
    conv_taps: int
    dense_hidden: int
    expert_hidden: int
    experts: int
    experts_per_token: int
    rope_theta: float
    norm_eps: float
    routed_scale: float
    route_eps: float
    router_bias_std: float
    qk_norm_gain: float
    embedding_std: float
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
    def conv_layers(self) -> int:
        return self.depth - self.full_layers

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

    def stacks(self) -> dict:
        """``{the program's parameter stack: (moe, full, the layers run
        here that lie in it)}`` in the order of their first layers:
        ``dense`` / ``moe`` hold the convolution layers, ``dense_full`` /
        ``moe_full`` the attention layers."""
        out = {}
        for i in range(self.depth):
            moe, full = self.layer_is_moe(i), self.layer_is_full(i)
            name = ("moe" if moe else "dense") + ("_full" if full else "")
            out.setdefault(name, (moe, full, []))[2].append(i)
        return out


_EQUATIONS = (("conv_bias", False), ("norm_topk_prob", True),
              ("use_expert_bias", True))
_LAYER_TYPES = {"conv": "conv", "full_attention": "full"}


def dims_of(config: dict, depth: int | None = None) -> Dims:
    """``Dims`` from a configuration file's object (the published keys
    under their published names, the cut under ``depth`` and
    ``first_layer``); ``depth`` is the cell's cut: that many published
    layers from ``first_layer`` on."""
    depth = int(depth or config["depth"])
    first = int(config["first_layer"])
    published = config["num_hidden_layers"]
    types = config["layer_types"]
    if not 0 <= first < first + depth <= published \
            or len(types) != published:
        raise ValueError(f"layers {first}..{first + depth} are not of the "
                         f"published {published}")
    for key, want in _EQUATIONS:
        if config[key] != want:
            raise ValueError(f"the lfm2_moe family's equations hold for "
                             f"{key} = {want!r}, not {config[key]!r}")
    rope = config["rope_parameters"]
    if rope["rope_type"] != "default":
        raise ValueError(f"the lfm2_moe family's equations hold for plain "
                         f"rotary positions, not {rope['rope_type']!r}")
    unknown = set(types) - set(_LAYER_TYPES)
    if unknown:
        raise ValueError(f"layer_types holds {sorted(unknown)}: a layer is "
                         f"one of {sorted(_LAYER_TYPES)}")
    heads = config["num_attention_heads"]
    if config["hidden_size"] % heads or heads % config["num_key_value_heads"]:
        raise ValueError("the heads do not divide the width, or the "
                         "key/value heads the query heads")
    d = Dims(dim=config["hidden_size"], depth=depth, first_layer=first,
             dense_layers=min(max(config["num_dense_layers"] - first, 0),
                              depth),
             layer_types=tuple(_LAYER_TYPES[t]
                               for t in types[first:first + depth]),
             heads=heads, kv_heads=config["num_key_value_heads"],
             head_dim=config["hidden_size"] // heads,
             conv_taps=config["conv_L_cache"],
             dense_hidden=config["intermediate_size"],
             expert_hidden=config["moe_intermediate_size"],
             experts=config["num_experts"],
             experts_per_token=config["num_experts_per_tok"],
             rope_theta=float(rope["rope_theta"]),
             norm_eps=float(config["norm_eps"]),
             routed_scale=float(config["routed_scaling_factor"]),
             route_eps=float(config["route_eps"]),
             router_bias_std=float(config["router_bias_std"]),
             qk_norm_gain=float(config["qk_norm_gain"]),
             embedding_std=float(config["embedding_std"]),
             text_seq_len=config["text_seq_len"],
             image_grid=config["image_grid"],
             num_text_tokens=config["num_text_tokens"],
             num_image_tokens=config["num_image_tokens"])
    if d.total_tokens != config["vocab_size"]:
        raise ValueError(f"text ids, image ids and EOS are {d.total_tokens} "
                         f"rows, the vocabulary {config['vocab_size']}: "
                         f"every row is held here")
    return d


def _gain(key, dim, dtype, around: float = 1.0):
    return {"g": _stored(around * (1.0 + 0.05 * jax.random.normal(
        key, (dim,), jnp.float32)), dtype)}


def _linear(key, fan_in, fan_out, dtype):
    return {"w": _uniform(key, (fan_in, fan_out), fan_in, dtype)}


def _unit(key, d: Dims, hidden: int, dtype) -> dict:
    """A SiLU-gated unit: gate | up side by side, then down."""
    k_in, k_out = jax.random.split(key)
    return {"w_in": _uniform(k_in, (d.dim, 2 * hidden), d.dim, dtype),
            "w_out": _uniform(k_out, (hidden, d.dim), hidden, dtype)}


def experts(key, d: Dims, dtype) -> dict:
    """The routed experts of a layer, each drawn from its published
    index, stacked."""
    return jax.lax.map(
        lambda e: _unit(jax.random.fold_in(key, e), d, d.expert_hidden,
                        dtype), jnp.arange(d.experts))


def mixer(key, d: Dims, dtype, full: bool) -> dict:
    """A layer's mixer behind its norm: grouped-query attention with the
    norms over a query and a key head, or the gated short convolution
    (``in``: B | C | u side by side; ``conv.w``: a weight a tap a
    channel, oldest tap first)."""
    k = jax.random.split(key, 7)
    if full:
        return {
            "ln": _gain(k[0], d.dim, dtype),
            "q": _linear(k[1], d.dim, d.heads * d.head_dim, dtype),
            "k": _linear(k[2], d.dim, d.kv_heads * d.head_dim, dtype),
            "v": _linear(k[3], d.dim, d.kv_heads * d.head_dim, dtype),
            "out": _linear(k[4], d.heads * d.head_dim, d.dim, dtype),
            "q_ln": _gain(k[5], d.head_dim, dtype, d.qk_norm_gain),
            "k_ln": _gain(k[6], d.head_dim, dtype, d.qk_norm_gain),
        }
    return {
        "ln": _gain(k[0], d.dim, dtype),
        "in": _linear(k[1], d.dim, 3 * d.dim, dtype),
        "conv": {"w": _uniform(k[2], (d.conv_taps, d.dim), d.conv_taps,
                               dtype)},
        "out": _linear(k[3], d.dim, d.dim, dtype),
    }


def layer(key, d: Dims, dtype, moe: bool, full: bool) -> dict:
    """One block: its pre-normed mixer, then a pre-normed dense or routed
    feed-forward holding every expert."""
    k = jax.random.split(key, 6)
    if not moe:
        ff = _unit(k[1], d, d.dense_hidden, dtype)
    else:
        ff = {
            "router": {
                "w": _uniform(k[2], (d.dim, d.experts), d.dim, dtype),
                "bias": d.router_bias_std * jax.random.normal(
                    k[3], (d.experts,), jnp.float32)},
            "experts": experts(k[4], d, dtype),
        }
    return {"attn": mixer(k[0], d, dtype, full),
            "ff": {"ln": _gain(k[5], d.dim, dtype), **ff}}


def outer(key, d: Dims, dtype) -> dict:
    """The embedding rows, text first, image after, EOS last (never an
    input), which are the head's rows too, and the final norm."""
    k = jax.random.split(jax.random.fold_in(key, 3), 4)

    def normal(kk, shape):
        return _stored(d.embedding_std * jax.random.normal(
            kk, shape, jnp.float32), dtype)

    return {
        "text_emb": {"w": normal(k[0], (d.num_text_tokens, d.dim))},
        "image_emb": {"w": normal(k[1], (d.num_image_tokens, d.dim))},
        "eos_emb": {"w": normal(k[2], (1, d.dim))},
        "to_logits": {"ln": _gain(k[3], d.dim, dtype)},
    }


def tree(seed, d: Dims, dtype) -> dict:
    """The whole parameter tree in the program's layout: a subtree a
    parameter stack (``Dims.stacks``), each stacked on a leading axis of
    its own layers, in the published order. ``seed`` may be traced
    (``split_seed``'s pair). A layer's key is that of its PUBLISHED index.
    Layers are made one after the other (``lax.map``), so that the float32
    draws of one layer's experts are all that lives beside the tree."""
    key = seed_key_traced(seed)
    out = outer(key, d, dtype)
    out["transformer"] = {}
    for name, (moe, full, layers) in d.stacks().items():
        keys = jax.vmap(lambda i: layer_key(key, i))(
            d.first_layer + jnp.asarray(layers))
        out["transformer"][name] = jax.lax.map(
            lambda kk, moe=moe, full=full: layer(kk, d, dtype, moe, full),
            keys)
    return out
