"""Seeded weights of the ``mimo_v2`` family's configurations, in plain jax.

The family is the block that ``model_type mimo_v2`` configurations publish
(Xiaomi's MiMo-V2 models): two pre-norms a layer (RMSNorm) and nothing
else around the branches; grouped-query attention with
``num_attention_heads`` query heads of ``head_dim`` over key heads of the
same size and VALUE heads of ``v_head_dim`` (narrower), the key/value heads
``num_key_value_heads`` in a full layer and ``swa_num_key_value_heads`` in
a window layer (``hybrid_layer_pattern``: 1 window, 0 full), rotary
positions on the first ``int(head_dim * partial_rotary_factor)`` numbers of
a head at a base a layer type (``rope_theta`` full, ``swa_rope_theta``
window), values times ``attention_value_scale``, no query/key norm, no
gate, no bias, and in a window layer (``sliding_window`` rows, a strict
sliding window) one learned logit a query head that joins the softmax's
denominator and takes no value (``add_swa_attention_sink_bias``); SiLU-gated
feed-forwards without biases: the dense layers of ``moe_layer_freq`` (0),
then layers of ``n_routed_experts`` routed experts with
``num_experts_per_tok`` picked by sigmoid scores plus a selection bias, the
picked scores renormalised, no shared expert and no further scale; an
untied head behind a final norm.

A configuration runs ONE CHIP'S SHARE of a stated deployment: ``depth``
layers from the published layer ``first_layer`` on, ``experts_held`` routed
experts from ``first_expert`` on (the router stays ``n_routed_experts``
wide) and ``vocab_held`` rows of the vocabulary. A layer's and an expert's
weights are drawn from their PUBLISHED indices, so the shares of one seed
are parts of one model (``tests/test_mimo_block.py`` adds them up).

One jitted call on the device makes the whole tree from ``--seed`` in the
program's parameter layout (``tree``); the plain reference
(``reference.py``) calls ``layer`` and ``outer`` layer by layer and never
sees an array the program has held. Nothing here imports the program.

Distributions (``assumed`` in the configuration file says why each):
uniform +-1/sqrt(fan_in) for linears, the query and key projections' times
``qk_init_gain``; embeddings N(0, ``embedding_std``^2); every norm's gain 1
+ N(0, 0.05^2), near 1 and not 1, so that a gain left out would show; the
sink logits N(``sink_logit_mean``, ``sink_logit_std``^2) in float32; the
router's selection bias N(0, ``router_bias_std``^2).

Layout choices of the program that the reference follows by slicing: the
gate and up projections of a feed-forward lie side by side in ``w_in``
(dim, 2 x hidden); query, key and value projections lie apart (the
published ``attention_projection_layout fused_qkv`` is a layout too).
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
    kv_heads: int               # of a window layer
    full_kv_heads: int          # of a full layer
    head_dim: int               # of a query and a key head
    v_head_dim: int
    rotary_dim: int             # the leading numbers of a head that turn
    window: int
    dense_hidden: int
    expert_hidden: int
    experts: int                # published: the router's width
    experts_held: int
    first_expert: int
    experts_per_token: int
    rope_theta: float           # a window layer's base
    full_rope_theta: float
    value_scale: float
    norm_eps: float
    router_bias_std: float
    qk_init_gain: float
    sink_logit_mean: float
    sink_logit_std: float
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
    def window_layers(self) -> int:
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

    def kv_heads_of(self, full: bool) -> int:
        return self.full_kv_heads if full else self.kv_heads

    def stacks(self) -> dict:
        """``{the program's parameter stack: (moe, full, the layers run
        here that lie in it)}`` in the order of their first layers:
        ``dense`` / ``moe`` hold the window layers, ``dense_full`` /
        ``moe_full`` the full ones (their key/value projections differ in
        shape and they hold no sink)."""
        out = {}
        for i in range(self.depth):
            moe, full = self.layer_is_moe(i), self.layer_is_full(i)
            name = ("moe" if moe else "dense") + ("_full" if full else "")
            out.setdefault(name, (moe, full, []))[2].append(i)
        return out


_EQUATIONS = (
    ("n_group", 1), ("topk_group", 1), ("scoring_func", "sigmoid"),
    ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
    ("n_shared_experts", None), ("routed_scaling_factor", None),
    ("hidden_act", "silu"), ("tie_word_embeddings", False),
    ("attention_bias", False), ("add_swa_attention_sink_bias", True),
    ("add_full_attention_sink_bias", False), ("hybrid_block_size", None))
# a layer type's own keys that the published configurations set alike
_ALIKE = (("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim"),
          ("swa_num_attention_heads", "num_attention_heads"),
          ("sliding_window_size", "sliding_window"))


def dims_of(config: dict, depth: int | None = None) -> Dims:
    """``Dims`` from a configuration file's object (the published keys
    under their published names, the cut under ``depth``, ``first_layer``,
    ``experts_held``, ``first_expert``, ``vocab_held``); ``depth`` is the
    cell's cut: that many published layers from ``first_layer`` on."""
    depth = int(depth or config["depth"])
    first = int(config["first_layer"])
    published = config["num_hidden_layers"]
    pattern, routed = config["hybrid_layer_pattern"], config["moe_layer_freq"]
    if not 0 <= first < first + depth <= published \
            or len(pattern) != published or len(routed) != published:
        raise ValueError(f"layers {first}..{first + depth} are not of the "
                         f"published {published}")
    for key, want in _EQUATIONS:
        if config[key] != want:
            raise ValueError(f"the mimo_v2 family's equations hold for "
                             f"{key} = {want!r}, not {config[key]!r}")
    for key, other in _ALIKE:
        if config[key] != config[other]:
            raise ValueError(f"the mimo_v2 family's equations hold for "
                             f"{key} = {other}, not {config[key]!r} and "
                             f"{config[other]!r}")
    here = [int(bool(r)) for r in routed[first:first + depth]]
    dense = here.index(1) if 1 in here else depth
    if 0 in here[dense:]:
        raise ValueError(f"the dense layers of layers {first}.."
                         f"{first + depth} do not all lead")
    d = Dims(dim=config["hidden_size"], depth=depth, first_layer=first,
             dense_layers=dense,
             layer_types=tuple("sliding" if w else "full" for w in
                               pattern[first:first + depth]),
             heads=config["num_attention_heads"],
             kv_heads=config["swa_num_key_value_heads"],
             full_kv_heads=config["num_key_value_heads"],
             head_dim=config["head_dim"], v_head_dim=config["v_head_dim"],
             rotary_dim=int(config["head_dim"]
                            * config["partial_rotary_factor"]),
             window=config["sliding_window"],
             dense_hidden=config["intermediate_size"],
             expert_hidden=config["moe_intermediate_size"],
             experts=config["n_routed_experts"],
             experts_held=config["experts_held"],
             first_expert=config["first_expert"],
             experts_per_token=config["num_experts_per_tok"],
             rope_theta=float(config["swa_rope_theta"]),
             full_rope_theta=float(config["rope_theta"]),
             value_scale=float(config["attention_value_scale"]),
             norm_eps=float(config["layernorm_epsilon"]),
             router_bias_std=float(config["router_bias_std"]),
             qk_init_gain=float(config["qk_init_gain"]),
             sink_logit_mean=float(config["sink_logit_mean"]),
             sink_logit_std=float(config["sink_logit_std"]),
             embedding_std=float(config["embedding_std"]),
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
            <= d.experts or d.heads % d.kv_heads \
            or d.heads % d.full_kv_heads or d.rotary_dim % 2 \
            or not 0 < d.rotary_dim <= d.head_dim:
        raise ValueError("the held experts are no share of the published "
                         "ones, the query heads no multiple of a layer "
                         "type's key/value heads, or the turned part of a "
                         "head no even number of its numbers")
    return d


def _gain(key, dim, dtype):
    return {"g": _stored(1.0 + 0.05 * jax.random.normal(
        key, (dim,), jnp.float32), dtype)}


def _linear(key, fan_in, fan_out, dtype, gain: float = 1.0):
    bound = gain * fan_in ** -0.5
    return {"w": _stored(jax.random.uniform(
        key, (fan_in, fan_out), jnp.float32, -bound, bound), dtype)}


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


def layer(key, d: Dims, dtype, moe: bool, full: bool) -> dict:
    """One block: pre-normed grouped-query attention of its layer type (a
    window layer holds the sink logits), then a pre-normed dense or routed
    feed-forward holding this chip's experts."""
    k = jax.random.split(key, 12)
    h, kv = d.heads, d.kv_heads_of(full)
    attn = {
        "ln": _gain(k[0], d.dim, dtype),
        "q": _linear(k[1], d.dim, h * d.head_dim, dtype, d.qk_init_gain),
        "k": _linear(k[2], d.dim, kv * d.head_dim, dtype, d.qk_init_gain),
        "v": _linear(k[3], d.dim, kv * d.v_head_dim, dtype),
        "out": _linear(k[4], h * d.v_head_dim, d.dim, dtype),
    }
    if not full:
        attn["sink"] = d.sink_logit_mean + d.sink_logit_std \
            * jax.random.normal(k[5], (h,), jnp.float32)
    if not moe:
        ff = _unit(k[6], d, d.dense_hidden, dtype)
    else:
        ff = {
            "router": {
                "w": _uniform(k[7], (d.dim, d.experts), d.dim, dtype),
                "bias": d.router_bias_std * jax.random.normal(
                    k[8], (d.experts,), jnp.float32)},
            "experts": experts(k[9], d, dtype, d.first_expert,
                               d.experts_held),
        }
    return {"attn": attn, "ff": {"ln": _gain(k[10], d.dim, dtype), **ff}}


def outer(key, d: Dims, dtype) -> dict:
    """The held rows of the vocabulary's embedding, divided into the text
    rows and the image rows (EOS, the last row, is never an input), and
    the untied head over the same rows behind its norm."""
    k = jax.random.split(jax.random.fold_in(key, 3), 4)

    def normal(kk, shape):
        return _stored(d.embedding_std * jax.random.normal(
            kk, shape, jnp.float32), dtype)

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
