"""Seeded weights of the ``qwen3_next`` family's configurations, in plain
jax.

The family is the block that ``model_type qwen3_next`` configurations
publish (Qwen3-Next): two pre-norms a layer (zero-centred RMSNorms, ``x /
rms(x) * (1 + w)``) and nothing else around the branches; layer ``i`` is
gated grouped-query attention where ``(i + 1) % full_attention_interval ==
0`` (``num_attention_heads`` query heads over ``num_key_value_heads``
key/value heads of ``head_dim``, a zero-centred RMSNorm over each query and
key head, rotate-half RoPE on a head's first ``partial_rotary_factor x
head_dim`` numbers at ``rope_theta``, the output times ``sigmoid(gate)``,
no bias) and a gated delta-rule layer otherwise (``linear_num_key_heads``
key heads of ``linear_key_head_dim`` under ``linear_num_value_heads`` value
heads of ``linear_value_head_dim``, a depthwise causal convolution of
``linear_conv_kernel_dim`` taps with SiLU over ``[q; k; v]``, l2-normed
queries and keys, a decay and a write strength a value head, a float32
matrix state a value head, a gated RMSNorm over each head's output); every
layer's feed-forward is routed (``decoder_sparse_step`` 1, ``mlp_only_layers
[]``): ``num_experts`` SiLU-gated experts of ``moe_intermediate_size``
scored by a softmax over all of them, ``num_experts_per_tok`` picked, their
scores over their sum (``norm_topk_prob``), beside a shared unit of
``shared_expert_intermediate_size`` times ``sigmoid(w_s . x)``; a final
norm and an untied head.

A configuration runs ONE CHIP'S SHARE of a stated deployment: ``depth``
layers from the published layer ``first_layer`` on, ``experts_held`` routed
experts from ``first_expert`` on (the router stays ``num_experts`` wide)
and ``vocab_held`` rows of the vocabulary. A layer's and an expert's
weights are drawn from their PUBLISHED indices, so the shares of one seed
are parts of one model (``tests/test_qwen3_next_block.py`` adds them up)
and a deeper cut of one seed is more of one model.

One jitted call on the device makes the whole tree from ``--seed`` in the
program's parameter layout (``tree``); the plain reference
(``reference.py``) calls ``layer`` and ``outer`` layer by layer and never
sees an array the program has held. Nothing here imports the program.

Distributions (``assumed`` in the configuration file says why each):
uniform +-1/sqrt(fan_in) for linears and for the convolution's taps (fan-in
``linear_conv_kernel_dim``: four taps of one scale, so the three earlier
ones carry three quarters of the convolution); embeddings N(0,
``embedding_std``^2); every norm's gain ``1 + w`` with ``w`` N(0, 0.05^2)
(the zero-centred norms hold ``w``; stored here is ``g = 1 + w``, the gain
itself: near 1 and not 1, so that one left out would show), the gains of
the norms over a query and a key head besides times ``qk_norm_gain``; the
gated norm's plain gain drawn the same way; ``A`` uniform in (0, 16) a
value head (``a_log`` its logarithm) and ``dt_bias`` 1, the published
initialisers, both float32.

Layout choices of the program that the reference follows by slicing: a
delta-rule layer's four input streams lie side by side in ``in`` (dim, 2 x
key width + 2 x value width) in the order q, k, v, z, whole streams and not
interleaved by key head as the published ``in_proj_qkvz`` is, and ``ba``
(dim, 2 x value heads) as b, then a; ``a_log`` and ``dt_bias`` lie (key
heads, value heads a key head): value head ``h`` is ``[h // group, h %
group]``; an attention layer's query and gate projections are apart (``q``,
``gate``), not interleaved by head in one ``q_proj``; the gate and up
projections of a feed-forward side by side in ``w_in`` (dim, 2 x hidden).
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
    layer_types: tuple          # "delta" | "full", a layer run here
    heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    key_heads: int              # a delta-rule layer's
    value_heads: int
    key_head_dim: int
    value_head_dim: int
    conv_taps: int
    expert_hidden: int
    shared_hidden: int
    experts: int                # published: the router's width
    experts_held: int
    first_expert: int
    experts_per_token: int
    norm_eps: float
    qk_norm_gain: float         # what the q/k norms' gains are drawn around
    embedding_std: float
    text_seq_len: int
    image_grid: int
    num_text_tokens: int
    num_image_tokens: int

    @property
    def moe_layers(self) -> int:
        return self.depth

    @property
    def full_layers(self) -> int:
        return sum(t == "full" for t in self.layer_types)

    @property
    def delta_layers(self) -> int:
        return self.depth - self.full_layers

    @property
    def key_dim(self) -> int:
        return self.key_heads * self.key_head_dim

    @property
    def value_dim(self) -> int:
        return self.value_heads * self.value_head_dim

    @property
    def conv_dim(self) -> int:
        """The convolution's channels: q, k and v side by side."""
        return 2 * self.key_dim + self.value_dim

    @property
    def image_seq_len(self) -> int:
        return self.image_grid * self.image_grid

    @property
    def seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    @property
    def total_tokens(self) -> int:
        return self.num_text_tokens + self.num_image_tokens + 1

    def layer_is_full(self, layer: int) -> bool:
        return self.layer_types[layer] == "full"

    def stacks(self) -> dict:
        """``{the program's parameter stack: (full, the layers run here
        that lie in it)}`` in the order of their first layers: ``moe``
        holds the delta-rule layers, ``moe_full`` the attention layers."""
        out = {}
        for i in range(self.depth):
            full = self.layer_is_full(i)
            out.setdefault("moe_full" if full else "moe",
                           (full, []))[1].append(i)
        return out


_EQUATIONS = (("decoder_sparse_step", 1), ("mlp_only_layers", []),
              ("norm_topk_prob", True), ("hidden_act", "silu"),
              ("rope_scaling", None), ("tie_word_embeddings", False),
              ("use_sliding_window", False))


def dims_of(config: dict, depth: int | None = None) -> Dims:
    """``Dims`` from a configuration file's object (the published keys
    under their published names, the cut under ``depth``, ``first_layer``,
    ``experts_held``, ``first_expert``, ``vocab_held``); ``depth`` is the
    cell's cut: that many published layers from ``first_layer`` on."""
    depth = int(depth or config["depth"])
    first = int(config["first_layer"])
    published = config["num_hidden_layers"]
    if not 0 <= first < first + depth <= published:
        raise ValueError(f"layers {first}..{first + depth} are not of the "
                         f"published {published}")
    for key, want in _EQUATIONS:
        if config[key] != want:
            raise ValueError(f"the qwen3_next family's equations hold for "
                             f"{key} = {want!r}, not {config[key]!r}")
    every = int(config["full_attention_interval"])
    head_dim = config["head_dim"]
    rotary = int(head_dim * config["partial_rotary_factor"])
    d = Dims(dim=config["hidden_size"], depth=depth, first_layer=first,
             layer_types=tuple(
                 "full" if (i + 1) % every == 0 else "delta"
                 for i in range(first, first + depth)),
             heads=config["num_attention_heads"],
             kv_heads=config["num_key_value_heads"], head_dim=head_dim,
             rotary_dim=rotary, rope_theta=float(config["rope_theta"]),
             key_heads=config["linear_num_key_heads"],
             value_heads=config["linear_num_value_heads"],
             key_head_dim=config["linear_key_head_dim"],
             value_head_dim=config["linear_value_head_dim"],
             conv_taps=config["linear_conv_kernel_dim"],
             expert_hidden=config["moe_intermediate_size"],
             shared_hidden=config["shared_expert_intermediate_size"],
             experts=config["num_experts"],
             experts_held=config["experts_held"],
             first_expert=config["first_expert"],
             experts_per_token=config["num_experts_per_tok"],
             norm_eps=float(config["rms_norm_eps"]),
             qk_norm_gain=float(config["qk_norm_gain"]),
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
            or d.value_heads % d.key_heads:
        raise ValueError("the held experts are no share of the published "
                         "ones, or the query heads no multiple of the "
                         "key/value heads, or the value heads of the key "
                         "heads")
    if rotary % 2 or not 0 < rotary <= head_dim:
        raise ValueError(f"partial_rotary_factor turns {rotary} of a head's "
                         f"{head_dim} numbers: no whole pairs")
    return d


def _gain(key, dim, dtype, around: float = 1.0):
    """``g = 1 + w``, ``w`` N(0, 0.05^2) (times ``around``)."""
    return {"g": _stored(around * (1.0 + 0.05 * jax.random.normal(
        key, (dim,), jnp.float32)), dtype)}


def _linear(key, fan_in, fan_out, dtype):
    return {"w": _uniform(key, (fan_in, fan_out), fan_in, dtype)}


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


def mixer(key, d: Dims, dtype, full: bool) -> dict:
    """A layer's mixer behind its norm: gated grouped-query attention with
    the norms over a query and a key head, or the gated delta-rule layer
    (``in``: q | k | v | z side by side; ``ba``: b | a; ``conv.w``: a
    weight a tap a channel, oldest tap first)."""
    k = jax.random.split(key, 8)
    if full:
        h, kv, dh = d.heads, d.kv_heads, d.head_dim
        return {
            "ln": _gain(k[0], d.dim, dtype),
            "q": _linear(k[1], d.dim, h * dh, dtype),
            "k": _linear(k[2], d.dim, kv * dh, dtype),
            "v": _linear(k[3], d.dim, kv * dh, dtype),
            "gate": _linear(k[4], d.dim, h * dh, dtype),
            "q_ln": _gain(k[5], dh, dtype, d.qk_norm_gain),
            "k_ln": _gain(k[6], dh, dtype, d.qk_norm_gain),
            "out": _linear(k[7], h * dh, d.dim, dtype),
        }
    group = d.value_heads // d.key_heads
    return {
        "ln": _gain(k[0], d.dim, dtype),
        "in": _linear(k[1], d.dim, d.conv_dim + d.value_dim, dtype),
        "ba": _linear(k[2], d.dim, 2 * d.value_heads, dtype),
        "conv": {"w": _uniform(k[3], (d.conv_taps, d.conv_dim),
                               d.conv_taps, dtype)},
        "a_log": jnp.log(jax.random.uniform(
            k[4], (d.key_heads, group), jnp.float32, 1e-3, 16.0)),
        "dt_bias": jnp.ones((d.key_heads, group), jnp.float32),
        "norm": _gain(k[5], d.value_head_dim, dtype),
        "out": _linear(k[6], d.value_dim, d.dim, dtype),
    }


def layer(key, d: Dims, dtype, full: bool, first: int | None = None,
          count: int | None = None) -> dict:
    """One block: its pre-normed mixer, then the pre-normed routed
    feed-forward holding the experts ``first`` .. ``first + count`` (this
    chip's unless told), the shared unit and its gate's row."""
    k = jax.random.split(key, 6)
    first = d.first_expert if first is None else first
    count = d.experts_held if count is None else count
    ff = {
        "ln": _gain(k[1], d.dim, dtype),
        "router": {"w": _uniform(k[2], (d.dim, d.experts), d.dim, dtype)},
        "experts": experts(k[3], d, dtype, first, count),
        "shared": _unit(k[4], d, d.shared_hidden, dtype),
        "shared_gate": _linear(k[5], d.dim, 1, dtype),
    }
    return {"attn": mixer(k[0], d, dtype, full), "ff": ff}


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
    for name, (full, layers) in d.stacks().items():
        keys = jax.vmap(lambda i: layer_key(key, i))(
            d.first_layer + jnp.asarray(layers))
        out["transformer"][name] = jax.lax.map(
            lambda kk, full=full: layer(kk, d, dtype, full), keys)
    return out
