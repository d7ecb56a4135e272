"""Seeded weights of the ``mla_moe`` family's configurations, in plain jax.

The family is the latent-attention block with routed and shared experts
that DeepSeek-V3-style configurations publish (``model_type
deepseek_v3``): RMSNorm, rotary positions, a cached latent of
``kv_lora_rank`` + ``qk_rope_head_dim`` numbers a token a layer, SiLU-gated
feed-forwards without biases, ``first_k_dense_replace`` leading dense
layers and then layers of ``n_routed_experts`` experts with
``num_experts_per_tok`` picked by sigmoid scores plus a selection bias,
and ``n_shared_experts`` shared ones.

One jitted call on the device makes the whole tree from ``--seed`` in the
program's parameter layout (``tree``); the plain reference
(``reference.py``) calls ``layer`` and ``outer`` layer by layer and never
sees an array the program has held. Nothing here imports the program.

Distributions are the ``dalle`` family's: uniform +-1/sqrt(fan_in) for
linears, N(0, 1) for embeddings, unit norms; the router's selection bias
(``e_score_correction_bias``, a trained buffer with no initialiser in the
published code) is N(0, ``router_bias_std``), the size stated in the
configuration file under ``assumed``.

Layout choices of the program that the reference follows by slicing: the
gate and up projections of a feed-forward lie side by side in ``w_in``
(dim, 2 x hidden), and the published ``kv_b_proj`` is kept as its two
halves per head, ``k_up`` and ``v_up`` (kv_lora_rank, heads, .).
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
    depth: int                  # layers run here, the dense ones included
    dense_layers: int
    heads: int
    kv_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    dense_hidden: int
    expert_hidden: int
    experts: int
    experts_per_token: int
    shared_experts: int
    routed_scale: float
    rope_theta: float
    norm_eps: float
    router_bias_std: float
    text_seq_len: int
    image_grid: int
    num_text_tokens: int
    num_image_tokens: int

    @property
    def moe_layers(self) -> int:
        return self.depth - self.dense_layers

    @property
    def shared_hidden(self) -> int:
        return self.shared_experts * self.expert_hidden

    @property
    def entry_width(self) -> int:
        return self.kv_rank + self.qk_rope

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


def dims_of(config: dict, depth: int | None = None) -> Dims:
    """``Dims`` from a configuration file's object (the published keys
    under their published names); ``depth`` is the cell's cut: the leading
    dense layers and then expert layers."""
    depth = int(depth or config["num_hidden_layers"])
    dense = int(config["first_k_dense_replace"])
    if not dense < depth <= config["num_hidden_layers"]:
        raise ValueError(f"depth {depth} is not {dense} dense layer(s) and "
                         f"at least one expert layer of the published "
                         f"{config['num_hidden_layers']}")
    for key, want in (("q_lora_rank", None), ("n_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1),
                      ("scoring_func", "sigmoid"), ("norm_topk_prob", True),
                      ("rope_interleave", True), ("rope_scaling", None),
                      ("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if config[key] != want:
            raise ValueError(f"the mla_moe family's equations hold for "
                             f"{key} = {want!r}, not {config[key]!r}")
    d = Dims(dim=config["hidden_size"], depth=depth, dense_layers=dense,
             heads=config["num_attention_heads"],
             kv_rank=config["kv_lora_rank"],
             qk_nope=config["qk_nope_head_dim"],
             qk_rope=config["qk_rope_head_dim"],
             v_head=config["v_head_dim"],
             dense_hidden=config["intermediate_size"],
             expert_hidden=config["moe_intermediate_size"],
             experts=config["n_routed_experts"],
             experts_per_token=config["num_experts_per_tok"],
             shared_experts=config["n_shared_experts"],
             routed_scale=float(config["routed_scaling_factor"]),
             rope_theta=float(config["rope_theta"]),
             norm_eps=float(config["rms_norm_eps"]),
             router_bias_std=float(config["router_bias_std"]),
             text_seq_len=config["text_seq_len"],
             image_grid=config["image_grid"],
             num_text_tokens=config["num_text_tokens"],
             num_image_tokens=config["num_image_tokens"])
    if d.total_tokens != config["vocab_size"]:
        raise ValueError(f"text ids, image ids and EOS are {d.total_tokens} "
                         f"rows, the vocabulary {config['vocab_size']}")
    if d.qk_nope + d.qk_rope != config["qk_head_dim"]:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    return d


def _gain(dim, dtype):
    return {"g": jnp.ones((dim,), dtype)}


def _unit(key, d: Dims, hidden: int, dtype, lead=()) -> dict:
    """A SiLU-gated unit: gate | up side by side, then down."""
    k_in, k_out = jax.random.split(key)
    return {"w_in": _uniform(k_in, lead + (d.dim, 2 * hidden), d.dim, dtype),
            "w_out": _uniform(k_out, lead + (hidden, d.dim), hidden, dtype)}


def layer(key, d: Dims, dtype, moe: bool) -> dict:
    """One block: PreNorm latent attention, then a PreNorm dense or
    routed-and-shared feed-forward."""
    k = jax.random.split(key, 10)
    h = d.heads
    attn = {
        "ln": _gain(d.dim, dtype),
        "q": {"w": _uniform(k[0], (d.dim, h * (d.qk_nope + d.qk_rope)),
                            d.dim, dtype)},
        "kva": {"w": _uniform(k[1], (d.dim, d.kv_rank + d.qk_rope), d.dim,
                              dtype)},
        "kv_ln": _gain(d.kv_rank, dtype),
        "k_up": _uniform(k[2], (d.kv_rank, h, d.qk_nope), d.kv_rank, dtype),
        "v_up": _uniform(k[3], (d.kv_rank, h, d.v_head), d.kv_rank, dtype),
        "out": {"w": _uniform(k[4], (h * d.v_head, d.dim), h * d.v_head,
                              dtype)},
    }
    if not moe:
        ff = _unit(k[5], d, d.dense_hidden, dtype)
    else:
        ff = {
            "router": {
                "w": _uniform(k[6], (d.dim, d.experts), d.dim, dtype),
                "bias": d.router_bias_std * jax.random.normal(
                    k[7], (d.experts,), jnp.float32)},
            "experts": _unit(k[8], d, d.expert_hidden, dtype,
                             lead=(d.experts,)),
            "shared": _unit(k[9], d, d.shared_hidden, dtype),
        }
    return {"attn": attn, "ff": {"ln": _gain(d.dim, dtype), **ff}}


def outer(key, d: Dims, dtype) -> dict:
    """The vocabulary's embedding, divided into the text rows and the
    image rows (EOS, the last row, is never an input), and the untied
    head behind its norm."""
    k = jax.random.split(jax.random.fold_in(key, 3), 3)

    def normal(kk, shape):
        return _stored(jax.random.normal(kk, shape, jnp.float32), dtype)

    return {
        "text_emb": {"w": normal(k[0], (d.num_text_tokens, d.dim))},
        "image_emb": {"w": normal(k[1], (d.num_image_tokens, d.dim))},
        "to_logits": {
            "ln": _gain(d.dim, dtype),
            "proj": {"w": _uniform(k[2], (d.dim, d.total_tokens), d.dim,
                                   dtype)},
        },
    }


def tree(seed, d: Dims, dtype) -> dict:
    """The whole parameter tree in the program's layout: the dense layers
    and the expert layers as two subtrees, each stacked on a leading axis
    of its own layers. ``seed`` may be traced (``split_seed``'s pair).
    Layers are made one after the other (``lax.map``), so that the float32
    draws of one layer's experts are all that lives beside the tree."""
    key = seed_key_traced(seed)
    out = outer(key, d, dtype)

    def stack(first, n, moe):
        keys = jax.vmap(lambda i: layer_key(key, i))(first + jnp.arange(n))
        return jax.lax.map(lambda kk: layer(kk, d, dtype, moe), keys)

    out["transformer"] = {"dense": stack(0, d.dense_layers, False),
                          "moe": stack(d.dense_layers, d.moe_layers, True)}
    return out
