"""Seeded weights of the benchmark's configurations, in plain jax.

The benchmark makes the weights itself: one jitted call on the device from
``--seed``, in the type they are served or trained in. The program under
test receives the tree (its layout is the program's parameter layout, see
``tree``); the plain reference (``reference.py``) calls the same functions
layer by layer and never sees an array the program has held. Nothing here
imports the program.

Distributions follow the program's own initialisers in family (uniform
+-1/sqrt(fan_in) for linears, N(0, 1) for embeddings, unit layer norms),
which keeps logits and gradients at trained-model scale.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration as one cell runs it."""
    dim: int
    depth: int
    heads: int
    dim_head: int
    ff_mult: int
    text_seq_len: int
    image_grid: int
    num_text_tokens: int
    num_image_tokens: int
    pattern: tuple          # one period of "dense" / "sparse"
    sparse_block: int = 16
    sparse_local_blocks: int = 4

    @property
    def inner(self) -> int:
        return self.heads * self.dim_head

    @property
    def hidden(self) -> int:
        return self.dim * self.ff_mult

    @property
    def image_seq_len(self) -> int:
        return self.image_grid * self.image_grid

    @property
    def seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    @property
    def total_tokens(self) -> int:
        return self.num_text_tokens + self.num_image_tokens + 1

    def layer_is_sparse(self, layer: int) -> bool:
        return self.pattern[layer % len(self.pattern)] == "sparse"

    @property
    def sparse_layers(self) -> tuple:
        return tuple(self.layer_is_sparse(i) for i in range(self.depth))


def dims_of(config: dict, depth: int | None = None) -> Dims:
    """``Dims`` from a configuration file's object; ``depth`` is the
    cell's cut (a whole number of pattern periods)."""
    pattern = tuple(config["attention_pattern"])
    depth = int(depth or config["depth"])
    if depth % len(pattern):
        raise ValueError(f"depth {depth} is not whole periods of {pattern}")
    return Dims(dim=config["dim"], depth=depth, heads=config["heads"],
                dim_head=config["dim_head"], ff_mult=config["ff_mult"],
                text_seq_len=config["text_seq_len"],
                image_grid=config["image_grid"],
                num_text_tokens=config["num_text_tokens"],
                num_image_tokens=config["num_image_tokens"],
                pattern=pattern,
                sparse_block=config.get("sparse_block", 16),
                sparse_local_blocks=config.get("sparse_local_blocks", 4))


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(20260927)
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _stored(x, dtype):
    """``x`` (float32) rounded to ``dtype`` by ``reduce_precision``, which
    no compiler pass removes: a plain convert to bfloat16 and back is
    elided when both land in one fusion (XLA's simplify-fp-conversions),
    and the reference would then see weights the program never had."""
    info = jnp.finfo(dtype)
    x = jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                 mantissa_bits=info.nmant)
    return x.astype(dtype)


def _uniform(key, shape, fan_in, dtype):
    bound = 1.0 / math.sqrt(fan_in)
    return _stored(jax.random.uniform(key, shape, jnp.float32, -bound, bound),
                   dtype)


def _ln(dim, dtype):
    return {"g": jnp.ones((dim,), dtype), "b": jnp.zeros((dim,), dtype)}


def layer(key, d: Dims, dtype) -> dict:
    """One block: PreNorm attention (fused qkv, no bias; out with bias)
    and PreNorm GEGLU feed-forward (w1 to 2 x hidden, w2 back)."""
    k = jax.random.split(key, 6)
    return {
        "attn": {
            "ln": _ln(d.dim, dtype),
            "qkv": {"w": _uniform(k[0], (d.dim, 3 * d.inner), d.dim, dtype)},
            "out": {"w": _uniform(k[1], (d.inner, d.dim), d.inner, dtype),
                    "b": _uniform(k[2], (d.dim,), d.inner, dtype)},
        },
        "ff": {
            "ln": _ln(d.dim, dtype),
            "w1": {"w": _uniform(k[3], (d.dim, 2 * d.hidden), d.dim, dtype),
                   "b": _uniform(k[4], (2 * d.hidden,), d.dim, dtype)},
            "w2": {"w": _uniform(k[5], (d.hidden, d.dim), d.hidden, dtype),
                   "b": jnp.zeros((d.dim,), dtype)},
        },
    }


def layer_key(key, index):
    return jax.random.fold_in(jax.random.fold_in(key, 7), index)


def outer(key, d: Dims, dtype) -> dict:
    """Embeddings, position tables and the logits head."""
    k = jax.random.split(jax.random.fold_in(key, 3), 7)

    def normal(kk, shape):
        return _stored(jax.random.normal(kk, shape, jnp.float32), dtype)

    return {
        "text_emb": {"w": normal(k[0], (d.num_text_tokens, d.dim))},
        "image_emb": {"w": normal(k[1], (d.num_image_tokens, d.dim))},
        "text_pos_emb": {"w": normal(k[2], (d.text_seq_len, d.dim))},
        "image_pos_emb": {"rows": normal(k[3], (d.image_grid, d.dim)),
                          "cols": normal(k[4], (d.image_grid, d.dim))},
        "to_logits": {
            "ln": _ln(d.dim, dtype),
            "proj": {"w": _uniform(k[5], (d.dim, d.total_tokens), d.dim,
                                   dtype),
                     "b": _uniform(k[6], (d.total_tokens,), d.dim, dtype)},
        },
    }


def tree(seed, d: Dims, dtype) -> dict:
    """The whole parameter tree, blocks stacked on a leading depth axis.
    ``seed`` may be traced (a uint32 pair from ``split_seed``)."""
    key = seed_key_traced(seed)
    keys = jax.vmap(lambda i: layer_key(key, i))(jnp.arange(d.depth))
    out = outer(key, d, dtype)
    out["transformer"] = jax.vmap(lambda kk: layer(kk, d, dtype))(keys)
    return out


def split_seed(seed: int):
    """The seed as two non-negative int32 halves, safe to pass to jit."""
    seed = int(seed)
    return (jnp.int32(seed & 0x7FFFFFFF), jnp.int32((seed >> 31) & 0x7FFFFFFF))


def seed_key_traced(halves):
    lo, hi = halves
    key = jax.random.PRNGKey(20260927)
    return jax.random.fold_in(jax.random.fold_in(key, lo), hi)
