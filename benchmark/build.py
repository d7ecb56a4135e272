"""The one place where the benchmark touches the program's model code:
a ``DALLEConfig`` from a configuration file and a cell's flags, and the
seeded parameters placed in the program's layout. Everything else that
imports the program lives in ``train_cell.py`` and ``serve_cell.py``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights as W


def dalle_config(config: dict, dims: W.Dims, flags: dict):
    """The ``DALLEConfig`` that ``cli.train_dalle`` builds from the
    deployment's flags (and that ``cli.serve`` restores from such a
    run's checkpoint), at this cell's depth."""
    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.models import vae as V
    vae = V.VAEConfig(image_size=dims.image_grid * 8,
                      num_tokens=dims.num_image_tokens, num_layers=3,
                      codebook_dim=dims.dim)
    sparse = dims.sparse_layers if any(dims.sparse_layers) else False
    return D.DALLEConfig(
        dim=dims.dim, depth=dims.depth, vae=vae,
        num_text_tokens=dims.num_text_tokens,
        text_seq_len=dims.text_seq_len, heads=dims.heads,
        dim_head=dims.dim_head, sparse_attn=sparse,
        sparse_block=dims.sparse_block,
        attn_dropout=float(flags.get("attn_dropout", 0.0)),
        ff_dropout=float(flags.get("ff_dropout", 0.0)),
        attn_impl=flags.get("attn_impl", "xla"),
        attn_bwd_impl=flags.get("attn_bwd_impl", "xla"),
        sparse_impl=flags.get("sparse_impl", "windowed"),
        loss_chunk=int(flags.get("loss_chunk", 0)),
        remat=flags.get("remat", "none"))


def init_fn(dims: W.Dims, dtype):
    """Jitted ``(seed halves) -> parameter tree``: one call, on the
    device, in the stored type."""
    return jax.jit(functools.partial(W.tree, d=dims, dtype=jnp.dtype(dtype)))
