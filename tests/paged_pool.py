"""What the classic page pool's test files share beside tests/tiny_model.py
(tests/test_paged_attention.py, test_paged_per_layer_read.py,
test_paged_read_oracle.py, test_paged_row_writes.py,
test_decode_narrowed_read.py): the sparse configuration and a pool of
random pages. A helper module, not a test file: a test module that another
imports is collected twice over and ties two files' order."""

import dataclasses

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.serve import kv_pool as KV
from tiny_model import CFG, VCFG

# the sparse-reads step needs sparse layers whose window is narrower than
# the 24-token sequence (tests/test_sparse_reads.py's configuration)
SPARSE_CFG = D.DALLEConfig(dim=16, depth=2, vae=VCFG, num_text_tokens=50,
                           text_seq_len=8, heads=2, dim_head=8,
                           sparse_attn=(True, False), sparse_block=4)


def random_pool(key, page_size, num_pages, quantized, *, dim_head=None,
                 dtype=jnp.float32, heads=None):
    """A pool with fully-random page content — including the trash page
    and unallocated pages, so an out-of-bounds read cannot hide behind
    zeros. A page is whole rows (``kv_pool.page_layout``)."""
    tcfg = CFG.transformer
    heads = heads or tcfg.heads
    shape = (tcfg.depth, num_pages, page_size,
             heads * (dim_head or tcfg.dim_head))
    assert shape[2:] == KV.page_layout(dataclasses.replace(
        tcfg, heads=heads, dim_head=shape[-1] // heads), page_size)["k"][0]
    if quantized:
        scales = shape[:-1] + (heads,)
        return {
            "k": jax.random.randint(jax.random.fold_in(key, 0), shape,
                                    -127, 128, jnp.int8),
            "v": jax.random.randint(jax.random.fold_in(key, 1), shape,
                                    -127, 128, jnp.int8),
            "k_scale": jax.random.uniform(jax.random.fold_in(key, 2),
                                          scales, minval=0.01, maxval=0.1),
            "v_scale": jax.random.uniform(jax.random.fold_in(key, 3),
                                          scales, minval=0.01, maxval=0.1),
        }
    return {"k": jax.random.normal(jax.random.fold_in(key, 0), shape,
                                   dtype),
            "v": jax.random.normal(jax.random.fold_in(key, 1), shape,
                                   dtype)}
