"""The classic pool's per-layer gather read against the dense view's oracle,
over the pools' types, head shapes, table forms and the mesh seam (108
cases cut out of tests/test_paged_per_layer_read.py, which holds the
grouped read, the whole-row read's forms and the fused loops;
tests/paged_pool.py holds what the files share): a file of its own so
that neither is a sixth of the run (ROADMAP.md Queue 3 item 1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.serve import kv_pool as KV
from paged_pool import random_pool
from tiny_model import CFG  # noqa: F401


class TestPerLayerReadOracle:
    """ISSUE 25 and 36: the gather path attends ONE layer's pages,
    page-major, straight from the pool, a page whole rows. The oracle is
    the all-layer dense view it replaced: ``paged_view`` +
    ``_gather_read`` -- same rows, same masks, same scales."""

    PS, DEPTH = 8, CFG.transformer.depth

    def _case(self, kind, heads, dim_head, total_len, tables):
        key = jax.random.PRNGKey(dim_head + total_len)
        need = KV.pages_for(total_len, self.PS)
        dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
        pool = random_pool(key, self.PS, 3 * need + 1, kind == "int8",
                            dim_head=dim_head, dtype=dtype, heads=heads)
        bt = np.zeros((3, need), np.int32)
        bt[0] = np.arange(1, need + 1)
        bt[1] = np.arange(need + 1, 2 * need + 1)
        bt[2] = np.arange(2 * need + 1, 3 * need + 1)
        pos = [total_len - 1, total_len // 2, 5]
        if tables == "wide":
            # the pool-max table a caller holds: tail columns map OTHER
            # live pages, which must never reach the read
            bt = np.concatenate(
                [bt, np.full((3, 3), need, np.int32)], axis=1)
        elif tables == "shared":
            # copy-on-write fan-out: two slots read the same prompt page
            bt[1, 0] = bt[0, 0]
        else:
            # unmapped entries on the trash page (random content there):
            # a mid-sequence slot and a parked dead one
            bt[1, KV.pages_for(pos[1] + 1, self.PS):] = 0
            bt[2] = 0
            pos[2] = 0
        qkv = [jax.random.normal(jax.random.fold_in(key, 10 + i),
                                 (3, heads, 1, dim_head), dtype)
               for i in range(3)]
        allowed = (jnp.arange(total_len)[None, :]
                   < jnp.asarray(pos)[:, None])
        allowed = allowed.at[0, 1].set(False)        # a padded-off row
        return pool, jnp.asarray(bt), qkv, allowed

    @pytest.mark.parametrize("mesh", [False, True],
                             ids=["whole_rows", "mesh_seam"])
    @pytest.mark.parametrize("tables", ["wide", "shared", "trash"])
    @pytest.mark.parametrize("total_len", [24, 20],
                             ids=["whole_pages", "partial_last_page"])
    @pytest.mark.parametrize("heads,dim_head", [(4, 64), (2, 128), (6, 64)],
                             ids=["4x64", "2x128", "6x64"])
    @pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
    def test_per_layer_read_matches_view_oracle(self, kind, heads, dim_head,
                                                total_len, tables, mesh):
        """ISSUE 36: a page is whole rows ``(ps, heads * dh)`` and the
        read contracts them whole (the grouped-query read at ``kv_heads ==
        heads``), or per head under the mesh seam: both equal the dense
        oracle at 64- and 128-wide heads, an even and an odd head count,
        a float32 page of whole tiles (8 rows) and bf16 / int8 pages short
        of one."""
        pool, bt, (q, k, v), allowed = self._case(kind, heads, dim_head,
                                                  total_len, tables)
        scale = dim_head ** -0.5
        view = decode_ops.paged_view(pool, bt, total_len, heads)
        need = KV.pages_for(total_len, self.PS)
        tol = dict(rtol=2e-2, atol=2e-2) if kind == "bf16" else \
            dict(rtol=2e-5, atol=2e-5)
        for layer in range(self.DEPTH):
            want = decode_ops._gather_read(
                q, k, v, view["k"][layer], view["v"][layer], allowed,
                scale=scale,
                ksc=view["k_scale"][layer] if kind == "int8" else None,
                vsc=view["v_scale"][layer] if kind == "int8" else None)
            gk = decode_ops.layer_pool_view(
                pool["k"], jnp.asarray(layer), bt[:, :need])
            assert gk.shape == (3, need, self.PS, heads * dim_head)
            got = decode_ops._paged_gather_attend(
                pool, jnp.asarray(layer), bt[:, :need], q, k, v, allowed,
                scale=scale, mesh=mesh)
            assert got.shape == want.shape and got.dtype == want.dtype
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                **tol)
