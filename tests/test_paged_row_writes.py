"""The two writes of the classic pool's row page (ISSUE 36), cut out of
tests/test_paged_attention.py (tests/paged_pool.py holds what they
share): a step's new rows through ``_store_entries_paged`` and the
admission's whole pages, the round trip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.serve import kv_pool as KV
from paged_pool import random_pool


class TestRowPageWrites:
    """ISSUE 36: the two writes of the classic pool's row page: a step's
    new rows through ``_store_entries_paged`` (``_store_rows_paged`` forms
    the classic rows and hands them on) and the admission's whole pages
    (``_store_prompt_pages``)."""

    PS, HEADS, DH = 8, 3, 16

    @pytest.mark.parametrize("wide", [1, 3], ids=["one_row", "three_rows"])
    @pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
    def test_store_then_read_round_trip(self, kind, wide):
        """Rows stored through the tables are the rows the dense writer
        stores into the oracle's view of the same pool, and the row-page
        read of them is the dense read; a row on a page boundary, a slot
        whose rows run past the sequence end, an INACTIVE slot whose table
        still maps another request's pages (its rows go to the trash
        page), nothing else touched."""
        L, ps, heads, dh = 24, self.PS, self.HEADS, self.DH
        need = KV.pages_for(L, ps)
        key = jax.random.PRNGKey(36 + wide)
        dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
        pool = random_pool(key, ps, 3 * need + 1, kind == "int8",
                            dim_head=dh, dtype=dtype, heads=heads)
        bt = jnp.asarray(np.arange(1, 3 * need + 1, dtype=np.int32)
                         .reshape(3, need))
        pos = jnp.asarray([ps - 1, L - 2, 5], jnp.int32)
        active = jnp.asarray([True, True, False])
        ks, vs = [jax.random.normal(jax.random.fold_in(key, 20 + i),
                                    (2, 3, heads, wide, dh), dtype)
                  for i in range(2)]
        total_len = L if wide > 1 else None
        after = decode_ops._store_rows_paged(pool, ks, vs, pos, bt, active,
                                             total_len)
        # the oracle: the dense writer over the dense view of the pool
        view = decode_ops.paged_view(pool, bt, L, heads)
        want = decode_ops._store_rows_wide(view, ks, vs, pos)
        got = decode_ops.paged_view(after, bt, L, heads)
        for name in got:
            np.testing.assert_array_equal(
                np.asarray(got[name][:, :2], np.float32),
                np.asarray(want[name][:, :2], np.float32))
            # the inactive slot's pages are as they were: its rows went
            # to the trash page, the only other page that may differ
            np.testing.assert_array_equal(
                np.asarray(got[name][:, 2], np.float32),
                np.asarray(view[name][:, 2], np.float32))
            changed = np.any(np.asarray(after[name], np.float32)
                             != np.asarray(pool[name], np.float32),
                             axis=(0, 2, 3))
            touched = {int(bt[0, 0]), int(bt[1, need - 1]), 0}
            if wide > 1:        # slot 0 crossed into its second page
                touched.add(int(bt[0, 1]))
            assert set(np.flatnonzero(changed)) <= touched
        # and the read of the stored rows is the dense read of them
        q, k, v = [jax.random.normal(jax.random.fold_in(key, 30 + i),
                                     (3, heads, 1, dh), dtype)
                   for i in range(3)]
        allowed = jnp.arange(L)[None, :] < (pos + wide)[:, None]
        for layer in range(2):
            dense = decode_ops._gather_read(
                q, k, v, want["k"][layer], want["v"][layer], allowed,
                scale=0.25,
                ksc=want["k_scale"][layer] if kind == "int8" else None,
                vsc=want["v_scale"][layer] if kind == "int8" else None)
            paged = decode_ops._paged_gather_attend(
                after, jnp.asarray(layer), bt, q, k, v, allowed, scale=0.25)
            tol = 2e-2 if kind == "bf16" else 2e-5
            np.testing.assert_allclose(
                np.asarray(paged[:2], np.float32),
                np.asarray(dense[:2], np.float32), rtol=tol, atol=tol)

    @pytest.mark.parametrize("bucket", [16, 12],
                             ids=["whole_pages", "partial_last_page"])
    @pytest.mark.parametrize("width,dtype", [
        (3 * 16, jnp.float32), (3 * 16, jnp.int8), (3, jnp.float32)],
        ids=["rows", "int8_rows", "scale_rows"])
    def test_admission_whole_pages_equal_the_row_scatter(self, bucket,
                                                         width, dtype):
        """The admission writes a group's prompt rows as whole pages by
        page id (``_store_prompt_pages``): every row lands where the row
        scatter it replaced placed it (row j of group-row g in page
        ``page_rows[g, j]`` at offset ``j % ps``), the unused group rows'
        pages and the pages past a prompt's grants go to the trash page,
        and no other page is touched. What is new: the tail of a
        partial last page is zeros (never read before it is rewritten)."""
        ps, G, layers, num_pages = self.PS, 4, 2, 9
        key = jax.random.PRNGKey(bucket + width)
        draw = (lambda k, shape: jax.random.randint(k, shape, -127, 128,
                                                    jnp.int8)) \
            if dtype == jnp.int8 else jax.random.normal
        buf = draw(jax.random.fold_in(key, 0), (layers, num_pages, ps, width))
        rows = draw(jax.random.fold_in(key, 1), (layers, G, bucket, width))
        # as the engine builds it: two admitted rows with their grants
        # (the second's run out before the bucket does), two dummy rows
        tables = np.zeros((G, KV.pages_for(bucket, ps)), np.int32)
        tables[0] = [3, 7]
        tables[1, 0] = 5
        page_rows = tables[:, np.arange(bucket) // ps]          # (G, bucket)
        got = np.asarray(decode_ops._store_prompt_pages(
            buf, rows, jnp.asarray(page_rows[:, ::ps].reshape(-1))))
        want = np.array(buf)
        for g in range(G):              # the row scatter, written out
            for j in range(bucket):
                want[:, page_rows[g, j], j % ps] = np.asarray(rows)[:, g, j]
        granted = [3, 7, 5]
        np.testing.assert_array_equal(got[:, 3], want[:, 3])
        np.testing.assert_array_equal(got[:, 5], want[:, 5])
        np.testing.assert_array_equal(got[:, 7, :bucket - ps],
                                      want[:, 7, :bucket - ps])
        assert not got[:, 7, bucket - ps:].any()     # the zero-filled tail
        untouched = [p for p in range(1, num_pages) if p not in granted]
        np.testing.assert_array_equal(got[:, untouched],
                                      np.asarray(buf)[:, untouched])
        # the trash page holds one of the pages that were sent there
        sent = np.asarray(jnp.pad(rows, ((0, 0), (0, 0), (
            0, -bucket % ps), (0, 0)))).reshape(layers, -1, ps, width)
        ids = page_rows[:, ::ps].reshape(-1)
        assert any(np.array_equal(got[:, 0], sent[:, i])
                   for i in np.flatnonzero(ids == 0))
