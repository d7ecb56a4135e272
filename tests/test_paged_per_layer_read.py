"""The classic pool's per-layer gather read (ISSUE 25, 31, 36; its 108
cases against the dense view's oracle: tests/test_paged_read_oracle.py),
cut out of tests/test_paged_attention.py, whose pools, requests and
reference it shares (tests/paged_pool.py, tests/tiny_model.py): the gather
path reads the pool per layer and page-major (``layer_pool_view`` + ``_paged_gather_attend``), held to
the ``paged_view`` + ``_gather_read`` oracle, to the dense loop's tokens,
and to a temporaries budget that an all-layer view cannot meet; the read
runs a slot group at a time, held to the one-group read, the rule to the
cells' shapes; a page is whole rows, the read the grouped-query one at
``kv_heads == heads``. A file of its own: a worker takes a file, and no
file is to be a sixth of the run (ROADMAP.md Queue 3 item 1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.models import vae as V
from dalle_pytorch_tpu.ops import attention as attn_ops
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve import RequestQueue
from dalle_pytorch_tpu.serve.engine import Engine
from paged_pool import SPARSE_CFG, random_pool
from tiny_model import bundle, CFG, reference_tokens, REQS, VCFG  # noqa: F401


class TestPerLayerRead:
    """ISSUE 25: the gather path attends ONE layer's pages inside the
    layer scan, page-major, straight from the pool. The oracle is the
    all-layer dense view it replaced: ``paged_view`` + ``_gather_read``
    — same rows, same masks, same scales."""

    PS = 8
    HEADS, DEPTH = CFG.transformer.heads, CFG.transformer.depth

    @staticmethod
    def _laid_out(page, dtype):
        """A page's bytes as the TPU lays it out, written out here on its
        own: the minor dimension in whole 128-lane tiles, the rows in
        whole tiles of 8 four-byte words."""
        size = jnp.dtype(dtype).itemsize
        tile_rows = 8 * max(4 // size, 1)
        return (int(np.prod(page[:-2])) * -(-page[-2] // tile_rows)
                * tile_rows * -(-page[-1] // 128) * 128 * size)

    def _force_groups(self, monkeypatch, pool, slots, columns, groups):
        """Set the VMEM budget (the constant, not a knob of the program)
        so that the rule gives ``groups`` for this pool and table, with
        the ordering's halving of a group (ISSUE 38) out of the way."""
        monkeypatch.setattr(decode_ops, "_halving_pays",
                            lambda per, slots, slot_bytes: False)
        monkeypatch.setattr(decode_ops, "_VIEW_VMEM_BYTES", 1)
        # with a budget of 1 byte nothing fits: the rule's floor, one slot
        assert decode_ops.pool_view_groups(pool, slots, columns) == slots
        buf = pool["k"]
        monkeypatch.setattr(
            decode_ops, "_VIEW_VMEM_BYTES", slots // groups * columns
            * self._laid_out(buf.shape[2:], buf.dtype))
        assert decode_ops.pool_view_groups(pool, slots, columns) == groups

    @pytest.mark.parametrize("groups", [1, 2, 3])
    @pytest.mark.parametrize("dim_head", [64, 128])
    @pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
    def test_grouped_read_matches_one_group_and_oracle(
            self, monkeypatch, kind, dim_head, groups):
        """ISSUE 31: the slots are attended in the groups the rule gives
        (``_read_in_slot_groups``). Six slots, one sharing a page with
        another (copy-on-write), one mid-sequence with trash entries, one
        parked dead: the grouped read equals the one-group read bit for
        bit (a slot's result does not depend on its group) and the
        ``paged_view`` + ``_gather_read`` oracle within rounding."""
        total_len = 20                               # partial last page
        key = jax.random.PRNGKey(31 + dim_head)
        need = KV.pages_for(total_len, self.PS)
        dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
        pool = random_pool(key, self.PS, 6 * need + 1, kind == "int8",
                            dim_head=dim_head, dtype=dtype)
        bt = np.arange(1, 6 * need + 1, dtype=np.int32).reshape(6, need)
        pos = np.array([total_len - 1, 9, 5, 0, 13, total_len - 1])
        bt[4, 0] = bt[0, 0]                          # a shared page
        bt[1, KV.pages_for(pos[1] + 1, self.PS):] = 0    # trash entries
        bt[3] = 0                                    # a parked dead slot
        bt = jnp.asarray(bt)
        q, k, v = [jax.random.normal(jax.random.fold_in(key, 10 + i),
                                     (6, self.HEADS, 1, dim_head), dtype)
                   for i in range(3)]
        allowed = (jnp.arange(total_len)[None, :]
                   < jnp.asarray(pos)[:, None]).at[0, 1].set(False)
        scale = dim_head ** -0.5
        layer = jnp.asarray(1)

        def attend():
            return decode_ops._paged_gather_attend(
                pool, layer, bt, q, k, v, allowed, scale=scale)

        # six slots: one group by their bytes, two of three where the
        # ordering halves a group over four, VMEM's where it is out of
        # the way
        assert decode_ops.pool_view_groups(pool, 6, need) == 1
        monkeypatch.setattr(decode_ops, "_halving_pays",
                            lambda per, slots, slot_bytes: per > 4)
        assert decode_ops.pool_view_groups(pool, 6, need) == 2
        self._force_groups(monkeypatch, pool, 6, need, 1)
        whole = attend()
        self._force_groups(monkeypatch, pool, 6, need, groups)
        got = attend()
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(whole, np.float32))
        view = decode_ops.paged_view(pool, bt, total_len, self.HEADS)
        want = decode_ops._gather_read(
            q, k, v, view["k"][1], view["v"][1], allowed, scale=scale,
            ksc=view["k_scale"][1] if kind == "int8" else None,
            vsc=view["v_scale"][1] if kind == "int8" else None)
        assert got.shape == want.shape and got.dtype == want.dtype
        tol = dict(rtol=2e-2, atol=2e-2) if kind == "bf16" else \
            dict(rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol)

    @pytest.mark.parametrize("slots,columns,page,dtype,vmem,want", [
        (16, 72, (16, 16 * 128), jnp.bfloat16, 1, 4),  # rudalle-xl.serve-full
        (12, 80, (16, 62 * 64), jnp.bfloat16, 2, 3),   # dalle-12b.serve-full
        (32, 272, (16, 640), jnp.bfloat16, 2, 4),      # the latent pool
        (12, 80, (16, 62 * 64), jnp.int8, 2, 3),       # 12b's int8 pool
        (12, 80, (16, 62), jnp.float32, 1, 1),         # and its scale pages
        (7, 160, (16, 62 * 64), jnp.bfloat16, 7, 7),   # no divisor fits
        (16, 592, (16, 1024), jnp.bfloat16, 4, 8),     # trinity's full layer
        (32, 272, (16, 1280), jnp.bfloat16, 4, 8),     # phi's full layer
        (32, 33, (16, 1280), jnp.bfloat16, 1, 2),      # and its rings
    ], ids=["rudalle-xl", "dalle-12b", "latent", "dalle-12b-int8",
            "dalle-12b-int8-scales", "prime_slots", "trinity-full",
            "phi-full", "phi-ring"])
    def test_group_rule_on_the_cells_shapes(self, slots, columns, page,
                                            dtype, vmem, want):
        """The rule sees slots, table columns, the page's shape (rows,
        width) and the pool's dtype, and counts bytes as laid out (the
        width in whole 128-lane tiles, 16 int8 rows a 32-row tile). VMEM
        alone (``vmem``: a table read whole in slot order, a ring or a
        sparse layer's visible columns, ``ordered=False``):
        ruDALL-E's 75.5 MB a buffer is one group; 12b's row of 62 x 64 =
        3968 numbers is 31 whole tiles, so its 122 MB are two groups of
        61 MB where the page per head, half padding, made three of 81 MB
        (ISSUE 36; its int8 pool's the same two); the latent pool's 178
        MB two; a slot count with no divisor that fits falls to one slot a
        group and does not raise. With the ordering (ISSUE 38: a group
        reads the width of its furthest slot, so it is halved while that
        saves more bytes than a further group costs,
        ``_VIEW_GROUP_BYTES``) the count rises to ``want``: groups of four
        slots in ruDALL-E, 12b and phi, of eight over the latent pool's
        narrow rows and 32 slots, of two over trinity's long table (whose
        full layers, runs of one, read whole all the same); it never
        falls under what VMEM asks."""
        groups = decode_ops.view_slot_groups(slots, columns, page, dtype)
        assert groups == want
        assert slots % groups == 0
        slot_bytes = columns * self._laid_out(page, dtype)

        def saved_by_halving(per):      # (per / 2) ** 2 / slots of a table
            return per * per * slot_bytes / (4 * slots)
        if groups < slots:
            assert slots // groups * slot_bytes <= decode_ops._VIEW_VMEM_BYTES
            assert saved_by_halving(slots // groups) \
                <= decode_ops._VIEW_GROUP_BYTES
        if groups > 1:       # and one group fewer would have broken a bound
            fewer = max(g for g in range(1, groups) if slots % g == 0)
            assert slots // fewer * slot_bytes > decode_ops._VIEW_VMEM_BYTES \
                or saved_by_halving(slots // fewer) \
                > decode_ops._VIEW_GROUP_BYTES
        assert decode_ops.view_slot_groups(
            slots, columns, page, dtype, ordered=False) == vmem <= want

    # ---- ISSUEs 34, 36: whole rows against all heads' queries ----

    @staticmethod
    def _tile_case(kind, total_len, slots=4, heads=4, dim_head=128):
        """A pool whose page is a whole tile of rows (16 bf16 rows, 32
        int8 rows), random everywhere (trash and unmapped pages too),
        ``slots`` slots at ragged positions: one sharing a page, one with
        trash entries, one on its last row."""
        ps = 32 if kind == "int8" else 16
        need = KV.pages_for(total_len, ps)
        dtype = jnp.float32 if kind == "int8" else jnp.bfloat16
        key = jax.random.PRNGKey(34 + total_len)
        pool = random_pool(key, ps, slots * need + 1, kind == "int8",
                            dim_head=dim_head, dtype=dtype, heads=heads)
        bt = np.arange(1, slots * need + 1, dtype=np.int32).reshape(
            slots, need)
        pos = np.array([total_len - 1, total_len // 2, 5, total_len - 3])
        bt[3, 0] = bt[0, 0]                          # a shared page
        bt[1, KV.pages_for(pos[1] + 1, ps):] = 0     # trash entries
        q, k, v = [jax.random.normal(jax.random.fold_in(key, 10 + i),
                                     (slots, heads, 1, dim_head), dtype)
                   for i in range(3)]
        allowed = (jnp.arange(total_len)[None, :]
                   < jnp.asarray(pos)[:, None]).at[0, 1].set(False)
        return pool, jnp.asarray(bt), (q, k, v), allowed, ps

    @staticmethod
    def _spy_read_form(monkeypatch):
        """-> the list that collects (slots, per_head) of every call of
        the one read (``ops.attention.gqa_attend_rows``)."""
        calls = []
        real = attn_ops.gqa_attend_rows

        def spy(q, *a, **kw):
            calls.append((q.shape[0], kw["per_head"]))
            return real(q, *a, **kw)
        monkeypatch.setattr(attn_ops, "gqa_attend_rows", spy)
        return calls

    @pytest.mark.parametrize("groups", [1, 2], ids=["one_group",
                                                    "two_groups"])
    @pytest.mark.parametrize("total_len", [96, 83],
                             ids=["whole_pages", "partial_last_page"])
    @pytest.mark.parametrize("table", ["full", "visible_slice"])
    @pytest.mark.parametrize("kind", ["bf16", "int8"])
    def test_whole_row_read_matches_view_oracle_and_per_head(
            self, monkeypatch, kind, table, total_len, groups):
        """All heads' queries against a slot's pages as whole rows equals
        the ``paged_view`` + ``_gather_read`` oracle under the same masks
        and the per-head form that a mesh gets: over the bf16 pool and the
        int8 pool with its scale pages, the full table and a sparse
        layer's visible slice of it, whole pages and a partial last page,
        one slot group and two (``v_after_k``)."""
        pool, bt, (q, k, v), allowed, ps = self._tile_case(kind, total_len)
        slots, need = bt.shape
        scale = 128 ** -0.5
        layer = jnp.asarray(1)
        view = decode_ops.paged_view(pool, bt, total_len, 4)
        if table == "visible_slice":
            # a sparse layer reads a narrower table: each slot's visible
            # logical pages, and the row mask remapped onto its columns
            visible = jnp.asarray(
                [[0, need - 1], [0, 1], [0, 0], [1, need - 1]], jnp.int32)
            live = jnp.asarray([2, 2, 1, 2])
            cols = (visible[:, :, None] * ps
                    + jnp.arange(ps)[None, None, :]).reshape(slots, -1)
            pad_ok = jnp.repeat(jnp.arange(2)[None, :] < live[:, None], ps,
                                axis=1)
            read_allowed = (jnp.take_along_axis(
                allowed, jnp.minimum(cols, total_len - 1), axis=1)
                & pad_ok & (cols < total_len))
            read_bt = KV.visible_table_view(bt, visible)
            seen = jnp.zeros((slots, need * ps), bool).at[
                jnp.arange(slots)[:, None], cols].max(pad_ok)
            oracle_allowed = allowed & seen[:, :total_len]
        else:
            read_bt, read_allowed, oracle_allowed = bt, allowed, allowed
        want = decode_ops._gather_read(
            q, k, v, view["k"][1], view["v"][1], oracle_allowed, scale=scale,
            ksc=view["k_scale"][1] if kind == "int8" else None,
            vsc=view["v_scale"][1] if kind == "int8" else None)

        def attend(mesh):
            return decode_ops._paged_gather_attend(
                pool, layer, read_bt, q, k, v, read_allowed, scale=scale,
                mesh=mesh)

        if groups > 1:
            self._force_groups(monkeypatch, pool, slots, read_bt.shape[1],
                               groups)
        calls = self._spy_read_form(monkeypatch)
        got = attend(False)
        assert calls == [(slots // groups, False)] * groups  # whole rows
        per_head = attend(True)
        assert calls[groups:] == [(slots // groups, True)] * groups
        assert got.shape == want.shape and got.dtype == want.dtype
        tol = dict(rtol=2e-2, atol=2e-2) if kind == "bf16" else \
            dict(rtol=2e-5, atol=2e-5)
        for other in (want, per_head):
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(other, np.float32), **tol)

    @pytest.mark.parametrize("mesh", [False, True],
                             ids=["one_device", "mesh_seam"])
    def test_step_hands_the_read_the_mesh_seam(self, monkeypatch, mesh):
        """The step decides the form from what it is handed and from
        nothing else: with ``out_sync`` given (the mesh engine's seam)
        every layer's read is per head, without it whole rows."""
        cfg = self.WIDE_CFG
        tcfg = cfg.transformer
        params = D.dalle_init(jax.random.PRNGKey(0), cfg,
                              V.vae_init(jax.random.PRNGKey(1), VCFG))
        L, ps = cfg.seq_len, 8
        mp = KV.pages_for(L, ps)
        pool = random_pool(jax.random.PRNGKey(3), ps, 2 * mp + 1, False,
                            dim_head=128)
        bt = jnp.asarray(np.arange(1, 2 * mp + 1, dtype=np.int32)
                         .reshape(2, mp))
        calls = self._spy_read_form(monkeypatch)
        decode_ops._decode_step_math(
            params["transformer"], jnp.zeros((2, tcfg.dim)),
            jnp.asarray([9, 3], jnp.int32), pool, cfg=tcfg,
            key_mask=jnp.ones((2, L), bool), block_tables=bt,
            out_sync=(lambda out: out) if mesh else None)
        assert calls and all(form == (2, mesh) for form in calls)

    def _loop_args(self, bundle, page_size, quantized, cfg=CFG):
        """A mid-sequence chunk: 3 slots at ragged positions (one parked
        dead), random page content everywhere, greedy sampling through
        the model's own embedding and logits head."""
        params, _ = bundle
        tcfg = cfg.transformer
        L = cfg.seq_len
        mp = KV.pages_for(L, page_size)
        pool = random_pool(jax.random.PRNGKey(21), page_size,
                            3 * mp + 1, quantized, dim_head=tcfg.dim_head)
        bt = jnp.asarray(np.arange(1, 3 * mp + 1, dtype=np.int32)
                         .reshape(3, mp))
        pos = jnp.asarray([9, 14, 0], jnp.int32)
        active = jnp.asarray([True, True, False])
        cur = jnp.asarray([3, 7, 0], jnp.int32)

        def embed_fn(tok, p):
            return D.decode_token_embed(params, cfg, tok, p)

        def sample_fn(h, pred_pos):
            return jnp.argmax(D.to_logits(params, h), -1).astype(jnp.int32)

        kw = dict(cfg=tcfg, key_mask=jnp.ones((3, L), bool), steps=6,
                  embed_fn=embed_fn, sample_fn=sample_fn)
        return params["transformer"], cur, pos, active, pool, bt, L, kw

    @pytest.mark.parametrize("page_size", [8, 16],
                             ids=["whole_pages", "partial_last_page"])
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["f32", "int8"])
    def test_loop_tokens_identical_to_dense_loop(self, bundle, page_size,
                                                 quantized):
        """``decode_loop_paged`` (gather) emits the dense loop's tokens
        under greedy, from the same rows: the dense cache is the
        oracle's view of the same pool."""
        tp, cur, pos, active, pool, bt, L, kw = self._loop_args(
            bundle, page_size, quantized)
        heads = CFG.transformer.heads
        dense = decode_ops.decode_loop(
            tp, cur, pos, active, decode_ops.paged_view(pool, bt, L, heads),
            **kw)
        paged = decode_ops.decode_loop_paged(
            tp, cur, pos, active, pool, bt, total_len=L, **kw)
        np.testing.assert_array_equal(np.asarray(paged[4]),
                                      np.asarray(dense[4]))
        assert (np.asarray(paged[4])[:2] >= 0).all()   # real tokens
        for i in range(3):                             # tok, pos, active
            np.testing.assert_array_equal(np.asarray(paged[i]),
                                          np.asarray(dense[i]))
        # and the rows the chunk stored are the rows the dense loop stored
        after = decode_ops.paged_view(paged[3], bt, L, heads)
        for name in after:
            np.testing.assert_allclose(
                np.asarray(after[name][:, :2], np.float32),
                np.asarray(dense[3][name][:, :2], np.float32),
                rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["f32", "int8"])
    @pytest.mark.parametrize("sparse_reads", [False, True],
                             ids=["dense_reads", "sparse_reads"])
    def test_grouped_loop_tokens_identical_to_dense_loop(
            self, monkeypatch, bundle, sparse_reads, quantized):
        """ISSUE 31: with the rule forced to one slot a group (the
        constant patched here, no knob in the program) the fused loop
        still emits the dense loop's tokens, for the plain gather step
        and for ``sparse_reads=True`` (whose sparse layers read a
        narrower table through the same loop)."""
        cfg = SPARSE_CFG if sparse_reads else CFG
        if sparse_reads:
            params = D.dalle_init(jax.random.PRNGKey(0), cfg, bundle[1])
            bundle = (params, bundle[1])
        tp, cur, pos, active, pool, bt, L, kw = self._loop_args(
            bundle, 8, quantized, cfg)
        dense = decode_ops.decode_loop(
            tp, cur, pos, active,
            decode_ops.paged_view(pool, bt, L, cfg.heads), **kw)
        monkeypatch.setattr(decode_ops, "_VIEW_VMEM_BYTES", 1)
        assert decode_ops.pool_view_groups(pool, *bt.shape) == 3
        paged = decode_ops.decode_loop_paged(
            tp, cur, pos, active, pool, bt, total_len=L,
            sparse_reads=sparse_reads, **kw)
        np.testing.assert_array_equal(np.asarray(paged[4]),
                                      np.asarray(dense[4]))
        assert (np.asarray(paged[4])[:2] >= 0).all()   # real tokens
        for i in range(3):                             # tok, pos, active
            np.testing.assert_array_equal(np.asarray(paged[i]),
                                          np.asarray(dense[i]))

    WIDE_CFG = D.DALLEConfig(dim=16, depth=2, vae=VCFG, num_text_tokens=50,
                             text_seq_len=8, heads=2, dim_head=128)
    WIDE_SPARSE_CFG = D.DALLEConfig(
        dim=16, depth=2, vae=VCFG, num_text_tokens=50, text_seq_len=8,
        heads=2, dim_head=128, sparse_attn=(True, False), sparse_block=4)

    @pytest.mark.parametrize("groups", [1, 3], ids=["one_group",
                                                    "a_slot_a_group"])
    @pytest.mark.parametrize("quantized,page_size", [(False, 8), (True, 32)],
                             ids=["f32", "int8"])
    @pytest.mark.parametrize("sparse_reads", [False, True],
                             ids=["dense_reads", "sparse_reads"])
    def test_whole_row_loop_tokens_equal_per_head_loop(
            self, monkeypatch, sparse_reads, quantized, page_size, groups):
        """ISSUEs 34, 36: on float32 weights the fused loop emits the same
        greedy tokens whether its reads contract whole rows or, handed the
        mesh seam (an ``out_sync`` that does nothing here), a head's own
        columns; both emit the dense loop's."""
        cfg = self.WIDE_SPARSE_CFG if sparse_reads else self.WIDE_CFG
        vae_params = V.vae_init(jax.random.PRNGKey(1), VCFG)
        params = D.dalle_init(jax.random.PRNGKey(0), cfg, vae_params)
        tp, cur, pos, active, pool, bt, L, kw = self._loop_args(
            (params, vae_params), page_size, quantized, cfg)
        assert pool["k"].shape[2:] == (page_size, 2 * 128)
        if groups > 1:
            monkeypatch.setattr(decode_ops, "_VIEW_VMEM_BYTES", 1)
        assert decode_ops.pool_view_groups(pool, *bt.shape) == groups
        calls = self._spy_read_form(monkeypatch)

        def loop(**seam):
            return decode_ops.decode_loop_paged(
                tp, cur, pos, active, pool, bt, total_len=L,
                sparse_reads=sparse_reads, **kw, **seam)

        whole = loop()
        assert calls and not any(per_head for _, per_head in calls)
        del calls[:]
        per_head = loop(out_sync=lambda out: out)
        assert calls and all(per_head for _, per_head in calls)
        dense = decode_ops.decode_loop(
            tp, cur, pos, active,
            decode_ops.paged_view(pool, bt, L, cfg.heads), **kw)
        assert (np.asarray(whole[4])[:2] >= 0).all()   # real tokens
        for other in (per_head, dense):
            for i in (0, 1, 2, 4):                # tok, pos, active, ring
                np.testing.assert_array_equal(np.asarray(whole[i]),
                                              np.asarray(other[i]))

    @pytest.mark.parametrize("budget,want", [(None, 1), (1, 2)],
                             ids=["the_rule", "one_slot_a_group"])
    def test_engine_reports_the_groups_it_traced(self, monkeypatch,
                                                 bundle, budget, want):
        """``stats()["kv_view_groups"]``: the group count the decode
        program was traced with; the served tokens do not depend on it."""
        params, vae_params = bundle
        if budget is not None:
            monkeypatch.setattr(decode_ops, "_VIEW_VMEM_BYTES", budget)
        queue = RequestQueue(max_depth=4)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4,
                        kv="paged", page_size=8)
        assert engine.stats()["kv_view_groups"] == 1   # nothing traced yet
        h = queue.submit(REQS[0])
        engine.run_until_idle()
        np.testing.assert_array_equal(
            np.asarray(h.result(5).tokens),
            reference_tokens(params, vae_params, REQS[0]))
        assert engine.stats()["kv_view_groups"] == want
        assert engine.decode_traces == 1

    def test_decode_program_holds_no_second_pool(self, bundle):
        """The mechanism, not the speed: the compiled gather loop's
        temporaries stay under half the pool's bytes. With the all-layer
        dense view (``paged_view`` before the layer scan) they were over
        one whole pool, so the view cannot come back unnoticed."""
        tp, cur, pos, active, _, bt, L, kw = self._loop_args(
            bundle, 8, False)
        tcfg = CFG.transformer
        num_pages = 40 * KV.pages_for(L, 8) + 1    # pool >> everything else
        pool = {n: jnp.zeros((tcfg.depth, num_pages, 8,
                              tcfg.heads * tcfg.dim_head))
                for n in ("k", "v")}
        pool_bytes = sum(a.size * a.dtype.itemsize for a in pool.values())

        def loop(pool, bt, cur, pos, active):
            return decode_ops.decode_loop_paged(
                tp, cur, pos, active, pool, bt, total_len=L, **kw)

        compiled = jax.jit(loop, donate_argnums=0).lower(
            pool, bt, cur, pos, active).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < pool_bytes / 2, (temp, pool_bytes)
