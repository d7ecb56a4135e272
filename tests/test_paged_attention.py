"""Ragged paged-attention kernel tests (ISSUE 9 acceptance criteria).

The load-bearing contract is the oracle relation: the Pallas kernel
(``ops/paged_attention.py``, ``attn_impl='kernel'``) must agree with
``_decode_step_math`` over ``paged_view``'s dense gather — allclose on
the step outputs under the same masking (rows >= pos dead, trash-page
rows never attended), and BYTE-IDENTICAL emitted tokens end-to-end
against ``generate_images`` through the serve engine, for K ∈ {1, 8},
fp32 and int8-KV, page_size ∈ {8, 16}, ragged per-slot positions
(including pos=0 parked dead slots and a slot on its last row), under
``guards.no_transfers`` with the decode program compiled exactly once.
Plus the typed page-size gate (``kv_pool.PageSizeError`` at pool init,
naming the kernel tile constraint) and the ``paged_view`` trim: the
gather never drags K/V or scale pages for wholly-unmapped logical pages
beyond ``total_len``. Since ISSUE 25 the gather path itself reads the
pool per layer and page-major (``layer_pool_view`` +
``_paged_gather_attend``): ``TestPerLayerRead`` holds it to the
``paged_view`` + ``_gather_read`` oracle, to the dense loop's tokens,
and to a temporaries budget that an all-layer view cannot meet; since
ISSUE 31 the read runs a slot group at a time, and the same class holds
the grouped read to the one-group read, the rule to the cells' shapes.
Since ISSUE 36 a page of the classic pool is whole rows ``(page_size,
heads * dim_head)``: the read is the grouped-query one at ``kv_heads ==
heads`` (held to the oracle and to its per-head form under the mesh seam
at several head shapes), the store ``_store_entries_paged``
(``TestRowPageWrites``: the round trip, and the admission's whole-page
write).

All CPU (the kernel runs under the Pallas interpreter — the same code
path CI's serve-perf kernel leg smokes), tiny model, inside tier-1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.analysis import guards
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.ops import paged_attention as PA
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve import RequestQueue
from dalle_pytorch_tpu.serve.engine import Engine
from paged_pool import random_pool
from tiny_model import bundle, CFG, reference_tokens, REQS, VCFG  # noqa: F401


class TestKernelVsGatherOracle:
    """Direct math parity: the kernel against ``_decode_step_math`` over
    the gathered view — the oracle relation ISSUE 9 names."""

    @pytest.mark.parametrize("page_size", [8, 16])
    @pytest.mark.parametrize("quantized", [False, True])
    def test_step_math_matches_gather_view(self, bundle, page_size,
                                           quantized):
        """Ragged per-slot positions — a slot on its LAST row
        (pos = seq_len - 1), one mid-sequence, one parked dead at
        pos 0 whose unmapped table rows all point at the trash page —
        with random content in every physical page (a read through an
        unmapped entry would show up, not read zeros)."""
        params, _ = bundle
        tcfg = CFG.transformer
        L = CFG.seq_len
        mp = KV.pages_for(L, page_size)
        pool = random_pool(jax.random.PRNGKey(7), page_size,
                            2 * mp + 1, quantized)
        bt = np.zeros((3, mp), np.int32)
        bt[0] = np.arange(1, mp + 1)             # slot at the last row
        bt[1] = np.arange(mp + 1, 2 * mp + 1)    # ragged mid-sequence
        #                                          (trailing cols trash)
        bt[1, KV.pages_for(6, page_size):] = 0
        bt = jnp.asarray(bt)                     # slot 2: all trash
        pos = jnp.asarray([L - 1, 5, 0], jnp.int32)
        # one slot carries a padded-off prompt row: the kernel must
        # honor the pad mask exactly like the gather's key_mask
        key_mask = jnp.ones((3, L), bool).at[1, 1].set(False)
        x_tok = jax.random.normal(jax.random.PRNGKey(9), (3, CFG.dim))

        view = decode_ops.paged_view(pool, bt, L, tcfg.heads)
        h_g, ks_g, vs_g = decode_ops._decode_step_math(
            params["transformer"], x_tok, pos, view, cfg=tcfg,
            key_mask=key_mask)
        h_k, ks_k, vs_k = decode_ops._decode_step_math(
            params["transformer"], x_tok, pos, pool, cfg=tcfg,
            key_mask=key_mask, attn_impl="kernel", block_tables=bt)
        np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_g),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(ks_k), np.asarray(ks_g),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(vs_k), np.asarray(vs_g),
                                   rtol=2e-5, atol=2e-6)

    def test_kernel_requires_per_slot_pos_and_tables(self, bundle):
        params, _ = bundle
        pool = random_pool(jax.random.PRNGKey(0), 8, 7, False)
        key_mask = jnp.ones((2, CFG.seq_len), bool)
        x_tok = jnp.zeros((2, CFG.dim))
        with pytest.raises(ValueError, match="per-slot"):
            decode_ops._decode_step_math(
                params["transformer"], x_tok, 3, pool,
                cfg=CFG.transformer, key_mask=key_mask,
                attn_impl="kernel",
                block_tables=jnp.zeros((2, 3), jnp.int32))
        with pytest.raises(ValueError, match="block_tables"):
            decode_ops._decode_step_math(
                params["transformer"], x_tok,
                jnp.zeros((2,), jnp.int32), pool,
                cfg=CFG.transformer, key_mask=key_mask,
                attn_impl="kernel")


class TestKernelEngineTokens:
    """End-to-end through the serve engine: ``paged_attn='kernel'`` must
    emit byte-identical tokens to ``generate_images`` inside the same
    one-compile fused-K emit-ring regime as the gather path."""

    @pytest.mark.parametrize("k", [1, 8])
    def test_tokens_identical_across_chunk_sizes(self, bundle, k):
        """3 requests over 2 slots (slot reuse; mixed prompt lengths /
        temperature / top-k / top-p; slots die mid-chunk into the dead
        mask at K=8) — byte-identical streams, ONE decode trace, every
        page back in the pool."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r) for r in REQS]
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=k,
                        kv="paged", page_size=8, paged_attn="kernel")
        handles = [queue.submit(r) for r in REQS]
        with guards.compile_count(lambda: engine.decode_traces, expect=1,
                                  label="paged-attention kernel decode"):
            engine.run_until_idle()
        for h, ref in zip(handles, refs):
            res = h.result(timeout=5)
            assert res.status == "ok"
            np.testing.assert_array_equal(np.asarray(res.tokens), ref)
        assert engine.alloc.in_use == 0
        assert engine.stats()["paged_attn"] == "kernel"

    def test_tokens_identical_at_page_size_16(self, bundle):
        """page_size 16 leaves the last logical page PARTIAL (seq 24 =
        one full page + 8 rows) — the kernel's whole-page mask padding
        must keep the tail rows dead."""
        params, vae_params = bundle
        ref = reference_tokens(params, vae_params, REQS[0])
        queue = RequestQueue(max_depth=4)
        engine = Engine(params, CFG, queue, num_slots=2, kv="paged",
                        page_size=16, paged_attn="kernel")
        h = queue.submit(REQS[0])
        engine.run_until_idle()
        np.testing.assert_array_equal(np.asarray(h.result(5).tokens),
                                      ref)

    def test_int8_kv_tokens_identical(self, bundle):
        """int8-KV composes: per-page dequantization inside the kernel
        (scales outside the contractions) matches
        generate_images(quantize_cache=True) token-for-token."""
        params, vae_params = bundle
        req = REQS[0]
        ref = reference_tokens(params, vae_params, req,
                               quantize_cache=True)
        queue = RequestQueue(max_depth=4)
        engine = Engine(params, CFG, queue, num_slots=2, kv="paged",
                        page_size=8, paged_attn="kernel",
                        quantize_cache=True)
        h = queue.submit(req)
        engine.run_until_idle()
        np.testing.assert_array_equal(np.asarray(h.result(5).tokens),
                                      ref)

    def test_steady_state_transfer_clean_midstream_join(self, bundle):
        """The transfer-discipline contract survives the kernel path:
        full chunks, double-buffered harvest, AND a mid-stream join
        (paged prefill + block-table growth) under
        ``guards.no_transfers()`` — the interpreted Pallas call is
        traced device code, not a host round-trip."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r)
                for r in REQS[:2]]
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4,
                        kv="paged", page_size=8, paged_attn="kernel")
        for r in REQS[:2]:              # warm: compile decode + buckets
            queue.submit(r)
        engine.run_until_idle()
        h_a = queue.submit(REQS[0])
        engine.step_once()              # a admitted, chunk 1 in flight
        with guards.no_transfers():
            h_b = queue.submit(REQS[1])
            engine.step_once()          # join + chunk 2 + harvest 1
            engine.step_once()          # pure steady-state chunk
        engine.run_until_idle()
        np.testing.assert_array_equal(
            np.asarray(h_a.result(timeout=5).tokens), refs[0])
        np.testing.assert_array_equal(
            np.asarray(h_b.result(timeout=5).tokens), refs[1])
        assert engine.decode_traces == 1


class TestPageSizeValidation:
    """The typed pool-init gate: a page size the kernel cannot tile is
    rejected with the constraint NAMED, not an opaque Mosaic failure
    inside pallas_call."""

    def test_kernel_engine_rejects_untileable_page_size(self, bundle):
        params, _ = bundle
        for bad in (4, 12):
            with pytest.raises(KV.PageSizeError,
                               match="paged_attention"):
                Engine(params, CFG, RequestQueue(max_depth=2),
                       num_slots=1, kv="paged", page_size=bad,
                       paged_attn="kernel")

    def test_gather_engine_keeps_arbitrary_page_sizes(self, bundle):
        """The gather path has no tile floor — page_size 4 (the
        pre-kernel test suite's size) must keep constructing."""
        params, _ = bundle
        Engine(params, CFG, RequestQueue(max_depth=2), num_slots=1,
               kv="paged", page_size=4)       # no raise

    def test_kernel_requires_paged_kv(self, bundle):
        params, _ = bundle
        with pytest.raises(ValueError, match="kv='paged'"):
            Engine(params, CFG, RequestQueue(max_depth=2), num_slots=1,
                   kv="dense", paged_attn="kernel")

    def test_validate_page_size_typed_record(self):
        KV.validate_page_size(8)
        KV.validate_page_size(16)
        with pytest.raises(KV.PageSizeError) as ei:
            KV.validate_page_size(4)
        rec = ei.value.record
        assert rec["kind"] == "serve_page_size_invalid"
        assert rec["page_size"] == 4
        assert rec["min_page_size"] == KV.KERNEL_MIN_PAGE_SIZE

    def test_kernel_entry_validates_directly(self):
        """A direct caller (no Engine in front) hits the same typed
        error at the kernel entry."""
        pool = random_pool(jax.random.PRNGKey(0), 4, 7, False)
        with pytest.raises(KV.PageSizeError):
            PA.paged_decode_attention(
                jnp.zeros((1, CFG.heads, CFG.dim_head)),
                pool["k"][0], pool["v"][0],
                jnp.zeros((1, 6), jnp.int32),
                jnp.zeros((1,), jnp.int32),
                jnp.ones((1, 24), bool), scale=1.0)


class TestPagedViewTrim:
    """The scale-gather trim (ISSUE 9 fix): ``paged_view`` must trim the
    block tables to ``ceil(total_len / page_size)`` columns BEFORE the
    gather, so K/V — and the int8 pool's k_scale/v_scale — never move
    pages that are wholly unmapped beyond ``total_len``."""

    def _pool_and_tables(self):
        L = CFG.seq_len                          # 24 -> 3 pages of 8
        pool = random_pool(jax.random.PRNGKey(3), 8, 9, True)
        need = KV.pages_for(L, 8)
        bt = jnp.asarray(np.arange(1, 2 * need + 1, dtype=np.int32)
                         .reshape(2, need))
        # a WIDER table (the pool-max shape a caller actually holds):
        # tail columns point at other live pages — if they leaked into
        # the gather's output window the values would differ
        bt_wide = jnp.concatenate(
            [bt, jnp.full((2, 4), 8, jnp.int32)], axis=1)
        return L, pool, bt, bt_wide

    def test_shapes_and_values_independent_of_tail_columns(self):
        L, pool, bt, bt_wide = self._pool_and_tables()
        tcfg = CFG.transformer
        view = decode_ops.paged_view(pool, bt, L, tcfg.heads)
        wide = decode_ops.paged_view(pool, bt_wide, L, tcfg.heads)
        for k in ("k", "v"):
            assert wide[k].shape == (tcfg.depth, 2, tcfg.heads, L,
                                     tcfg.dim_head)
        for k in ("k_scale", "v_scale"):
            # the shape contract the fix pins: scales slice to the SAME
            # total_len window as the rows
            assert wide[k].shape == (tcfg.depth, 2, tcfg.heads, L)
        for k in view:
            np.testing.assert_array_equal(np.asarray(wide[k]),
                                          np.asarray(view[k]))

    def test_gather_consumes_only_trimmed_tables(self):
        """Shape regression at the jaxpr level: the ONLY consumer of
        the over-wide table is the trim slice — every downstream eqn
        (the K/V takes AND the scale takes) sees the
        ``pages_for(total_len)``-column table, so unmapped tail pages
        are never gathered at all."""
        L, pool, _, bt_wide = self._pool_and_tables()
        need = KV.pages_for(L, 8)
        wide_shape = tuple(bt_wide.shape)
        jaxpr = jax.make_jaxpr(
            lambda bt: decode_ops.paged_view(pool, bt, L, CFG.heads))(bt_wide)
        consumers = [eqn for eqn in jaxpr.jaxpr.eqns
                     if any(getattr(v, "aval", None) is not None
                            and v.aval.shape == wide_shape
                            and v.aval.dtype == jnp.int32
                            for v in eqn.invars)]
        assert consumers, "expected the trim slice to consume the table"
        assert all(e.primitive.name == "slice" for e in consumers), \
            [e.primitive.name for e in consumers]
        assert all(tuple(e.outvars[0].aval.shape) == (2, need)
                   for e in consumers)


class TestVisibilityOracle:
    """ISSUE 12 oracle: the precomputed per-(layer, position) visible-
    page set must agree EXACTLY with the dense ``_sparse_layout`` row
    under the any-token-in-page reduction — for every position, across
    page sizes and both sparse layout shapes the repo serves (the
    reference block-16 VariableSparsity and the tighter block-4 layout
    the sparse-reads tests/bench use)."""

    @pytest.mark.parametrize("page_size", [8, 16])
    @pytest.mark.parametrize("block,num_local_blocks",
                             [(16, 4), (4, 4), (4, 2)])
    def test_visible_pages_matches_layout_row_reduction(
            self, page_size, block, num_local_blocks):
        from dalle_pytorch_tpu.ops import sparse as sparse_ops
        L = 108
        vis, cnt = sparse_ops.visible_pages(
            L, page_size, block, num_local_blocks=num_local_blocks)
        padded = ((L + block - 1) // block) * block
        layout = sparse_ops.token_layout_mask(
            padded, block, num_local_blocks=num_local_blocks)[:L, :L]
        for p in range(L):
            want = sorted({t // page_size for t in range(L)
                           if layout[p, t]})
            got = list(vis[p, :cnt[p]])
            assert got == want, (p, got, want)
            # padding entries are zeros, never visibility grants
            assert (vis[p, cnt[p]:] == 0).all()
        # ascending order is load-bearing: the kernel's online-softmax
        # walk and the causal prefix trim both assume it
        assert all(list(vis[p, :cnt[p]])
                   == sorted(vis[p, :cnt[p]]) for p in range(L))

    @pytest.mark.parametrize("page_size", [8, 16])
    def test_causal_trip_counts(self, page_size):
        """``_sparse_page_visibility``'s decode trip count: the prefix
        of visible pages starting strictly before p — page g readable
        iff g*ps < p (its first row is cached), matching the prefix
        walk's ceil(pos/ps) raggedness page-for-page."""
        from dalle_pytorch_tpu.ops import decode as dec
        L = CFG.seq_len
        cfg = D.DALLEConfig(dim=16, depth=2, vae=VCFG,
                            num_text_tokens=50, text_seq_len=8, heads=2,
                            dim_head=8, sparse_attn=(True, False),
                            sparse_block=4).transformer
        vis, cnt, ccnt = dec._sparse_page_visibility(cfg, L, page_size)
        for p in range(L):
            want = sum(1 for g in vis[p, :cnt[p]] if g * page_size < p)
            assert ccnt[p] == want
        assert ccnt[0] == 0      # a parked dead slot walks zero pages


class TestReadBytesModel:
    def test_kernel_model_reads_fewer_bytes_than_gather(self):
        """The analytic read-bytes model: the kernel's
        ragged-page reads must undercut the gather's full-view reads
        for any prompt shorter than the sequence."""
        common = dict(depth=2, heads=8, dim_head=64, total_len=1088,
                      page_size=16, prompt_len=64, itemsize=2)
        g = PA.modeled_kv_read_bytes_per_token(impl="gather", **common)
        k = PA.modeled_kv_read_bytes_per_token(impl="kernel", **common)
        assert k < g
        # at prompt ~= total_len the two converge (every page live)
        late = dict(common, prompt_len=1087)
        g2 = PA.modeled_kv_read_bytes_per_token(impl="gather", **late)
        k2 = PA.modeled_kv_read_bytes_per_token(impl="kernel", **late)
        assert k2 == pytest.approx(g2, rel=0.02)
        with pytest.raises(ValueError, match="impl"):
            PA.modeled_kv_read_bytes_per_token(impl="x", **common)

    def test_sparse_reads_model_undercuts_dense_reads(self):
        """The sparse-reads model: sparse layers read only visible
        pages, dense layers unchanged — so bytes drop for both impls,
        by more when more layers are sparse, and the sparse pattern is
        required (silently modeling a dense stack as sparse would fake
        the win)."""
        common = dict(depth=2, heads=2, dim_head=16, total_len=108,
                      page_size=8, prompt_len=4, itemsize=2,
                      sparse_block=4)
        for impl in ("gather", "kernel"):
            dense = PA.modeled_kv_read_bytes_per_token(impl=impl,
                                                       **common)
            half = PA.modeled_kv_read_bytes_per_token(
                impl=impl, sparse_reads=True,
                sparse_pattern=(True, False), **common)
            full = PA.modeled_kv_read_bytes_per_token(
                impl=impl, sparse_reads=True,
                sparse_pattern=(True, True), **common)
            assert full < half < dense, (impl, full, half, dense)
            # the all-sparse block-4 layout sees <= 3 of 14 pages: the
            # acceptance-criterion ratio holds with margin
            assert full <= 0.5 * dense, (impl, full, dense)
        with pytest.raises(ValueError, match="sparse_pattern"):
            PA.modeled_kv_read_bytes_per_token(impl="kernel",
                                               sparse_reads=True,
                                               **common)
