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

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.analysis import guards
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.models import vae as V
from dalle_pytorch_tpu.ops import attention as attn_ops
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.ops import paged_attention as PA
from dalle_pytorch_tpu.serve import (Request, RequestQueue,
                                     SamplingParams)
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve.engine import Engine

VCFG = V.VAEConfig(image_size=16, num_tokens=32, codebook_dim=16,
                   num_layers=2, hidden_dim=8)
CFG = D.DALLEConfig(dim=16, depth=2, vae=VCFG, num_text_tokens=50,
                    text_seq_len=8, heads=2, dim_head=8)


# the sparse-reads step needs sparse layers whose window is narrower than
# the 24-token sequence (tests/test_sparse_reads.py's configuration)
SPARSE_CFG = D.DALLEConfig(dim=16, depth=2, vae=VCFG, num_text_tokens=50,
                           text_seq_len=8, heads=2, dim_head=8,
                           sparse_attn=(True, False), sparse_block=4)


@pytest.fixture(scope="module")
def bundle():
    key = jax.random.PRNGKey(0)
    vae_params = V.vae_init(jax.random.fold_in(key, 1), VCFG)
    params = D.dalle_init(key, CFG, vae_params)
    return params, vae_params


_REF_CACHE: dict = {}


def reference_tokens(params, vae_params, req: Request,
                     quantize_cache: bool = False) -> np.ndarray:
    """Memoized generate_images at batch 1 — the one-shot stream every
    engine path must reproduce token-for-token (test_serve's idiom)."""
    key = (quantize_cache, req.codes, req.seed, req.sampling.temperature,
           req.sampling.filter_thres, req.sampling.top_p)
    if key not in _REF_CACHE:
        text = jnp.asarray([req.codes], jnp.int32)
        _, img_seq = D.generate_images(
            params, vae_params, text, cfg=CFG,
            rng=jax.random.PRNGKey(req.seed),
            filter_thres=req.sampling.filter_thres,
            top_p=req.sampling.top_p,
            temperature=req.sampling.temperature,
            quantize_cache=quantize_cache, return_img_seq=True)
        _REF_CACHE[key] = np.asarray(img_seq)[0]
    return _REF_CACHE[key]


REQS = [
    Request(codes=(3, 7, 9), seed=11),
    Request(codes=(5, 2, 8, 1, 4), seed=23,
            sampling=SamplingParams(temperature=0.7, filter_thres=0.8)),
    Request(codes=(6, 6), seed=5,
            sampling=SamplingParams(temperature=1.3, top_p=0.9)),
]


def _random_pool(key, page_size, num_pages, quantized, *, dim_head=None,
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
        pool = _random_pool(jax.random.PRNGKey(7), page_size,
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
        pool = _random_pool(jax.random.PRNGKey(0), 8, 7, False)
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
        pool = _random_pool(jax.random.PRNGKey(0), 4, 7, False)
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
        pool = _random_pool(jax.random.PRNGKey(3), 8, 9, True)
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


class TestPerLayerRead:
    """ISSUE 25: the gather path attends ONE layer's pages inside the
    layer scan, page-major, straight from the pool. The oracle is the
    all-layer dense view it replaced: ``paged_view`` + ``_gather_read``
    — same rows, same masks, same scales."""

    PS = 8
    HEADS, DEPTH = CFG.transformer.heads, CFG.transformer.depth

    def _case(self, kind, heads, dim_head, total_len, tables):
        key = jax.random.PRNGKey(dim_head + total_len)
        need = KV.pages_for(total_len, self.PS)
        dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
        pool = _random_pool(key, self.PS, 3 * need + 1, kind == "int8",
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
        pool = _random_pool(key, self.PS, 6 * need + 1, kind == "int8",
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
        pool = _random_pool(key, ps, slots * need + 1, kind == "int8",
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
        pool = _random_pool(jax.random.PRNGKey(3), ps, 2 * mp + 1, False,
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
        pool = _random_pool(jax.random.PRNGKey(21), page_size,
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
        pool = _random_pool(key, ps, 3 * need + 1, kind == "int8",
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
