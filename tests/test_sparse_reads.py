"""Sparsity-aware decode reads (ISSUE 12): engine + step-math coverage.

The contract: with ``sparse_reads=True`` every emitted token is
BYTE-IDENTICAL to ``generate_images`` (and therefore to the dense-read
engine) — sparse layers skip only pages whose every token the trained
VariableSparsity layout masks, and under the finite ``neg_inf`` fill
those pages carry exactly-zero softmax weight — while the per-token KV
read traffic drops by the visibility ratio. Pinned here across
K ∈ {1, 8} × gather/kernel × fp32/int8-KV, through a transfer-guarded
mid-stream join (the static visibility tables must not retrace the one
fused decode program), at the direct step-math level (the sparse-reads
kernel walk is BIT-equal to the prefix walk), and at the typed-
validation level (paged-only, sparse-layers-only, periodic-only).

The config uses ``sparse_block=4`` so the window (4 blocks = 16 tokens)
is narrower than the 24-token sequence — at the reference block 16 the
tiny sequence fits one window and visibility degenerates to
everything-visible. All CPU (the kernel runs under the Pallas
interpreter), tiny model, inside tier-1.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.analysis import guards
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.serve import Request, RequestQueue
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve.engine import Engine
import tiny_model
from tiny_model import REQS, VCFG, bundle  # noqa: F401

CFG = D.DALLEConfig(dim=16, depth=2, vae=VCFG, num_text_tokens=50,
                    text_seq_len=8, heads=2, dim_head=8,
                    sparse_attn=(True, False), sparse_block=4)
reference_tokens = functools.partial(tiny_model.reference_tokens, cfg=CFG)

def _random_pool(key, page_size, num_pages, quantized):
    tcfg = CFG.transformer
    # a page is whole rows, every head's numbers side by side
    shape = (tcfg.depth, num_pages, page_size, tcfg.heads * tcfg.dim_head)
    assert shape[2:] == KV.page_layout(tcfg, page_size)["k"][0]
    scales = shape[:-1] + (tcfg.heads,)
    if quantized:
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
    return {"k": jax.random.normal(jax.random.fold_in(key, 0), shape),
            "v": jax.random.normal(jax.random.fold_in(key, 1), shape)}


class TestStepMathParity:
    """Direct ``_decode_step_math(sparse_reads=True)`` against the two
    established oracles, at ragged per-slot positions (last row /
    mid-sequence with a padded-off prompt row / parked dead at 0)."""

    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("pattern", [(True, False), (True, True)])
    def test_sparse_reads_matches_oracles(self, bundle, quantized,
                                          pattern):
        params, _ = bundle
        cfg = D.DALLEConfig(dim=16, depth=2, vae=VCFG, num_text_tokens=50,
                            text_seq_len=8, heads=2, dim_head=8,
                            sparse_attn=pattern,
                            sparse_block=4).transformer
        L, ps = CFG.seq_len, 8
        mp = KV.pages_for(L, ps)
        pool = _random_pool(jax.random.PRNGKey(7), ps, 2 * mp + 1,
                            quantized)
        bt = np.zeros((3, mp), np.int32)
        bt[0] = np.arange(1, mp + 1)
        bt[1] = np.arange(mp + 1, 2 * mp + 1)
        bt = jnp.asarray(bt)
        pos = jnp.asarray([L - 1, 17, 0], jnp.int32)
        key_mask = jnp.ones((3, L), bool).at[1, 1].set(False)
        x_tok = jax.random.normal(jax.random.PRNGKey(9), (3, CFG.dim))
        kw = dict(cfg=cfg, key_mask=key_mask)

        view = decode_ops.paged_view(pool, bt, L, cfg.heads)
        h_ref, ks_ref, vs_ref = decode_ops._decode_step_math(
            params["transformer"], x_tok, pos, view, **kw)
        h_k, ks_k, _ = decode_ops._decode_step_math(
            params["transformer"], x_tok, pos, pool, attn_impl="kernel",
            block_tables=bt, **kw)

        h_sk, ks_sk, _ = decode_ops._decode_step_math(
            params["transformer"], x_tok, pos, pool, attn_impl="kernel",
            block_tables=bt, sparse_reads=True, **kw)
        # the sparse-reads kernel walk is BIT-equal to the PREFIX walk
        # (every skipped page is an exact identity of the online
        # softmax); vs the gather oracle it inherits the kernel's
        # summation-order allclose bound
        np.testing.assert_array_equal(np.asarray(h_sk), np.asarray(h_k))
        np.testing.assert_array_equal(np.asarray(ks_sk),
                                      np.asarray(ks_k))
        np.testing.assert_allclose(np.asarray(h_sk), np.asarray(h_ref),
                                   rtol=2e-5, atol=2e-6)

        h_sg, ks_sg, vs_sg = decode_ops._decode_step_math(
            params["transformer"], x_tok, pos, pool, attn_impl="gather",
            block_tables=bt, sparse_reads=True, **kw)
        np.testing.assert_allclose(np.asarray(h_sg), np.asarray(h_ref),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(ks_sg),
                                   np.asarray(ks_ref),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(vs_sg),
                                   np.asarray(vs_ref),
                                   rtol=2e-5, atol=2e-6)


class TestSparseReadsEngineTokens:
    """End-to-end: the sparse-reads engine must emit byte-identical
    tokens to ``generate_images`` in the same one-compile fused-K
    emit-ring regime — K x impl x cache-dtype full cross."""

    @pytest.mark.parametrize("quantize_cache", [False, True])
    @pytest.mark.parametrize("k", [1, 8])
    @pytest.mark.parametrize("impl", ["gather", "kernel"])
    def test_tokens_byte_identical(self, bundle, impl, k,
                                   quantize_cache):
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r, quantize_cache)
                for r in REQS]
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=k,
                        kv="paged", page_size=8, paged_attn=impl,
                        sparse_reads=True,
                        quantize_cache=quantize_cache)
        handles = [queue.submit(r) for r in REQS]
        with guards.compile_count(lambda: engine.decode_traces, expect=1,
                                  label=f"sparse-reads {impl} decode"):
            engine.run_until_idle()
        for h, ref in zip(handles, refs):
            res = h.result(timeout=5)
            assert res.status == "ok", res.reason
            np.testing.assert_array_equal(np.asarray(res.tokens), ref)
        assert engine.alloc.in_use == 0
        stats = engine.stats()
        assert stats["sparse_reads"] is True
        assert stats["kv_read_bytes_per_token"] \
            < stats["kv_read_bytes_per_token_dense_reads"]

    @pytest.mark.parametrize("impl", ["gather", "kernel"])
    def test_transfer_clean_midstream_join(self, bundle, impl):
        """Sparse visibility must not retrace or transfer: the tables
        are trace-time constants, so a mid-stream join (paged prefill +
        block-table growth) stays inside the one compiled program with
        no implicit host<->device traffic."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r)
                for r in REQS[:2]]
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4,
                        kv="paged", page_size=8, paged_attn=impl,
                        sparse_reads=True)
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


class TestSparseReadsComposition:
    @pytest.mark.parametrize("impl", ["gather", "kernel"])
    def test_eviction_replay_stays_token_exact(self, bundle, impl):
        """Sparse reads compose with paged EVICTION: an overcommitted
        pool evicts mid-decode, the victim replays on re-admission, and
        every stream still equals the one-shot reference — visibility
        is positional, so block-table remapping churn cannot touch it."""
        params, vae_params = bundle
        reqs = [REQS[0],
                Request(codes=REQS[1].codes, seed=REQS[1].seed,
                        sampling=REQS[1].sampling, priority=7),
                REQS[2]]
        refs = [reference_tokens(params, vae_params, r) for r in reqs]
        queue = RequestQueue(max_depth=8)
        # seq 24 at page_size 8 = 3 pages/request; 4 usable pages with
        # 2 slots is a genuine overcommit (two mid-sequence requests
        # need up to 6)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4,
                        kv="paged", page_size=8, num_pages=5,
                        paged_attn=impl, sparse_reads=True)
        handles = [queue.submit(r) for r in reqs]
        with guards.compile_count(lambda: engine.decode_traces, expect=1,
                                  label=f"sparse-reads {impl} eviction"):
            engine.run_until_idle()
        assert engine.evicted >= 1, "pool was sized to force eviction"
        for h, ref in zip(handles, refs):
            res = h.result(timeout=5)
            assert res.status == "ok", res.reason
            np.testing.assert_array_equal(np.asarray(res.tokens), ref)
        assert engine.alloc.in_use == 0


class TestSparseReadsValidation:
    """The flag's preconditions are typed at construction, naming the
    constraint — never a trace-time surprise."""

    def test_requires_paged_kv(self, bundle):
        params, _ = bundle
        with pytest.raises(ValueError, match="paged"):
            Engine(params, CFG, RequestQueue(max_depth=2), num_slots=1,
                   kv="dense", sparse_reads=True)

    def test_requires_sparse_layers(self, bundle):
        params, _ = bundle
        dense_cfg = D.DALLEConfig(dim=16, depth=2, vae=VCFG,
                                  num_text_tokens=50, text_seq_len=8,
                                  heads=2, dim_head=8)
        with pytest.raises(ValueError, match="no sparse layers"):
            Engine(params, dense_cfg, RequestQueue(max_depth=2),
                   num_slots=1, kv="paged", page_size=8,
                   sparse_reads=True)

    def test_requires_periodic_pattern(self):
        cfg5 = D.DALLEConfig(dim=16, depth=5, vae=VCFG,
                             num_text_tokens=50, text_seq_len=8,
                             heads=2, dim_head=8,
                             sparse_attn=(True, False, False, False,
                                          True), sparse_block=4)
        params5 = D.dalle_init(jax.random.PRNGKey(2), cfg5)
        with pytest.raises(ValueError, match="periodic"):
            Engine(params5, cfg5, RequestQueue(max_depth=2),
                   num_slots=1, kv="paged", page_size=8,
                   sparse_reads=True)

    def test_off_by_default_and_stats_report_it(self, bundle):
        params, _ = bundle
        engine = Engine(params, CFG, RequestQueue(max_depth=2),
                        num_slots=1, kv="paged", page_size=8)
        stats = engine.stats()
        assert stats["sparse_reads"] is False
        # with sparse reads off the two modeled numbers coincide
        assert stats["kv_read_bytes_per_token"] \
            == stats["kv_read_bytes_per_token_dense_reads"]
