"""Speculative decode tests (ISSUE 19 acceptance criteria).

The load-bearing contract is BYTE-IDENTITY: draft-and-verify
speculation changes how many sequential full-depth passes each token
costs, never which token is emitted. Deterministic per-position
sampling (``fold_in(rng, pos)``) makes the k-wide verify compute
exactly the token the eager loop would emit at every offset, so
acceptance is an equality test — the emitted stream equals
``generate_images``' at every acceptance rate, not just in
distribution. Covered here:

  * the speculative-vs-eager identity matrix: K in {1, 8} x
    dense / paged-gather / paged-kernel x fp32 / int8-KV, under a
    SHALLOW draft (draft_layers=1 — rejection-heavy, the hard case),
    with ``decode_traces == 1`` (one verify program per k, ever);
  * a full-depth draft (draft_layers == depth) accepting every
    proposal — the acceptance-rate ceiling, pinned at exactly 1.0;
  * a mid-stream slot join under ``guards.no_transfers`` — the
    speculative steady state is as transfer-clean as the eager one;
  * the rejection-at-every-offset sweep, driving
    ``ops.decode.speculative_verify`` directly with handcrafted
    corrupted drafts: rejection at offset j accepts exactly j+1
    tokens, all byte-equal to eager, and the verify sample at the
    rejected offset is itself the correct continuation;
  * token accounting through a rejection-heavy run: rejected drafts
    never reach ``tokens_decoded``/occupancy — delivered tokens are
    counted exactly;
  * crash-mid-speculation failover (replay on a survivor) and live
    migration mid-speculation: both byte-identical — speculation is
    invisible to the replay contract;
  * a 2-device MeshEngine with speculation: the spec loop keeps the
    pinned replicated/sharded output structure, so sharded serving
    composes unchanged.

All CPU, tiny model (total_len 24; the migration row uses the same
config with chunk_steps=1 to hold a mid-stream export window).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.analysis import guards
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.serve import OK, Request, RequestQueue
from dalle_pytorch_tpu.serve.engine import Engine
from tiny_model import (CFG, REQS, VCFG, bundle,  # noqa: F401
                        reference_tokens)

def _kv_kwargs(layout: str) -> dict:
    return {"dense": dict(kv="dense"),
            "paged_gather": dict(kv="paged", page_size=4,
                                 paged_attn="gather"),
            "paged_kernel": dict(kv="paged", page_size=8,
                                 paged_attn="kernel")}[layout]


# tier-1 time budget: the k=8 rows are compile-heavy on the single-core
# CPU container (the interpret-mode kernel rows alone cost ~90s), so
# tier-1 keeps every k=1 row plus two representative k=8 rows —
# dense/fp32 (the canonical wide verify) and paged_gather/int8kv (paged
# write path + quantized scales) — and marks the rest slow. Full-matrix
# parity is kept in CI's serve-perf speculative leg, which runs this
# file unfiltered.
_TIER1_K8 = {("dense", False), ("paged_gather", True)}
_MATRIX = [
    pytest.param(k, layout, qc,
                 id=f"{k}-{layout}-{'int8kv' if qc else 'fp32'}",
                 marks=[pytest.mark.slow]
                 if k == 8 and (layout, qc) not in _TIER1_K8 else [])
    for k in (1, 8)
    for layout in ("dense", "paged_gather", "paged_kernel")
    for qc in (False, True)
]


class TestSpeculativeByteIdentity:
    @pytest.mark.parametrize("k,layout,quantize_cache", _MATRIX)
    def test_matrix(self, bundle, k, layout, quantize_cache):
        """The acceptance matrix: every (k, KV layout, cache dtype)
        combination emits the eager stream byte-for-byte under the
        SHALLOW 1-layer draft (low acceptance — every round exercises
        the rejection path), and the fused verify program compiles
        exactly once. k=1 is the degenerate no-draft round: speculation
        reduces to the eager step exactly."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r, quantize_cache)
                for r in REQS]
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=2,
                        speculative=k, draft_layers=1,
                        quantize_cache=quantize_cache,
                        **_kv_kwargs(layout))
        handles = [queue.submit(r) for r in REQS]
        with guards.compile_count(lambda: engine.decode_traces,
                                  expect=1,
                                  label=f"speculative decode k={k}"):
            engine.run_until_idle()
        for h, ref in zip(handles, refs):
            res = h.result(timeout=5)
            assert res.status == OK
            np.testing.assert_array_equal(np.asarray(res.tokens), ref)
        st = engine.stats()
        assert st["speculative"] == k and st["draft_layers"] == 1
        # the verify sample always lands, so acceptance never drops
        # below the 1/k total-rejection floor
        assert 1.0 / k <= st["spec_acceptance_rate"] <= 1.0

    def test_full_depth_draft_accepts_everything(self, bundle):
        """With draft_layers == depth the draft IS the target model run
        through the same sampler, so every proposal verifies — the
        acceptance rate is exactly 1.0 (bitwise, not approximately:
        both sides compute the identical program)."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r) for r in REQS]
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=2,
                        speculative=4,
                        draft_layers=CFG.transformer.depth)
        handles = [queue.submit(r) for r in REQS]
        engine.run_until_idle()
        for h, ref in zip(handles, refs):
            np.testing.assert_array_equal(
                np.asarray(h.result(timeout=5).tokens), ref)
        st = engine.stats()
        assert st["spec_acceptance_rate"] == 1.0
        # tokens/round sits just under k: only the clamped final round
        # of each request (sequence end mid-window) delivers fewer
        assert 3.5 <= st["spec_tokens_per_round"] <= 4.0

    def test_guided_pair_under_speculation(self, bundle):
        """A CFG pair's uncond shadow drafts and verifies partner
        copies of the cond stream, so both slots accept identical
        lengths every round and stay in lockstep — the guided stream
        equals the non-speculative engine's guided stream."""
        params, _ = bundle

        def run(spec):
            queue = RequestQueue(max_depth=8)
            engine = Engine(params, CFG, queue, num_slots=4,
                            chunk_steps=2, speculative=spec,
                            draft_layers=1 if spec else 0)
            h = queue.submit(Request(codes=(3, 7, 9), seed=11,
                                     cfg_scale=1.5))
            engine.run_until_idle()
            res = h.result(timeout=5)
            assert res.status == OK
            return np.asarray(res.tokens)

        np.testing.assert_array_equal(run(4), run(0))

    def test_midstream_join_is_transfer_clean(self, bundle):
        """Speculative steady state — k-wide chunks, double-buffered
        harvest, a slot joining mid-stream — runs under
        ``guards.no_transfers()``: the wider emit ring is still the one
        explicit device_get per chunk, and nothing else crosses."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r)
                for r in REQS[:2]]
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=2,
                        speculative=4, draft_layers=1)
        # warm run compiles the verify program + both prefill buckets
        for r in REQS[:2]:
            queue.submit(r)
        engine.run_until_idle()
        h_a = queue.submit(REQS[0])
        engine.step_once()          # a admitted, spec chunk 1 in flight
        with guards.no_transfers():
            h_b = queue.submit(REQS[1])
            engine.step_once()      # join + chunk 2 + harvest chunk 1
            engine.step_once()      # pure speculative steady state
        engine.run_until_idle()
        np.testing.assert_array_equal(
            np.asarray(h_a.result(timeout=5).tokens), refs[0])
        np.testing.assert_array_equal(
            np.asarray(h_b.result(timeout=5).tokens), refs[1])
        assert engine.decode_traces == 1

    def test_accounting_exact_under_rejection_heavy_run(self, bundle):
        """Rejected draft tokens never inflate the delivered-token
        accounting: after a rejection-heavy run (1-layer draft, k=8)
        ``tokens_decoded`` equals the exact number of tokens the
        requests needed — same invariant the eviction/migration
        un-credit paths enforce — and the speculative counters agree
        with it."""
        params, _ = bundle
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=2,
                        speculative=8, draft_layers=1)
        handles = [queue.submit(r) for r in REQS]
        engine.run_until_idle()
        for h in handles:
            assert h.result(timeout=5).status == OK
        st = engine.stats()
        exact = sum(CFG.seq_len - len(r.codes) for r in REQS)
        assert st["tokens_decoded"] == exact
        assert engine.occupancy_sum == exact
        assert engine.spec_delivered == exact
        # rounds ran: delivered = sum of per-round accepted lengths,
        # each in [1, k] — both bounds must hold exactly
        assert engine.spec_rounds >= -(-exact // 8)
        assert engine.spec_rounds <= exact


class TestRejectionSweep:
    def test_rejection_at_every_offset(self, bundle):
        """Drive ``speculative_verify`` directly: drafts that match the
        eager continuation for the first j offsets and are corrupted at
        offset j must accept EXACTLY j+1 tokens (positions pos..pos+j,
        every one byte-equal to eager), and the next-round token is the
        verify sample at the rejected offset — the free token that
        makes even total rejection advance one position."""
        params, _ = bundle
        tc = CFG.transformer
        b, k, t0 = len(REQS), 6, 4
        total_len = CFG.seq_len
        key_mask = jnp.ones((b, total_len), bool)
        rng = jnp.stack([jax.random.PRNGKey(r.seed) for r in REQS])
        temp = jnp.asarray([r.sampling.temperature for r in REQS])
        topk = jnp.asarray(
            [max(1, int(33 * (1 - r.sampling.filter_thres)))
             for r in REQS], jnp.int32)
        topp = jnp.asarray([r.sampling.top_p for r in REQS])
        partner = jnp.arange(b)
        cfgs = jnp.zeros((b,))
        uncond = jnp.zeros((b,), bool)

        def embed_fn(tok, p):
            return D.decode_token_embed(params, CFG, tok, p)

        def sample_fn(h, pred_pos):
            return D.sample_per_slot(
                D.to_logits(params, h), pred_pos, rng, temp, topk,
                topp, CFG, partner=partner, cfg_scale=cfgs,
                uncond=uncond)

        # seed a cache with t0 narrow steps, then compute the EAGER
        # continuation (the next k tokens) from a copy
        cache = decode_ops.init_cache(tc, b, total_len,
                                      dtype=jnp.float32)
        pos = jnp.zeros((b,), jnp.int32)
        cur = jnp.full((b,), 5, jnp.int32)
        for _ in range(t0):
            x = embed_fn(cur, pos)
            h, cache = decode_ops.decode_step(
                params["transformer"], x, pos, cache, cfg=tc,
                key_mask=key_mask)
            cur = sample_fn(h, pos + 1)
            pos = pos + 1
        act = jnp.ones((b,), bool)
        _, _, _, _, ring = decode_ops.decode_loop(
            params["transformer"], cur, pos, act,
            jax.tree.map(lambda a: a.copy(), cache), cfg=tc,
            key_mask=key_mask, steps=k, embed_fn=embed_fn,
            sample_fn=sample_fn)
        eager = np.asarray(ring)            # (b, k): tokens pos..pos+k-1
        # the eager token at pos+k (what cur_new must be on a clean
        # accept of all k-1 drafts): one more narrow step
        cache2 = jax.tree.map(lambda a: a.copy(), cache)
        c2, p2 = cur, pos
        for _ in range(k):
            x = embed_fn(c2, p2)
            h, cache2 = decode_ops.decode_step(
                params["transformer"], x, p2, cache2, cfg=tc,
                key_mask=key_mask)
            c2 = sample_fn(h, p2 + 1)
            p2 = p2 + 1
        eager_next = np.asarray(c2)         # token at pos+k

        good = jnp.asarray(eager[:, 1:k])   # perfect drafts (k-1 wide)
        for j in range(k):
            if j < k - 1:
                drafts = good.at[:, j].add(1)   # corrupt offset j
            else:
                drafts = good                   # full acceptance
            emit, cur_new, pos_new, act_new, _, _ = \
                decode_ops.speculative_verify(
                    params["transformer"], cur, drafts, pos, act,
                    jax.tree.map(lambda a: a.copy(), cache), cfg=tc,
                    key_mask=key_mask, total_len=total_len,
                    embed_fn=embed_fn, sample_fn=sample_fn)
            emit = np.asarray(emit)
            accepted = j + 1
            for i in range(b):
                assert (emit[i] >= 0).sum() == accepted, (j, i)
                np.testing.assert_array_equal(
                    emit[i, :accepted], eager[i, :accepted])
                assert emit[i, accepted:].tolist() == \
                    [-1] * (k - accepted)
            np.testing.assert_array_equal(np.asarray(pos_new),
                                          np.asarray(pos) + accepted)
            # the continuation token is the eager token at the first
            # un-emitted position — the rejected offset's verify
            # sample IS correct, rejection costs only the draft work
            want = eager[:, accepted] if accepted < k else eager_next
            np.testing.assert_array_equal(np.asarray(cur_new), want)
            assert bool(act_new.all())


class TestSpeculativeResilience:
    def test_crash_mid_speculation_failover_replays_identical(
            self, bundle):
        """An engine abandoned mid-speculation (chunks in flight,
        rounds half-accepted) loses nothing the replay contract needs:
        a survivor re-running the same request from token zero — with
        OR without speculation — emits the byte-identical stream.
        Speculation holds no hidden sampling state; (codes, seed) fully
        determine the tokens."""
        params, vae_params = bundle
        ref = reference_tokens(params, vae_params, REQS[0])
        crashed = Engine(params, CFG, RequestQueue(max_depth=4),
                         num_slots=2, chunk_steps=2, speculative=4,
                         draft_layers=1)
        h0 = crashed.queue.submit(REQS[0])
        crashed.step_once()
        crashed.step_once()         # chunks in flight, mid-speculation
        assert not h0.done()
        crashed.fenced = True       # the supervisor's kill switch —
        #                             this engine never fulfils h0
        for spec in (4, 0):
            survivor = Engine(params, CFG, RequestQueue(max_depth=4),
                              num_slots=2, chunk_steps=2,
                              speculative=spec,
                              draft_layers=1 if spec else 0)
            h = survivor.queue.submit(Request(codes=REQS[0].codes,
                                              seed=REQS[0].seed))
            survivor.run_until_idle()
            np.testing.assert_array_equal(
                np.asarray(h.result(timeout=5).tokens), ref)

    def test_migration_mid_speculation_byte_identical(self, bundle):
        """Live migration out of a SPECULATIVE paged engine mid-stream:
        the export payload (emitted prefix + pos + rng row + KV pages)
        fully describes the stream — rejected-draft rows past pos are
        stale by the write-before-read invariant and never ship — so
        the target (itself speculative) finishes byte-identical."""
        params, vae_params = bundle
        ref = reference_tokens(params, vae_params, REQS[0])
        kw = dict(num_slots=2, chunk_steps=1, kv="paged", page_size=4,
                  speculative=4, draft_layers=1)
        src = Engine(params, CFG, RequestQueue(max_depth=4), **kw)
        dst = Engine(params, CFG, RequestQueue(max_depth=4), **kw)
        h = src.queue.submit(REQS[0])
        rid = h.request.request_id
        import time as _time
        deadline = _time.perf_counter() + 120.0
        while _time.perf_counter() < deadline:
            src.step_once()
            if h.done():
                raise AssertionError("finished before export window")
            if src.progress_snapshot().get(rid, 0) >= 4:
                break
        payload, handle = src.export_request(rid)
        assert len(payload["emitted"]) >= 4
        dst.import_slot(payload, handle)
        dst.run_until_idle()
        res = h.result(timeout=30)
        assert res.status == OK
        np.testing.assert_array_equal(np.asarray(res.tokens), ref)


class TestSpeculativeMesh:
    def test_mesh_engine_speculative_identity(self, bundle):
        """The spec loop returns the same (cur_tok, pos, active, cache,
        ring) structure the mesh engine pins replicated/sharded output
        shardings onto, so a 2-device MeshEngine speculates unchanged —
        and byte-identical to the single-device eager stream."""
        from dalle_pytorch_tpu.serve.mesh_engine import MeshEngine
        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs 2 devices (conftest forces 8 on CPU)")
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r) for r in REQS]
        queue = RequestQueue(max_depth=8)
        engine = MeshEngine(params, CFG, queue, devices=devs[:2],
                            num_slots=2, chunk_steps=2, speculative=4,
                            draft_layers=1)
        handles = [queue.submit(r) for r in REQS]
        engine.run_until_idle()
        for h, ref in zip(handles, refs):
            np.testing.assert_array_equal(
                np.asarray(h.result(timeout=5).tokens), ref)
        assert engine.decode_traces == 1


class TestSpeculativeValidation:
    def test_rejects_sparse_reads_combo(self, bundle):
        params, _ = bundle
        sp_cfg = D.DALLEConfig(
            dim=16, depth=2, vae=VCFG, num_text_tokens=50,
            text_seq_len=8, heads=2, dim_head=8,
            sparse_attn=(False, True), sparse_block=4)
        sp_params = D.dalle_init(jax.random.PRNGKey(0), sp_cfg)
        with pytest.raises(ValueError, match="sparse_reads"):
            Engine(sp_params, sp_cfg, RequestQueue(max_depth=4),
                   kv="paged", page_size=8, sparse_reads=True,
                   speculative=4)

    def test_rejects_bad_draft_depth(self, bundle):
        params, _ = bundle
        with pytest.raises(ValueError, match="draft_layers"):
            Engine(params, CFG, RequestQueue(max_depth=4),
                   speculative=4, draft_layers=3)
        with pytest.raises(ValueError, match="speculative"):
            Engine(params, CFG, RequestQueue(max_depth=4),
                   speculative=-1)

    def test_draft_helpers_slice_consistently(self, bundle):
        params, _ = bundle
        d = 1
        dcfg = D.draft_transformer_config(CFG.transformer, d)
        assert dcfg.depth == d
        assert dcfg.sparse_pattern == CFG.transformer.sparse_pattern[:d]
        dp = D.draft_transformer_params(params["transformer"], d)
        for leaf, full in zip(jax.tree.leaves(dp),
                              jax.tree.leaves(params["transformer"])):
            assert leaf.shape[0] == d
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(full[:d]))
        with pytest.raises(ValueError):
            D.draft_transformer_config(CFG.transformer, 0)
