"""Serving subsystem tests (ISSUE 2 + ISSUE 4 acceptance criteria).

The load-bearing one is equivalence: for the same params/prompt/seed/
sampling knobs, the slot-batched engine's emitted image tokens are
IDENTICAL to ``models.dalle.generate_images`` at batch 1 — including
requests that join mid-stream while other slots are mid-decode, different
prompt lengths, per-request temperature/top-k/top-p, and EVERY fused
chunk size K (the device-resident loop only changes where the host reads
the stream, never what the device computes). Plus the structured-
backpressure contract (queue-full and deadline-exceeded are typed results,
no hangs, no silent drops) and the compile/transfer contracts: the fused
decode program traces exactly once across a multi-request run, each
prefill BUCKET traces exactly once for the engine's life, and the whole
steady-state iteration — chunk dispatch, double-buffered emit-ring
harvest, and a mid-stream join — holds under
``analysis.guards.no_transfers()``.

All CPU, tiny model (total_len 24) so the whole file stays cheap inside
tier-1.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.analysis import guards
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.serve import (DEADLINE_EXCEEDED, ERROR, OK,
                                     InvalidRequest, PageAllocator,
                                     PagePoolExhausted, QueueClosed,
                                     QueueFull, Request, RequestQueue,
                                     SamplingParams, bucket_for, pages_for,
                                     prefill_buckets, prefill_groups)
from dalle_pytorch_tpu.serve.engine import Engine
from tiny_model import (CFG, REQS, VCFG, bundle,  # noqa: F401
                        reference_tokens)

_REF_CACHE: dict = {}


def reference_tokens_int8(params, vae_params, req: Request) -> np.ndarray:
    """Memoized generate_images(quantize_cache=True) reference — shared
    by the dense and paged int8-KV equivalence tests (identical
    one-shot side, ~one generate_images run saved per extra caller)."""
    key = ("int8", req.codes, req.seed)
    if key not in _REF_CACHE:
        text = jnp.asarray([req.codes], jnp.int32)
        _, img_seq = D.generate_images(
            params, vae_params, text, cfg=CFG,
            rng=jax.random.PRNGKey(req.seed), return_img_seq=True,
            quantize_cache=True)
        _REF_CACHE[key] = np.asarray(img_seq)[0]
    return _REF_CACHE[key]


class TestEquivalence:
    def test_tokens_identical_to_generate_images(self, bundle):
        """3 requests (different prompt lengths / temperatures / top-k /
        top-p) through a 2-slot pool: more requests than slots, so slots
        are reused (leave + join) — every emitted image-token sequence
        must equal the one-shot sampler's, and the decode program must
        have compiled exactly once."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r) for r in REQS]

        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2)
        handles = [queue.submit(r) for r in REQS]
        # the shared guard (analysis.guards): a recompiling decode step
        # fails tier-1
        with guards.compile_count(lambda: engine.decode_traces, expect=1,
                                  label="serve decode program"):
            engine.run_until_idle()

        for h, ref in zip(handles, refs):
            res = h.result(timeout=5)
            assert res.status == OK
            np.testing.assert_array_equal(np.asarray(res.tokens), ref)
            assert res.total_s > 0 and res.decode_s > 0
        # prefill compiles once per BUCKET admission padded into, never
        # per request or per distinct prompt length
        used = {bucket_for(len(r.codes), engine.buckets) for r in REQS}
        assert engine.prefill_traces == len(used)
        for b in used:
            assert engine.prefill_trace_count(b) == 1

    def test_steady_state_decode_is_transfer_clean(self, bundle):
        """Full K-step chunks — dispatch, double-buffered emit-ring
        harvest, AND a mid-chunk slot join (admission prefill + the
        device-side state merge) — run under ``guards.no_transfers()``:
        per-slot decode state never leaves the device, every crossing is
        an explicit device_put/device_get at its site (there is no
        per-step allowance left to waive), and the guard must not
        perturb the token stream. Each prefill bucket compiles exactly
        once for the engine's LIFE (the guards.compile_count contract),
        even though both buckets admit twice."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r)
                for r in REQS[:2]]
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4)
        b0 = bucket_for(len(REQS[0].codes), engine.buckets)
        b1 = bucket_for(len(REQS[1].codes), engine.buckets)
        assert b0 != b1             # the join exercises a SECOND bucket
        with guards.compile_count(
                lambda: engine.prefill_trace_count(b0), expect=1,
                label=f"prefill bucket {b0}"), \
            guards.compile_count(
                lambda: engine.prefill_trace_count(b1), expect=1,
                label=f"prefill bucket {b1}"):
            # warm run: compiles the fused decode program + both buckets
            for r in REQS[:2]:
                queue.submit(r)
            engine.run_until_idle()
            # steady state, transfer-guarded: a runs, b joins mid-stream
            h_a = queue.submit(REQS[0])
            engine.step_once()      # a admitted, chunk 1 in flight
            with guards.no_transfers():
                h_b = queue.submit(REQS[1])
                engine.step_once()  # join + chunk 2 + harvest of chunk 1
                engine.step_once()  # pure steady-state chunk
            engine.run_until_idle()
        np.testing.assert_array_equal(
            np.asarray(h_a.result(timeout=5).tokens), refs[0])
        np.testing.assert_array_equal(
            np.asarray(h_b.result(timeout=5).tokens), refs[1])
        assert engine.decode_traces == 1

    @pytest.mark.parametrize("k", [1, 32])
    def test_tokens_identical_across_chunk_sizes(self, bundle, k):
        """The fused chunk size K must not change a single emitted token
        — K only moves the host read boundary. K=1 degenerates to the
        old per-step engine, K=32 covers a whole request in one chunk
        (every slot finishes into the dead mask mid-chunk); the default
        K=8 mid-chunk-boundary case is every other test in the file."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r) for r in REQS]
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=k)
        handles = [queue.submit(r) for r in REQS]
        engine.run_until_idle()
        for h, ref in zip(handles, refs):
            np.testing.assert_array_equal(
                np.asarray(h.result(timeout=5).tokens), ref)
        assert engine.decode_traces == 1

    def test_fulfillment_timestamped_at_harvest(self, bundle):
        """A request that emits its last token mid-chunk becomes
        observable only when the emit ring lands on the host (one chunk
        later, double-buffered) — its recorded latency must be the
        harvest-time, caller-observed number, not the in-chunk finish
        (docs/SERVING.md 'Choosing K')."""
        params, _ = bundle

        class Clock:
            t = 0.0

            def __call__(self):
                return self.t

        clock = Clock()
        queue = RequestQueue(max_depth=4, clock=clock)
        engine = Engine(params, CFG, queue, num_slots=1, chunk_steps=64,
                        clock=clock)
        h = queue.submit(REQS[0])       # submit_t = 0.0
        engine.step_once()              # one 64-step chunk covers the
        #                                 whole sequence: finished ON
        #                                 DEVICE, but not yet harvested
        assert not h.done()
        clock.t = 5.0
        engine.step_once()              # harvest lands the ring NOW
        res = h.result(timeout=5)
        assert res.status == OK
        assert res.total_s == 5.0       # caller-observed harvest time
        assert res.decode_s == 5.0

    def test_join_midstream_does_not_perturb_running_slot(self, bundle):
        """A request admitted while another slot is mid-decode (the
        continuous-batching join) must not change either slot's tokens."""
        params, vae_params = bundle
        r_a, r_b = REQS[0], REQS[1]
        ref_a = reference_tokens(params, vae_params, r_a)
        ref_b = reference_tokens(params, vae_params, r_b)

        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=2)
        h_a = queue.submit(r_a)
        for _ in range(3):                  # a is ~6 tokens into decode
            engine.step_once()
        assert engine.active_slots() == 1
        h_b = queue.submit(r_b)             # b joins mid-stream
        engine.run_until_idle()

        np.testing.assert_array_equal(
            np.asarray(h_a.result(timeout=5).tokens), ref_a)
        np.testing.assert_array_equal(
            np.asarray(h_b.result(timeout=5).tokens), ref_b)
        assert engine.decode_traces == 1

    @pytest.mark.parametrize("kv", [{}, {"kv": "paged", "page_size": 4}],
                             ids=["dense", "paged"])
    def test_nucleus_join_keeps_one_trace_and_counts_its_chunks(
            self, bundle, kv):
        """A nucleus request joins a pool of top-k ones and leaves it
        again: the decode program (whose vocabulary sort lies under a
        conditional the program steers from its own ``top_p`` input) is
        still traced once, every request's tokens are the one-shot
        sampler's, and ``sample_sorted_chunks`` counts the chunks
        dispatched while the nucleus request held a slot — found here by
        its request id — and no others."""
        params, vae_params = bundle
        topk_a, topk_b, nucleus = REQS
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=2,
                        **kv)
        dispatch, resident = engine._dispatch_chunk, []

        def spy(now):
            resident.append(any(
                engine.find_slot(h.request.request_id) is not None
                for h in handles if h.request.sampling.top_p > 0))
            dispatch(now)

        engine._dispatch_chunk = spy
        handles = [queue.submit(topk_a)]
        for _ in range(3):
            engine.step_once()          # top-k alone: nothing sorts
        assert engine.stats()["sample_sorted_chunks"] == 0
        # the nucleus request joins mid-stream, ends, and a top-k
        # request takes its slot and outlives it
        handles += [queue.submit(nucleus), queue.submit(topk_b),
                    queue.submit(topk_a)]
        engine.run_until_idle()

        for h in handles:
            np.testing.assert_array_equal(
                np.asarray(h.result(timeout=5).tokens),
                reference_tokens(params, vae_params, h.request))
        assert engine.decode_traces == 1
        st = engine.stats()
        assert len(resident) == st["decode_steps"] // st["chunk_steps"]
        assert resident[:3] == [False] * 3 and not resident[-1]
        assert 0 < sum(resident) < len(resident)
        assert st["sample_sorted_chunks"] == sum(resident)

    def test_int8_kv_slot_cache_runs(self, bundle):
        """quantize_cache composes with the slot pool: the engine matches
        generate_images(quantize_cache=True) token-for-token (both sides
        quantize rows the same way, ops.decode._store_rows)."""
        params, vae_params = bundle
        req = REQS[0]
        ref = reference_tokens_int8(params, vae_params, req)
        queue = RequestQueue(max_depth=4)
        engine = Engine(params, CFG, queue, num_slots=2,
                        quantize_cache=True)
        h = queue.submit(req)
        engine.run_until_idle()
        np.testing.assert_array_equal(np.asarray(h.result(5).tokens),
                                      ref)


class TestPagedKV:
    """The paged KV-cache subsystem (serve/kv_pool.py +
    ops.decode.decode_loop_paged): block-pool memory manager, paged
    decode path, and the PagePoolExhausted eviction/requeue
    backpressure. The load-bearing contract is the same as dense —
    token-for-token equality with ``generate_images`` at batch 1 — plus
    page accounting (allocate on admission, grow across page boundaries,
    free on completion) and the compile/transfer discipline unchanged:
    ONE decode trace for the engine's life and a transfer-clean steady
    state (block-table growth is an explicit device_put)."""

    @pytest.mark.parametrize("k", [1, 8, 32])
    def test_paged_tokens_identical_across_chunk_sizes(self, bundle, k):
        """Paged-vs-dense token-exact equivalence for K in {1, 8, 32}:
        more requests than slots (slot reuse), mixed prompt lengths /
        temperatures / top-k / top-p, page_size 4 so every request
        crosses several page boundaries mid-stream — and the fused
        paged decode program compiles exactly once."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r) for r in REQS]
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=k,
                        kv="paged", page_size=4)
        handles = [queue.submit(r) for r in REQS]
        with guards.compile_count(lambda: engine.decode_traces, expect=1,
                                  label="paged decode program"):
            engine.run_until_idle()
        for h, ref in zip(handles, refs):
            res = h.result(timeout=5)
            assert res.status == OK
            np.testing.assert_array_equal(np.asarray(res.tokens), ref)
        # every page returned to the pool once the engine drained
        assert engine.alloc.in_use == 0
        assert engine.alloc.peak_in_use > 0

    def test_paged_int8_kv_tokens_identical(self, bundle):
        """int8-KV composes with paging: the paged int8 pool matches
        generate_images(quantize_cache=True) token-for-token (same
        _quantize_rows, same scale discipline, per page)."""
        params, vae_params = bundle
        req = REQS[0]
        ref = reference_tokens_int8(params, vae_params, req)
        queue = RequestQueue(max_depth=4)
        engine = Engine(params, CFG, queue, num_slots=2, kv="paged",
                        page_size=4, quantize_cache=True)
        h = queue.submit(req)
        engine.run_until_idle()
        np.testing.assert_array_equal(np.asarray(h.result(5).tokens),
                                      ref)

    def test_paged_steady_state_transfer_clean_midstream_join(self,
                                                              bundle):
        """The dense engine's transfer-discipline test, on the paged
        path: full chunks, double-buffered harvest, AND a mid-stream
        join (paged prefill + block-table update + page growth across a
        boundary) under ``guards.no_transfers()`` — the only paged-
        specific crossing is the explicit block-table device_put."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r)
                for r in REQS[:2]]
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4,
                        kv="paged", page_size=4)
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

    def test_eviction_victim_completes_after_readmission(self, bundle):
        """The PagePoolExhausted backpressure path end-to-end: a pool
        too small for the offered concurrency must EVICT the lowest-
        priority active request back to the queue (pages freed, handle
        re-queued, never dropped) — and the victim must still complete
        with the exact one-shot token stream after re-admission
        (deterministic sampling replays it). The higher-priority
        requests' streams must be untouched by the churn."""
        params, vae_params = bundle
        # REQS[1] made lowest priority (highest value) -> the victim
        reqs = [REQS[0],
                Request(codes=REQS[1].codes, seed=REQS[1].seed,
                        sampling=REQS[1].sampling, priority=7),
                REQS[2]]
        refs = [reference_tokens(params, vae_params, r) for r in reqs]
        queue = RequestQueue(max_depth=8)
        # seq 24 at page_size 4 = 6 pages/request; 8 usable pages with
        # 2 slots is a genuine overcommit: two mid-sequence requests
        # need up to 12
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4,
                        kv="paged", page_size=4, num_pages=9)
        handles = [queue.submit(r) for r in reqs]
        with guards.compile_count(lambda: engine.decode_traces, expect=1,
                                  label="paged decode under eviction"):
            engine.run_until_idle()
        assert engine.evicted >= 1, "pool was sized to force eviction"
        assert queue.requeued >= 1
        for h, ref in zip(handles, refs):
            res = h.result(timeout=5)
            assert res.status == OK
            np.testing.assert_array_equal(np.asarray(res.tokens), ref)
        assert engine.alloc.in_use == 0
        # tokens_decoded counts DISTINCT delivered tokens: a victim's
        # harvested prefix is un-credited at eviction (its replay
        # re-credits every token), so the counter equals the per-request
        # decode spans exactly — no eviction inflation
        assert engine.tokens_decoded == sum(
            engine.total_len - len(r.codes) for r in reqs)

    def test_admission_gated_on_free_pages_not_slots(self, bundle):
        """With free slots but no free pages, admission is gated: the
        request WAITS in the queue (no per-chunk pop/defer/requeue churn
        — a dry pool means the engine doesn't pop at all) until
        completions free pages, then runs to the exact reference
        stream."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r)
                for r in REQS[:2]]
        queue = RequestQueue(max_depth=8)
        # exactly one full sequence of pages: the second request CANNOT
        # be admitted while the first holds the pool
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=24,
                        kv="paged", page_size=4, num_pages=7)
        h_a = queue.submit(REQS[0])
        engine.step_once()      # a admitted and mapped ahead (all pages)
        assert engine.alloc.free == 0
        h_b = queue.submit(REQS[1])
        engine.step_once()      # pool dry: b stays queued, un-popped
        assert queue.depth() == 1
        assert queue.requeued == 0              # no churn while waiting
        assert not h_b.done()                   # gated, not dropped
        engine.run_until_idle()
        np.testing.assert_array_equal(
            np.asarray(h_a.result(timeout=5).tokens), refs[0])
        np.testing.assert_array_equal(
            np.asarray(h_b.result(timeout=5).tokens), refs[1])

    def test_one_page_budget_holds_more_requests_than_dense_slots(
            self, bundle):
        """What the paged pool is for: the bytes of ``dense_slots``
        whole-sequence caches, spent through block tables, hold MORE
        requests at once than the dense engine has slots (a request
        maps only the pages it has reached), and the overcommit costs
        no request — every one completes token-exact in both engines."""
        params, vae_params = bundle
        page_size, dense_slots = 4, 2
        budget = dense_slots * pages_for(CFG.seq_len, page_size)
        layouts = {
            "dense": dict(num_slots=dense_slots),
            # + 1: the reserved trash page is no part of the budget
            "paged": dict(num_slots=2 * dense_slots, kv="paged",
                          page_size=page_size, num_pages=budget + 1)}
        reqs = REQS + REQS                  # more than either has slots
        peaks = {}
        for kv, kw in layouts.items():
            queue = RequestQueue(max_depth=8)
            engine = Engine(params, CFG, queue, chunk_steps=4, **kw)
            handles = [queue.submit(r) for r in reqs]
            peaks[kv] = 0
            while engine.step_once() or not engine.idle():
                peaks[kv] = max(peaks[kv], engine.active_slots())
            for h, r in zip(handles, reqs):
                res = h.result(timeout=5)
                assert res.status == OK, (kv, res.status, res.reason)
                np.testing.assert_array_equal(
                    np.asarray(res.tokens),
                    reference_tokens(params, vae_params, r))
            if kv == "paged":
                assert engine.stats()["pages_peak"] <= budget
        assert peaks["dense"] == dense_slots
        assert peaks["paged"] > dense_slots, peaks

    def test_head_of_line_request_not_starved_by_smaller(self, bundle):
        """No-starvation: a page-deferred request at the head of the
        line RESERVES its page need — a later, smaller request must not
        be admitted past it on the pages freed for it (requeue preserves
        arrival order; the admission floor becomes the head's need)."""
        params, vae_params = bundle
        # b needs bucket 8 = 2 pages at admission; c (submitted AFTER b)
        # needs bucket 2 = 1 page
        reqs = [REQS[0],
                Request(codes=(4, 1, 2, 3, 5, 6, 7, 8), seed=31),
                REQS[2]]
        refs = [reference_tokens(params, vae_params, r) for r in reqs]
        queue = RequestQueue(max_depth=8)
        # capacity 7 pages at page_size 4 (6/full sequence): once a is
        # admitted and mapped ahead, exactly ONE page stays free
        engine = Engine(params, CFG, queue, num_slots=3, chunk_steps=24,
                        kv="paged", page_size=4, num_pages=8)
        h_a = queue.submit(reqs[0])
        engine.step_once()              # a admitted, mapped to the end
        assert engine.alloc.free == 1
        h_b = queue.submit(reqs[1])
        h_c = queue.submit(reqs[2])
        engine.step_once()
        # b cannot be mapped (needs 2) -> it AND c wait; the one free
        # page must NOT go to c even though c alone would fit (a may
        # have completed inside this same step — harvest runs after
        # admission — so only the head-of-line state is deterministic)
        assert not h_b.done() and not h_c.done()
        assert queue.depth() == 2
        assert engine._hol_rid == h_b.request.request_id
        assert engine._hol_need == 2
        engine.run_until_idle()
        for h, ref in zip([h_a, h_b, h_c], refs):
            res = h.result(timeout=5)
            assert res.status == OK
            np.testing.assert_array_equal(np.asarray(res.tokens), ref)
        assert engine.alloc.in_use == 0

    def test_pool_must_hold_one_full_sequence(self, bundle):
        params, _ = bundle
        with pytest.raises(ValueError, match="full sequence"):
            Engine(params, CFG, RequestQueue(max_depth=2), num_slots=1,
                   kv="paged", page_size=4, num_pages=4)

    def test_allocator_typed_exhaustion_and_reuse(self):
        alloc = PageAllocator(4)            # 3 usable + trash
        a = alloc.alloc(2)
        assert 0 not in a                   # trash page never handed out
        with pytest.raises(PagePoolExhausted) as ei:
            alloc.alloc(2)
        rec = ei.value.record
        assert rec["kind"] == "serve_page_exhausted"
        assert rec["pages_needed"] == 2 and rec["pages_free"] == 1
        alloc.release(a)
        assert alloc.free == 3
        assert alloc.peak_in_use == 2

    def test_allocator_double_release_is_hard_error(self):
        """A page freed twice would eventually be handed to TWO live
        slots (silent KV corruption) — the allocator fails at the bug's
        site instead."""
        alloc = PageAllocator(4)
        a = alloc.alloc(2)
        alloc.release(a)
        with pytest.raises(ValueError, match="double release"):
            alloc.release([a[0]])
        with pytest.raises(ValueError, match="never allocatable"):
            alloc.release([0])              # the trash page

    def test_paged_stats_surface(self, bundle):
        params, _ = bundle
        queue = RequestQueue(max_depth=4)
        engine = Engine(params, CFG, queue, num_slots=2, kv="paged",
                        page_size=4)
        queue.submit(REQS[0])
        engine.run_until_idle()
        stats = engine.stats()
        assert stats["kv"] == "paged"
        assert stats["page_size"] == 4
        assert stats["pages_in_use"] == 0           # drained
        assert stats["pages_peak"] >= 1
        assert stats["pages_in_use_p95"] >= 1
        assert stats["kv_hbm_bytes"] > 0
        # dense engines report layout + bytes too (bench compares them)
        dense = Engine(params, CFG, RequestQueue(max_depth=2),
                       num_slots=2)
        assert dense.stats()["kv"] == "dense"
        assert dense.stats()["kv_hbm_bytes"] > stats["kv_hbm_bytes"] / 2


class TestBucketedPrefill:
    """Prompt-length bucketing: admission pads prompts up to a small
    fixed set of lengths so prefill compiles once per bucket, ever —
    and padding must be invisible in the tokens (causality: rows and
    first-token logits depend only on positions < the true length)."""

    def test_default_buckets_are_powers_of_two_to_text_seq_len(self):
        assert prefill_buckets(8) == (1, 2, 4, 8)
        assert prefill_buckets(5) == (1, 2, 4, 5)
        assert prefill_buckets(1) == (1,)
        assert prefill_buckets(256)[-1] == 256

    def test_bucket_for_picks_smallest_holding_bucket(self):
        assert bucket_for(3, (1, 2, 4, 8)) == 4
        assert bucket_for(4, (1, 2, 4, 8)) == 4
        assert bucket_for(8, (1, 2, 4, 8)) == 8
        with pytest.raises(ValueError, match="largest bucket"):
            bucket_for(9, (1, 2, 4, 8))

    def test_engine_rejects_buckets_not_ending_at_text_seq_len(self,
                                                              bundle):
        params, _ = bundle
        with pytest.raises(ValueError, match="prefill_buckets"):
            Engine(params, CFG, RequestQueue(max_depth=2), num_slots=1,
                   prefill_buckets=(1, 2, 4))  # can't hold a full prompt

    def test_custom_buckets_share_one_prefill_program(self, bundle):
        """With a single bucket = text_seq_len, EVERY prompt length
        admits through ONE prefill program — and stays token-identical
        to the unpadded one-shot path."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r) for r in REQS[:2]]
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2,
                        prefill_buckets=(CFG.text_seq_len,))
        handles = [queue.submit(r) for r in REQS[:2]]
        with guards.compile_count(
                lambda: engine.prefill_traces, expect=1,
                label="single-bucket prefill"):
            engine.run_until_idle()
        for h, ref in zip(handles, refs):
            np.testing.assert_array_equal(
                np.asarray(h.result(timeout=5).tokens), ref)


class TestPrefillGroupSizedToNeed:
    """An admission prefills the smallest group of rows that holds it
    (``scheduler.prefill_groups``: a small group or all the slots): one
    request joining a busy engine does not pay for ``num_slots`` rows,
    and the group's size is invisible in the tokens."""

    @pytest.mark.parametrize("slots,groups", [
        (1, (1,)), (3, (3,)), (4, (4,)), (5, (4, 5)), (32, (4, 32))])
    def test_groups_are_a_small_one_and_all_the_slots(self, slots, groups):
        assert prefill_groups(slots) == groups

    @pytest.mark.parametrize("kv", ["dense", "paged"])
    def test_one_request_takes_the_small_group_a_burst_the_whole(
            self, bundle, kv):
        """Six slots: a lone request admits through the 4-row program, a
        burst of six through the 6-row one, each compiled once, under
        their own names; every stream equals the one-shot sampler's."""
        params, vae_params = bundle
        refs = [reference_tokens(params, vae_params, r) for r in REQS]
        queue = RequestQueue(max_depth=16)
        paged = dict(kv="paged", page_size=4) if kv == "paged" else {}
        engine = Engine(params, CFG, queue, num_slots=6,
                        prefill_buckets=(CFG.text_seq_len,), **paged)
        b = CFG.text_seq_len
        assert engine.prefill_groups == (4, 6)
        assert engine.stats()["prefill_groups"] == [4, 6]

        lone = queue.submit(REQS[0])
        assert engine.compile_pending()
        engine.run_until_idle()
        assert (engine.prefill_trace_count(b, 4),
                engine.prefill_trace_count(b)) == (1, 0)
        np.testing.assert_array_equal(
            np.asarray(lone.result(timeout=5).tokens), refs[0])

        burst = [queue.submit(r) for r in REQS + REQS]
        assert engine.compile_pending()     # the whole group is not built
        engine.run_until_idle()
        assert (engine.prefill_trace_count(b, 4),
                engine.prefill_trace_count(b)) == (1, 1)
        for h, ref in zip(burst, refs + refs):
            np.testing.assert_array_equal(
                np.asarray(h.result(timeout=5).tokens), ref)

        again = [queue.submit(r) for r in REQS[:2]]
        assert not engine.compile_pending()
        with guards.compile_count(lambda: engine.prefill_traces, expect=0,
                                  label="prefill groups, both built"):
            engine.run_until_idle()
        for h, ref in zip(again, refs):
            np.testing.assert_array_equal(
                np.asarray(h.result(timeout=5).tokens), ref)
        assert engine.prefill_runs == 3
        assert sorted(engine.device_scopes()) == sorted(
            [engine._decode_fn.__name__, f"prefill_b{b}",
             f"prefill_b{b}_g4"])


class TestBackpressure:
    def test_queue_full_is_typed_and_structured(self, bundle):
        params, _ = bundle
        events = []
        queue = RequestQueue(max_depth=2, on_event=events.append)
        for i in range(2):
            queue.submit(Request(codes=(1, 2), seed=i))
        with pytest.raises(QueueFull) as ei:
            queue.submit(Request(codes=(1, 2), seed=9))
        rec = ei.value.record
        assert rec["kind"] == "serve_reject"
        assert rec["reason"] == "queue_full"
        assert rec["queue_depth"] == 2
        assert events and events[0]["kind"] == "serve_reject"
        assert queue.rejected == 1

    def test_deadline_expired_in_queue(self, bundle):
        """A request whose deadline passes while queued completes as a
        typed deadline_exceeded result without ever taking a slot."""
        params, _ = bundle
        queue = RequestQueue(max_depth=4)
        engine = Engine(params, CFG, queue, num_slots=1)
        h = queue.submit(Request(codes=(1, 2), seed=0, deadline_s=0.0))
        time.sleep(0.01)
        engine.run_until_idle()
        res = h.result(timeout=5)
        assert res.status == DEADLINE_EXCEEDED
        assert "queued" in res.reason
        assert engine.decode_steps == 0     # never spent a slot on it

    def test_deadline_expired_mid_decode(self, bundle):
        """A deadline that passes while the request is decoding cancels
        the slot with a typed result; other slots keep their exact token
        streams."""
        params, vae_params = bundle
        ref = reference_tokens(params, vae_params, REQS[0])
        queue = RequestQueue(max_depth=4)
        engine = Engine(params, CFG, queue, num_slots=2)
        h_ok = queue.submit(REQS[0])
        h_dead = queue.submit(Request(codes=(2, 2), seed=1,
                                      deadline_s=0.005))
        engine.step_once()                  # both admitted, one step in
        time.sleep(0.02)                    # deadline passes mid-decode
        engine.run_until_idle()
        res = h_dead.result(timeout=5)
        assert res.status == DEADLINE_EXCEEDED
        assert "decoding" in res.reason
        np.testing.assert_array_equal(
            np.asarray(h_ok.result(timeout=5).tokens), ref)

    def test_expired_reaped_even_with_full_pool(self, bundle):
        """A dead queued entry must get its typed result (and stop
        holding queue capacity) even while every slot is busy — reaping
        is not gated on free slots."""
        params, _ = bundle
        queue = RequestQueue(max_depth=2)
        engine = Engine(params, CFG, queue, num_slots=1)
        queue.submit(Request(codes=(1, 1), seed=0))
        engine.step_once()                  # pool now full
        h_dead = queue.submit(Request(codes=(2, 2), seed=1,
                                      deadline_s=0.0))
        time.sleep(0.01)
        engine.step_once()                  # free == 0, still reaps
        res = h_dead.result(timeout=1)
        assert res.status == DEADLINE_EXCEEDED
        assert queue.depth() == 0           # capacity released

    def test_cancel_active_fulfills_inflight_slots(self, bundle):
        """Shutdown covers requests already admitted to slots, not just
        queued ones (the no-hangs contract through close())."""
        from dalle_pytorch_tpu.serve import CANCELLED
        params, _ = bundle
        queue = RequestQueue(max_depth=4)
        engine = Engine(params, CFG, queue, num_slots=2)
        h = queue.submit(Request(codes=(1, 2), seed=0))
        engine.step_once()                  # admitted, mid-decode
        assert engine.active_slots() == 1
        assert engine.cancel_active() == 1
        assert h.result(timeout=1).status == CANCELLED
        assert engine.active_slots() == 0

    def test_priority_orders_admission(self, bundle):
        """With one slot busy, a later high-priority (lower value) submit
        is admitted before an earlier low-priority one."""
        params, _ = bundle
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=1)
        running = queue.submit(Request(codes=(1, 1), seed=0))
        engine.step_once()                  # occupies the only slot
        low = queue.submit(Request(codes=(2, 2), seed=1, priority=5))
        high = queue.submit(Request(codes=(3, 3), seed=2, priority=0))
        order = []
        done = set()
        while len(done) < 3:
            engine.step_once()
            for name, h in (("running", running), ("low", low),
                            ("high", high)):
                if name not in done and h.done():
                    done.add(name)
                    order.append(name)
        assert order == ["running", "high", "low"]

    def test_requeue_preserves_arrival_order(self):
        """An evicted/page-deferred request re-enters at its ORIGINAL
        position in its priority class — later-arriving requests never
        leapfrog it (the scheduler half of the no-starvation
        guarantee)."""
        queue = RequestQueue(max_depth=8)
        a = queue.submit(Request(codes=(1,), seed=0))
        popped, _ = queue.pop_ready(1)
        assert popped == [a]
        b = queue.submit(Request(codes=(2,), seed=0))
        queue.requeue(a)
        popped, _ = queue.pop_ready(2)
        assert popped == [a, b]

    def test_requeue_after_drain_is_cancelled_not_stranded(self):
        """A requeue landing after the shutdown drain (engine thread
        outliving close()'s join timeout) must fulfil the handle as
        cancelled — the heap is dead, so enqueueing would strand the
        caller in result() forever."""
        queue = RequestQueue(max_depth=8)
        h = queue.submit(Request(codes=(1,), seed=0))
        queue.pop_ready(1)
        queue.close()
        assert queue.drain() == []
        queue.requeue(h)
        res = h.result(timeout=1)
        assert res.status == "cancelled"
        assert queue.depth() == 0


class TestFaultHardening:
    """A malformed or unlucky request must produce a typed reject/error —
    never a dead serving loop (the no-hangs contract under faults)."""

    def test_invalid_prompt_typed_reject_at_submit(self, bundle):
        params, vae_params = bundle
        from dalle_pytorch_tpu.serve.server import InferenceServer
        server = InferenceServer(params, vae_params, CFG, num_slots=1,
                                 queue_depth=4, decode_images=False)
        too_long = tuple(range(CFG.text_seq_len + 1))
        with pytest.raises(InvalidRequest) as ei:
            server.submit(too_long)
        rec = ei.value.record
        assert rec["reason"] == "invalid_prompt"
        assert rec["prompt_len"] == CFG.text_seq_len + 1
        assert rec["max_prompt_len"] == CFG.text_seq_len
        with pytest.raises(InvalidRequest):
            server.submit(())
        server.close()

    def test_malformed_admission_errors_not_crashes(self, bundle):
        """A raw queue has no prompt validation; the engine must turn an
        impossible prompt into a typed error result at admission and keep
        serving the well-formed request behind it."""
        params, vae_params = bundle
        ref = reference_tokens(params, vae_params, REQS[0])
        queue = RequestQueue(max_depth=8)       # no max_prompt_len
        engine = Engine(params, CFG, queue, num_slots=2)
        h_bad = queue.submit(Request(
            codes=tuple(range(CFG.text_seq_len + 3)), seed=0))
        h_ok = queue.submit(REQS[0])
        engine.run_until_idle()
        res = h_bad.result(timeout=5)
        assert res.status == ERROR
        assert "invalid prompt length" in res.reason
        np.testing.assert_array_equal(
            np.asarray(h_ok.result(timeout=5).tokens), ref)

    def test_run_loop_survives_step_exception(self, bundle):
        """An exception out of a decode step must fail the in-slot
        requests with typed error results and leave the serving thread
        alive and correct for the next request."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2)
        good_fn = engine._decode_fn

        def boom(*a, **k):
            raise RuntimeError("injected decode fault")

        h_bad = queue.submit(REQS[0])
        engine._decode_fn = boom
        stop = threading.Event()
        t = threading.Thread(target=engine.run, args=(stop,), daemon=True)
        t.start()
        try:
            res = h_bad.result(timeout=30)
            assert res.status == ERROR
            assert "injected decode fault" in res.reason
            assert t.is_alive(), "serving loop died on a step exception"
            # recovered: the same engine serves the next request with
            # token-exact results (admission rewrites the slot state)
            engine._decode_fn = good_fn
            ref = reference_tokens(params, vae_params, REQS[1])
            h_ok = queue.submit(REQS[1])
            np.testing.assert_array_equal(
                np.asarray(h_ok.result(timeout=60).tokens), ref)
        finally:
            stop.set()
            t.join(10)

    def test_submit_racing_close_is_typed_reject(self, bundle):
        params, vae_params = bundle
        from dalle_pytorch_tpu.serve.server import InferenceServer
        server = InferenceServer(params, vae_params, CFG, num_slots=1,
                                 queue_depth=4, decode_images=False)
        server.close()
        with pytest.raises(QueueClosed) as ei:
            server.submit((1, 2))
        assert ei.value.record["reason"] == "queue_closed"


class TestBurstOccupancy:
    def test_burst_fills_slots_and_decodes_concurrently(self, bundle):
        """A burst larger than the pool keeps every slot busy — the
        continuous-batching win over one-at-a-time gen_dalle."""
        params, _ = bundle
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=3)
        handles = [queue.submit(Request(codes=(1 + i, 2), seed=i))
                   for i in range(6)]
        engine.step_once()
        assert engine.active_slots() == 3   # full pool from the burst
        engine.run_until_idle()
        assert all(h.result(5).status == OK for h in handles)
        stats = engine.stats()
        assert stats["mean_occupancy"] > 1.5
        assert stats["decode_compiles"] == 1
        assert stats["completed"] == 6


class TestServerPipeline:
    def test_server_decodes_images_and_matches_one_shot(self, bundle):
        """The full pipeline (queue -> engine thread -> postprocess
        thread): the returned image equals generate_images' decoded
        pixels for the same request."""
        params, vae_params = bundle
        from dalle_pytorch_tpu.serve.server import InferenceServer
        req = REQS[0]
        text = jnp.asarray([req.codes], jnp.int32)
        ref_img = np.asarray(D.generate_images(
            params, vae_params, text, cfg=CFG,
            rng=jax.random.PRNGKey(req.seed)))[0]

        server = InferenceServer(params, vae_params, CFG, num_slots=2,
                                 queue_depth=8).start()
        try:
            res = server.generate(req.codes, seed=req.seed, timeout=60)
            assert res.status == OK
            np.testing.assert_allclose(res.image, ref_img, rtol=1e-5,
                                       atol=1e-5)
            stats = server.stats()
            assert stats["completed"] == 1
            # latency is recorded at fulfillment, AFTER postprocess time
            # lands in total_s — the percentile must equal what the
            # caller saw, not the decode-only number
            assert stats["p50_latency_s"] == round(res.total_s, 4)
        finally:
            server.close()

    def test_clip_scores_completed_text_span_like_one_shot(self, bundle):
        """CLIP rerank through the pipeline scores the COMPLETED text
        span — for a prompt shorter than text_seq_len the score must
        match generate_images' rerank (which scores full[:, :text_seq_len]
        including the model-sampled text tokens), not a zero-padded
        prompt."""
        params, vae_params = bundle
        from dalle_pytorch_tpu.models import clip as C
        from dalle_pytorch_tpu.serve.server import InferenceServer
        clip_cfg = C.CLIPConfig(
            dim_text=16, dim_image=16, dim_latent=16,
            num_text_tokens=CFG.num_text_tokens,
            text_enc_depth=1, text_seq_len=CFG.text_seq_len, text_heads=2,
            visual_enc_depth=1, visual_heads=2,
            visual_image_size=VCFG.image_size, visual_patch_size=8,
            sparse_attn=False)
        clip_params = C.clip_init(jax.random.PRNGKey(7), clip_cfg)
        req = REQS[0]                       # len 3 < text_seq_len 8
        text = jnp.asarray([req.codes], jnp.int32)
        _, ref_scores = D.generate_images(
            params, vae_params, text, cfg=CFG,
            rng=jax.random.PRNGKey(req.seed),
            clip_params=clip_params, clip_cfg=clip_cfg)

        server = InferenceServer(params, vae_params, CFG, num_slots=2,
                                 queue_depth=8, clip_params=clip_params,
                                 clip_cfg=clip_cfg).start()
        try:
            res = server.generate(req.codes, seed=req.seed, timeout=60)
            assert res.status == OK
            assert len(res.text_tokens) == CFG.text_seq_len
            np.testing.assert_array_equal(res.text_tokens[:len(req.codes)],
                                          req.codes)
            np.testing.assert_allclose(
                res.clip_score, float(np.asarray(ref_scores)[0]),
                rtol=1e-4, atol=1e-5)
        finally:
            server.close()

    def test_server_close_cancels_queued(self, bundle):
        params, vae_params = bundle
        from dalle_pytorch_tpu.serve import CANCELLED
        from dalle_pytorch_tpu.serve.server import InferenceServer
        server = InferenceServer(params, vae_params, CFG, num_slots=1,
                                 queue_depth=8, decode_images=False)
        # never started: everything queued is cancelled with a typed
        # result at close
        h = server.submit((1, 2), seed=0)
        server.close()
        assert h.result(timeout=5).status == CANCELLED

    def test_http_generate_and_stats(self, bundle):
        """The stdlib HTTP facade end-to-end on a loopback port."""
        import json
        import urllib.request
        params, vae_params = bundle
        from dalle_pytorch_tpu.serve.server import (InferenceServer,
                                                    make_http_server)
        server = InferenceServer(params, vae_params, CFG, num_slots=2,
                                 queue_depth=8,
                                 decode_images=False).start()
        httpd = make_http_server(server, "127.0.0.1", 0)   # ephemeral port
        port = httpd.server_address[1]
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        try:
            body = json.dumps({"codes": [3, 7, 9], "seed": 11}).encode()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/generate", data=body,
                    timeout=60) as resp:
                out = json.loads(resp.read())
            assert out["status"] == "ok"
            ref = reference_tokens(params, vae_params, REQS[0])
            assert out["tokens"] == [int(t) for t in ref]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/stats", timeout=10) as resp:
                stats = json.loads(resp.read())
            assert stats["completed"] == 1
            assert stats["decode_compiles"] == 1
            # a malformed request is a 400 at the edge — it must never
            # reach (and kill) the engine thread
            import urllib.error
            bad = json.dumps(
                {"codes": list(range(CFG.text_seq_len + 1))}).encode()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/generate", data=bad,
                    timeout=10)
            assert ei.value.code == 400
            assert json.loads(ei.value.read())["reason"] == "invalid_prompt"
            # the serving loop is still alive and healthy afterwards
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
                assert json.loads(resp.read())["ok"] is True
            body2 = json.dumps({"codes": [6, 6], "seed": 5,
                                "temperature": 1.3, "top_p": 0.9}).encode()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/generate", data=body2,
                    timeout=60) as resp:
                out2 = json.loads(resp.read())
            assert out2["status"] == "ok"
            ref2 = reference_tokens(params, vae_params, REQS[2])
            assert out2["tokens"] == [int(t) for t in ref2]
        finally:
            httpd.shutdown()
            httpd.server_close()
            server.close()


class TestSamplingValidation:
    def test_bad_sampling_params_raise_at_construction(self):
        with pytest.raises(ValueError, match="temperature"):
            SamplingParams(temperature=0.0)
        with pytest.raises(ValueError, match="top_p"):
            SamplingParams(top_p=1.5)
