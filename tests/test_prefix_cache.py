"""Cross-request prefix cache + per-request CFG tests (ISSUE 13).

The load-bearing contracts:

  * WARM-HIT BYTE-IDENTITY: a prompt admitted through the prefix cache's
    warm path (shared pages mapped refcounted, boundary page forked
    copy-on-write, first token sampled from the cached last hidden row —
    zero prefill FLOPs) emits tokens byte-identical to a cold run of the
    same request, across fused chunk sizes K, both paged-attention
    impls (gather / Pallas kernel in interpret mode), and both cache
    dtypes (fp32 / int8) — with ``decode_traces == 1`` and the warm
    steady state transfer-clean under ``guards.no_transfers``.
  * REFCOUNTED COW SAFETY: a page mapped by several block tables (or
    held by the index) returns to the free list only at refcount zero —
    eviction of one sharer must never hand a sibling's page to the next
    allocation (the satellite bugfix), and release past zero is the
    typed ``PageReleaseUnderflow``.
  * PER-REQUEST CFG: ``Request.cfg_scale > 0`` admits a cond/uncond
    slot pair whose emitted tokens are byte-identical to
    ``generate_images(guidance=scale)``, with the guided mix inside the
    ONE fused decode program, pair-atomic teardown, and (with the
    prefix cache) physical sharing of every cacheable prompt span.
  * FAULT COMPOSITION: a replica crash mid-decode replays a CFG pair on
    a survivor with byte-identical tokens (the fault-catalog row the
    satellite names).

All CPU, tiny model (total_len 24) so the file stays cheap in tier-1.
"""

import time

import numpy as np
import pytest

from dalle_pytorch_tpu.analysis import guards
from dalle_pytorch_tpu.serve import (ERROR, OK, PageAllocator,
                                     PageReleaseUnderflow, PrefixEntry,
                                     PrefixIndex, Request, RequestQueue,
                                     SamplingParams, pages_for)
from dalle_pytorch_tpu.serve.engine import Engine
from tiny_model import CFG, bundle, reference_tokens  # noqa: F401

# len-8 prompt: two FULL pages at page_size 4 (physical sharing), one
# full page at page_size 8 (the kernel's tile minimum); len-5 prompt:
# exercises the partial-boundary COW snapshot at both page sizes
P8 = (4, 1, 2, 3, 5, 6, 7, 2)
P5 = (5, 2, 8, 1, 4)


def drain_tokens(engine, queue, reqs, timeout=30):
    handles = [queue.submit(r) for r in reqs]
    engine.run_until_idle()
    out = []
    for h in handles:
        res = h.result(timeout=timeout)
        assert res.status == OK, (res.status, res.reason)
        out.append(np.asarray(res.tokens))
    return out


class TestRefcountedAllocator:
    def test_retain_release_frees_only_at_zero(self):
        alloc = PageAllocator(6)
        pages = alloc.alloc(3)
        assert alloc.in_use == 3 and alloc.pages_shared == 0
        alloc.retain(pages[:2])
        assert alloc.pages_shared == 2
        assert alloc.refs_saved == 2
        # in_use counts PHYSICAL pages: sharing never inflates it
        assert alloc.in_use == 3
        alloc.release(pages)            # first reference drops
        assert alloc.in_use == 2        # only the unshared page freed
        assert alloc.free == 3
        alloc.release(pages[:2])        # second reference drops
        assert alloc.in_use == 0 and alloc.free == 5

    def test_release_past_zero_is_typed_underflow(self):
        alloc = PageAllocator(4)
        pages = alloc.alloc(1)
        alloc.release(pages)
        with pytest.raises(PageReleaseUnderflow, match="double release"):
            alloc.release(pages)
        rec = pytest.raises(
            PageReleaseUnderflow, alloc.release, pages).value.record
        assert rec["kind"] == "serve_page_release_underflow"
        assert rec["page"] == pages[0]
        # the underflow is still a ValueError: pre-refcount callers that
        # matched the double-release guard keep matching
        assert isinstance(PageReleaseUnderflow(rec), ValueError)

    def test_retain_of_free_page_is_hard_error(self):
        alloc = PageAllocator(4)
        pages = alloc.alloc(1)
        alloc.release(pages)
        with pytest.raises(ValueError, match="retain of free page"):
            alloc.retain(pages)
        with pytest.raises(ValueError, match="never allocatable"):
            alloc.retain([0])           # the trash page

    def test_shared_page_survives_one_owners_release(self):
        """The eviction-victim bugfix in allocator form: two owners map
        one page; the first teardown must NOT return it to the free
        list — the next alloc must hand out a DIFFERENT page."""
        alloc = PageAllocator(8)
        (shared,) = alloc.alloc(1)
        alloc.retain([shared])
        alloc.release([shared])         # owner 1 (the eviction victim)
        fresh = alloc.alloc(3)
        assert shared not in fresh, \
            "a still-referenced page was handed to a new owner"
        alloc.release([shared])         # owner 2 -> now truly free


class TestPrefixIndexUnit:
    def _entry(self, alloc, key, codes, pages):
        return PrefixEntry(key, codes, len(codes), pages, None,
                           h_last=None)

    def test_collision_reads_as_miss_never_wrong_kv(self):
        alloc = PageAllocator(8)
        idx = PrefixIndex(alloc)
        pages = alloc.alloc(2)
        idx.insert(self._entry(alloc, "k1", (1, 2, 3), pages))
        assert idx.lookup("k1", (1, 2, 3)) is not None
        # same key, different tokens (a hash collision): MISS — the
        # stored tuple verifies what the hash only addresses
        assert idx.lookup("k1", (9, 9, 9)) is None

    def test_lru_capacity_and_shrink_release_references(self):
        alloc = PageAllocator(16)
        idx = PrefixIndex(alloc, max_entries=2)
        held = []
        for i in range(3):
            pages = alloc.alloc(2)
            held.append(pages)
            idx.insert(self._entry(alloc, f"k{i}", (i,), pages))
            alloc.release(pages)        # the "slot" reference drops
        # capacity 2: k0 was evicted LRU, its pages truly freed
        assert len(idx) == 2
        assert idx.lookup("k0", (0,)) is None
        assert alloc.in_use == 4
        # shrink until 20 pages would be free -> drops everything
        idx.shrink(20)
        assert len(idx) == 0 and alloc.in_use == 0

    def test_engine_gate_prefix_requires_paged(self, bundle):
        params, _ = bundle
        with pytest.raises(ValueError, match="prefix_cache requires"):
            Engine(params, CFG, RequestQueue(max_depth=2), num_slots=1,
                   prefix_cache=True)


class TestWarmHitEquivalence:
    """The tentpole acceptance: warm-hit tokens byte-identical to a
    cold run, across K x paged-attention impl x cache dtype — and the
    warm path genuinely skips prefill (``prefill_runs`` frozen)."""

    @pytest.mark.parametrize("k,impl,quant", [
        (1, "gather", False),
        (8, "gather", False),
        (1, "kernel", False),
        (8, "kernel", False),
        (8, "gather", True),
        (8, "kernel", True),
    ])
    def test_warm_hit_tokens_byte_identical_to_cold(self, bundle, k,
                                                    impl, quant):
        params, vae_params = bundle
        # gather at page_size 4 exercises 2-full-page sharing AND the
        # boundary snapshot (P5); the kernel's 8-row tile floor makes
        # P8 one full shared page and P5 snapshot-only
        ps = 4 if impl == "gather" else 8
        reqs = [Request(codes=P8, seed=3), Request(codes=P5, seed=7),
                Request(codes=P8, seed=11), Request(codes=P5, seed=13)]
        cold_q = RequestQueue(max_depth=8)
        cold_e = Engine(params, CFG, cold_q, num_slots=2, chunk_steps=k,
                        kv="paged", page_size=ps, paged_attn=impl,
                        quantize_cache=quant)
        cold = drain_tokens(cold_e, cold_q, reqs)

        q = RequestQueue(max_depth=8)
        e = Engine(params, CFG, q, num_slots=2, chunk_steps=k,
                   kv="paged", page_size=ps, paged_attn=impl,
                   quantize_cache=quant, prefix_cache=True)
        # cold pass populates the index...
        warm0 = drain_tokens(e, q, reqs[:2])
        runs_after_cold = e.prefill_runs
        # ...and the second pass of the SAME prompts admits warm: zero
        # prefill dispatches, tokens byte-identical to the cold engine
        warm1 = drain_tokens(e, q, reqs[2:])
        assert e.prefill_runs == runs_after_cold, \
            "warm hits must not dispatch prefill"
        assert e.prefix_hits == 2
        assert e.warm_admits == 2
        assert e.decode_traces == 1
        assert e.warm_admit_traces == 1
        for got, want in zip(warm0 + warm1, cold):
            np.testing.assert_array_equal(got, want)
        # fp32 gather additionally pins the one-shot oracle directly
        if impl == "gather" and not quant:
            for got, r in zip(warm0 + warm1, reqs):
                np.testing.assert_array_equal(
                    got, reference_tokens(params, vae_params, r))

    def test_warm_admission_is_transfer_clean(self, bundle):
        """Steady state with a WARM mid-stream join under
        ``guards.no_transfers``: shared-page mapping, the COW boundary
        fork, and the warm-admission program are all explicit device
        traffic — and the fused decode program never retraces."""
        params, vae_params = bundle
        q = RequestQueue(max_depth=8)
        e = Engine(params, CFG, q, num_slots=2, chunk_steps=4,
                   kv="paged", page_size=4, prefix_cache=True)
        drain_tokens(e, q, [Request(codes=P8, seed=1)])   # seed index
        drain_tokens(e, q, [Request(codes=P8, seed=2)])   # warm compile
        h_a = q.submit(Request(codes=(3, 7, 9), seed=3))
        e.step_once()               # a admitted, chunk 1 in flight
        with guards.no_transfers():
            h_b = q.submit(Request(codes=P8, seed=4))
            e.step_once()           # WARM join + chunk + harvest
            e.step_once()           # pure steady-state chunk
        e.run_until_idle()
        np.testing.assert_array_equal(
            np.asarray(h_b.result(timeout=5).tokens),
            reference_tokens(params, vae_params,
                             Request(codes=P8, seed=4)))
        assert h_a.result(timeout=5).status == OK
        assert e.decode_traces == 1

    def test_fanout_same_batch_shares_prompt_span_once(self, bundle):
        """N samples of ONE prompt submitted together: the first row
        prefills cold and inserts; its siblings admit warm IN THE SAME
        admission — the shared span is allocated once, and peak pages
        obey pages(1 request) + N x pages(private span)."""
        params, vae_params = bundle
        ps, n = 4, 3
        q = RequestQueue(max_depth=8)
        e = Engine(params, CFG, q, num_slots=n, kv="paged", page_size=ps,
                   prefix_cache=True)
        reqs = [Request(codes=P8, seed=s) for s in (1, 2, 3)]
        handles = [q.submit(r) for r in reqs]
        e.step_once()
        assert e.active_slots() == n
        assert e.prefix_hits == n - 1      # one cold, two warm-after
        shared_full = len(P8) // ps
        st = e.stats()
        assert st["pages_shared"] == shared_full
        full = pages_for(CFG.seq_len, ps)
        # physical accounting mid-decode: never more than one full map
        # plus (n-1) private spans (map-ahead grows lazily below that)
        assert e.alloc.in_use <= full + (n - 1) * (full - shared_full)
        e.run_until_idle()
        # peak: the shared span was allocated ONCE — one full request
        # plus n-1 private (generated + boundary) spans, strictly under
        # the refcount-blind n x full
        assert e.alloc.peak_in_use \
            == full + (n - 1) * (full - shared_full)
        assert e.alloc.peak_in_use <= full + n * (full - shared_full)
        for h, r in zip(handles, reqs):
            np.testing.assert_array_equal(
                np.asarray(h.result(timeout=5).tokens),
                reference_tokens(params, vae_params, r))
        # drained: only the index's own references remain resident
        assert e.alloc.in_use == shared_full
        assert e.prefix.pages_held == shared_full

    def test_cow_fork_under_mid_decode_eviction(self, bundle):
        """The COW fork x eviction composition (satellite): two sharers
        of one prompt span on a pool too small for both to finish — the
        victim's release must NOT free the still-shared pages (the
        sibling keeps decoding against them), and the victim replays to
        the exact cold stream after re-admission."""
        params, vae_params = bundle
        reqs = [Request(codes=P8, seed=1),
                Request(codes=P8, seed=2, priority=7)]   # the victim
        q = RequestQueue(max_depth=8)
        # 6 pages/full sequence at ps 4; 9 usable is a genuine
        # overcommit for two mid-sequence requests sharing 2
        e = Engine(params, CFG, q, num_slots=2, chunk_steps=4,
                   kv="paged", page_size=4, num_pages=10,
                   prefix_cache=True)
        handles = [q.submit(r) for r in reqs]
        with guards.compile_count(lambda: e.decode_traces, expect=1,
                                  label="decode under COW eviction"):
            e.run_until_idle()
        assert e.evicted >= 1, "pool was sized to force eviction"
        for h, r in zip(handles, reqs):
            res = h.result(timeout=5)
            assert res.status == OK
            np.testing.assert_array_equal(
                np.asarray(res.tokens),
                reference_tokens(params, vae_params, r))
        # the shared span survived every teardown exactly as the
        # index's references say it should
        assert e.alloc.in_use == e.prefix.pages_held

    def test_index_shrinks_before_live_request_eviction(self, bundle):
        """Page pressure drops cached prefixes (LRU) FIRST: with the
        pool nearly full of index-held entries, a fresh admission must
        shrink the cache instead of deferring or evicting live work."""
        params, vae_params = bundle
        q = RequestQueue(max_depth=8)
        e = Engine(params, CFG, q, num_slots=2, chunk_steps=24,
                   kv="paged", page_size=4, num_pages=8,
                   prefix_cache=True)
        drain_tokens(e, q, [Request(codes=P8, seed=1)])
        assert len(e.prefix) == 1
        # capacity 7, index holds 2; a full-sequence admission needs 6
        got = drain_tokens(e, q, [Request(codes=(1, 2, 3, 4, 5, 6),
                                          seed=9)])[0]
        np.testing.assert_array_equal(
            got, reference_tokens(params, vae_params,
                                  Request(codes=(1, 2, 3, 4, 5, 6),
                                          seed=9)))
        assert e.evicted == 0, \
            "cache entries must be dropped before live work"


class TestPerRequestCFG:
    def test_guided_tokens_match_one_shot_guidance(self, bundle):
        """cfg_scale through the engine == generate_images(guidance=s),
        byte-for-byte, on both KV layouts — with one decode compile."""
        params, vae_params = bundle
        req = Request(codes=P5, seed=11, cfg_scale=2.0)
        ref = reference_tokens(params, vae_params, req)
        for kw in (dict(kv="paged", page_size=4, prefix_cache=True),
                   dict()):
            q = RequestQueue(max_depth=4)
            e = Engine(params, CFG, q, num_slots=2, **kw)
            with guards.compile_count(lambda: e.decode_traces, expect=1,
                                      label="guided decode program"):
                got = drain_tokens(e, q, [req])[0]
            np.testing.assert_array_equal(got, ref)
            assert e.cfg_pairs == 1
            assert e.stats()["cfg_pairs"] == 1

    def test_guided_and_plain_share_the_pool(self, bundle):
        """A guided pair and plain requests decode side by side in one
        slot pool — each stream exact, shadow tokens never credited."""
        params, vae_params = bundle
        reqs = [Request(codes=P5, seed=11, cfg_scale=1.5),
                Request(codes=(3, 7, 9), seed=5),
                Request(codes=(6, 6), seed=23,
                        sampling=SamplingParams(temperature=0.7))]
        q = RequestQueue(max_depth=8)
        e = Engine(params, CFG, q, num_slots=3, kv="paged", page_size=4)
        got = drain_tokens(e, q, reqs)
        for g, r in zip(got, reqs):
            np.testing.assert_array_equal(
                g, reference_tokens(params, vae_params, r))
        # tokens_decoded counts DELIVERED tokens: the uncond shadow's
        # mirrored stream must not double-count
        assert e.tokens_decoded == sum(
            CFG.seq_len - len(r.codes) for r in reqs)
        assert e.alloc.in_use == 0

    def test_second_guided_request_shares_prompt_and_null_spans(
            self, bundle):
        """The affordability claim: with the prefix cache, a repeat
        guided request admits BOTH pair members warm — the null caption
        is one cache entry for all guided traffic of that length."""
        params, vae_params = bundle
        r1 = Request(codes=P8, seed=5, cfg_scale=1.5)
        r2 = Request(codes=P8, seed=9, cfg_scale=1.5)
        q = RequestQueue(max_depth=8)
        e = Engine(params, CFG, q, num_slots=2, kv="paged", page_size=4,
                   prefix_cache=True)
        np.testing.assert_array_equal(
            drain_tokens(e, q, [r1])[0],
            reference_tokens(params, vae_params, r1))
        assert e.prefix_hits == 0
        np.testing.assert_array_equal(
            drain_tokens(e, q, [r2])[0],
            reference_tokens(params, vae_params, r2))
        assert e.prefix_hits == 2      # cond AND uncond admitted warm
        assert e.cfg_pairs == 2
        assert e.prefill_runs == 1     # one cold group, ever

    def test_pair_expires_and_tears_down_atomically(self, bundle):
        """A guided request's deadline mid-decode kills BOTH slots and
        frees both page sets; a plain neighbour is untouched."""
        params, vae_params = bundle
        ref = reference_tokens(params, vae_params,
                               Request(codes=(3, 7, 9), seed=5))
        q = RequestQueue(max_depth=4)
        e = Engine(params, CFG, q, num_slots=3, kv="paged", page_size=4)
        h_ok = q.submit(Request(codes=(3, 7, 9), seed=5))
        h_dead = q.submit(Request(codes=P5, seed=1, cfg_scale=2.0,
                                  deadline_s=0.005))
        e.step_once()
        assert e.active_slots() == 3       # plain + cond + shadow
        time.sleep(0.02)
        e.run_until_idle()
        res = h_dead.result(timeout=5)
        assert res.status == "deadline_exceeded"
        assert e.active_slots() == 0
        assert e.alloc.in_use == 0         # both members' pages freed
        np.testing.assert_array_equal(
            np.asarray(h_ok.result(timeout=5).tokens), ref)

    def test_guidance_needs_two_slots_typed_error(self, bundle):
        params, _ = bundle
        q = RequestQueue(max_depth=4)
        e = Engine(params, CFG, q, num_slots=1)
        h = q.submit(Request(codes=(1, 2), seed=0, cfg_scale=2.0))
        e.run_until_idle()
        res = h.result(timeout=5)
        assert res.status == ERROR
        assert "cfg_scale" in res.reason

    def test_negative_cfg_scale_rejected_at_construction(self):
        with pytest.raises(ValueError, match="cfg_scale"):
            Request(codes=(1, 2), cfg_scale=-0.5)

    def test_server_submit_and_default_scale(self, bundle):
        """The server surface: per-request cfg_scale and the server-wide
        default both reach the engine."""
        params, vae_params = bundle
        from dalle_pytorch_tpu.serve.server import InferenceServer
        req = Request(codes=P5, seed=11, cfg_scale=2.0)
        ref = reference_tokens(params, vae_params, req)
        server = InferenceServer(params, vae_params, CFG, num_slots=2,
                                 queue_depth=8, kv="paged", page_size=4,
                                 prefix_cache=True,
                                 default_cfg_scale=2.0,
                                 decode_images=False).start()
        try:
            res = server.generate(req.codes, seed=req.seed, timeout=60)
            assert res.status == OK            # default scale applied
            np.testing.assert_array_equal(np.asarray(res.tokens), ref)
            res2 = server.generate(req.codes, seed=req.seed,
                                   cfg_scale=0.0, timeout=60)
            np.testing.assert_array_equal(
                np.asarray(res2.tokens),
                reference_tokens(params, vae_params,
                                 Request(codes=P5, seed=11)))
            stats = server.stats()
            assert stats["cfg_pairs"] == 1
            assert stats["prefix_cache"] is True
        finally:
            server.close()


class TestCFGFailover:
    pytestmark = pytest.mark.faults

    def test_guided_pair_replays_on_survivor_replica(self, bundle):
        """The fault-catalog row the satellite names: replica 1 of 2
        crashes mid-decode while guided and plain requests are in
        flight; every request — the CFG pair included — completes on a
        survivor with tokens byte-identical to the undisturbed run."""
        from dalle_pytorch_tpu.resilience import faults
        from dalle_pytorch_tpu.resilience.retry import RetryPolicy
        from dalle_pytorch_tpu.serve.replica import ReplicaSet
        params, vae_params = bundle
        faults.deactivate()
        reqs = [Request(codes=P5, seed=11, cfg_scale=2.0),
                Request(codes=(3, 7, 9), seed=5),
                Request(codes=P8, seed=7, cfg_scale=1.5),
                Request(codes=(6, 6), seed=13)]
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4, kv="paged", page_size=4,
                        prefix_cache=True,
                        bringup_policy=RetryPolicy(
                            max_attempts=1, deadline_s=None,
                            base_backoff_s=0.01, backoff_multiplier=2.0,
                            max_backoff_s=0.1, jitter=0.0))
        handles = [queue.submit(r) for r in reqs]
        try:
            with faults.injected(fault_replica=1,
                                 replica_crash_at_chunk=2):
                rs.run_until_idle()
        finally:
            faults.deactivate()
        assert rs.failovers == 1
        for h, r in zip(handles, reqs):
            res = h.result(timeout=10)
            assert res.status == OK, (r, res.status, res.reason)
            np.testing.assert_array_equal(
                np.asarray(res.tokens),
                reference_tokens(params, vae_params, r))


class TestStatsSurface:
    def test_prefix_and_sharing_stats(self, bundle):
        """/stats counts a shared page ONCE and carries the new gauges
        (the satellite): prefix_hits / pages_shared / cfg_pairs, with
        pages_in_use and kv_hbm_bytes refcount-aware — the live pool
        bytes equal the layout model regardless of sharing."""
        from dalle_pytorch_tpu.serve import kv_pool as KV
        from dalle_pytorch_tpu.serve.mesh_engine import hbm_report
        params, _ = bundle
        q = RequestQueue(max_depth=8)
        e = Engine(params, CFG, q, num_slots=3, kv="paged", page_size=4,
                   prefix_cache=True)
        for s in (1, 2, 3):
            q.submit(Request(codes=P8, seed=s))
        e.step_once()
        st = e.stats()
        assert st["prefix_cache"] is True
        assert st["prefix_hits"] == 2
        assert st["pages_shared"] == 2
        # 2 pages x 3 extra refs each (two warm slots + the index)
        assert st["pages_shared_saved"] == 6
        assert st["prefill_runs"] == 1
        assert st["warm_admits"] == 2
        # physical accounting: the pool's resident bytes are the
        # ALLOCATED arrays, invariant under sharing, and equal to the
        # config model — sharing shows up as fewer pages_in_use, never
        # as phantom bytes
        assert st["kv_hbm_bytes"] == KV.modeled_kv_bytes(
            CFG.transformer, kv="paged", num_slots=3,
            total_len=CFG.seq_len, page_size=4)
        assert st["pages_in_use"] == e.alloc.in_use
        rep = hbm_report(e)
        assert rep["kv_hbm_bytes"] == st["kv_hbm_bytes"]
        e.run_until_idle()

    def test_admission_timing_surface(self, bundle):
        """The always-on loop counters time cold and warm admissions
        alike, with no sync put into the admission they time:
        ``admit_prefill_s`` (inside the prefill / warm-admit call) grows
        with each of ``prefill_runs`` + ``warm_admits`` and stays inside
        ``admit_s`` — what bench's prefix_compare reads."""
        params, _ = bundle
        q = RequestQueue(max_depth=8)
        e = Engine(params, CFG, q, num_slots=2, kv="paged", page_size=4,
                   prefix_cache=True)
        seen = [e.stats()]
        # 1st: cold prefill; 2nd and 3rd: warm admissions
        for s in (1, 2, 3):
            q.submit(Request(codes=P8, seed=s))
            e.run_until_idle()
            seen.append(e.stats())
        assert [s["prefill_runs"] for s in seen] == [0, 1, 1, 1]
        assert [s["warm_admits"] for s in seen] == [0, 0, 1, 2]
        for a, b in zip(seen, seen[1:]):
            assert b["admit_prefill_s"] > a["admit_prefill_s"]
            assert b["admit_s"] - a["admit_s"] \
                >= b["admit_prefill_s"] - a["admit_prefill_s"]
