"""Best-of-N fan-out tests (serve/fanout.py + the group lifecycle
through engine, replica set, and COW page sharing).

The load-bearing one is the equivalence matrix: every member of a
best-of-N group is an ORDINARY request — its tokens byte-identical to
a standalone request submitted with the derived ``sample_seed(seed,
i)`` — across dense/paged KV, gather/kernel paged reads, and fp32/int8
KV. That identity is what makes groups compose with eviction replay,
failover, and migration for free. Plus: COW accounting (a group's
lifetime page peak is bounded by ONE prompt span + N generation
spans), atomic admission (a mid-group queue reject cancels the
already-admitted prefix), group-atomic completion and ranked assembly,
and THE resilience criterion — a replica killed mid-group loses zero
samples, and the multiplexed stream's high-water marks dedupe the
replay so every position still arrives exactly once.

Tiny model (test_serve's 24-position config), all CPU, tier-1 cheap.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.resilience import faults
from dalle_pytorch_tpu.serve import (OK, QueueFull, Request,
                                     RequestQueue, pages_for)
from dalle_pytorch_tpu.serve import scheduler as S
from dalle_pytorch_tpu.serve.engine import Engine
from tiny_model import (CFG, VCFG, _no_leaked_plan,  # noqa: F401
                        bundle)
from dalle_pytorch_tpu.serve.fanout import (group_pages_saved,
                                            rank_samples, sample_seed,
                                            submit_group)

_REF_CACHE: dict = {}


def reference_tokens(params, vae_params, req: Request,
                     quantize_cache=False) -> np.ndarray:
    key = (req.codes, req.seed, quantize_cache)
    if key not in _REF_CACHE:
        text = jnp.asarray([req.codes], jnp.int32)
        _, img_seq = D.generate_images(
            params, vae_params, text, cfg=CFG,
            rng=jax.random.PRNGKey(req.seed), return_img_seq=True,
            quantize_cache=quantize_cache)
        _REF_CACHE[key] = np.asarray(img_seq)[0]
    return _REF_CACHE[key]


# ---------------------------------------------------------------------------
# pure functions
# ---------------------------------------------------------------------------


class TestSampleSeed:
    def test_index_zero_is_identity(self):
        """best-of-1 must be byte-identical to a plain request."""
        for seed in (0, 1, 42, 2**31, 2**32 - 1):
            assert sample_seed(seed, 0) == seed

    def test_distinct_and_deterministic(self):
        seeds = [sample_seed(42, i) for i in range(64)]
        assert len(set(seeds)) == 64
        assert seeds == [sample_seed(42, i) for i in range(64)]
        assert all(0 <= s < 2**32 for s in seeds)

    def test_different_base_seeds_diverge(self):
        a = {sample_seed(1, i) for i in range(32)}
        b = {sample_seed(2, i) for i in range(32)}
        assert len(a & b) <= 1      # avalanche: essentially disjoint


class TestPagesSaved:
    def test_cow_dividend(self):
        assert group_pages_saved(4, 32, 8) == 3 * 4
        # partial boundary page saves nothing (forked private)
        assert group_pages_saved(4, 35, 8) == 3 * 4
        assert group_pages_saved(1, 32, 8) == 0     # singleton
        assert group_pages_saved(4, 32, 0) == 0     # dense: no pages


class TestRank:
    def test_ok_first_clip_desc_index_tiebreak(self):
        rs = [
            S.Result(status=S.OK, request_id=0, clip_score=0.1),
            S.Result(status=S.ERROR, request_id=1, clip_score=9.0),
            S.Result(status=S.OK, request_id=2, clip_score=0.7),
            S.Result(status=S.OK, request_id=3, clip_score=0.1),
        ]
        got = [r.request_id for r in rank_samples(rs)]
        assert got == [2, 0, 3, 1]

    def test_all_scores_none_keeps_sample_order(self):
        rs = [S.Result(status=S.OK, request_id=i) for i in range(3)]
        assert [r.request_id for r in rank_samples(rs)] == [0, 1, 2]


# ---------------------------------------------------------------------------
# admission + group future (no backend)
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_members_are_ordinary_requests(self):
        queue = RequestQueue(max_depth=16)
        g = submit_group(queue, Request(codes=(1, 2), seed=42,
                                        n_samples=3, stream=True))
        assert len(g.members) == 3 and len(g.sinks) == 3
        for i, m in enumerate(g.members):
            assert m.request.n_samples == 1
            assert m.request.seed == sample_seed(42, i)
            assert m.sink is g.sinks[i]
            assert g.sinks[i].request_id == m.request.request_id
        # the group is addressed by its leader
        assert g.request.request_id == g.members[0].request.request_id
        assert g.sink is g.sinks[0]

    def test_atomic_admission_mid_group_reject(self):
        """Member 3 of 4 hits a full queue: the typed reject propagates
        AND the already-admitted prefix is cancelled — a failed group
        never leaks half its samples into the engine."""
        queue = RequestQueue(max_depth=2)
        with pytest.raises(QueueFull):
            submit_group(queue, Request(codes=(1,), seed=7,
                                        n_samples=4, stream=True))
        # the admitted prefix is already terminal: an engine popping
        # them skips done handles, and no caller can hang on them
        for h in queue.drain():
            assert h.done()
            assert h.result(timeout=1).status == S.CANCELLED

    def test_non_streamed_group_has_no_sinks(self):
        queue = RequestQueue(max_depth=8)
        g = submit_group(queue, Request(codes=(1,), seed=0,
                                        n_samples=2))
        assert g.sinks == [] and g.sink is None

    def test_group_cancel_fans_out_and_closes_channel(self):
        queue = RequestQueue(max_depth=8)
        g = submit_group(queue, Request(codes=(1,), seed=0,
                                        n_samples=2, stream=True))
        assert g.fulfill(S.Result(status=S.CANCELLED,
                                  request_id=g.request.request_id,
                                  reason="client disconnected"))
        assert g.done()
        for m in g.members:
            assert m.result(timeout=1).status == S.CANCELLED
        # every member's fulfill closed its sink: the channel ended
        kinds = [e["event"] for e in g.sink.events()]
        assert kinds.count("sample_done") == 2
        # first-write-wins like the handle it imitates
        assert not g.fulfill(S.Result(status=S.OK, request_id=0))
        assert g.result(timeout=1).status == S.CANCELLED


# ---------------------------------------------------------------------------
# the equivalence matrix
# ---------------------------------------------------------------------------


MATRIX = [
    ("dense", "gather", False),
    ("dense", "gather", True),
    ("paged", "gather", False),
    ("paged", "gather", True),
    ("paged", "kernel", False),
]


class TestEquivalence:
    @pytest.mark.parametrize("kv,paged_attn,int8", MATRIX)
    def test_members_byte_identical_to_standalone(self, bundle, kv,
                                                  paged_attn, int8):
        """Every member of a best-of-3 group reproduces the one-shot
        sampler at its derived seed — across KV layouts, paged-read
        implementations, and KV dtypes. The group machinery must not
        touch what the device computes."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        engine = Engine(params, CFG, queue, num_slots=4, chunk_steps=4,
                        kv=kv, page_size=8 if kv == "paged" else 0,
                        paged_attn=paged_attn, quantize_cache=int8)
        g = submit_group(queue, Request(codes=(3, 7, 9), seed=11,
                                        n_samples=3))
        engine.run_until_idle()
        res = g.result(timeout=60)
        assert res.ok and len(res.samples) == 3
        for i, m in enumerate(g.members):
            ref = reference_tokens(
                params, vae_params,
                Request(codes=(3, 7, 9), seed=sample_seed(11, i)),
                quantize_cache=int8)
            np.testing.assert_array_equal(
                np.asarray(m.result(timeout=1).tokens), ref,
                err_msg=f"member {i} diverged ({kv}/{paged_attn}/"
                        f"{'int8' if int8 else 'fp32'})")

    def test_group_result_assembles_ranked(self, bundle):
        params, _ = bundle
        queue = RequestQueue(max_depth=16)
        engine = Engine(params, CFG, queue, num_slots=4, chunk_steps=4)
        g = submit_group(queue, Request(codes=(6, 6), seed=5,
                                        n_samples=3))
        engine.run_until_idle()
        res = g.result(timeout=60)
        assert res.status == OK
        assert [s.request_id for s in res.samples] \
            == [m.request.request_id for m in g.members]  # None scores:
        #                                      sample order is the rank
        np.testing.assert_array_equal(np.asarray(res.tokens),
                                      np.asarray(res.samples[0].tokens))
        assert res.total_s >= max(s.total_s for s in res.samples)


class TestCOWSharing:
    def test_group_pays_prompt_once(self, bundle):
        """Paged + prefix cache: a best-of-4 group's lifetime page peak
        is bounded by ONE prompt span + 4 generation spans, the warm
        siblings' retains prove the leader's span was shared, and every
        stream still matches its standalone reference."""
        params, vae_params = bundle
        page_size = 8
        prompt = tuple(1 + (i % 7) for i in range(CFG.text_seq_len))
        n = 4
        queue = RequestQueue(max_depth=16)
        engine = Engine(params, CFG, queue, num_slots=n, chunk_steps=4,
                        kv="paged", page_size=page_size,
                        prefix_cache=True)
        g = submit_group(queue, Request(codes=prompt, seed=9,
                                        n_samples=n))
        engine.run_until_idle()
        assert g.result(timeout=60).ok
        full = pages_for(CFG.seq_len, page_size)
        shared = len(prompt) // page_size
        assert engine.alloc.peak_in_use <= shared + n * (full - shared)
        assert engine.stats()["prefix_hits"] >= n - 1
        assert engine.alloc.retains >= (n - 1) * shared
        for i, m in enumerate(g.members):
            np.testing.assert_array_equal(
                np.asarray(m.result(timeout=1).tokens),
                reference_tokens(params, vae_params,
                                 Request(codes=prompt,
                                         seed=sample_seed(9, i))))


class TestServerGroup:
    def test_streamed_group_previews_rank_and_stats(self, bundle):
        """One ``submit(n_samples=N, stream=True)`` through the whole
        server (paged KV, prefix cache, previews, CLIP re-rank): the
        ranked samples are CLIP-score descending; each sample's token
        events, reassembled by position, are its result; each sample's
        closing ``final`` preview frame IS its result image, bit for
        bit (the same zero-padded row through the same jitted VAE
        program); and /stats banks the group, its COW dividend and its
        preview frames, with no stream left open."""
        from dalle_pytorch_tpu.models import clip as C
        from dalle_pytorch_tpu.serve import unpack_image
        from dalle_pytorch_tpu.serve.server import InferenceServer

        params, vae_params = bundle
        ccfg = C.CLIPConfig(
            dim_text=16, dim_image=16, dim_latent=16,
            num_text_tokens=CFG.num_text_tokens, text_enc_depth=1,
            text_seq_len=CFG.text_seq_len, text_heads=2,
            visual_enc_depth=1, visual_heads=2,
            visual_image_size=VCFG.image_size, visual_patch_size=8,
            sparse_attn=False)
        clip_params = C.clip_init(jax.random.PRNGKey(7), ccfg)
        n, page_size = 3, 8
        prompt = tuple(1 + (i % 7) for i in range(CFG.text_seq_len))
        shared = len(prompt) // page_size
        server = InferenceServer(
            params, vae_params, CFG, num_slots=n, queue_depth=16,
            chunk_steps=4, kv="paged", page_size=page_size,
            prefix_cache=True, preview_every=2, clip_params=clip_params,
            clip_cfg=ccfg).start()
        try:
            group = server.submit(prompt, seed=7, n_samples=n,
                                  stream=True)
            streamed = {i: {} for i in range(n)}
            finals = {}
            for ev in group.sink.events():
                if ev["event"] == "tokens":
                    streamed[ev["sample"]][ev["pos"]] = ev["tokens"]
                elif ev["event"] == "preview" and ev.get("final"):
                    finals[ev["sample"]] = unpack_image(ev["image"])
            res = group.result(timeout=120)
            assert res.ok, (res.status, res.reason)
            assert len(res.samples) == n and all(s.ok for s in res.samples)
            scores = [s.clip_score for s in res.samples]
            assert None not in scores and scores == sorted(scores,
                                                           reverse=True)
            for i, m in enumerate(group.members):
                mres = m.result(timeout=5)
                toks = [t for pos in sorted(streamed[i])
                        for t in streamed[i][pos]]
                np.testing.assert_array_equal(
                    np.asarray(toks[-len(mres.tokens):], np.int32),
                    np.asarray(mres.tokens))
                np.testing.assert_array_equal(finals[i], mres.image)
            st = server.stats()
            assert st["groups_completed"] == 1
            assert st["fanout_pages_saved"] == (n - 1) * shared
            assert st["preview_frames"] >= n
            assert st["streams_active"] == 0
            assert st["groups_in_flight"] == 0
        finally:
            server.close()


# ---------------------------------------------------------------------------
# THE resilience criterion: replica death mid-group
# ---------------------------------------------------------------------------


class TestGroupFailover:
    pytestmark = pytest.mark.faults

    def test_replica_kill_mid_group_zero_samples_lost(self, bundle):
        """Replica 1 of 2 crashes after its 2nd fused chunk while a
        best-of-4 streamed group is in flight: every sample completes
        token-exact against its standalone reference, the multiplexed
        channel still closes group-atomically, and the replayed
        positions are deduped — each absolute position arrives in the
        stream exactly once."""
        from dalle_pytorch_tpu.serve.replica import ReplicaSet

        params, vae_params = bundle
        queue = RequestQueue(max_depth=16)
        rs = ReplicaSet(params, CFG, queue, replicas=2, num_slots=2,
                        chunk_steps=4)
        g = submit_group(queue, Request(codes=(3, 7, 9), seed=11,
                                        n_samples=4, stream=True))
        with faults.injected(fault_replica=1, replica_crash_at_chunk=2):
            rs.run_until_idle()
        assert rs.failovers == 1
        res = g.result(timeout=60)
        assert res.ok, (res.status, res.reason)
        assert all(s.ok for s in res.samples) and len(res.samples) == 4

        streamed: dict = {i: {} for i in range(4)}
        for ev in g.sink.events():
            if ev["event"] == "tokens":
                seen = streamed[ev["sample"]]
                for off, tok in enumerate(ev["tokens"]):
                    pos = ev["pos"] + off
                    assert pos not in seen, \
                        f"position {pos} delivered twice after replay"
                    seen[pos] = tok
        for i, m in enumerate(g.members):
            ref = reference_tokens(
                params, vae_params,
                Request(codes=(3, 7, 9), seed=sample_seed(11, i)))
            mres = m.result(timeout=1)
            np.testing.assert_array_equal(np.asarray(mres.tokens), ref)
            toks = [streamed[i][p] for p in sorted(streamed[i])]
            np.testing.assert_array_equal(
                np.asarray(toks[-len(ref):], np.int32), ref,
                err_msg=f"sample {i}'s streamed positions diverged")


# ---------------------------------------------------------------------------
# variable resolution riding the same buckets
# ---------------------------------------------------------------------------


class TestShortGrid:
    def test_override_is_causal_prefix(self, bundle):
        """image_seq_len_override truncates the SAME sampling stream:
        the short grid's tokens are the full run's prefix, it completes
        early (fewer decode steps), and it composes with a group."""
        params, _ = bundle
        queue = RequestQueue(max_depth=16)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4)
        L = CFG.image_seq_len // 2
        h_short = queue.submit(Request(codes=(3, 7, 9), seed=11,
                                       image_seq_len_override=L))
        h_full = queue.submit(Request(codes=(3, 7, 9), seed=11))
        engine.run_until_idle()
        short, full = h_short.result(timeout=30), \
            h_full.result(timeout=30)
        assert short.status == OK and len(short.tokens) == L
        np.testing.assert_array_equal(np.asarray(short.tokens),
                                      np.asarray(full.tokens)[:L])

    def test_override_composes_with_group(self, bundle):
        params, _ = bundle
        queue = RequestQueue(max_depth=16)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=4)
        L = CFG.image_seq_len // 2
        g = submit_group(queue, Request(codes=(6, 6), seed=5,
                                        n_samples=2,
                                        image_seq_len_override=L))
        engine.run_until_idle()
        res = g.result(timeout=60)
        assert res.ok
        assert all(len(s.tokens) == L for s in res.samples)
