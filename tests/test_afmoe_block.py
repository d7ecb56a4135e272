"""The window-and-full, grouped-query, gated-attention block holding a
share of its routed experts (``ops.transformer.WindowGQABlock``) at toy
widths, float32, seeded: the program against the benchmark family's plain
reference (``benchmark/families/afmoe/reference.py``) at logit level on a
sequence several windows long, the two pools and the window's ring, the
held share of the experts (all the shares add up to the uncut layer), and
every option that cannot run the block refusing it by the one typed
error.

Tolerances: the program and the reference compute the same float32
mathematics in another order (grouped products, a cached ring read in
ring order, one matrix product a head group); at these widths the
logits (spread 0.6) agree to 2e-5, which a dropped norm, gate, window row
or expert would miss by three orders of magnitude."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, seeds
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.ops import attention as attn_ops
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.ops import moe as moe_ops
from dalle_pytorch_tpu.ops import transformer as T
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve.engine import Engine, MigrationError
from dalle_pytorch_tpu.serve.scheduler import (Request, RequestQueue,
                                               SamplingParams)

FAMILY = harness.load_family("afmoe")
SEED = 2 ** 31 + 13
PS = 4                      # page size: the text window is not a multiple
CONF = dict(harness.load_json(
    harness.ROOT + "/benchmark/configs/trinity-large-preview.json"),
    **FAMILY.tiny)
# a window of two pages in a sequence of nine: the ring (three pages)
# turns twice
CONF.update(text_seq_len=10, image_grid=5, sliding_window=8)


def _dims(**kw):
    return FAMILY.weights.dims_of(dict(CONF, **kw), 5)


DIMS = _dims()
CFG = FAMILY.build.program_config(DIMS, {})
TCFG = CFG.transformer
BLK = TCFG.block
RING = BLK.ring_pages(PS, DIMS.seq_len)
WIDTH = KV.pages_for(DIMS.seq_len, PS)
FULL_LAYERS = [i for i, t in enumerate(DIMS.layer_types) if t == "full"]
WINDOW_LAYERS = [i for i, t in enumerate(DIMS.layer_types) if t != "full"]


def _tree(dims):
    return jax.jit(lambda h: FAMILY.weights.tree(h, dims, jnp.float32))(
        seeds.split_seed(SEED))


@pytest.fixture(scope="module")
def params():
    return _tree(DIMS)


@pytest.fixture(scope="module")
def sequences():
    rng = np.random.default_rng(3)
    return np.concatenate(
        [rng.integers(1, DIMS.num_text_tokens, (2, DIMS.text_seq_len)),
         rng.integers(0, DIMS.num_image_tokens, (2, DIMS.image_seq_len))], 1)


@pytest.fixture(scope="module")
def ref_logits(sequences):
    return np.asarray(FAMILY.reference.served_logits(
        SEED, DIMS, jnp.float32, sequences.tolist()))


def _close(got, want, atol=2e-5):
    fin = np.isfinite(want)
    assert (np.asarray(got)[~fin] < -1e30).all()      # forbidden either way
    np.testing.assert_allclose(np.asarray(got)[fin], want[fin], atol=atol,
                               rtol=0)


def test_the_toy_is_the_published_pattern_and_wraps_its_window():
    assert DIMS.layer_types == ("sliding", "sliding", "full", "sliding",
                                "sliding") and DIMS.dense_layers == 1
    assert (RING, WIDTH) == (3, 9) and DIMS.seq_len > 4 * DIMS.window
    assert (DIMS.experts, DIMS.experts_held, DIMS.first_expert) == (16, 4, 4)
    runs = T.layer_runs(BLK, 5)
    assert [(r.moe, r.full, r.count, r.at, r.cache) for r in runs] \
        == [(False, False, 1, 0, 0), (True, False, 1, 0, 1),
            (True, True, 1, 1, 0), (True, False, 2, 2, 2)]
    # the whole published model is the same code with more runs
    whole = dataclasses.replace(
        BLK, dense_layers=6, layer_types=("sliding",) * 3 + ("full",)
        + ("sliding",) * 3 + ("full",) + ("sliding",) * 2)
    assert [(r.moe, r.full, r.count) for r in T.layer_runs(whole, 10)] == [
        (False, False, 3), (False, True, 1), (False, False, 2),
        (True, False, 1), (True, True, 1), (True, False, 2)]


# -- (i) the full forward against the reference -------------------------------

def test_dalle_apply_matches_the_reference_logits(params, sequences,
                                                  ref_logits):
    t = DIMS.text_seq_len
    logits = D.dalle_apply(params, jnp.asarray(sequences[:, :t]),
                           jnp.asarray(sequences[:, t:-1]), cfg=CFG)
    _close(logits, ref_logits)


# -- (ii) prefill, then the paged gather decode through both pools -------------

def _tables(b):
    return {"full": 1 + jnp.arange(b * WIDTH, dtype=jnp.int32).reshape(
                b, WIDTH),
            "window": 1 + jnp.arange(b * RING, dtype=jnp.int32).reshape(
                b, RING)}


def _prefilled_pools(params, sequences, t0, upto=None):
    """The prompt's rows [0, t0) of the sequences in the two pools: a
    full layer's row j in page j // PS of the slot's full table, a window
    layer's in column (j // PS) % RING of its ring, later rows over
    earlier ones (page 0 of each pool is the trash page); with ``upto``
    (b,), slot i's rows [0, upto[i]) alone."""
    b = sequences.shape[0]
    tables = _tables(b)
    pool = KV.init_page_pool(TCFG, 1 + b * WIDTH, PS,
                             window_pages=1 + b * RING)
    # a row: both key/value heads' 8 numbers side by side
    assert pool["k"].shape == (1, 1 + b * WIDTH, PS, 2 * 8)
    assert pool["window_v"].shape == (4, 1 + b * RING, PS, 2 * 8)
    t = min(t0, DIMS.text_seq_len)
    x = D.embed_prompt(params, CFG, jnp.asarray(sequences[:, :t]),
                       jnp.asarray(sequences[:, t:t0]))
    h, cache = decode_ops.prefill(params["transformer"], x, cfg=TCFG,
                                  total_len=DIMS.seq_len)
    # the prompt's rows come a buffer of the pool each, over the layers
    # that store to it
    assert cache["k"].shape == (len(FULL_LAYERS), b, t0, 2, 8)
    assert cache["window_k"].shape == (len(WINDOW_LAYERS), b, t0, 2, 8)
    pool = dict(pool)
    for name in ("k", "v"):
        full, ring = (np.array(pool[n]) for n in (name, "window_" + name))
        rows, ring_rows = (np.asarray(cache[n]) for n in
                           (name, "window_" + name))
        for i in range(b):
            for j in range(t0 if upto is None else upto[i]):
                full[:, tables["full"][i, j // PS], j % PS] = \
                    rows[:, i, j].reshape(len(FULL_LAYERS), -1)
                ring[:, tables["window"][i, (j // PS) % RING], j % PS] = \
                    ring_rows[:, i, j].reshape(len(WINDOW_LAYERS), -1)
        pool[name], pool["window_" + name] = (jnp.asarray(full),
                                              jnp.asarray(ring))
    return h, pool, tables


def _teacher_forced(params, sequences):
    def embed_fn(tok, pos):
        return D.decode_token_embed(params, CFG, tok, pos)

    def sample_fn(_h, pred_pos):
        # the NEXT token of the given sequences, as the loop stores it
        return jnp.take_along_axis(jnp.asarray(sequences),
                                   pred_pos[:, None], axis=1)[:, 0]
    return embed_fn, sample_fn


@pytest.mark.parametrize("t0", [7, 14])     # inside one page of the ring;
def test_prefill_then_paged_decode_matches_the_full_forward(    # past it
        params, sequences, ref_logits, t0):
    h, pool, tables = _prefilled_pools(params, sequences, t0)
    b = sequences.shape[0]
    key_mask = jnp.ones((b, DIMS.seq_len), bool)
    active = jnp.ones((b,), bool)
    forbidden = np.asarray(D.logits_mask(CFG))
    first = np.where(forbidden[t0 - 1], -np.inf,
                     np.asarray(D.to_logits(params, h[:, -1], CFG)))
    fin = np.isfinite(ref_logits[:, t0 - 1])
    np.testing.assert_allclose(first[fin], ref_logits[:, t0 - 1][fin],
                               atol=2e-5, rtol=0)     # the prefill's own row
    # position by position to the sequence's end (the ring turns twice),
    # logits against the reference's full forward
    step_pool = pool
    step = jax.jit(lambda x, p, pool: decode_ops.decode_step_block(
        params["transformer"], x, p, pool, tables, cfg=TCFG,
        key_mask=key_mask, active=active))
    for pos in range(t0, DIMS.seq_len - 1):
        p = jnp.full((b,), pos, jnp.int32)
        x = D.decode_token_embed(params, CFG, jnp.asarray(sequences[:, pos]),
                                 p)
        h_tok, step_pool, load = step(x, p, step_pool)
        logits = np.asarray(D.to_logits(params, h_tok, CFG))
        logits = np.where(forbidden[pos], -np.inf, logits)
        fin = np.isfinite(ref_logits[:, pos])
        np.testing.assert_allclose(logits[fin], ref_logits[:, pos][fin],
                                   atol=2e-5, rtol=0)
        picks = b * BLK.experts_per_token * DIMS.moe_layers
        assert int(load[0]) == picks and 0 <= int(load[4]) <= picks
    # the same steps in chunks of 8 write the same pools and count the picks
    embed_fn, sample_fn = _teacher_forced(params, sequences)
    cur = jnp.asarray(sequences[:, t0])
    p = jnp.full((b,), t0, jnp.int32)
    chunk_pool, picks = pool, 0
    for _ in range(2):
        cur, p, act, chunk_pool, ring, load = decode_ops.decode_loop_paged(
            params["transformer"], cur, p, active, chunk_pool, tables,
            cfg=TCFG, key_mask=key_mask, total_len=DIMS.seq_len, steps=8,
            embed_fn=embed_fn, sample_fn=sample_fn)
        picks += int(load[0])
    assert picks == 16 * b * BLK.experts_per_token * DIMS.moe_layers
    np.testing.assert_array_equal(np.asarray(ring)[:, -1],
                                  sequences[:, t0 + 15])
    # the full pool's rows t0 .. t0 + 16 (the stepwise pool went on to the
    # end; two compiled programs round a row's norms in another order: a
    # few float32 units of a K row, whose numbers carry the key norm's
    # gain and reach 6, hence the relative part)
    live, want = (np.asarray(decode_ops.layer_pool_view(
        pl["k"], jnp.int32(0), tables["full"])).reshape(b, -1, 2 * 8)
        for pl in (chunk_pool, step_pool))
    np.testing.assert_allclose(live[:, t0:t0 + 16], want[:, t0:t0 + 16],
                               atol=1e-5, rtol=1e-5)


def _step_at(params, seqs, positions):
    """One decode step with slot i at ``positions[i]`` of ``seqs[i]``,
    the rows before it in its pages of both pools (its ring as far as it
    has turned) -> the logits (forbidden ones -inf)."""
    _, pool, tables = _prefilled_pools(params, seqs, int(positions.max()),
                                       positions)
    p = jnp.asarray(positions)
    b = len(positions)
    x = D.decode_token_embed(
        params, CFG, jnp.asarray(seqs[np.arange(b), positions]), p)
    _step_at.plan = decode_ops.block_view_plan(
        TCFG, params["transformer"], pool, b, DIMS.seq_len)
    h_tok, _, _ = jax.jit(lambda x, p, pool: decode_ops.decode_step_block(
        params["transformer"], x, p, pool, tables, cfg=TCFG,
        key_mask=jnp.ones((b, DIMS.seq_len), bool),
        active=jnp.ones((b,), bool)))(x, p, pool)
    return np.where(np.asarray(D.logits_mask(CFG))[positions], -np.inf,
                    np.asarray(D.to_logits(params, h_tok, CFG)))


@pytest.mark.parametrize("at", [0, 3])
def test_slots_at_spread_positions_match_the_full_forward(
        params, sequences, ref_logits, profile_positions, reads_at, at,
        release_programs, four_slots_a_group, switch_placement):
    """ISSUE 38 in this block: the published pattern's full layers are
    runs of ONE layer (every fourth). At the published sizes the routed
    experts' stacks are too much to hand out of ONE switch around the
    scans that read the full pool (``block_view_plan``), so each scanned
    read would switch for itself, and a lone layer, which no scan runs,
    reads its pool whole in slot order: ``a_switch_a_read``, where the
    stand-ins for the width rule change nothing. The toy's experts are
    small, so by its own shapes ONE switch stands around the full layers'
    span and they read at the step's profile: ``one_switch``, where the
    planted fault must be caught. Either way a window layer reads its
    ring whole, and sixteen slots at positions spread as a width profile
    has them (some rings unwrapped, some wrapped, a parked slot, the last
    row) give the reference's full-forward logits at every slot's own
    position."""
    profiles = decode_ops.view_profiles(4, WIDTH)
    positions = profile_positions(profiles[at], PS, DIMS.seq_len - 1)
    assert (positions < RING * PS).sum() >= 2 <= (
        positions > RING * PS).sum()
    full = [r for r in T.layer_runs(BLK, 5) if r.full]
    assert full and all(r.count == 1 for r in full)
    rows = np.arange(len(positions)) % len(sequences)
    seqs = sequences[rows]
    want = ref_logits[rows, positions]
    got = _step_at(params, seqs, positions)
    _close(got, want)
    plan = _step_at.plan
    with reads_at("too_narrow"):
        cut = _step_at(params, seqs, positions)
    if switch_placement == "a_switch_a_read":
        assert plan.span is None and plan.by_rule == 0 \
            and plan.whole == len(full)
        np.testing.assert_array_equal(got, cut)
    else:
        assert plan.span is not None and plan.whole == 0 \
            and plan.by_rule == len(full) and plan.groups == 4
        with reads_at("full_width"):
            whole = _step_at(params, seqs, positions)
        np.testing.assert_array_equal(got.argmax(-1), whole.argmax(-1))
        if at:
            with pytest.raises(AssertionError):
                _close(cut, want)


def test_window_rows_are_the_latest_positions_of_a_ring():
    rows, window = 12, 8
    pos = jnp.asarray([0, 1, 5, 12, 13, 30])
    held, ok = decode_ops.window_rows(pos, rows, window)
    for i, p in enumerate(np.asarray(pos)):
        want = {}
        for q in range(p):              # position q is written at q % rows
            want[q % rows] = q
        for r in range(rows):
            assert (np.asarray(held)[i, r] >= 0) == (r in want)
            if r in want:
                assert np.asarray(held)[i, r] == want[r]
            assert bool(np.asarray(ok)[i, r]) == (
                r in want and p - want[r] < window)
        # every row of the window is in the ring, once
        assert sorted(np.asarray(held)[i][np.asarray(ok)[i]]) == list(
            range(max(p - window + 1, 0), p))


@pytest.mark.parametrize("total_len", [29, 36])   # a partial last turn; whole
def test_ring_key_mask_is_the_key_mask_at_the_rows_positions(total_len):
    """The selects a turn of the ring give what a gather of each row's
    own position gives (exact: booleans)."""
    rows = 12
    pos = jnp.asarray([0, 1, 5, 12, 13, total_len - 1, total_len])
    held, _ = decode_ops.window_rows(pos, rows, 8)
    key_mask = jax.random.bernoulli(jax.random.PRNGKey(3), 0.6,
                                    (pos.shape[0], total_len))
    want = (held >= 0) & jnp.take_along_axis(
        key_mask, jnp.clip(held, 0, total_len - 1), axis=1)
    np.testing.assert_array_equal(
        np.asarray(decode_ops.ring_key_mask(key_mask, held)),
        np.asarray(want))


def test_cached_rows_read_equals_the_materialised_read():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    p = attn_ops.gqa_init(k[0], 32, 4, BLK)
    b, m = 3, 2 * PS
    h = jax.random.normal(k[1], (b, m + 1, 32))
    q, gate, (kk, vv) = attn_ops.gqa_project(p, h, jnp.arange(m + 1), 4, BLK,
                                             full=False)
    assert q.shape == (b, m + 1, 4, 8) and kk.shape == (b, m + 1, 2, 8)
    allowed = jax.random.bernoulli(k[2], 0.7, (b, m))
    full = jnp.concatenate([allowed, jnp.ones((b, 1), bool)], axis=1)
    want = attn_ops.gqa_attend_materialised(
        q[:, -1:], kk, vv, full[:, None, None, :], TCFG.scale,
        window=False)[:, 0]

    def rows(x):            # (b, m, kvh, dh) -> (b, m, kvh * dh)
        return x[:, :m].reshape(b, m, 2 * 8)

    got = attn_ops.gqa_attend_rows(
        q[:, -1], kk[:, m], vv[:, m], rows(kk), lambda _w: rows(vv),
        allowed, TCFG.scale, window=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # query head i reads key/value head i // 2: with head 1's keys and
    # values moved, only query heads 2 and 3 change
    moved = attn_ops.gqa_attend_materialised(
        q[:, -1:], kk.at[:, :, 1].add(1.0), vv.at[:, :, 1].add(1.0),
        full[:, None, None, :], TCFG.scale, window=False)[:, 0]
    same = np.isclose(np.asarray(moved), np.asarray(want)).all(axis=(0, 2))
    assert same.tolist() == [True, True, False, False]


# -- (iii) the engine: both pools, the ring, chunks of 8 -----------------------

def test_engine_serves_the_reference_s_tokens_in_chunks_of_8(
        params, switch_placement):
    """Through the engine: admission's whole-page write into both pools,
    the ring's pages reused as the slots move on, slot reuse, the fused
    chunks. Greedy tokens are the reference's best at every served
    position (gap 0 but for float32 near-ties), the routed load comes
    out with the ring, and both pools are empty at the end. The counters
    of the width rule (ISSUE 38) count what the traced program reads:
    nothing where the one full layer, a run of one, reads whole
    (``a_switch_a_read``: the published sizes), the layer's table a step
    where it stands in the step's switch."""
    queue = RequestQueue(max_depth=8)
    engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=8,
                    kv="paged", page_size=PS)
    assert engine.window.ring == RING and engine.block_tables[
        "window"].shape == (2, RING)
    greedy = SamplingParams(filter_thres=1.0)
    reqs = [Request(codes=(3, 7, 9), seed=11, sampling=greedy),
            Request(codes=tuple(range(1, 11)), seed=2, sampling=greedy),
            Request(codes=(6, 6, 1, 2, 3, 9, 4), seed=3, sampling=greedy)]
    handles = [queue.submit(r) for r in reqs]
    most = 0
    while not engine.idle():
        engine.step_once()
        for i in range(2):
            held = engine.window.pages_of(i)
            most = max(most, len(held))
            assert len(held) <= BLK.window // PS + 1 == RING
            assert len(held) <= len(engine._slot_pages[i])
        st = engine.stats()
        assert st["window_pages_in_use"] <= 2 * RING
        assert st["layer_pages_in_use"] <= st["layer_pages_all_full"]
    assert most == RING
    seqs, lens = [], []
    for r, h in zip(reqs, handles):
        res = h.result(timeout=5)
        assert res.status == "ok"
        seqs.append(list(np.asarray(res.text_tokens))
                    + list(np.asarray(res.tokens)))
        lens.append(len(r.codes))
        assert seqs[-1][:lens[-1]] == list(r.codes)
    gaps, served = FAMILY.reference.served_gaps(SEED, DIMS, jnp.float32,
                                                seqs, lens)
    assert float(np.asarray(gaps)[np.asarray(served)].max()) < 1e-5
    st = engine.stats()
    assert engine.decode_traces == 1
    assert engine.alloc.in_use == 0 and engine.window.alloc.in_use == 0
    assert st["full_pages_in_use"] == st["window_pages_in_use"] == 0
    # three requests each ran six pages past their ring
    assert st["window_pages_reused"] == 3 * (WIDTH - RING)
    assert st["moe_picks"] == (st["decode_steps"] * engine.num_slots
                               * BLK.experts_per_token * DIMS.moe_layers)
    assert 0 < st["moe_picks_held"] < st["moe_picks"]
    # two slots' pairs are under the row ladder's first step: all handed on
    assert st["moe_rows_computed"] == st["moe_picks"]
    assert 0 < st["moe_experts_touched"] <= \
        st["decode_steps"] * DIMS.moe_layers * BLK.experts_held
    assert st["kv_hbm_bytes"] == KV.modeled_kv_bytes(
        TCFG, kv="paged", num_slots=2, total_len=DIMS.seq_len,
        page_size=PS)
    # a pool per layer type: 4 window layers of 2 x 3 + 1 pages, one full
    # layer of 2 x 9 + 1, K and V of 2 heads x 4 rows x 8 floats
    assert st["kv_hbm_bytes"] == (4 * 7 + 1 * 19) * 2 * (2 * PS * 8) * 4
    assert st["kv_read_bytes_per_token"] == \
        (4 * RING + 1 * WIDTH) * 2 * (2 * PS * 8) * 4
    rows = [r for r in engine.loop_ring.dump() if "kind" not in r]
    if switch_placement == "a_switch_a_read":
        assert engine._view_plan is None
        assert st["kv_view_columns_read"] == st["kv_view_columns_full"] == 0
        assert rows and all(r["view_read_pct"] is None for r in rows)
    else:
        # two slots are one group, which reads the whole table
        assert engine._view_plan.by_rule == 1 and st["kv_view_groups"] == 1
        assert st["kv_view_columns_read"] == st["kv_view_columns_full"] \
            == st["decode_steps"] * 2 * WIDTH
        assert rows and all(r["view_read_pct"] == 100.0 for r in rows)


def test_an_undersized_pool_evicts_and_replays_the_same_tokens(params):
    greedy = SamplingParams(filter_thres=1.0)
    reqs = [Request(codes=(3, 7, 9, 2), seed=5, sampling=greedy),
            Request(codes=(8, 1, 4, 4, 2, 6), seed=6, sampling=greedy)]

    def serve(**kw):
        queue = RequestQueue(max_depth=8)
        engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=8,
                        kv="paged", page_size=PS, **kw)
        handles = [queue.submit(dataclasses.replace(r)) for r in reqs]
        engine.run_until_idle()
        return engine, [list(np.asarray(h.result(timeout=5).tokens))
                        for h in handles]

    roomy, want = serve()
    tight, got = serve(num_pages=WIDTH + 4)     # one sequence and a bit
    assert tight.window.alloc.num_pages < roomy.window.alloc.num_pages
    assert got == want and tight.evicted > 0
    assert tight.alloc.in_use == 0 and tight.window.alloc.in_use == 0


def test_window_pages_hold_a_ring_and_release_it():
    w = KV.WindowPages(num_slots=2, num_pages=8, ring=3, page_size=4)
    assert w.prompt_need(1) == 1 and w.prompt_need(9) == 3 \
        and w.prompt_need(30) == 3
    # a prompt of 18 rows: logical pages 0-4, the ring holds 2, 3, 4
    grants = w.alloc.alloc(w.prompt_need(18))
    pages = w.admit(0, 18, grants)
    assert pages.tolist() == [0, 0] + grants
    assert w.tables[0].tolist() == [grants[1], grants[2], grants[0]]
    assert w.short(0, 40) == 0
    w.grow(0, 26)                       # logical pages 5, 6: reused
    assert w.reused == 2 and w.alloc.in_use == 3
    # a short prompt grows page by page, then turns
    w.admit(1, 3, w.alloc.alloc(1))
    assert w.short(1, 9) == 2
    w.grow(1, 9)
    assert len(w.pages_of(1)) == 3 and w.reused == 2
    w.grow(1, 13)
    assert len(w.pages_of(1)) == 3 and w.reused == 3
    with pytest.raises(KV.PagePoolExhausted):
        w.alloc.alloc(2)                # 7 allocatable, 6 held
    w.release(0)
    w.release(1)
    assert w.alloc.in_use == 0 and not w.tables.any()


# -- (iv) the held share of the experts ----------------------------------------

def test_all_the_shares_add_up_to_the_uncut_reference_layer():
    """The routed parts that the 4 shares of 4 experts give, with the
    shared expert (which every chip computes alike) counted once, are the
    reference's whole layer of 16 experts."""
    whole = _dims(experts_held=16, first_expert=0)
    key = seeds.layer_key(seeds.seed_key(SEED), whole.first_layer + 2)
    ref_p = FAMILY.weights.layer(key, whole, jnp.float32, True)["ff"]
    m = jax.random.normal(jax.random.PRNGKey(4), (24, whole.dim))
    want = FAMILY.reference._unit(ref_p["shared"], m, None) \
        + FAMILY.reference.routed(
            ref_p["experts"], m, FAMILY.reference.route(ref_p, m, whole))
    shared = np.asarray(D.core.swiglu(ref_p["shared"], m))
    total, held = shared.copy(), 0
    for first in range(0, 16, 4):
        dims = _dims(first_expert=first)
        blk = FAMILY.build.program_config(dims, {}).transformer.block
        p = FAMILY.weights.layer(key, dims, jnp.float32, True)["ff"]
        np.testing.assert_array_equal(
            np.asarray(p["experts"]["w_in"]),
            np.asarray(ref_p["experts"]["w_in"][first:first + 4]))
        out, load = moe_ops.dropless_apply(p, m, blk)
        total += np.asarray(out) - shared
        held += int(load[4])
        assert int(load[0]) == 24 * 2 and int(load[1]) <= 4
        # and the family's reference, given the same share, agrees
        R = FAMILY.reference
        np.testing.assert_allclose(
            np.asarray(out) - shared, np.asarray(R.routed(
                p["experts"], m, R.route(p, m, dims)[:, first:first + 4])),
            atol=1e-5)
    assert held == 24 * 2               # every pick is held somewhere, once
    np.testing.assert_allclose(total, np.asarray(want), atol=2e-5)


def test_a_share_routes_over_all_experts_and_computes_its_own():
    blk = dataclasses.replace(BLK, num_experts=8, experts_per_token=2,
                              experts_held=3, first_expert=2)
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    p = moe_ops.dropless_init(k[0], 32, blk)
    assert p["router"]["w"].shape == (32, 8)
    assert p["experts"]["w_in"].shape == (3, 32, 24)
    # every token's first pick falls on expert 3 (held), the rest anywhere
    p["router"]["bias"] = jnp.zeros((8,)).at[3].set(4.0)
    n = 24
    x = jax.random.normal(k[1], (2, n // 2, 32))
    out, load = moe_ops.dropless_apply(p, x, blk)
    xt = np.asarray(x).reshape(n, 32)
    picks, weights = moe_ops.route(p["router"], jnp.asarray(xt), 2,
                                   blk.routed_scale)
    picks, weights = np.asarray(picks), np.asarray(weights)
    np.testing.assert_allclose(weights.sum(-1), blk.routed_scale, rtol=1e-6)

    def unit(w_in, w_out, v):
        g, u = np.split(v @ w_in, 2)
        return (g / (1 + np.exp(-g)) * u) @ w_out

    want = np.zeros((n, 32), np.float32)
    w_in, w_out = (np.asarray(p["experts"][k_]) for k_ in ("w_in", "w_out"))
    for t in range(n):                  # the per-token loop over held picks
        for e, w in zip(picks[t], weights[t]):
            if 2 <= e < 5:
                want[t] += w * unit(w_in[e - 2], w_out[e - 2], xt[t])
        want[t] += unit(np.asarray(p["shared"]["w_in"]),
                        np.asarray(p["shared"]["w_out"]), xt[t])
    np.testing.assert_allclose(np.asarray(out).reshape(n, 32), want,
                               atol=1e-5)
    sizes = np.bincount(picks.reshape(-1), minlength=8)[2:5]
    assert sizes[1] == n and sizes.sum() < 2 * n
    assert list(np.asarray(load)) == [n * 2, (sizes > 0).sum(), sizes.max(),
                                      (sizes > 0).sum(), sizes.sum(), n * 2]
    # with every expert held, the load has no fifth or sixth entry
    assert moe_ops.load_width(dataclasses.replace(
        blk, experts_held=8, first_expert=0)) == 4


# -- (v) every path that cannot run the block refuses it ----------------------

def _engine(params, **kw):
    kw.setdefault("kv", "paged")
    return Engine(params, CFG, RequestQueue(max_depth=2), num_slots=1, **kw)


def _mesh_engine(params):
    from dalle_pytorch_tpu.serve.mesh_engine import MeshEngine
    return MeshEngine(params, CFG, RequestQueue(max_depth=2),
                      devices=jax.devices()[:2], num_slots=1, kv="paged")


REFUSED = {
    "kv_dense": lambda p: _engine(p, kv="dense"),
    "paged_attn_kernel": lambda p: _engine(p, paged_attn="kernel",
                                           page_size=8),
    "speculative": lambda p: _engine(p, speculative=2),
    "sparse_reads": lambda p: _engine(p, sparse_reads=True),
    "quantize_cache": lambda p: _engine(p, quantize_cache=True),
    "prefix_cache": lambda p: _engine(p, prefix_cache=True),
    "mesh_engine": _mesh_engine,
    "quantize_int8": lambda p: D.quantize_for_decode(p),
    "generate_images": lambda p: D.generate_images(
        p, None, jnp.ones((1, 4), jnp.int32), cfg=CFG,
        rng=jax.random.PRNGKey(0)),
    "train": lambda p: D.dalle_apply(
        p, jnp.ones((1, 10), jnp.int32), jnp.ones((1, 25), jnp.int32),
        cfg=CFG, train=True, return_loss=True),
    "reversible": lambda p: dataclasses.replace(CFG, reversible=True)
    .transformer,
    "sparse_attn": lambda p: dataclasses.replace(CFG, sparse_attn=True)
    .transformer,
    "attn_impl_flash": lambda p: dataclasses.replace(CFG, attn_impl="flash")
    .transformer,
    "remat": lambda p: dataclasses.replace(CFG, remat="full").transformer,
    "capacity_moe": lambda p: dataclasses.replace(CFG, moe_experts=4)
    .transformer,
    "dense_cache": lambda p: decode_ops.init_cache(TCFG, 1, 8),
    "dense_decode_step": lambda p: decode_ops.decode_step(
        p["transformer"], jnp.zeros((1, 32)), 3, {}, cfg=TCFG,
        key_mask=jnp.ones((1, 8), bool)),
    "speculative_loop": lambda p: decode_ops.decode_loop_spec_paged(
        p["transformer"], None, None, None, None, {}, None, cfg=TCFG,
        draft_cfg=None, key_mask=None, total_len=8, steps=1, k=2,
        embed_fn=None, sample_fn=None),
    "kernel_loop": lambda p: decode_ops.decode_loop_paged(
        p["transformer"], None, None, None, {}, None, cfg=TCFG,
        key_mask=None, total_len=8, steps=1, embed_fn=None, sample_fn=None,
        attn_impl="kernel"),
    "int8_pool": lambda p: KV.init_page_pool(TCFG, 4, PS, quantized=True),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_every_refused_option_raises_the_one_typed_error(params, option):
    with pytest.raises(T.BlockOptionError) as e:
        REFUSED[option](params)
    assert e.value.block == BLK.name == "window_gqa_moe" and e.value.option
    assert BLK.name in str(e.value) and e.value.option in str(e.value)


@pytest.mark.parametrize("call", ["export", "import"])
def test_migration_refuses_the_block_and_falls_back_to_replay(params, call):
    engine = _engine(params, page_size=PS)
    with pytest.raises(MigrationError, match="window_gqa_moe.*export/import") \
            as e:
        engine.export_slot(0) if call == "export" \
            else engine.import_slot({"weights_version": "0"})
    assert e.value.reason == "block"


def test_a_block_that_names_no_share_or_the_wrong_layers_is_refused():
    with pytest.raises(ValueError, match="share"):
        dataclasses.replace(BLK, experts_held=20, first_expert=0,
                            num_experts=16)
    with pytest.raises(ValueError, match="layer_types names 5"):
        dataclasses.replace(TCFG, depth=4)
    with pytest.raises(ValueError, match="sliding.*full"):
        dataclasses.replace(BLK, layer_types=("global",) * 5)
