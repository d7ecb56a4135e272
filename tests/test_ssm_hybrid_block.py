"""The state-space + window + shared-full-cache hybrid block
(``ops.transformer.SSMHybridBlock``) at toy widths, float32, seeded: the
program against the benchmark family's plain reference
(``benchmark/families/phi4flash/reference.py``) at logit level on a
sequence several windows long; the two forms of the state-space layer as
one identity; the recurrent state a slot beside the page pools (written at
each row's own prompt length, never advanced for an inactive slot,
overwritten when a slot is reused, rebuilt by the replay after an
eviction); the one full layer's pages read by every cross layer; and every
option that cannot run the block refusing it by the one typed error.

Tolerances: the program and the reference compute the same float32
mathematics in another order (all positions' products at once, a cached
ring read in ring order, one matrix product a slot for all heads); at
these widths the logits (spread 7) agree to 5e-5, which a dropped norm,
bias, lambda, window row or state update would miss by three orders of
magnitude."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, seeds
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.ops import attention as attn_ops
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.ops import ssm as ssm_ops
from dalle_pytorch_tpu.ops import transformer as T
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve.engine import Engine, MigrationError
from dalle_pytorch_tpu.serve.scheduler import (Request, RequestQueue,
                                               SamplingParams)

FAMILY = harness.load_family("phi4flash")
SEED = 2 ** 31 + 13
PS = 4                      # page size: the text window is not a multiple
CONF = dict(harness.load_json(
    harness.ROOT + "/benchmark/configs/phi-4-mini-flash-reasoning.json"),
    **FAMILY.tiny)
# a window of two pages in a sequence of nine: the ring (three pages)
# turns twice
CONF.update(text_seq_len=10, image_grid=5, sliding_window=8)
DIMS = FAMILY.weights.dims_of(CONF, 12)
CFG = FAMILY.build.program_config(DIMS, {})
TCFG = CFG.transformer
BLK = TCFG.block
RING = BLK.ring_pages(PS, DIMS.seq_len)
WIDTH = KV.pages_for(DIMS.seq_len, PS)
ATOL = 5e-5


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda h: FAMILY.weights.tree(h, DIMS, jnp.float32))(
        seeds.split_seed(SEED))


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


def _close(got, want, atol=ATOL):
    fin = np.isfinite(want)
    assert (np.asarray(got)[~fin] < -1e30).all()      # forbidden either way
    np.testing.assert_allclose(np.asarray(got)[fin], want[fin], atol=atol,
                               rtol=0)


def test_the_toy_is_the_published_pattern_scanned_in_periods():
    assert DIMS.mixers == ("ssm", "window") * 3 + ("ssm", "full") \
        + ("gmu", "cross") * 2
    assert (RING, WIDTH) == (3, 9) and DIMS.seq_len > 4 * DIMS.window
    scans = T.stack_scans(BLK, 12)
    assert [[(r.kind.mixer, r.full, r.count, r.at, r.cache) for r in scan]
            for scan in scans] == [
        [("ssm", False, 3, 0, 0), ("attn", False, 3, 0, 0)],
        [("ssm", False, 1, 3, 3), ("attn", True, 1, 3, 0)],
        [("gmu", False, 2, 0, 0), ("cross", True, 2, 0, 0)]]
    # the published model: 8 state-space + window pairs as one scan,
    # layers 16-17 once, 7 memory-unit + cross pairs as one scan
    whole = FAMILY.build.program_config(FAMILY.weights.dims_of(dict(
        CONF, num_hidden_layers=32), 32), {}).transformer
    assert [(len(scan), scan[0].count, scan[0].kind.mixer,
             scan[1].kind.mixer, scan[1].full)
            for scan in T.stack_scans(whole.block, 32)] == [
        (2, 8, "ssm", "attn", False), (2, 1, "ssm", "attn", True),
        (2, 7, "gmu", "cross", True)]
    # the pools count the layers that STORE, not those that read
    plan = KV.pool_plan(whole, 100, 40, num_slots=5)
    assert plan == {"k": (1, 100), "v": (1, 100), "window_k": (8, 40),
                    "window_v": (8, 40), "ssm_state": (9, 5),
                    "ssm_conv": (9, 5)}
    # and a state a slot is never planned for no slots
    with pytest.raises(ValueError, match="state a slot"):
        KV.pool_plan(whole, 100, 40)


# -- (i) the full forward against the reference -------------------------------

def test_dalle_apply_matches_the_reference_logits(params, sequences,
                                                  ref_logits):
    t = DIMS.text_seq_len
    logits = D.dalle_apply(params, jnp.asarray(sequences[:, :t]),
                           jnp.asarray(sequences[:, t:-1]), cfg=CFG)
    _close(logits, ref_logits)


# -- (ii) the two forms of the state-space layer ------------------------------

def _ssm_layer(params, i=1):
    return jax.tree.map(lambda a: a[i], params["transformer"]["ssm"]["attn"])


@pytest.mark.parametrize("masked", [False, True])
def test_ssm_sequence_is_ssm_step_folded_over_the_positions(params, masked):
    p = _ssm_layer(params)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 11, DIMS.dim))
    lens = np.asarray([11, 7, 2]) if masked else np.asarray([11, 11, 11])
    mask = jnp.arange(11)[None, :] < lens[:, None]
    out, m, (state, tail) = ssm_ops.ssm_sequence(p, x, mask if masked
                                                 else None)
    assert state.shape == (3, DIMS.d_state, DIMS.d_inner) \
        and state.dtype == jnp.float32 and tail.shape == (3, 3, DIMS.d_inner)
    carried = ssm_ops.zero_state(p, 3, x.dtype)
    for t in range(11):
        o_t, m_t, new = ssm_ops.ssm_step(p, x[:, t], carried)
        live = t < lens
        np.testing.assert_allclose(np.asarray(o_t)[live],
                                   np.asarray(out[:, t])[live], atol=1e-6)
        np.testing.assert_allclose(np.asarray(m_t)[live],
                                   np.asarray(m[:, t])[live], atol=1e-6)
        # a row past its own length keeps what it carried
        carried = tuple(jnp.where(jnp.asarray(live).reshape(
            (3,) + (1,) * (a.ndim - 1)), a, b) for a, b in zip(new, carried))
    np.testing.assert_allclose(np.asarray(carried[0]), np.asarray(state),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(carried[1]), np.asarray(tail),
                               atol=1e-6)


def test_a_rows_state_after_a_padded_prefill_is_its_own_prompts(params,
                                                                sequences):
    """Prompts of 4 and 9 tokens in one bucket of 10: each row's state and
    tail equal those of a prefill of exactly its own prompt."""
    lens = [4, 9]
    x = D.embed_prompt(params, CFG, jnp.asarray(sequences[:, :10]))
    _, padded = decode_ops.prefill(params["transformer"], x, cfg=TCFG,
                                   total_len=DIMS.seq_len,
                                   lens=jnp.asarray(lens))
    assert padded["ssm_state"].shape == (4, 2, DIMS.d_state, DIMS.d_inner)
    assert padded["k"].shape == (1, 2, 10, 2, 8) \
        and padded["window_k"].shape == (3, 2, 10, 2, 8)
    for row, n in enumerate(lens):
        _, own = decode_ops.prefill(params["transformer"],
                                    x[row:row + 1, :n], cfg=TCFG,
                                    total_len=DIMS.seq_len)
        for name in ("ssm_state", "ssm_conv"):
            np.testing.assert_allclose(np.asarray(padded[name][:, row]),
                                       np.asarray(own[name][:, 0]),
                                       atol=1e-5)
    # without the lengths the padding would have advanced it
    _, blind = decode_ops.prefill(params["transformer"], x, cfg=TCFG,
                                  total_len=DIMS.seq_len)
    assert np.abs(np.asarray(blind["ssm_state"][:, 0])
                  - np.asarray(padded["ssm_state"][:, 0])).max() > 1e-3


def test_a_padded_prefills_last_row_is_its_own_prompts(params, sequences):
    """What an admission samples from: the output at each row's own last
    position in the bucket equals the last row of a prefill of exactly
    its own prompt, through every layer (the memory units read the scan
    output of that position, the cross layers the full layer's rows up
    to it)."""
    lens = [4, 9]
    x = D.embed_prompt(params, CFG, jnp.asarray(sequences[:, :10]))
    h, _ = decode_ops.prefill(params["transformer"], x, cfg=TCFG,
                              total_len=DIMS.seq_len,
                              lens=jnp.asarray(lens))
    assert h.shape == (2, 10, DIMS.dim)
    for row, n in enumerate(lens):
        own, _ = decode_ops.prefill(params["transformer"],
                                    x[row:row + 1, :n], cfg=TCFG,
                                    total_len=DIMS.seq_len)
        np.testing.assert_allclose(np.asarray(h[row, n - 1]),
                                   np.asarray(own[0, -1]), atol=2e-5)


# -- (iii) prefill, then the paged gather decode -------------------------------

def _tables(b):
    return {"full": 1 + jnp.arange(b * WIDTH, dtype=jnp.int32).reshape(
                b, WIDTH),
            "window": 1 + jnp.arange(b * RING, dtype=jnp.int32).reshape(
                b, RING)}


def _prefilled_pools(params, sequences, t0, upto=None):
    """The prompt's rows [0, t0) of the sequences in the two pools (a
    full layer's row j in page j // PS of the slot's full table, a window
    layer's in column (j // PS) % RING of its ring, later rows over
    earlier ones; page 0 of each pool is the trash page) and each
    sequence's state in its slot; with ``upto`` (b,), slot i's rows [0,
    upto[i]) alone and its state after exactly that many tokens."""
    b = sequences.shape[0]
    tables = _tables(b)
    pool = dict(KV.init_page_pool(TCFG, 1 + b * WIDTH, PS,
                                  window_pages=1 + b * RING, num_slots=b))
    assert pool["k"].shape == (1, 1 + b * WIDTH, PS, 2 * 8)
    assert pool["window_v"].shape == (3, 1 + b * RING, PS, 2 * 8)
    assert pool["ssm_state"].shape == (4, b, DIMS.d_state, DIMS.d_inner) \
        and pool["ssm_state"].dtype == jnp.float32
    assert pool["ssm_conv"].shape == (4, b, 3, DIMS.d_inner)
    t = min(t0, DIMS.text_seq_len)
    x = D.embed_prompt(params, CFG, jnp.asarray(sequences[:, :t]),
                       jnp.asarray(sequences[:, t:t0]))
    h, cache = decode_ops.prefill(
        params["transformer"], x, cfg=TCFG, total_len=DIMS.seq_len,
        lens=None if upto is None else jnp.asarray(upto))
    for name, table, ring in (("k", "full", False), ("v", "full", False),
                              ("window_k", "window", True),
                              ("window_v", "window", True)):
        buf, rows = np.array(pool[name]), np.asarray(cache[name])
        for i in range(b):
            for j in range(t0 if upto is None else upto[i]):
                col = (j // PS) % RING if ring else j // PS
                buf[:, tables[table][i, col], j % PS] = \
                    rows[:, i, j].reshape(buf.shape[0], -1)
        pool[name] = jnp.asarray(buf)
    pool["ssm_state"], pool["ssm_conv"] = cache["ssm_state"], \
        cache["ssm_conv"]
    return h, pool, tables


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
                               atol=ATOL, rtol=0)     # the prefill's own row
    # position by position to the sequence's end (the ring turns twice),
    # logits against the reference's full forward
    step = jax.jit(lambda x, p, pool: decode_ops.decode_step_block(
        params["transformer"], x, p, pool, tables, cfg=TCFG,
        key_mask=key_mask, active=active))
    step_pool = pool
    for pos in range(t0, DIMS.seq_len - 1):
        p = jnp.full((b,), pos, jnp.int32)
        x = D.decode_token_embed(params, CFG, jnp.asarray(sequences[:, pos]),
                                 p)
        h_tok, step_pool, load = step(x, p, step_pool)
        assert load.shape == (0,)               # no routed layer
        logits = np.asarray(D.to_logits(params, h_tok, CFG))
        logits = np.where(forbidden[pos], -np.inf, logits)
        fin = np.isfinite(ref_logits[:, pos])
        np.testing.assert_allclose(logits[fin], ref_logits[:, pos][fin],
                                   atol=ATOL, rtol=0)


def _step_at(params, seqs, positions):
    """One decode step with slot i at ``positions[i]`` of ``seqs[i]``:
    the rows before it in its pages of both pools (its ring as far as it
    has turned), its state after that many tokens -> the logits
    (forbidden ones -inf)."""
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


@pytest.mark.parametrize("at", [0, 1, 3])
def test_slots_up_to_each_width_profile_match_the_full_forward(
        params, sequences, ref_logits, profile_positions, reads_at, at,
        release_programs, four_slots_a_group, switch_placement):
    """ISSUE 38: the reads of the pool whose rows lie in order stop at
    the rows that are written: the full layer's differential
    read and the cross layers' of the SAME table at the full layer's
    index, each with a query of its own (a window layer reads its ring
    whole, in slot order; the positions leave some rings unwrapped and
    wrap others).
    Sixteen slots in shuffled phase order whose positions need profile
    ``at`` of the table's staircases (in every group a slot AT its
    width's edge, one a row before it, one a row after the edge of the
    group before; a slot at 1; the last row at the last profile):
    one step by the rule gives the reference's full-forward logits at
    every slot's own position, and the greedy tokens of the same step at
    full width; the profile before (the planted fault) fails the same
    comparison."""
    width = WIDTH
    assert decode_ops.view_slot_groups(16, width, (PS, 2 * 8),
                                       jnp.float32) == 4
    profiles = decode_ops.view_profiles(4, width)
    assert len(profiles) == 4
    positions = profile_positions(profiles[at], PS, DIMS.seq_len - 1)
    # (a state after no token at all is no prompt's: the slot parked at 0
    # is the classic pool's case)
    positions = np.where(positions == 0, 2, positions)
    assert int(decode_ops.view_profile_index(
        np.sort(positions), 4, width, PS, xp=np)) == at
    assert (positions < RING * PS).sum() >= 2 <= (
        positions > RING * PS).sum()
    rows = np.arange(len(positions)) % len(sequences)
    seqs = sequences[rows]
    want = ref_logits[rows, positions]
    got = _step_at(params, seqs, positions)
    _close(got, want)
    # where the switch stands (``block_view_plan``): one around the span
    # of scans that read the ordered pool, every reader at the profile;
    # or one a scanned read, a run of one layer whole
    plan = _step_at.plan
    readers = [r for r in T.layer_runs(BLK, TCFG.depth)
               if r.kind.pool == "full"]
    lone = sum(r.count for r in readers if r.count == 1)
    assert lone and plan.groups == 4
    if switch_placement == "one_switch":
        assert plan.span is not None and plan.whole == 0
    else:
        assert plan.span is None and plan.whole == lone
    assert plan.by_rule + plan.whole == sum(r.count for r in readers)
    with reads_at("full_width"):
        whole = _step_at(params, seqs, positions)
    _close(whole, want)
    np.testing.assert_array_equal(got.argmax(-1), whole.argmax(-1))
    if at:
        with reads_at("too_narrow"):
            cut = _step_at(params, seqs, positions)
        with pytest.raises(AssertionError):
            _close(cut, want)


def test_an_inactive_slots_state_is_not_advanced(params, sequences):
    _, pool, tables = _prefilled_pools(params, sequences, 7)
    p = jnp.full((2,), 7, jnp.int32)
    x = D.decode_token_embed(params, CFG, jnp.asarray(sequences[:, 7]), p)
    _, after, _ = decode_ops.decode_step_block(
        params["transformer"], x, p, pool, tables, cfg=TCFG,
        key_mask=jnp.ones((2, DIMS.seq_len), bool),
        active=jnp.asarray([True, False]))
    for name in ("ssm_state", "ssm_conv"):
        np.testing.assert_array_equal(np.asarray(after[name][:, 1]),
                                      np.asarray(pool[name][:, 1]))
        assert np.abs(np.asarray(after[name][:, 0])
                      - np.asarray(pool[name][:, 0])).max() > 1e-4
    # and its rows went to the trash page
    np.testing.assert_array_equal(
        np.asarray(after["k"][:, tables["full"][1, 1]]),
        np.asarray(pool["k"][:, tables["full"][1, 1]]))


def test_every_cross_layer_reads_the_full_layers_pages(params, sequences):
    """Change one page of the full pool's ONE layer: the full layer and
    both cross layers move, and no layer before the full one."""
    _, pool, tables = _prefilled_pools(params, sequences, 14)
    p = jnp.full((2,), 14, jnp.int32)
    x = D.decode_token_embed(params, CFG, jnp.asarray(sequences[:, 14]), p)
    key_mask = jnp.ones((2, DIMS.seq_len), bool)

    def per_layer_outputs(pool):
        """Each layer's two branches' sum for the token, the stack
        unrolled by hand: one layer at a time, each on the stream that
        the layers before it left."""
        read_of, _ = decode_ops._block_reads(TCFG, pool, tables, p,
                                             key_mask)
        outs, h, shared = [], x, BLK.carried(x)
        for scan in T.stack_scans(BLK, 12):
            for local in range(scan[0].count):
                for run in scan:
                    lp = jax.tree.map(
                        lambda a: a[run.at + local],
                        params["transformer"][BLK.stack_of(run.kind)])
                    layer = run.cache + local if run.kind.stores \
                        else run.cache
                    before = h
                    h, shared, _ = T.block_layer(
                        lp, h, shared, p, read_of(jnp.int32(layer), run),
                        TCFG, run)
                    outs.append(np.asarray(h - before))
        return outs

    base = per_layer_outputs(pool)
    page = int(tables["full"][0, 1])
    moved = dict(pool, v=pool["v"].at[0, page].add(1.0))
    got = per_layer_outputs(moved)
    for i, mixer in enumerate(DIMS.mixers):
        same = np.allclose(got[i], base[i], atol=1e-7)
        if mixer in ("full", "cross"):
            assert not same, i
        elif i < DIMS.kv_source:
            assert same, i          # nothing before the full layer reads it


# -- (iv) the differential read over gathered rows -----------------------------

@pytest.mark.parametrize("window", [False, True])
def test_differential_read_over_rows_equals_the_materialised_read(window):
    """One query a slot over cached rows plus its own row, against the
    whole-sequence read's last row; ``window``: the rows lie in ring
    order."""
    rng = np.random.default_rng(5)
    b, m, heads, kvh, dh = 2, 12, 8, 4, 8
    q = jnp.asarray(rng.normal(size=(b, m + 1, heads, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, m + 1, kvh, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, m + 1, kvh, dh)), jnp.float32)
    lam = jnp.float32(0.37)
    allowed = jnp.tril(jnp.ones((m + 1, m + 1), bool))[None, None]
    want = attn_ops.gqa_attend_materialised(q, k, v, allowed, 0.35,
                                            window=window, diff_lam=lam)
    assert want.shape == (b, m + 1, heads // 2, 2 * dh)
    order = np.roll(np.arange(m), 5) if window else np.arange(m)

    def rows(x):            # (b, m, kvh, dh) -> (b, m, kvh * dh)
        return x[:, order].reshape(b, m, kvh * dh)

    got = attn_ops.gqa_attend_rows(
        q[:, -1], k[:, -1], v[:, -1], rows(k[:, :m]),
        lambda _w: rows(v[:, :m]), jnp.ones((b, m), bool), 0.35,
        window=window, diff_lam=lam)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, -1]),
                               atol=2e-6)
    # it IS a difference of two softmaxes over a pair of key heads
    w1 = jax.nn.softmax(jnp.einsum("bd,bjd->bj", q[:, -1, 0], k[:, :, 0])
                        * 0.35, axis=-1)
    w2 = jax.nn.softmax(jnp.einsum("bd,bjd->bj", q[:, -1, 2], k[:, :, 1])
                        * 0.35, axis=-1)
    pair = jnp.einsum("bj,bjd->bd", w1 - lam * w2,
                      v[:, :, :2].reshape(b, m + 1, 2 * dh))
    np.testing.assert_allclose(np.asarray(want[:, -1, 0]), np.asarray(pair),
                               atol=2e-6)


# -- (v) the engine: state beside the pools ------------------------------------

GREEDY = SamplingParams(filter_thres=1.0)


def _serve(params, reqs, **kw):
    queue = RequestQueue(max_depth=8)
    kw.setdefault("num_slots", 2)
    engine = Engine(params, CFG, queue, chunk_steps=8, kv="paged",
                    page_size=PS, **kw)
    handles = [queue.submit(dataclasses.replace(r)) for r in reqs]
    engine.run_until_idle()
    out = []
    for r, h in zip(reqs, handles):
        res = h.result(timeout=5)
        assert res.status == "ok"
        out.append(list(np.asarray(res.text_tokens))
                   + list(np.asarray(res.tokens)))
        assert out[-1][:len(r.codes)] == list(r.codes)
    return engine, out


REQS = [Request(codes=(3, 7, 9), seed=11, sampling=GREEDY),
        Request(codes=tuple(range(1, 11)), seed=2, sampling=GREEDY),
        Request(codes=(6, 6, 1, 2, 3, 9, 4), seed=3, sampling=GREEDY)]


def test_engine_serves_the_reference_s_tokens_in_chunks_of_8(params):
    """Through the engine: prompts of 3 and 10 tokens admitted in one
    bucket (each row's state at its own length), the whole-page write
    into both pools, the ring's pages reused, a slot reused by the third
    request, the fused chunks. Greedy tokens are the reference's best at
    every served position (gap 0 but for float32 near-ties)."""
    engine, seqs = _serve(params, REQS)
    lens = [len(r.codes) for r in REQS]
    assert all(len(s) == DIMS.seq_len for s in seqs)
    gaps, served = FAMILY.reference.served_gaps(SEED, DIMS, jnp.float32,
                                                seqs, lens)
    assert float(np.asarray(gaps)[np.asarray(served)].max()) < 1e-4
    st = engine.stats()
    assert engine.decode_traces == 1
    assert engine.alloc.in_use == 0 and engine.window.alloc.in_use == 0
    assert st["window_pages_reused"] > 0
    assert st["state_bytes"] == 4 * 2 * (DIMS.d_inner * DIMS.d_state * 4
                                         + 3 * DIMS.d_inner * 4)
    assert "moe_picks" not in st
    assert st["kv_hbm_bytes"] == KV.modeled_kv_bytes(
        TCFG, kv="paged", num_slots=2, total_len=DIMS.seq_len,
        page_size=PS)
    # the full pool's one layer is read by three layers, the rings by
    # three, the state by four
    row = 2 * (2 * PS * 8) * 4
    assert st["kv_read_bytes_per_token"] == (
        3 * WIDTH + 3 * RING) * row + st["state_bytes"] // 2


def test_a_reused_slot_gives_the_tokens_of_a_fresh_engine(params):
    """One slot, three requests one after the other: the second and third
    start in a slot whose state their predecessor left."""
    _, shared = _serve(params, REQS, num_slots=1)
    for i, req in enumerate(REQS):
        _, alone = _serve(params, [req], num_slots=1)
        assert shared[i] == alone[0], i


def test_a_row_joining_through_the_small_group_leaves_the_others_state(
        params):
    """Six slots, so an admission takes 4 rows or 6
    (``scheduler.prefill_groups``): two requests start in the 4-row
    group, a third joins them mid-image in it (its unused rows' state
    is dropped, not written over a running slot's), then six at once
    take the whole group. Every stream is the one a fresh engine gives."""
    alone = [_serve(params, [r], num_slots=1)[1][0] for r in REQS]
    queue = RequestQueue(max_depth=16)
    bucket = CFG.text_seq_len       # one bucket: a burst is one group
    engine = Engine(params, CFG, queue, chunk_steps=8, kv="paged",
                    page_size=PS, num_slots=6, prefill_buckets=(bucket,))
    first = [queue.submit(dataclasses.replace(r)) for r in REQS[1:]]
    engine.step_once()
    engine.step_once()
    assert engine.active_slots() == 2 and engine.prefill_runs == 1
    late = queue.submit(dataclasses.replace(REQS[0]))
    engine.run_until_idle()
    burst = [queue.submit(dataclasses.replace(r)) for r in REQS + REQS]
    engine.run_until_idle()
    assert (engine.prefill_trace_count(bucket, 4),
            engine.prefill_trace_count(bucket, 6)) == (1, 1)
    for h, want in zip([late] + first + burst, alone + alone + alone):
        res = h.result(timeout=5)
        assert list(np.asarray(res.text_tokens)) \
            + list(np.asarray(res.tokens)) == want


def test_an_evicted_request_replays_to_the_same_tokens(params):
    reqs = REQS[:2]
    roomy, want = _serve(params, reqs)
    tight, got = _serve(params, reqs, num_pages=WIDTH + 4)
    assert got == want and tight.evicted > 0 and roomy.evicted == 0
    assert tight.alloc.in_use == 0 and tight.window.alloc.in_use == 0


# -- (vi) every path that cannot run the block refuses it ----------------------

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
    assert e.value.block == BLK.name == "ssm_hybrid" and e.value.option
    assert BLK.name in str(e.value) and e.value.option in str(e.value)


@pytest.mark.parametrize("call", ["export", "import"])
def test_migration_refuses_the_block_and_falls_back_to_replay(params, call):
    engine = _engine(params, page_size=PS)
    with pytest.raises(MigrationError, match="ssm_hybrid.*export/import") \
            as e:
        engine.export_slot(0) if call == "export" \
            else engine.import_slot({"weights_version": "0"})
    assert e.value.reason == "block"


def test_a_block_whose_readers_have_no_source_is_refused():
    with pytest.raises(ValueError, match="gmu.*ssm"):
        dataclasses.replace(BLK, mixers=("gmu", "window"))
    with pytest.raises(ValueError, match="cross.*full"):
        dataclasses.replace(BLK, mixers=("ssm", "cross"))
    with pytest.raises(ValueError, match="mixers names 12"):
        dataclasses.replace(TCFG, depth=4)
    with pytest.raises(ValueError, match="one of"):
        dataclasses.replace(BLK, mixers=("ssm", "global"))
    with pytest.raises(ValueError, match="every one of its"):
        FAMILY.weights.dims_of(CONF, 8)
