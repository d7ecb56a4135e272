"""The window-and-full, grouped-query, gated-attention block holding a
share of its routed experts (``ops.transformer.WindowGQABlock``) at toy
widths, float32, seeded: the contract of every described block
(``block_contract.py``: the program against the benchmark family's plain
reference, ``benchmark/families/afmoe/reference.py``, at logit level on a
sequence several windows long; the paged decode through both pools; the
engine; every refusal), then its own: the window's ring, the width rule
where every full layer is a run of one, the held share of the experts
(all the shares add up to the uncut layer).

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

from benchmark import seeds
from block_contract import (BlockContract, Toy, params,  # noqa: F401
                            ref_logits, sequences, served)
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.ops import attention as attn_ops
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.ops import moe as moe_ops
from dalle_pytorch_tpu.ops import transformer as T
from dalle_pytorch_tpu.serve import kv_pool as KV

# a window of two pages in a sequence of nine: the ring (three pages)
# turns twice
TOY = Toy("afmoe", "trinity-large-preview", 5, "window_gqa_moe",
          overrides=dict(text_seq_len=10, image_grid=5, sliding_window=8),
          t0s=(7, 14),      # inside one page of the ring; past it
          chunked=(("k", 0, 1e-5, 1e-5),), evicted=(0, 1))
FAMILY, DIMS, TCFG, BLK = TOY.family, TOY.dims, TOY.tcfg, TOY.blk
PS, RING, WIDTH, SEED = TOY.page_size, TOY.ring, TOY.width, TOY.seed
RINGS_HELD = []         # the most pages a slot's ring held, an engine step


class TestContract(BlockContract):
    toy = TOY

    def step_loads(self, loads, b, t0):
        picks = b * BLK.experts_per_token * DIMS.moe_layers
        for load in loads:
            assert int(load[0]) == picks and 0 <= int(load[4]) <= picks

    def chunk_loads(self, loads, b):
        assert sum(int(load[0]) for load in loads) \
            == 16 * b * BLK.experts_per_token * DIMS.moe_layers

    def watch(self, engine):
        for i in range(2):
            held = engine.window.pages_of(i)
            RINGS_HELD.append(len(held))
            assert len(held) <= BLK.window // PS + 1 == RING
            assert len(held) <= len(engine._slot_pages[i])
        st = engine.stats()
        assert st["window_pages_in_use"] <= 2 * RING
        assert st["layer_pages_in_use"] <= st["layer_pages_all_full"]

    def test_engine_serves_the_reference_s_tokens_in_chunks_of_8(
            self, served, switch_placement):
        """Through the engine: admission's whole-page write into both
        pools, the ring's pages reused as the slots move on, slot reuse,
        the fused chunks; the routed load comes out with the ring, and
        both pools are empty at the end. The counters of the width rule
        (ISSUE 38) count what the traced program reads: nothing where the
        one full layer, a run of one, reads whole (``a_switch_a_read``:
        the published sizes), the layer's table a step where it stands in
        the step's switch."""
        self.engine_serves(self.together(served, switch_placement),
                           switch_placement)

    def engine_counters(self, engine, st, placement):
        assert engine.window.ring == RING and engine.block_tables[
            "window"].shape == (2, RING)
        assert max(RINGS_HELD) == RING
        assert st["full_pages_in_use"] == st["window_pages_in_use"] == 0
        # three requests each ran six pages past their ring
        assert st["window_pages_reused"] == 3 * (WIDTH - RING)
        assert 0 < st["moe_picks_held"] < st["moe_picks"]
        # two slots' pairs are under the row ladder's first step: all
        # handed on
        assert st["moe_rows_computed"] == st["moe_picks"]
        assert 0 < st["moe_experts_touched"] <= \
            st["decode_steps"] * DIMS.moe_layers * BLK.experts_held
        # a pool per layer type: 4 window layers of 2 x 3 + 1 pages, one
        # full layer of 2 x 9 + 1, K and V of 2 heads x 4 rows x 8 floats
        assert st["kv_hbm_bytes"] == (4 * 7 + 1 * 19) * 2 * (2 * PS * 8) * 4
        assert st["kv_read_bytes_per_token"] == \
            (4 * RING + 1 * WIDTH) * 2 * (2 * PS * 8) * 4
        rows = [r for r in engine.loop_ring.dump() if "kind" not in r]
        if placement == "a_switch_a_read":
            assert engine._view_plan is None
            assert st["kv_view_columns_read"] \
                == st["kv_view_columns_full"] == 0
            assert rows and all(r["view_read_pct"] is None for r in rows)
        else:
            # two slots are one group, which reads the whole table
            assert engine._view_plan.by_rule == 1 \
                and st["kv_view_groups"] == 1
            assert st["kv_view_columns_read"] == st["kv_view_columns_full"] \
                == st["decode_steps"] * 2 * WIDTH
            assert rows and all(r["view_read_pct"] == 100.0 for r in rows)


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


# -- (i) the width rule, the ring and the read -------------------------------

@pytest.mark.parametrize("at", [0, 3])
def test_slots_at_spread_positions_match_the_full_forward(
        params, sequences, ref_logits, profile_positions, reads_at, at,
        four_slots_a_group, switch_placement):
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
    got, _, plan = TOY.step_at(params, seqs, positions,
                               traced_as=(switch_placement, "by_rule"))
    TOY.close(got, want)
    with reads_at("too_narrow"):
        cut, _, _ = TOY.step_at(params, seqs, positions,
                                traced_as=(switch_placement, "too_narrow"))
    if switch_placement == "a_switch_a_read":
        assert plan.span is None and plan.by_rule == 0 \
            and plan.whole == len(full)
        np.testing.assert_array_equal(got, cut)
    else:
        assert plan.span is not None and plan.whole == 0 \
            and plan.by_rule == len(full) and plan.groups == 4
        with reads_at("full_width"):
            whole, _, _ = TOY.step_at(
                params, seqs, positions,
                traced_as=(switch_placement, "full_width"))
        np.testing.assert_array_equal(got.argmax(-1), whole.argmax(-1))
        if at:
            with pytest.raises(AssertionError):
                TOY.close(cut, want)


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


# -- (ii) the window pool's pages --------------------------------------------

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


# -- (iii) the held share of the experts --------------------------------------

def test_all_the_shares_add_up_to_the_uncut_reference_layer():
    """The routed parts that the 4 shares of 4 experts give, with the
    shared expert (which every chip computes alike) counted once, are the
    reference's whole layer of 16 experts."""
    whole = TOY.dims_of(experts_held=16, first_expert=0)
    key = seeds.layer_key(seeds.seed_key(SEED), whole.first_layer + 2)
    ref_p = FAMILY.weights.layer(key, whole, jnp.float32, True)["ff"]
    m = jax.random.normal(jax.random.PRNGKey(4), (24, whole.dim))
    want = FAMILY.reference._unit(ref_p["shared"], m, None) \
        + FAMILY.reference.routed(
            ref_p["experts"], m, FAMILY.reference.route(ref_p, m, whole))
    shared = np.asarray(D.core.swiglu(ref_p["shared"], m))
    total, held = shared.copy(), 0
    for first in range(0, 16, 4):
        dims = TOY.dims_of(first_expert=first)
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


def test_a_block_that_names_no_share_or_the_wrong_layers_is_refused():
    with pytest.raises(ValueError, match="share"):
        dataclasses.replace(BLK, experts_held=20, first_expert=0,
                            num_experts=16)
    with pytest.raises(ValueError, match="layer_types names 5"):
        dataclasses.replace(TCFG, depth=4)
    with pytest.raises(ValueError, match="sliding.*full"):
        dataclasses.replace(BLK, layer_types=("global",) * 5)
