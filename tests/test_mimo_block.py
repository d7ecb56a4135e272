"""The window-and-full block whose layer types differ in shape
(``ops.transformer.WindowGQABlock`` with ``full_kv_heads``, ``v_head_dim``,
``rotary_dim``, ``full_rope_theta``, ``value_scale``, ``sink`` and no
norms over heads, gate, second norms or shared expert) at toy widths,
float32, seeded: the contract of every described block
(``block_contract.py``: the program against the benchmark family's plain
reference, ``benchmark/families/mimo_v2/reference.py``, at logit level on a
sequence several windows long; the paged decode; the engine; every
refusal), then its own: the two pools, whose K rows are wider than
their V rows and whose key/value heads differ by layer type (four buffers
of four widths); the ring at its edges; the sink; the partial rotary
positions at two bases; the held share of the experts (all the shares add
up to the uncut layer, nothing counted once but the residual).

Tolerances: the program and the reference compute the same float32
mathematics in another order (grouped products, a cached ring read in
ring order, one matrix product a head group); at these widths the logits
(spread 0.6) agree to 2e-5, which a dropped sink, window row, rotary
number or expert misses by three orders of magnitude, and which the same
program in bfloat16 misses by two (``test_bfloat16_fails_the_tolerance``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, seeds
from block_contract import (BlockContract, Toy, params,  # noqa: F401
                            ref_logits, sequences, served)
from dalle_pytorch_tpu.ops import attention as attn_ops
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.ops import moe as moe_ops
from dalle_pytorch_tpu.ops import transformer as T
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve.engine import Engine
from dalle_pytorch_tpu.serve.scheduler import RequestQueue

# a window of two pages in a sequence of nine: the ring (three pages, 12
# rows) turns twice. A prompt inside the window's first page; one past the
# window (8) and the ring's first page boundary; one longer than the whole
# ring (12): its first rows are overwritten at admission. (The parameter
# tree's keys are the afmoe block's too.)
TOY = Toy("mimo_v2", "mimo-v2.5", 7, "window_sink_gqa_moe",
          overrides=dict(text_seq_len=10, image_grid=5, sliding_window=8,
                         sliding_window_size=8),
          t0s=(3, 9, 14), bf16_misses=100, tree_name="window_gqa_moe",
          chunked=(("k", 1, 1e-5, 1e-5), ("v", 1, 1e-5, 1e-5)))
FAMILY, CONF, DIMS, CFG, TCFG, BLK = (TOY.family, TOY.conf, TOY.dims,
                                      TOY.cfg, TOY.tcfg, TOY.blk)
PS, RING, WIDTH, SEED, DEPTH = (TOY.page_size, TOY.ring, TOY.width, TOY.seed,
                                TOY.depth)
FULL_LAYERS = [i for i, t in enumerate(DIMS.layer_types) if t == "full"]
WINDOW_LAYERS = [i for i, t in enumerate(DIMS.layer_types) if t != "full"]
# a row of each buffer: the layer type's key/value heads x a K or a V head
ROWS = {"k": 1 * 12, "v": 1 * 8, "window_k": 2 * 12, "window_v": 2 * 8}


class TestContract(BlockContract):
    toy = TOY

    def step_loads(self, loads, b, t0):
        """Every edge of the window (positions 7, 8, 9), every page
        boundary and both wraps of the ring (12, 24) lie on the way. With
        a sink the load is float32: the six counts, then the sink's
        weight over every window softmax of the step and their number
        (``decode_ops.load_like``)."""
        picks = b * BLK.experts_per_token * DIMS.moe_layers
        reads = b * TCFG.heads * len(WINDOW_LAYERS)
        mass = 0.0
        for load in loads:
            assert load.shape == (8,) and load.dtype == jnp.float32
            assert int(load[0]) == picks and 0 <= int(load[4]) <= picks
            assert int(load[5]) == picks    # too few pairs for a row ladder
            assert int(load[3]) == int(load[1])     # and for a second tile
            assert int(load[7]) == reads
            assert 0.0 < float(load[6]) < reads
            mass += float(load[6]) / reads
        # the draw gives the sink a real share of a window row's weight
        assert 0.05 < mass / (DIMS.seq_len - 1 - t0) < 0.9

    def chunk_loads(self, loads, b):
        assert sum(int(load[0]) for load in loads) \
            == 16 * b * BLK.experts_per_token * DIMS.moe_layers
        assert sum(int(load[7]) for load in loads) \
            == 16 * b * TCFG.heads * len(WINDOW_LAYERS)

    def watch(self, engine):
        st = engine.stats()
        assert st["window_pages_in_use"] <= 2 * RING
        assert st["layer_pages_in_use"] <= st["layer_pages_all_full"]

    def test_engine_serves_the_reference_s_tokens_in_chunks_of_8(
            self, served, switch_placement):
        """Through the engine: admission's whole-page write into both
        pools (a prompt longer than the window among them), the ring's
        pages reused as the slots move on, slot reuse, the fused chunks;
        the routed load and the sink's weight come out with the ring, and
        both pools are empty at the end. Both full layers are runs of
        one: at the published sizes (``a_switch_a_read``: the experts'
        stacks are too much to hand out of one switch) they read their
        tables whole and the width rule's counters stay 0; the toy's
        small experts let ONE switch stand around the scans from the
        first full layer to the last, and both read at the step's
        profile."""
        self.engine_serves(self.together(served, switch_placement),
                           switch_placement)

    def engine_counters(self, engine, st, placement):
        assert engine.window.ring == RING and engine.block_tables[
            "window"].shape == (2, RING)
        assert {n: a.shape[2:] for n, a in engine.cache.items()} == {
            n: (PS, w) for n, w in ROWS.items()}
        assert st["window_pages_reused"] == 3 * (WIDTH - RING)
        assert 0 < st["moe_picks_held"] < st["moe_picks"]
        # two slots' pairs are under the row ladder's first step: all
        # handed on
        assert st["moe_rows_computed"] == st["moe_picks"]
        # the sink's counters: a softmax a window layer a head an ACTIVE
        # slot a step, fewer than every slot's every step; its weight a
        # real share
        assert 0 < st["window_sink_reads"] <= st["decode_steps"] * 2 \
            * TCFG.heads * len(WINDOW_LAYERS)
        assert st["window_sink_reads"] % (TCFG.heads
                                          * len(WINDOW_LAYERS)) == 0
        assert 0.05 < st["window_sink_mass"] / st["window_sink_reads"] < 0.9
        assert st["kv_hbm_bytes"] == (2 * 19 * 20 + 5 * 7 * 40) * PS * 4
        # what a step's gathers read: a full layer its table, a window
        # layer its ring, each page at its own K and V widths
        assert st["kv_read_bytes_per_token"] == \
            (2 * WIDTH * 20 + 5 * RING * 40) * PS * 4
        if placement == "a_switch_a_read":
            assert engine._view_plan is None
            assert st["kv_view_columns_read"] \
                == st["kv_view_columns_full"] == 0
        else:
            # two slots are one group, which reads the whole table
            assert engine._view_plan.by_rule == 2 \
                and st["kv_view_groups"] == 1
            assert st["kv_view_columns_read"] == st["kv_view_columns_full"] \
                == st["decode_steps"] * 2 * 2 * WIDTH


def test_the_toy_is_the_published_pattern_and_wraps_its_window():
    assert DIMS.layer_types == ("full", "sliding", "sliding", "sliding",
                                "sliding", "full", "sliding")
    assert DIMS.dense_layers == 1 and DIMS.moe_layers == 6
    assert (DIMS.full_kv_heads, DIMS.kv_heads, DIMS.head_dim,
            DIMS.v_head_dim, DIMS.rotary_dim) == (1, 2, 12, 8, 4)
    assert (RING, WIDTH) == (3, 9) and DIMS.seq_len > 4 * DIMS.window
    assert (DIMS.experts, DIMS.experts_held, DIMS.first_expert) == (16, 4, 4)
    # a full layer's parameters differ in shape from a window layer's, so
    # they lie in stacks of their own; the window layer after the full one
    # is a run of its own, at its place in the window layers' stack
    runs = T.layer_runs(BLK, DEPTH)
    assert [(BLK.stack_of(r.kind), r.count, r.at, r.cache) for r in runs] \
        == [("dense_full", 1, 0, 0), ("moe", 4, 0, 0), ("moe_full", 1, 0, 1),
            ("moe", 1, 4, 4)]
    assert list(DIMS.stacks()) == ["dense_full", "moe", "moe_full"]
    # the published configuration is the same code at its own numbers
    real = FAMILY.weights.dims_of(harness.load_json(
        harness.ROOT + "/benchmark/configs/mimo-v2.5.json"))
    assert (real.heads, real.full_kv_heads, real.kv_heads, real.head_dim,
            real.v_head_dim, real.rotary_dim, real.window) \
        == (64, 4, 8, 192, 128, 64, 128)
    assert real.layer_types == DIMS.layer_types
    blk = FAMILY.build.program_config(real, {}).transformer.block
    assert blk.ring_pages(16, real.seq_len) == 9
    assert {n: blk.buffer_row_width(n) for n in ROWS} == {
        "k": 768, "v": 512, "window_k": 1536, "window_v": 1024}


# -- (i) each mechanism in the logits ----------------------------------------

@pytest.mark.parametrize("without", ["sink", "value_scale", "rotary_part",
                                     "full_base", "window_base"])
def test_each_mechanism_is_in_the_logits(params, sequences, ref_logits,
                                         without):
    """A program that leaves the sink out of the softmax, the scale off
    the values, turns the whole head instead of its first numbers, or
    turns a layer type at the other type's base, fails the tolerance."""
    p = params
    if without == "sink":
        # exp(-40) of a row's weight: the softmax without it
        p = jax.tree.map(lambda a: a, params)
        p["transformer"]["moe"]["attn"]["sink"] = jnp.full_like(
            params["transformer"]["moe"]["attn"]["sink"], -40.0)
        blk = BLK
    else:
        blk = dataclasses.replace(BLK, **{
            "value_scale": dict(value_scale=1.0),
            "rotary_part": dict(rotary_dim=None),
            "full_base": dict(full_rope_theta=BLK.rope_theta),
            "window_base": dict(rope_theta=BLK.full_rope_theta)}[without])
    got = np.asarray(TOY.apply(p, sequences,
                               dataclasses.replace(CFG, block=blk)))
    fin = np.isfinite(ref_logits)
    assert np.abs(got[fin] - ref_logits[fin]).max() > 50 * 2e-5


def test_partial_rotary_against_the_reference():
    """The first ``rotary_dim`` numbers of a head turn, as rotate-half
    pairs inside them, at the layer type's base; the others pass."""
    x = jax.random.normal(jax.random.PRNGKey(0), (7, 4, 12))
    pos = jnp.asarray([0, 1, 5, 127, 128, 129, 4351])
    for full in (False, True):
        theta = BLK.rope_theta_of(full)
        assert theta == (1e7 if full else 1e4)
        want = FAMILY.reference.rope(x, pos, theta, 4)
        got = attn_ops.rope_part(x, pos[:, None], theta, 4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(got[..., 4:]),
                                      np.asarray(x[..., 4:]))
        assert not np.allclose(np.asarray(got[1:, :, :4]),
                               np.asarray(x[1:, :, :4]))
    # the whole head is the plain rotation
    np.testing.assert_array_equal(
        np.asarray(attn_ops.rope_part(x, pos[:, None], 1e4, None)),
        np.asarray(attn_ops.rope_half(x, pos[:, None], 1e4)))


# -- (ii) the pools: a width a buffer -----------------------------------------

def test_a_page_has_its_buffer_s_own_width():
    layout = KV.page_layout(TCFG, PS)
    assert layout == {n: ((PS, w), None) for n, w in ROWS.items()}
    plan = KV.pool_plan(TCFG, 19, 7)
    assert plan == {"k": (2, 19), "v": (2, 19), "window_k": (5, 7),
                    "window_v": (5, 7)}
    pool = KV.init_page_pool(TCFG, 19, PS, window_pages=7)
    assert {n: a.shape for n, a in pool.items()} == {
        n: plan[n] + (PS, w) for n, w in ROWS.items()}
    # two full layers of 2 x 9 + 1 pages, five window layers of 2 x 3 + 1,
    # each page PS rows of its buffer's width, float32
    want = (2 * 19 * (12 + 8) + 5 * 7 * (24 + 16)) * PS * 4
    assert KV.pool_bytes(pool) == want == KV.modeled_kv_bytes(
        TCFG, kv="paged", num_slots=2, total_len=DIMS.seq_len, page_size=PS)
    # a page's snapshot and restore follow the buffers
    snap = KV.snapshot_page(pool, 3)
    assert {n: a.shape for n, a in snap.items()} == {
        n: (plan[n][0], PS, w) for n, w in ROWS.items()}
    KV.restore_page(pool, 5, snap)


@pytest.mark.parametrize("family, config, widths", [
    ("afmoe", "trinity-large-preview",
     {"k": 1024, "v": 1024, "window_k": 1024, "window_v": 1024}),
    ("phi4flash", "phi-4-mini-flash-reasoning",
     {"k": 1280, "v": 1280, "window_k": 1280, "window_v": 1280}),
    ("mla_moe", "kanana-2-30b-a3b", {"latent": 640}),
    ("dalle", "rudalle-xl", {"k": 2048, "v": 2048}),
    ("mimo_v2", "mimo-v2.5",
     {"k": 768, "v": 512, "window_k": 1536, "window_v": 1024}),
])
def test_every_configuration_s_page_widths(family, config, widths):
    """The existing blocks' layouts are what they were (one width a
    block); the new configuration's four buffers have four widths."""
    fam = harness.load_family(family)
    conf = harness.load_json(f"{harness.ROOT}/benchmark/configs/{config}.json")
    cell = harness.load_json(
        f"{harness.ROOT}/benchmark/cells/{config}.serve-full.json")
    dims = fam.weights.dims_of(conf, cell["depth"])
    tcfg = fam.build.program_config(dims, cell["flags"]).transformer
    pages = {n: shape for n, (shape, _) in KV.page_layout(tcfg, 16).items()
             if not n.startswith("ssm_")}
    assert pages == {n: (16, w) for n, w in widths.items()}


def test_slots_at_the_ring_s_edges_match_the_full_forward(
        params, sequences, ref_logits):
    """Sixteen slots at the positions where a ring can go wrong: the
    window's edge (7, 8, 9: the last row inside, the first that leaves),
    the ring's page boundaries (4, 12, 16), its wraps (11, 12, 13 and 23,
    24, 25), a parked slot and the last row. Both full layers are runs of
    one, which read their table whole in slot order."""
    positions = np.asarray([0, 1, 4, 7, 8, 9, 11, 12, 13, 16, 23, 24, 25,
                            30, 33, DIMS.seq_len - 2], np.int32)
    full = [r for r in T.layer_runs(BLK, DEPTH) if r.full]
    assert len(full) == 2 and all(r.count == 1 for r in full)
    rows = np.arange(len(positions)) % len(sequences)
    active = jnp.asarray(positions > 0)
    got, load, _ = TOY.step_at(params, sequences[rows], positions, active)
    TOY.close(got[1:], ref_logits[rows, positions][1:])
    # the parked slot's softmaxes are not counted
    assert int(load[7]) == 15 * TCFG.heads * len(WINDOW_LAYERS)


def test_window_rows_at_the_published_ring_s_edges():
    """The published ring: 9 pages of 16 rows under a window of 128. At
    positions 127, 128, 129 the window is the last 128 rows written; at
    the wrap (144) row 0 holds position 144 - 144 = 0 no longer."""
    rows, window = 144, 128
    pos = jnp.asarray([127, 128, 129, 143, 144, 145, 288, 4351])
    held, ok = decode_ops.window_rows(pos, rows, window)
    held, ok = np.asarray(held), np.asarray(ok)
    for i, p in enumerate(np.asarray(pos)):
        # a decode step at ``p`` attends the cached positions p - 127 ..
        # p - 1 and its own row: 128 in all
        want = list(range(max(p - window + 1, 0), p))
        assert sorted(held[i][ok[i]]) == want
        for q in want:
            assert held[i][q % rows] == q


def test_cached_rows_read_equals_the_materialised_read_with_a_sink():
    """One query against cached rows of K wider than V, with the sink:
    the decode read is the materialised one, the weights sum to 1 less
    the sink's share, and both report that share."""
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    p = attn_ops.gqa_init(k[0], 32, 4, BLK)
    assert p["k"]["w"].shape == (32, 2 * 12) and p["v"]["w"].shape \
        == (32, 2 * 8) and p["out"]["w"].shape == (4 * 8, 32)
    assert set(p) == {"q", "k", "v", "out", "sink"}
    assert set(attn_ops.gqa_init(k[0], 32, 4, BLK, full=True)) \
        == {"q", "k", "v", "out"}
    p["sink"] = jax.random.normal(k[3], (4,)) + 1.0
    b, m = 3, 2 * PS
    h = jax.random.normal(k[1], (b, m + 1, 32))
    q, gate, (kk, vv) = attn_ops.gqa_project(p, h, jnp.arange(m + 1), 4, BLK,
                                             full=False)
    assert gate is None and q.shape == (b, m + 1, 4, 12) \
        and kk.shape == (b, m + 1, 2, 12) and vv.shape == (b, m + 1, 2, 8)
    allowed = jax.random.bernoulli(k[2], 0.7, (b, m))
    full = jnp.concatenate([allowed, jnp.ones((b, 1), bool)], axis=1)
    want, want_mass = attn_ops.gqa_attend_materialised(
        q[:, -1:], kk, vv, full[:, None, None, :], TCFG.scale,
        window=True, sink=p["sink"])

    def rows(x):            # (b, m, kvh, d) -> (b, m, kvh * d)
        return x[:, :m].reshape(b, m, -1)

    got, mass = attn_ops.gqa_attend_rows(
        q[:, -1], kk[:, m], vv[:, m], rows(kk), lambda _w: rows(vv),
        allowed, TCFG.scale, window=True, sink=p["sink"])
    assert got.shape == (b, 4, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(mass), np.asarray(want_mass[:, 0]),
                               atol=2e-6)
    assert (np.asarray(mass) > 0.05 * 4).all() and (np.asarray(mass)
                                                    < 4).all()
    # with constant values the output IS the weights' sum: under 1
    ones = jnp.ones_like(vv)
    total, _ = attn_ops.gqa_attend_rows(
        q[:, -1], kk[:, m], ones[:, m], rows(kk), lambda _w: rows(ones),
        allowed, TCFG.scale, window=True, sink=p["sink"])
    assert (np.asarray(total) < 1.0 - 1e-3).all()
    np.testing.assert_allclose(np.asarray(total).mean(-1).sum(-1),
                               4 - np.asarray(mass), atol=1e-5)
    # the reference's weights, a head at a time
    R = FAMILY.reference
    score = jnp.einsum("bhd,bjhd->bhj", q[:, -1], jnp.repeat(kk, 2, axis=2)
                       ) * TCFG.scale
    for hd in range(4):
        w = R.attention_weights(score[:, hd], full, p["sink"][hd])
        np.testing.assert_allclose(
            1.0 - np.asarray(w).sum(-1), np.asarray(
                attn_ops.gqa_attend_materialised(
                    q[:, -1:, hd:hd + 1], kk[:, :, hd // 2:hd // 2 + 1],
                    vv[:, :, hd // 2:hd // 2 + 1], full[:, None, None, :],
                    TCFG.scale, window=True, sink=p["sink"][hd:hd + 1])[1]
            )[:, 0], atol=2e-6)


# -- (iii) the sink's counters -----------------------------------------------

def test_a_block_without_a_sink_has_no_sink_counters():
    fam = harness.load_family("afmoe")
    conf = dict(harness.load_json(
        harness.ROOT + "/benchmark/configs/trinity-large-preview.json"),
        **fam.tiny)
    dims = fam.weights.dims_of(conf, 5)
    cfg = fam.build.program_config(dims, {})
    assert not cfg.transformer.block.sink
    p = jax.jit(lambda h: fam.weights.tree(h, dims, jnp.float32))(
        seeds.split_seed(SEED))
    engine = Engine(p, cfg, RequestQueue(max_depth=2), num_slots=1,
                    kv="paged", page_size=PS)
    assert "window_sink_mass" not in engine.stats() \
        and "moe_picks" in engine.stats()
    assert set(p["transformer"]) == {"dense", "moe"}
    assert T.block_name_of(p["transformer"]) == "window_gqa_moe"


# -- (iv) the held share of the experts ---------------------------------------

def test_all_the_shares_add_up_to_the_uncut_reference_layer():
    """The routed parts that the 4 shares of 4 experts give add up to the
    reference's whole layer of 16 experts: no shared expert, so nothing
    is counted once but the residual, which the layer adds outside."""
    whole = TOY.dims_of(experts_held=16, first_expert=0)
    key = seeds.layer_key(seeds.seed_key(SEED), whole.first_layer + 2)
    ref_p = FAMILY.weights.layer(key, whole, jnp.float32, True, False)["ff"]
    assert "shared" not in ref_p
    m = jax.random.normal(jax.random.PRNGKey(4), (24, whole.dim))
    R = FAMILY.reference
    weights = R.route(ref_p, m, whole)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    assert ((np.asarray(weights) > 0).sum(-1) == 2).all()
    want = R.routed(ref_p["experts"], m, weights)
    total, held = np.zeros((24, whole.dim), np.float32), 0
    for first in range(0, 16, 4):
        dims = TOY.dims_of(first_expert=first)
        blk = FAMILY.build.program_config(dims, {}).transformer.block
        p = FAMILY.weights.layer(key, dims, jnp.float32, True, False)["ff"]
        np.testing.assert_array_equal(
            np.asarray(p["experts"]["w_in"]),
            np.asarray(ref_p["experts"]["w_in"][first:first + 4]))
        out, load = moe_ops.dropless_apply(p, m, blk)
        total += np.asarray(out)
        held += int(load[4])
        assert int(load[0]) == 24 * 2 and int(load[1]) <= 4
        # and the family's reference, given the same share, agrees
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(R.routed(
                p["experts"], m, R.route(p, m, dims)[:, first:first + 4])),
            atol=1e-5)
    assert held == 24 * 2               # every pick is held somewhere, once
    np.testing.assert_allclose(total, np.asarray(want), atol=2e-5)


def test_a_routed_layer_without_a_shared_expert():
    blk = dataclasses.replace(BLK, num_experts=8, experts_per_token=2,
                              experts_held=3, first_expert=2)
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    p = moe_ops.dropless_init(k[0], 32, blk)
    assert set(p) == {"router", "experts"}
    x = jax.random.normal(k[1], (24, 32))
    out, load = moe_ops.dropless_apply(p, x, blk)
    picks, weights = moe_ops.route(p["router"], x, 2, blk.routed_scale)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    # a token none of whose picks is held here gets nothing at all
    nothing = ~((np.asarray(picks) >= 2) & (np.asarray(picks) < 5)).any(-1)
    assert nothing.any() and not np.asarray(out)[nothing].any()
    assert np.asarray(out)[~nothing].any(-1).all()
    assert int(load[0]) == 48 and int(load[4]) < 48


def test_a_block_whose_turned_part_is_no_part_of_a_head_is_refused():
    for turned in (3, 14):
        with pytest.raises(ValueError, match="rotary_dim"):
            dataclasses.replace(BLK, rotary_dim=turned)
    with pytest.raises(ValueError, match="do not all lead"):
        TOY.dims_of(moe_layer_freq=[1, 0] + [1] * 46)
    with pytest.raises(ValueError, match="add_swa_attention_sink_bias"):
        TOY.dims_of(add_swa_attention_sink_bias=False)
