"""The short-convolution hybrid block (``ops.transformer.ShortConvGQABlock``)
at toy widths, float32, seeded: the contract of every described block
(``block_contract.py``: the program against the benchmark family's plain
reference, ``benchmark/families/lfm2_moe/reference.py``, at logit level;
the paged decode from prompts of 1 and 2 tokens; the engine, a reused slot
and an evicted request's replay; every refusal), then its own: the two
forms of the gated short convolution as one identity; the tail a slot
beside the one page pool (ONE buffer of two rows, written at each row's
own prompt length, never advanced for an inactive slot, overwritten when a
slot is reused, rebuilt by the replay after an
eviction); routed layers behind layers that cache no row.

Tolerances: the program and the reference compute the same float32
mathematics in another order (grouped products, a cached read in page
order, one matrix product a head group, the convolution a token at a time
against a carried tail); at these widths the logits (spread 0.11) agree to
2e-5, which a dropped tail, gate, norm, rotation or selection bias misses
by orders of magnitude, and which the same program in bfloat16 misses by
three (``test_bfloat16_fails_the_tolerance``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, seeds
from block_contract import (GREEDY, BlockContract, Toy, params,  # noqa: F401
                            ref_logits, sequences, served)
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.ops import core
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.ops import moe as moe_ops
from dalle_pytorch_tpu.ops import shortconv as conv_ops
from dalle_pytorch_tpu.ops import transformer as T
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve.engine import Engine
from dalle_pytorch_tpu.serve.scheduler import Request, RequestQueue

# prompts of 1, 10, 2 and 7 tokens: the tail of a prompt shorter than the
# taps; with two slots the third and fourth requests reuse one
TOY = Toy("lfm2_moe", "lfm2-24b-a2b", 9, "shortconv_gqa_moe",
          overrides=dict(text_seq_len=10, image_grid=5), gap=1e-4,
          t0s=(1, 2, 7), bf16_misses=100, evicted=(1, 3), reused=True,
          requests=(Request(codes=(3,), seed=11, sampling=GREEDY),
                    Request(codes=tuple(range(1, 11)), seed=2,
                            sampling=GREEDY),
                    Request(codes=(6, 6), seed=3, sampling=GREEDY),
                    Request(codes=(6, 6, 1, 2, 3, 9, 4), seed=5,
                            sampling=GREEDY)))
FAMILY, PUBLISHED, DIMS, CFG, TCFG, BLK = (TOY.family, TOY.published,
                                           TOY.dims, TOY.cfg, TOY.tcfg,
                                           TOY.blk)
R, SEED, PS, DEPTH, ATOL, WIDTH, REQS = (FAMILY.reference, TOY.seed,
                                         TOY.page_size, TOY.depth, TOY.atol,
                                         TOY.width, TOY.requests)
FULL_LAYERS = [i for i, t in enumerate(DIMS.layer_types) if t == "full"]
CONV_LAYERS = [i for i, t in enumerate(DIMS.layer_types) if t == "conv"]


class TestContract(BlockContract):
    toy = TOY

    def step_loads(self, loads, b, t0):
        """A step that carries a state a slot AND returns a routed load:
        the four counts of a block that holds every expert."""
        for load in loads:
            assert load.shape == (4,) and load.dtype == jnp.int32
            assert int(load[0]) \
                == b * BLK.experts_per_token * DIMS.moe_layers
            assert 0 < int(load[1]) <= DIMS.moe_layers * DIMS.experts

    def engine_counters(self, engine, st, placement):
        """Prompts of 1 and 10 tokens admitted in one bucket (each row's
        tail at its own length), slots reused by the third and fourth
        requests."""
        assert {n: a.shape for n, a in engine.cache.items()} == {
            "k": (2, 2 * WIDTH + 1, PS, 16), "v": (2, 2 * WIDTH + 1, PS, 16),
            "conv_tail": (7, 2, 2, 32)}
        # the state pool's bytes under the buffer's own name
        assert st["conv_tail_bytes"] == st["state_bytes"] \
            == 7 * 2 * 2 * 32 * 4
        assert "window_pages_in_use" not in st \
            and "window_sink_mass" not in st
        assert 0 < st["moe_experts_touched"] <= st["decode_steps"] \
            * DIMS.moe_layers * DIMS.experts
        assert "moe_picks_held" not in st and "moe_rows_computed" not in st
        # a step's pair rows are one row tile here: nothing read twice
        assert st["moe_group_reads"] == st["moe_experts_touched"]
        # what a step reads: two full layers' tables, seven layers' tails
        assert st["kv_read_bytes_per_token"] == (
            2 * WIDTH * 2 * PS * 16 + 7 * 2 * 32) * 4


# -- (i) the stack as it is scanned -------------------------------------------

def test_the_toy_is_the_published_layers_1_to_9_at_period_1():
    assert DIMS.layer_types == ("conv", "full", "conv", "conv", "conv",
                                "full", "conv", "conv", "conv")
    assert (DIMS.first_layer, DIMS.dense_layers, DIMS.moe_layers) == (1, 1, 8)
    assert (FULL_LAYERS, len(CONV_LAYERS)) == ([1, 5], 7)
    assert BLK.period == 1 and BLK.experts_held == BLK.num_experts == 8
    # runs of layers alike; the second full layer and the second run of
    # convolutions lie further along their stacks and their caches
    runs = T.layer_runs(BLK, DEPTH)
    assert [(BLK.stack_of(r.kind), r.kind.pool, r.count, r.at, r.cache)
            for r in runs] == [
        ("dense", "state", 1, 0, 0), ("moe_full", "full", 1, 0, 0),
        ("moe", "state", 3, 0, 1), ("moe_full", "full", 1, 1, 1),
        ("moe", "state", 3, 3, 4)]
    assert list(DIMS.stacks()) == ["dense", "moe_full", "moe"]
    assert all(len(scan) == 1 for scan in T.stack_scans(BLK, DEPTH))
    assert BLK.cache_layers("state", DEPTH) == tuple(CONV_LAYERS)
    assert BLK.cache_layers("full", DEPTH) == tuple(FULL_LAYERS)
    assert BLK.pools(DEPTH) == {"full": ("k", "v"), "state": ("conv_tail",)}
    assert tuple(BLK.state_layout(32)) == BLK.pool_buffers("state")


def test_all_40_published_layers_in_the_published_order():
    """The whole model is the same code with more scans: two dense
    convolutions, then nine times (full, conv x 3), a full layer and the
    last convolution."""
    dims = FAMILY.weights.dims_of(dict(PUBLISHED, first_layer=0), 40)
    blk = FAMILY.build.program_config(dims, {}).transformer.block
    assert (dims.dense_layers, dims.full_layers, dims.conv_layers) \
        == (2, 10, 30)
    assert [i for i, t in enumerate(dims.layer_types) if t == "full"] \
        == list(range(2, 40, 4))
    runs = T.layer_runs(blk, 40)
    assert len(runs) == len(T.stack_scans(blk, 40)) == 21
    assert [(blk.stack_of(r.kind), r.count) for r in runs] == [
        ("dense", 2)] + [("moe_full", 1), ("moe", 3)] * 9 + [
        ("moe_full", 1), ("moe", 1)]
    assert [r.at for r in runs if r.full] == list(range(10))
    assert [r.at for r in runs if r.moe and not r.full] \
        == list(range(0, 28, 3))
    # each run's first layer in its cache: the tails of 30 layers, the
    # rows of 10
    assert [r.cache for r in runs if not r.full] == [0] + list(
        range(2, 30, 3))
    assert [r.cache for r in runs if r.full] == list(range(10))
    # the published sizes
    assert (dims.dim, dims.heads, dims.kv_heads, dims.head_dim,
            dims.conv_taps, dims.dense_hidden, dims.expert_hidden,
            dims.experts, dims.experts_per_token, dims.total_tokens) \
        == (2048, 32, 8, 64, 3, 11776, 1536, 64, 4, 65536)


# -- (ii) the mixer: two forms, one identity ----------------------------------

def _conv_layer(i=0):
    key = seeds.layer_key(seeds.seed_key(SEED), DIMS.first_layer + i)
    return FAMILY.weights.layer(key, DIMS, jnp.float32, i > 0, False)["attn"]


@pytest.mark.parametrize("masked", [False, True])
def test_shortconv_sequence_is_shortconv_step_folded_over_the_positions(
        masked):
    """Token by token against a carried tail, from an empty one: the same
    outputs as the whole sequence at once (positions 0, 1 and 2, where
    taps fall before the sequence's start, among them), and the tail it
    ends with; both are the reference's convolution over the whole
    sequence. ``masked``: a row padded on the right carries the tail of
    its own length."""
    p = _conv_layer()
    rows, n = 3, 9
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, n, DIMS.dim))
    lens = np.asarray([n, 1, 2]) if masked else np.full((rows,), n)
    mask = jnp.asarray(np.arange(n)[None, :] < lens[:, None])
    hn = core.rmsnorm(p["ln"], x, eps=DIMS.norm_eps)
    out, tail = conv_ops.shortconv_sequence(p, hn, mask if masked else None)
    assert tail.shape == (rows, 2, DIMS.dim)
    carried = jnp.zeros_like(tail)          # before a row's first token
    for t in range(n):
        step_out, new = conv_ops.shortconv_step(p, hn[:, t], carried)
        live = np.asarray(mask[:, t])
        np.testing.assert_allclose(np.asarray(step_out)[live],
                                   np.asarray(out[:, t])[live], atol=1e-6)
        # a row past its own length keeps what it carried
        carried = jnp.where(mask[:, t][:, None, None], new, carried)
    np.testing.assert_allclose(np.asarray(carried), np.asarray(tail),
                               atol=1e-6)
    # prompts of 1 and 2 tokens: zeros before the sequence's start
    if masked:
        assert not np.asarray(tail[1, 0]).any() \
            and np.asarray(tail[1, 1]).any() and np.asarray(tail[2]).all()
    want = np.stack([np.asarray(R.short_conv(p, x[i], DIMS))
                     for i in range(rows)])
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-6)


def test_the_tail_is_the_last_two_gated_inputs():
    p = _conv_layer(2)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 6, DIMS.dim))
    _, tail = conv_ops.shortconv_sequence(p, x, None)
    b, _, u = jnp.split(core.linear(p["in"], x), 3, axis=-1)
    np.testing.assert_allclose(np.asarray(tail), np.asarray((b * u)[:, -2:]),
                               atol=1e-6)
    assert set(p) == {"ln", "in", "conv", "out"}
    assert set(conv_ops.shortconv_init(jax.random.PRNGKey(0), DIMS.dim, BLK)
               ) == {"in", "conv", "out"}


# -- (iii) each mechanism in the logits --------------------------------------

class _NoRope(T.ShortConvGQABlock):
    def rope_theta_of(self, full):
        return None


def _without(params, stacks, edit):
    """``params`` with ``edit(mixer or feed-forward subtree)`` applied in
    the named stacks."""
    out = jax.tree.map(lambda a: a, params)
    for stack, branch in stacks:
        out["transformer"][stack][branch] = edit(
            dict(out["transformer"][stack][branch]))
    return out


@pytest.mark.parametrize("without", ["tail", "b_gate", "c_gate", "qk_norm",
                                     "rope", "selection_bias"])
def test_each_mechanism_is_in_the_logits(sequences, without, monkeypatch):
    """A program that forgets the tail (the current tap alone), leaves
    out either gate of the short convolution, the norms over a query and
    a key head, the rotary positions, or the router's selection bias,
    fails the tolerance. (The selection bias at 0.5, not the published
    cell's 0.001: it moves a selection, and few at that scale.)"""
    dims = TOY.dims_of(router_bias_std=0.5) if without == "selection_bias" \
        else DIMS
    params = TOY.tree(dims)
    want = TOY.ref_logits(sequences, dims)
    TOY.close(TOY.apply(params, sequences), want)
    cfg, p = CFG, params
    convs = [("dense", "attn"), ("moe", "attn")]
    if without == "tail":
        def current_tap_alone(attn):
            w = attn["conv"]["w"]
            return dict(attn, conv={"w": w.at[:, :-1].set(0.0)})
        p = _without(params, convs, current_tap_alone)
    elif without == "b_gate":
        monkeypatch.setattr(conv_ops, "_gate_in", lambda b, u: u)
    elif without == "c_gate":
        mix = conv_ops._mix
        monkeypatch.setattr(conv_ops, "_mix", lambda prm, taps, c: mix(
            prm, taps, jnp.ones_like(c)))
    elif without == "qk_norm":
        p = _without(params, [("moe_full", "attn")], lambda attn: {
            k: v for k, v in attn.items() if k not in ("q_ln", "k_ln")})
    elif without == "rope":
        cfg = dataclasses.replace(CFG, block=_NoRope(
            **{f.name: getattr(BLK, f.name)
               for f in dataclasses.fields(BLK)}))
    else:
        def unbiased(ff):
            return dict(ff, router=dict(
                ff["router"], bias=jnp.zeros_like(ff["router"]["bias"])))
        p = _without(params, [("moe", "ff"), ("moe_full", "ff")], unbiased)
    got = np.asarray(TOY.apply(p, sequences, cfg))
    fin = np.isfinite(want)
    assert np.abs(got[fin] - want[fin]).max() > 50 * ATOL


def test_a_routed_layer_is_the_uncut_reference_s_layer():
    """Every expert is held (``experts_held == num_experts``): the
    grouped products over the picked pairs are the reference's plain loop
    over all the experts, the weights the picked scores over (their sum +
    1e-6). No share is cut, so there is nothing to add up."""
    key = seeds.layer_key(seeds.seed_key(SEED), DIMS.first_layer + 2)
    p = FAMILY.weights.layer(key, DIMS, jnp.float32, True, False)["ff"]
    assert set(p) == {"ln", "router", "experts"}
    assert p["experts"]["w_in"].shape == (8, DIMS.dim, 2 * DIMS.expert_hidden)
    m = jax.random.normal(jax.random.PRNGKey(4), (24, DIMS.dim))
    weights = R.route(p, m, DIMS)
    assert ((np.asarray(weights) > 0).sum(-1) == 2).all()
    total = np.asarray(weights).sum(-1)
    assert (total < 1.0).all() and (total > 1.0 - 1e-5).all()
    out, load = moe_ops.dropless_apply(p, m, BLK)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(R.routed(p["experts"], m, weights)),
        atol=1e-5)
    assert moe_ops.holds_all(BLK) and moe_ops.load_width(BLK) == 4
    assert load.shape == (4,) and int(load[0]) == 24 * 2 \
        and 0 < int(load[1]) <= 8 and int(load[2]) >= 24 * 2 // 8 \
        and int(load[3]) == int(load[1])        # 48 pair rows: one tile
    picks, w = moe_ops.route(p["router"], m, 2, 1.0, BLK.route_eps)
    dense = np.zeros((24, 8), np.float32)
    np.put_along_axis(dense, np.asarray(picks), np.asarray(w), axis=1)
    np.testing.assert_allclose(dense, np.asarray(weights), atol=1e-6)
    # the other blocks' weights are the picked scores over their sum alone
    _, plain = moe_ops.route(p["router"], m, 2, 1.0)
    np.testing.assert_allclose(np.asarray(plain).sum(-1), 1.0, rtol=1e-6)


# -- (iv) the pools: pages of rows, and ONE buffer that is not pages ----------

def test_the_state_pool_is_one_buffer_that_the_block_names():
    layout = KV.page_layout(TCFG, PS)
    assert layout == {"k": ((PS, 16), None), "v": ((PS, 16), None),
                      "conv_tail": ((2, 32), None)}
    plan = KV.pool_plan(TCFG, 19, 0, num_slots=2)
    assert plan == {"k": (2, 19), "v": (2, 19), "conv_tail": (7, 2)}
    pool = KV.init_page_pool(TCFG, 19, PS, num_slots=2, dtype=jnp.bfloat16)
    assert {n: (a.shape, a.dtype) for n, a in pool.items()} == {
        "k": ((2, 19, PS, 16), jnp.bfloat16),
        "v": ((2, 19, PS, 16), jnp.bfloat16),
        "conv_tail": ((7, 2, 2, 32), jnp.bfloat16)}
    want = (2 * 19 * 2 * 16 * PS + 7 * 2 * 2 * 32) * 4
    assert KV.modeled_kv_bytes(TCFG, kv="paged", num_slots=2,
                               total_len=DIMS.seq_len, page_size=PS) == want
    assert KV.window_pool_pages(TCFG, 2, DIMS.seq_len, PS, 19) == 0
    with pytest.raises(ValueError, match="num_slots"):
        KV.pool_plan(TCFG, 19, 0)


def test_the_published_cell_s_pools():
    """At the published widths: a (2, 2048) tail over 7 layers, K and V
    rows of 512 over 2; a slot's tails are 57 kB where its pages are 17.8
    MB, and an all-attention stack of the same depth would hold 80 MB."""
    cell = harness.load_json(
        harness.ROOT + "/benchmark/cells/lfm2-24b-a2b.serve-full.json")
    dims = FAMILY.weights.dims_of(PUBLISHED, cell["depth"])
    tcfg = FAMILY.build.program_config(dims, cell["flags"]).transformer
    assert KV.page_layout(tcfg, 16) == {
        "k": ((16, 512), None), "v": ((16, 512), None),
        "conv_tail": ((2, 2048), None)}
    slots = cell["num_slots"]
    pages = slots * 272 + 1
    assert KV.pool_plan(tcfg, pages, 0, slots) == {
        "k": (2, pages), "v": (2, pages), "conv_tail": (7, slots)}
    tails, rows = 7 * 2 * 2048 * 2, 2 * 272 * 16 * 1024 * 2
    assert (tails, rows) == (57344, 17825792)
    assert KV.modeled_kv_bytes(
        tcfg, kv="paged", num_slots=slots, total_len=dims.seq_len,
        page_size=16, dtype_bytes=2) == slots * (tails + rows) \
        + 2 * 16 * 1024 * 2                     # the trash page


@pytest.mark.parametrize("family, config, layout", [
    ("afmoe", "trinity-large-preview",
     {n: ((16, 1024), None) for n in ("k", "v", "window_k", "window_v")}),
    ("phi4flash", "phi-4-mini-flash-reasoning",
     {"k": ((16, 1280), None), "v": ((16, 1280), None),
      "window_k": ((16, 1280), None), "window_v": ((16, 1280), None),
      "ssm_state": ((16, 5120), 4), "ssm_conv": ((3, 5120), None)}),
    ("mla_moe", "kanana-2-30b-a3b", {"latent": ((16, 640), None)}),
    ("dalle", "rudalle-xl", {"k": ((16, 2048), None),
                             "v": ((16, 2048), None)}),
    ("mimo_v2", "mimo-v2.5",
     {"k": ((16, 768), None), "v": ((16, 512), None),
      "window_k": ((16, 1536), None), "window_v": ((16, 1024), None)}),
])
def test_every_other_configuration_s_layout_is_what_it_was(family, config,
                                                           layout):
    """The state pool's buffers now come from the block
    (``DescribedBlock.state_layout``); every existing block's page layout,
    its buffers' order and the bytes modeled from it are what they were."""
    fam = harness.load_family(family)
    conf = harness.load_json(f"{harness.ROOT}/benchmark/configs/{config}.json")
    cell = harness.load_json(
        f"{harness.ROOT}/benchmark/cells/{config}.serve-full.json")
    dims = fam.weights.dims_of(conf, cell["depth"])
    tcfg = fam.build.program_config(dims, cell["flags"]).transformer
    got = KV.page_layout(tcfg, 16)
    assert got == layout and list(got) == list(layout)
    if family == "phi4flash":
        slots = cell["num_slots"]
        plan = KV.pool_plan(tcfg, 100, 40, slots)
        assert plan["ssm_state"] == plan["ssm_conv"] == (9, slots)
        # (d_state x d_inner float32 + 3 x d_inner bfloat16) a slot a layer
        assert sum(np.prod(plan[n]) * np.prod(layout[n][0])
                   * (layout[n][1] or 2) for n in ("ssm_state", "ssm_conv")
                   ) == 9 * slots * (16 * 5120 * 4 + 3 * 5120 * 2)


def test_a_rows_tail_after_a_padded_prefill_is_its_own_prompts(params,
                                                               sequences):
    """Four rows of four prompt lengths (1 and 2 tokens among them) padded
    to one bucket: each row's tail is the one its own prompt gives alone,
    and a prefill that is not told the lengths carries the padding's."""
    seqs = np.concatenate([sequences, sequences[::-1]])
    lens = np.asarray([1, 2, 5, 8])
    _, padded, _ = TOY.prefilled_pool(params, seqs, 8, lens)
    _, blind, _ = TOY.prefilled_pool(params, seqs, 8)
    for i, n in enumerate(lens):
        _, alone, _ = TOY.prefilled_pool(params, seqs[i:i + 1], int(n))
        np.testing.assert_allclose(
            np.asarray(padded["conv_tail"][:, i]),
            np.asarray(alone["conv_tail"][:, 0]), atol=1e-6)
    assert not np.asarray(padded["conv_tail"][0, 0, 0]).any()   # before 0
    assert np.abs(np.asarray(blind["conv_tail"][:, 0])
                  - np.asarray(padded["conv_tail"][:, 0])).max() > 1e-3


def test_an_inactive_slots_tail_is_not_advanced(params, sequences):
    _, pool, tables = TOY.prefilled_pool(params, sequences, 7)
    p = jnp.full((2,), 7, jnp.int32)
    x = D.decode_token_embed(params, CFG, jnp.asarray(sequences[:, 7]), p)
    _, new, load = decode_ops.decode_step_block(
        params["transformer"], x, p, pool, tables, cfg=TCFG,
        key_mask=jnp.ones((2, DIMS.seq_len), bool),
        active=jnp.asarray([True, False]))
    old, got = np.asarray(pool["conv_tail"]), np.asarray(new["conv_tail"])
    np.testing.assert_array_equal(got[:, 1], old[:, 1])
    # the active slot's tail rolled by one: its older row is the old newer
    np.testing.assert_array_equal(got[:, 0, 0], old[:, 0, 1])
    assert np.abs(got[:, 0, 1] - old[:, 0, 1]).max() > 1e-3


# -- (v) the engine: the tail beside the pool --------------------------------

def test_a_four_row_group_writes_each_row_s_tail_at_its_own_length(params,
                                                                   served):
    """Six slots, so an admission takes 4 rows or 6
    (``scheduler.prefill_groups``): four requests of four prompt lengths
    (1, 10, 2 and 7) start in ONE 4-row group of one bucket, then a fifth
    joins mid-image (its group's unused rows are dropped, not written over
    a running slot's tail). Every stream is the one that one slot gives the
    request."""
    alone = served.one_slot().seqs
    queue = RequestQueue(max_depth=16)
    bucket = CFG.text_seq_len
    engine = Engine(params, CFG, queue, chunk_steps=8, kv="paged",
                    page_size=PS, num_slots=6, prefill_buckets=(bucket,))
    first = [queue.submit(dataclasses.replace(r)) for r in REQS]
    engine.step_once()
    engine.step_once()
    assert engine.active_slots() == 4 and engine.prefill_runs == 1
    assert engine.prefill_trace_count(bucket, 4) == 1
    late = queue.submit(dataclasses.replace(REQS[0]))
    engine.run_until_idle()
    for h, want in zip(first + [late], alone + alone[:1]):
        res = h.result(timeout=5)
        assert list(np.asarray(res.text_tokens)) \
            + list(np.asarray(res.tokens)) == want


# -- (vi) what the equations do not hold for ---------------------------------

def test_a_configuration_the_equations_do_not_hold_for_is_refused():
    with pytest.raises(ValueError, match="conv_bias"):
        TOY.dims_of(conv_bias=True)
    with pytest.raises(ValueError, match="every row is held"):
        TOY.dims_of(vocab_size=80)
    with pytest.raises(ValueError, match="layer_types holds"):
        TOY.dims_of(layer_types=["conv", "sliding"] * 20)
    with pytest.raises(ValueError, match="'conv' or 'full'"):
        dataclasses.replace(BLK, layer_types=("conv", "sliding"))
    with pytest.raises(ValueError, match="leaves no tail"):
        dataclasses.replace(BLK, conv_taps=1)
    with pytest.raises(ValueError, match="depth is 4"):
        dataclasses.replace(TCFG, depth=4)


def test_the_head_is_tied_behind_an_rmsnorm():
    p = D.dalle_init(jax.random.PRNGKey(0), CFG)
    assert set(p["to_logits"]) == {"ln"} and set(p["to_logits"]["ln"]) \
        == {"g"} and "eos_emb" in p
    assert set(p["transformer"]) == {"dense", "moe_full", "moe"}
    assert set(p["transformer"]["moe"]["attn"]) == {"ln", "in", "conv", "out"}
    assert set(p["transformer"]["moe_full"]["attn"]) == {
        "ln", "q", "k", "v", "out", "q_ln", "k_ln"}
    assert p["transformer"]["moe"]["attn"]["conv"]["w"].shape == (6, 3, 32)
