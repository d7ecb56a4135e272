"""The delta-rule hybrid block (``ops.transformer.DeltaGQABlock``) at toy
widths, float32, seeded: the contract of every described block
(``block_contract.py``: the program against the benchmark family's plain
reference, ``benchmark/families/qwen3_next/reference.py``, at logit level;
the paged decode from prompts of 1, 2 and 7 tokens; the engine, a reused
slot and an evicted request's replay; every refusal), then its own: the
two forms of the gated delta rule as one identity (the chunked sequence
form against the step form position by position, rows of several prompt
lengths in one group); the matrix state and the convolution's tail a slot
beside the one page pool (written at each row's own prompt length, never
advanced for an inactive slot, overwritten when a slot is reused, rebuilt
by the replay after an eviction); the shares of the experts adding up to
the uncut layer; softmax routing; each mechanism in the logits.

Tolerances: the program and the reference compute the same float32
mathematics in another order (the rule a chunk at a time through a
triangular solve, or a token at a time with the readout taken before the
write, where the reference recurs token by token; grouped products; a
cached read in page order). A layer alone agrees to 5e-7 on unit inputs;
through eight layers the logits (spread 0.57) agree to 1.2e-4: a head's
readout ``S^T q`` is normed to unit size whatever its own size, so where
``q . k`` of two random directions of 8 numbers is near nothing the norm
magnifies the last bits, and a state carries them on. 3e-4 is missed by
the same program in bfloat16 six thousand times over
(``test_bfloat16_fails_the_tolerance``) and by every mechanism left out
by fifty."""

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
from dalle_pytorch_tpu.ops import deltanet as delta_ops
from dalle_pytorch_tpu.ops import moe as moe_ops
from dalle_pytorch_tpu.ops import transformer as T
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve.engine import Engine
from dalle_pytorch_tpu.serve.scheduler import Request, RequestQueue

# prompts of 1, 10, 2 and 7 tokens: a tail shorter than the taps; with two
# slots the third and fourth requests reuse one
TOY = Toy("qwen3_next", "qwen3-next-80b-a3b", 8, "delta_gqa_moe",
          overrides=dict(text_seq_len=10, image_grid=5), atol=3e-4, gap=1e-3,
          t0s=(1, 2, 7), bf16_misses=1000, evicted=(1, 3), reused=True,
          requests=(Request(codes=(3,), seed=11, sampling=GREEDY),
                    Request(codes=tuple(range(1, 11)), seed=2,
                            sampling=GREEDY),
                    Request(codes=(6, 6), seed=3, sampling=GREEDY),
                    Request(codes=(6, 6, 1, 2, 3, 9, 4), seed=5,
                            sampling=GREEDY)))
FAMILY, PUBLISHED, DIMS, CFG, TCFG, BLK = (TOY.family, TOY.published,
                                           TOY.dims, TOY.cfg, TOY.tcfg,
                                           TOY.blk)
R, W, SEED, PS, DEPTH, ATOL, WIDTH, REQS = (
    FAMILY.reference, FAMILY.weights, TOY.seed, TOY.page_size, TOY.depth,
    TOY.atol, TOY.width, TOY.requests)
FULL_LAYERS = [i for i, t in enumerate(DIMS.layer_types) if t == "full"]
DELTA_LAYERS = [i for i, t in enumerate(DIMS.layer_types) if t == "delta"]
STATE = (4, 8, 8)       # value heads x dk x dv, float32
TAIL = (3, 64)          # taps - 1 x (2 x 16 + 32), the pool's type


class TestContract(BlockContract):
    toy = TOY

    def step_loads(self, loads, b, t0):
        """A step that carries a state a slot AND returns the load of a
        held share: six counts."""
        for load in loads:
            assert load.shape == (6,) and load.dtype == jnp.int32
            assert int(load[0]) == b * BLK.experts_per_token * DEPTH
            assert int(load[4]) <= int(load[0]) and int(load[1]) \
                <= DEPTH * BLK.experts_held

    def engine_counters(self, engine, st, placement):
        """Prompts of 1 and 10 tokens admitted in one bucket (each row's
        state at its own length), slots reused by the third and fourth
        requests."""
        assert {n: (a.shape, a.dtype) for n, a in engine.cache.items()} == {
            "k": ((2, 2 * WIDTH + 1, PS, 16), jnp.float32),
            "v": ((2, 2 * WIDTH + 1, PS, 16), jnp.float32),
            "delta_state": ((6, 2) + STATE, jnp.float32),
            "delta_conv": ((6, 2) + TAIL, jnp.float32)}
        # the state pool's bytes a buffer, under the buffer's own name
        assert st["delta_state_bytes"] == 6 * 2 * 4 * 8 * 8 * 4
        assert st["delta_conv_bytes"] == 6 * 2 * 3 * 64 * 4
        assert st["state_bytes"] == st["delta_state_bytes"] \
            + st["delta_conv_bytes"]
        assert "window_pages_in_use" not in st \
            and "window_sink_mass" not in st
        # a share is held: the six counters
        assert 0 < st["moe_picks_held"] < st["moe_picks"]
        assert 0 < st["moe_experts_touched"] <= st["decode_steps"] \
            * DEPTH * BLK.experts_held
        assert st["moe_rows_computed"] >= st["moe_picks_held"]
        assert st["moe_group_reads"] == st["moe_experts_touched"]
        # what a step reads: two full layers' tables, six layers' states
        assert st["kv_read_bytes_per_token"] == (
            2 * WIDTH * 2 * PS * 16 + 6 * (4 * 8 * 8 + 3 * 64)) * 4


# -- (i) the stack as it is scanned -------------------------------------------

def test_the_toy_is_the_published_layers_0_to_7_at_period_1():
    assert DIMS.layer_types == ("delta", "delta", "delta", "full") * 2
    assert (DIMS.first_layer, DIMS.moe_layers) == (0, 8)
    assert (FULL_LAYERS, len(DELTA_LAYERS)) == ([3, 7], 6)
    assert BLK.period == 1 and BLK.dense_layers == 0
    assert (BLK.experts_held, BLK.num_experts, BLK.first_expert) == (4, 16, 0)
    runs = T.layer_runs(BLK, DEPTH)
    assert [(BLK.stack_of(r.kind), r.kind.pool, r.count, r.at, r.cache)
            for r in runs] == [
        ("moe", "state", 3, 0, 0), ("moe_full", "full", 1, 0, 0),
        ("moe", "state", 3, 3, 3), ("moe_full", "full", 1, 1, 1)]
    assert all(r.moe for r in runs)
    assert list(DIMS.stacks()) == ["moe", "moe_full"]
    assert all(len(scan) == 1 for scan in T.stack_scans(BLK, DEPTH))
    assert BLK.cache_layers("state", DEPTH) == tuple(DELTA_LAYERS)
    assert BLK.cache_layers("full", DEPTH) == tuple(FULL_LAYERS)
    assert BLK.pools(DEPTH) == {"full": ("k", "v"),
                                "state": ("delta_state", "delta_conv")}
    assert tuple(BLK.state_layout(32)) == BLK.pool_buffers("state")
    assert T.LayerKind(True, False, "delta").pool == "state" \
        and T.LayerKind(True, False, "delta").stores


def test_all_48_published_layers_in_the_published_order():
    """The whole model is the same code with more scans: twelve times
    (delta x 3, full)."""
    dims = W.dims_of(dict(PUBLISHED, experts_held=512), 48)
    blk = FAMILY.build.program_config(dims, {}).transformer.block
    assert (dims.full_layers, dims.delta_layers) == (12, 36)
    assert [i for i, t in enumerate(dims.layer_types) if t == "full"] \
        == list(range(3, 48, 4))
    runs = T.layer_runs(blk, 48)
    assert len(runs) == len(T.stack_scans(blk, 48)) == 24
    assert [(blk.stack_of(r.kind), r.count) for r in runs] == [
        ("moe", 3), ("moe_full", 1)] * 12
    assert [r.at for r in runs if r.full] == list(range(12))
    assert [r.cache for r in runs if not r.full] == list(range(0, 36, 3))
    # the published sizes
    assert (dims.dim, dims.heads, dims.kv_heads, dims.head_dim,
            dims.rotary_dim, dims.key_heads, dims.value_heads,
            dims.key_head_dim, dims.value_head_dim, dims.conv_taps,
            dims.expert_hidden, dims.shared_hidden, dims.experts,
            dims.experts_per_token, dims.conv_dim) == (
        2048, 16, 2, 256, 64, 16, 32, 128, 128, 4, 512, 512, 512, 10, 8192)
    assert blk.state_layout(2048) == {
        "delta_state": ((32, 128, 128), 4), "delta_conv": ((3, 8192), None)}


# -- (ii) the mixer: two forms, one identity ----------------------------------

def _delta_layer(i=0):
    key = seeds.layer_key(seeds.seed_key(SEED), DIMS.first_layer + i)
    return W.layer(key, DIMS, jnp.float32, False)["attn"]


@pytest.mark.parametrize("n", [9, 150])
@pytest.mark.parametrize("masked", [False, True])
def test_delta_sequence_is_delta_step_folded_over_the_positions(masked, n):
    """Token by token against a carried state, from a zero one: the same
    outputs as the chunked rule over the whole sequence (``n`` 150: two
    chunks of 64 and a part of one) and the state and tail it ends with;
    both are the reference's token-by-token recurrence. ``masked``: rows
    of three prompt lengths in one group, each carrying the state of its
    own length."""
    p = _delta_layer(1)
    rows = 3
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, n, DIMS.dim))
    lens = np.asarray([n, 1, n // 2 + 1]) if masked else np.full((rows,), n)
    mask = jnp.asarray(np.arange(n)[None, :] < lens[:, None])
    hn = core.rmsnorm(p["ln"], x, eps=DIMS.norm_eps)
    out, (state, tail) = jax.jit(delta_ops.delta_sequence)(
        p, hn, mask if masked else None)
    assert state.shape == (rows,) + STATE and state.dtype == jnp.float32
    assert tail.shape == (rows,) + TAIL
    step = jax.jit(delta_ops.delta_step)
    carried, steps = delta_ops.zero_state(p, rows, x.dtype), []
    for t in range(n):
        step_out, new = step(p, hn[:, t], carried)
        steps.append(step_out)
        # a row past its own length keeps what it carried
        carried = tuple(jnp.where(mask[:, t].reshape((-1,) + (1,) * (
            old.ndim - 1)), fresh, old) for old, fresh in zip(carried, new))
    live = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(jnp.stack(steps, axis=1))[live],
                               np.asarray(out)[live], atol=5e-6)
    np.testing.assert_allclose(np.asarray(carried[0]), np.asarray(state),
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(carried[1]), np.asarray(tail),
                               atol=2e-6)
    if masked:      # a prompt of 1 token: zeros before the sequence's start
        assert not np.asarray(tail[1, :2]).any() \
            and np.asarray(tail[1, 2]).any()
    want = np.stack([np.asarray(R.delta_net(p, x[i], DIMS))
                     for i in range(rows)])
    for i, length in enumerate(lens):
        np.testing.assert_allclose(np.asarray(out[i, :length]),
                                   want[i, :length], atol=5e-6)


def test_the_state_is_float32_whatever_the_parameters_type():
    """In bfloat16 the products and the tail are bfloat16; the state and
    the rule are float32, in both forms."""
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                     if a.ndim > 2 or a.shape[-1] > 8 else a, _delta_layer())
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 6, DIMS.dim),
                          jnp.bfloat16)
    out, (state, tail) = delta_ops.delta_sequence(p, x, None)
    assert (out.dtype, state.dtype, tail.dtype) == (
        jnp.bfloat16, jnp.float32, jnp.bfloat16)
    out, (state, tail) = delta_ops.delta_step(
        p, x[:, 0], delta_ops.zero_state(p, 2, jnp.bfloat16))
    assert (out.dtype, state.dtype, tail.dtype) == (
        jnp.bfloat16, jnp.float32, jnp.bfloat16)
    text = str(jax.make_jaxpr(delta_ops.delta_step)(
        p, x[:, 0], delta_ops.zero_state(p, 2, jnp.bfloat16)))
    assert "f32[2,2,2,8,8]" in text and "bf16[2,2,2,8,8]" not in text
    assert set(p) == {"ln", "in", "ba", "conv", "a_log", "dt_bias", "norm",
                      "out"}
    assert set(delta_ops.delta_init(jax.random.PRNGKey(0), DIMS.dim, BLK)
               ) == set(p) - {"ln"}


# -- (iii) each mechanism in the logits --------------------------------------

def _without(params, stacks, edit):
    """``params`` with ``edit(mixer or feed-forward subtree)`` applied in
    the named stacks."""
    out = jax.tree.map(lambda a: a, params)
    for stack, branch in stacks:
        out["transformer"][stack][branch] = edit(
            dict(out["transformer"][stack][branch]))
    return out


@pytest.mark.parametrize("without", ["decay", "beta", "earlier_taps",
                                     "output_gate", "partial_rotary",
                                     "shared_gate", "qk_l2norm"])
def test_each_mechanism_is_in_the_logits(params, sequences, ref_logits,
                                         without, monkeypatch):
    """A program that leaves the decay out (``exp(g_t)`` taken as 1),
    takes ``beta`` as 1, forgets the convolution's earlier taps (the
    current one alone), leaves out the attention's output gate, turns the
    whole head and not its first quarter, leaves out the shared expert's
    gate, or the l2 norms of the rule's queries and keys, fails the
    tolerance."""
    cfg, p = CFG, params
    delta, full = [("moe", "attn")], [("moe_full", "attn")]
    inputs = delta_ops._rule_inputs
    if without == "decay":
        # A = exp(a_log) = 0: g = 0 whatever the input
        p = _without(params, delta, lambda attn: dict(
            attn, a_log=jnp.full_like(attn["a_log"], -1e9)))
    elif without == "beta":
        def beta_one(prm, c, b, a):
            q, k, v, beta, g = inputs(prm, c, b, a)
            return q, k, v, jnp.ones_like(beta), g
        monkeypatch.setattr(delta_ops, "_rule_inputs", beta_one)
    elif without == "qk_l2norm":
        monkeypatch.setattr(delta_ops, "_l2norm", lambda x: x)
    elif without == "earlier_taps":
        p = _without(params, delta, lambda attn: dict(attn, conv={
            "w": attn["conv"]["w"].at[:, :-1].set(0.0)}))
    elif without == "output_gate":
        p = _without(params, full, lambda attn: {
            k: v for k, v in attn.items() if k != "gate"})
    elif without == "partial_rotary":
        cfg = dataclasses.replace(CFG, block=dataclasses.replace(
            BLK, rotary_dim=None))
    else:
        p = _without(params, [("moe", "ff"), ("moe_full", "ff")],
                     lambda ff: {k: v for k, v in ff.items()
                                 if k != "shared_gate"})
    got = np.asarray(TOY.apply(p, sequences, cfg))
    fin = np.isfinite(ref_logits)
    assert np.abs(got[fin] - ref_logits[fin]).max() > 50 * ATOL


# -- (iv) the routed layer: softmax scores, a held share -----------------------

def test_softmax_routing_against_a_plain_top_10():
    """The published router: a softmax over all 512 in float32, the 10
    largest, their scores over their sum; no bias, no scale."""
    rng = np.random.default_rng(5)
    w = rng.normal(size=(64, 512)).astype(np.float32) / 8
    x = rng.normal(size=(40, 64)).astype(np.float32)
    picks, weights = moe_ops.route({"w": jnp.asarray(w)}, jnp.asarray(x), 10,
                                   1.0, scores="softmax")
    logits = x.astype(np.float64) @ w.astype(np.float64)
    prob = np.exp(logits - logits.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    top = np.argsort(-prob, axis=-1)[:, :10]
    np.testing.assert_array_equal(np.sort(np.asarray(picks), -1),
                                  np.sort(top, -1))
    want = np.take_along_axis(prob, np.asarray(picks), -1)
    np.testing.assert_allclose(np.asarray(weights),
                               want / want.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    # such a router holds no selection bias, and the block's layer none
    ff = moe_ops.dropless_init(jax.random.PRNGKey(0), DIMS.dim, BLK)
    assert set(ff) == {"router", "experts", "shared", "shared_gate"}
    assert set(ff["router"]) == {"w"} and ff["shared_gate"]["w"].shape \
        == (DIMS.dim, 1)


def test_the_four_quarters_of_the_experts_add_up_to_the_uncut_layer():
    """The shares test of the model-configs guide: at toy size the routed
    sums of the four quarters of the experts (each what one chip of the
    deployment computes), with the shared expert and its gate counted
    once, are the uncut reference's layer; the program's layer on each
    share is the reference's on that share."""
    key = seeds.layer_key(seeds.seed_key(SEED), 2)
    whole = W.dims_of(dict(TOY.conf, experts_held=16), DEPTH)
    uncut = W.layer(key, whole, jnp.float32, False)["ff"]
    x = jax.random.normal(jax.random.PRNGKey(4), (24, DIMS.dim))
    m = R._rms(uncut["ln"], x, DIMS.norm_eps)
    want = R.feed_forward(uncut, x, whole)
    total = R.shared(uncut, m)
    for first in range(0, 16, 4):
        dims = W.dims_of(dict(TOY.conf, first_expert=first), DEPTH)
        p = W.layer(key, dims, jnp.float32, False)["ff"]
        # an expert is drawn from its published index: a share is a part
        np.testing.assert_array_equal(
            np.asarray(p["experts"]["w_in"]),
            np.asarray(uncut["experts"]["w_in"][first:first + 4]))
        part = R.routed(p["experts"], m, R.route(p, m, dims)[
            :, first:first + 4])
        total = total + part
        blk = FAMILY.build.program_config(dims, {}).transformer.block
        out, load = moe_ops.dropless_apply(p, m, blk)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(part + R.shared(p, m)), atol=1e-5)
        assert load.shape == (6,) and int(load[0]) == 24 * 2 \
            and int(load[4]) <= 48
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


# -- (v) the pools: pages of rows, and two buffers that are not pages ---------

def test_the_state_pool_is_two_buffers_that_the_block_names():
    layout = KV.page_layout(TCFG, PS)
    assert layout == {"k": ((PS, 16), None), "v": ((PS, 16), None),
                      "delta_state": (STATE, 4), "delta_conv": (TAIL, None)}
    plan = KV.pool_plan(TCFG, 19, 0, num_slots=2)
    assert plan == {"k": (2, 19), "v": (2, 19), "delta_state": (6, 2),
                    "delta_conv": (6, 2)}
    pool = KV.init_page_pool(TCFG, 19, PS, num_slots=2, dtype=jnp.bfloat16)
    assert {n: (a.shape, a.dtype) for n, a in pool.items()} == {
        "k": ((2, 19, PS, 16), jnp.bfloat16),
        "v": ((2, 19, PS, 16), jnp.bfloat16),
        "delta_state": ((6, 2) + STATE, jnp.float32),
        "delta_conv": ((6, 2) + TAIL, jnp.bfloat16)}
    want = (2 * 19 * 2 * 16 * PS + 6 * 2 * (4 * 8 * 8 + 3 * 64)) * 4
    assert KV.modeled_kv_bytes(TCFG, kv="paged", num_slots=2,
                               total_len=DIMS.seq_len, page_size=PS) == want
    assert KV.window_pool_pages(TCFG, 2, DIMS.seq_len, PS, 19) == 0
    with pytest.raises(ValueError, match="num_slots"):
        KV.pool_plan(TCFG, 19, 0)


def test_the_published_cell_s_pools():
    """At the published widths: a float32 state of 32 x 128 x 128 and a
    tail of 3 x 8192 over 6 layers, K and V rows of 512 over 2: a slot's
    states are 12.9 MB where its pages are 17.8 MB (lfm2's tails: 57 kB,
    phi's states: 3.2 MB)."""
    cell = harness.load_json(
        harness.ROOT + "/benchmark/cells/qwen3-next-80b-a3b.serve-full.json")
    dims = W.dims_of(PUBLISHED, cell["depth"])
    tcfg = FAMILY.build.program_config(dims, cell["flags"]).transformer
    assert KV.page_layout(tcfg, 16) == {
        "k": ((16, 512), None), "v": ((16, 512), None),
        "delta_state": ((32, 128, 128), 4), "delta_conv": ((3, 8192), None)}
    slots = cell["num_slots"]
    pages = slots * 272 + 1
    assert KV.pool_plan(tcfg, pages, 0, slots) == {
        "k": (2, pages), "v": (2, pages), "delta_state": (6, slots),
        "delta_conv": (6, slots)}
    states = 6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    rows = 2 * 272 * 16 * 1024 * 2
    assert (states, rows) == (12877824, 17825792)
    assert KV.modeled_kv_bytes(
        tcfg, kv="paged", num_slots=slots, total_len=dims.seq_len,
        page_size=16, dtype_bytes=2) == slots * (states + rows) \
        + 2 * 16 * 1024 * 2                     # the trash page


def test_a_rows_state_after_a_padded_prefill_is_its_own_prompts(params,
                                                                sequences):
    """Four rows of four prompt lengths (1 and 2 tokens among them) padded
    to one bucket: each row's state and tail are those its own prompt
    gives alone, and a prefill that is not told the lengths carries the
    padding's."""
    seqs = np.concatenate([sequences, sequences[::-1]])
    lens = np.asarray([1, 2, 5, 8])
    _, padded, _ = TOY.prefilled_pool(params, seqs, 8, lens)
    _, blind, _ = TOY.prefilled_pool(params, seqs, 8)
    for name in ("delta_state", "delta_conv"):
        for i, n in enumerate(lens):
            _, alone, _ = TOY.prefilled_pool(params, seqs[i:i + 1], int(n))
            np.testing.assert_allclose(np.asarray(padded[name][:, i]),
                                       np.asarray(alone[name][:, 0]),
                                       atol=1e-5)
        assert np.abs(np.asarray(blind[name][:, 0])
                      - np.asarray(padded[name][:, 0])).max() > 1e-3
    assert not np.asarray(padded["delta_conv"][0, 0, :2]).any()  # before 0
    assert padded["delta_state"].dtype == jnp.float32


def test_an_inactive_slots_state_is_not_advanced(params, sequences):
    _, pool, tables = TOY.prefilled_pool(params, sequences, 7)
    p = jnp.full((2,), 7, jnp.int32)
    x = D.decode_token_embed(params, CFG, jnp.asarray(sequences[:, 7]), p)
    _, new, load = decode_ops.decode_step_block(
        params["transformer"], x, p, pool, tables, cfg=TCFG,
        key_mask=jnp.ones((2, DIMS.seq_len), bool),
        active=jnp.asarray([True, False]))
    for name in ("delta_state", "delta_conv"):
        old, got = np.asarray(pool[name]), np.asarray(new[name])
        np.testing.assert_array_equal(got[:, 1], old[:, 1])
        assert np.abs(got[:, 0] - old[:, 0]).max() > 1e-4
    # the active slot's tail rolled by one: its oldest row is the old second
    np.testing.assert_array_equal(np.asarray(new["delta_conv"])[:, 0, 0],
                                  np.asarray(pool["delta_conv"])[:, 0, 1])


# -- (vi) the engine: the state beside the pool -------------------------------

def test_a_reused_slot_does_not_read_the_last_request_s_state(params,
                                                              served):
    """One slot serves the four requests in turn: each admission writes
    the state of its own prompt over what the request before left there,
    so each stream is the one that a NEW engine gives the request alone;
    and an engine whose admission kept the old state would not (the state
    it left is not the next prompt's)."""
    alone = served.one_slot()
    for i, req in enumerate(REQS[1:], 1):
        _, fresh = TOY.serve(params, [req], num_slots=1)
        assert fresh[0] == alone.seqs[i]
    # what the slot holds at the end is the last request's, not zeros
    assert np.abs(np.asarray(alone.engine.cache["delta_state"])).max() > 0


def test_a_four_row_group_writes_each_row_s_state_at_its_own_length(params,
                                                                    served):
    """Six slots, so an admission takes 4 rows or 6
    (``scheduler.prefill_groups``): four requests of four prompt lengths
    (1, 10, 2 and 7) start in ONE 4-row group of one bucket, then a fifth
    joins mid-image (its group's unused rows are dropped, not written over
    a running slot's state). Every stream is the one that one slot gives
    the request."""
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


# -- (vii) what the equations do not hold for ---------------------------------

def test_a_configuration_the_equations_do_not_hold_for_is_refused():
    with pytest.raises(ValueError, match="decoder_sparse_step"):
        TOY.dims_of(decoder_sparse_step=2)
    with pytest.raises(ValueError, match="mlp_only_layers"):
        TOY.dims_of(mlp_only_layers=[0])
    with pytest.raises(ValueError, match="share held here"):
        TOY.dims_of(vocab_held=80)
    with pytest.raises(ValueError, match="no share"):
        TOY.dims_of(first_expert=14)
    with pytest.raises(ValueError, match="no whole pairs"):
        TOY.dims_of(head_dim=4)
    with pytest.raises(ValueError, match="'delta' or 'full'"):
        dataclasses.replace(BLK, layer_types=("delta", "conv") * 4)
    with pytest.raises(ValueError, match="leaves no tail"):
        dataclasses.replace(BLK, conv_taps=1)
    with pytest.raises(ValueError, match="no multiple"):
        dataclasses.replace(BLK, value_heads=3)
    with pytest.raises(ValueError, match="gated\\s+norm"):
        dataclasses.replace(BLK, norm_eps=1e-5)
    with pytest.raises(ValueError, match="depth is 4"):
        dataclasses.replace(TCFG, depth=4)


def test_the_head_is_untied_behind_an_rmsnorm():
    p = D.dalle_init(jax.random.PRNGKey(0), CFG)
    assert set(p["to_logits"]) == {"ln", "proj"} \
        and set(p["to_logits"]["ln"]) == {"g"} and "eos_emb" not in p
    assert set(p["transformer"]) == {"moe", "moe_full"}
    assert set(p["transformer"]["moe"]["attn"]) == {
        "ln", "in", "ba", "conv", "a_log", "dt_bias", "norm", "out"}
    assert set(p["transformer"]["moe_full"]["attn"]) == {
        "ln", "q", "k", "v", "gate", "out", "q_ln", "k_ln"}
    assert set(p["transformer"]["moe"]["ff"]) == {
        "ln", "router", "experts", "shared", "shared_gate"}
    assert p["transformer"]["moe"]["attn"]["conv"]["w"].shape == (6, 4, 64)
    assert p["transformer"]["moe"]["attn"]["a_log"].shape == (6, 2, 2)
    assert T.block_name_of(p["transformer"]) == "delta_gqa_moe"
