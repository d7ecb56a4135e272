"""The state-space + window + shared-full-cache hybrid block
(``ops.transformer.SSMHybridBlock``) at toy widths, float32, seeded: the
contract of every described block (``block_contract.py``: the program
against the benchmark family's plain reference,
``benchmark/families/phi4flash/reference.py``, at logit level on a
sequence several windows long; the paged decode; the engine, a reused slot
and an evicted request's replay; the width profiles of the one full
layer's pool, which the full layer's differential read and the cross
layers' read at the full layer's index, each with a query of its own;
every refusal), then its own: the two forms of the state-space layer as
one identity; the recurrent state a slot beside the page pools (written
at each row's own prompt length, never advanced for an inactive slot);
the one full layer's pages read by every cross layer.

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

from block_contract import (BlockContract, Toy, params,  # noqa: F401
                            ref_logits, sequences, served)
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.ops import attention as attn_ops
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.ops import ssm as ssm_ops
from dalle_pytorch_tpu.ops import transformer as T
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve.engine import Engine
from dalle_pytorch_tpu.serve.scheduler import RequestQueue

# a window of two pages in a sequence of nine: the ring (three pages)
# turns twice
TOY = Toy("phi4flash", "phi-4-mini-flash-reasoning", 12, "ssm_hybrid",
          overrides=dict(text_seq_len=10, image_grid=5, sliding_window=8),
          atol=5e-5, gap=1e-4,
          t0s=(7, 14),      # inside one page of the ring; past it
          evicted=(0, 1), reused=True, profiles=(0, 1, 3))
FAMILY, CONF, DIMS, CFG, TCFG, BLK = (TOY.family, TOY.conf, TOY.dims,
                                      TOY.cfg, TOY.tcfg, TOY.blk)
PS, RING, WIDTH, REQS = TOY.page_size, TOY.ring, TOY.width, TOY.requests


class TestContract(BlockContract):
    toy = TOY

    def step_loads(self, loads, b, t0):
        assert all(load.shape == (0,) for load in loads)   # no routed layer

    def engine_counters(self, engine, st, placement):
        """Prompts of 3 and 10 tokens admitted in one bucket (each row's
        state at its own length), the ring's pages reused, a slot reused
        by the third request."""
        assert st["window_pages_reused"] > 0
        assert st["state_bytes"] == 4 * 2 * (DIMS.d_inner * DIMS.d_state * 4
                                             + 3 * DIMS.d_inner * 4)
        # the full pool's one layer is read by three layers, the rings by
        # three, the state by four
        row = 2 * (2 * PS * 8) * 4
        assert st["kv_read_bytes_per_token"] == (
            3 * WIDTH + 3 * RING) * row + st["state_bytes"] // 2


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


# -- (i) the two forms of the state-space layer ------------------------------

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


# -- (ii) the state and the full layer's pages beside the pools --------------

def test_an_inactive_slots_state_is_not_advanced(params, sequences):
    _, pool, tables = TOY.prefilled_pool(params, sequences, 7)
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
    _, pool, tables = TOY.prefilled_pool(params, sequences, 14)
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


# -- (iv) the differential read over gathered rows ----------------------------

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


# -- (iii) the engine: state beside the pools --------------------------------

def test_a_row_joining_through_the_small_group_leaves_the_others_state(
        params, served):
    """Six slots, so an admission takes 4 rows or 6
    (``scheduler.prefill_groups``): two requests start in the 4-row
    group, a third joins them mid-image in it (its unused rows' state
    is dropped, not written over a running slot's), then six at once
    take the whole group. Every stream is the one that one slot gives the
    request."""
    alone = served.one_slot().seqs
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
