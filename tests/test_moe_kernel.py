"""The routed experts' two products in the repo's Pallas kernel (ops/moe.py
``expert_products``, ``kernel_hidden_tile``; ISSUE 44), interpreted on the
CPU at the least widths the kernel tiles: ``dropless_experts`` through the
kernel against a float32 loop over the pairs and against the
``lax.ragged_dot`` path, the load's entries, and the rule that decides from
a call's shapes which of the two runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark import harness
from dalle_pytorch_tpu.ops import moe as moe_ops
from dalle_pytorch_tpu.serve import scheduler as S
from test_moe_row_tiles import _reads_by_hand

DIM, HIDDEN, E, OF, LAYERS = 128, 256, 8, 32, 3


def _experts(held, seed):
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {"w_in": (jax.random.normal(k[0], (LAYERS, held, DIM, 2 * HIDDEN))
                     / DIM ** 0.5).astype(jnp.bfloat16),
            "w_out": (jax.random.normal(k[1], (LAYERS, held, HIDDEN, DIM))
                      / HIDDEN ** 0.5).astype(jnp.bfloat16)}


def _routing(flat, k, seed):
    picks = jnp.asarray(np.asarray(flat).reshape(-1, k), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(seed + 7), 2)
    tokens = picks.shape[0]
    return (picks, jax.random.uniform(keys[0], (tokens, k), minval=0.1),
            jax.random.normal(keys[1], (tokens, DIM), jnp.bfloat16))


def _from_sizes(sizes, pairs, first, seed):
    """``pairs`` pairs of which expert ``first + g`` receives ``sizes[g]``,
    the rest on experts held elsewhere, shuffled."""
    rng = np.random.default_rng(seed)
    flat = np.repeat(first + np.arange(len(sizes)), sizes)
    away = np.setdiff1d(np.arange(OF), first + np.arange(len(sizes)))
    flat = np.concatenate([flat, rng.choice(away, pairs - len(flat))])
    return rng.permutation(flat)


def _reference(w_in, w_out, x, picks, weights, first):
    """Each pair through its expert's own matrices in float32."""
    w_in, w_out, x = (a.astype(jnp.float32) for a in (w_in, w_out, x))
    held = w_in.shape[0]
    here = (picks >= first) & (picks < first + held)
    at = jnp.where(here, picks - first, 0)
    hp = lax.Precision.HIGHEST
    h = jnp.einsum("td,tkdf->tkf", x, w_in[at], precision=hp)
    gate, up = jnp.split(h, 2, axis=-1)
    out = jnp.einsum("tkf,tkfd->tkd", jax.nn.silu(gate) * up, w_out[at],
                     precision=hp)
    return jnp.sum(jnp.where(here[..., None], weights[..., None] * out, 0.0),
                   axis=1)


# name -> (held experts, first, picks a token, the held experts' sizes,
# pair rows, layer or None for a layer's own experts, the step handed)
ALL = dict(held=E, first=None, k=4)
SHARE = dict(held=4, first=8, k=8)
CASES = {
    "every_expert_held": dict(ALL, sizes=[40, 30, 20, 38, 32, 32, 34, 30],
                              rows=256, layer=None, handed=256),
    "the_first_layer_of_a_stack": dict(
        ALL, sizes=[40, 30, 20, 38, 32, 32, 34, 30], rows=256, layer=0,
        handed=256),
    "a_middle_layer_of_a_stack": dict(
        ALL, sizes=[40, 30, 20, 38, 32, 32, 34, 30], rows=256, layer=1,
        handed=256),
    "the_last_layer_of_a_stack": dict(
        ALL, sizes=[40, 30, 20, 38, 32, 32, 34, 30], rows=256, layer=2,
        handed=256),
    "kanana_s_192_rows_under_the_ridge": dict(
        ALL, sizes=[24] * 8, rows=192, layer=1, handed=192),
    "groups_of_no_row": dict(ALL, sizes=[0, 0, 100, 0, 0, 156, 0, 0],
                             rows=256, layer=1, handed=256),
    # expert 3 holds sorted rows 60 .. 69: both sides of row 64
    "a_group_straddles_a_tile_boundary": dict(
        ALL, sizes=[20, 20, 20, 10, 58, 64, 64, 0], rows=256, layer=None,
        handed=256),
    "a_group_spans_three_tiles": dict(
        ALL, sizes=[64, 150, 42, 0, 0, 0, 0, 0], rows=256, layer=2,
        handed=256),
    "every_pair_on_one_expert": dict(
        ALL, sizes=[0, 0, 0, 0, 0, 128, 0, 0], rows=128, layer=None,
        handed=128),
    "one_tile_of_rows": dict(ALL, sizes=[8] * 8, rows=64, layer=0,
                             handed=64),
    "a_share_s_first_step": dict(SHARE, sizes=[10, 0, 25, 5], rows=512,
                                 layer=1, handed=128),
    "a_share_s_second_step": dict(SHARE, sizes=[60, 70, 3, 40], rows=512,
                                  layer=None, handed=256),
    "a_share_s_every_row": dict(SHARE, sizes=[100, 90, 60, 50], rows=512,
                                layer=2, handed=512),
    "a_share_that_no_pair_falls_on": dict(SHARE, sizes=[0, 0, 0, 0],
                                          rows=512, layer=0, handed=128),
    "a_share_without_a_ladder": dict(SHARE, sizes=[9, 0, 30, 2], rows=64,
                                     layer=1, handed=64),
}


# a grid step takes a whole expert, or (these) a hidden tile of two
TWO_TILES = ("every_expert_held", "a_middle_layer_of_a_stack",
             "a_group_straddles_a_tile_boundary", "a_group_spans_three_tiles",
             "a_share_s_first_step", "a_share_that_no_pair_falls_on")


@pytest.mark.parametrize("name,hidden_tile", [
    pytest.param(name, HIDDEN, id=name) for name in CASES] + [
    pytest.param(name, HIDDEN // 2, id=name + "-two_hidden_tiles")
    for name in TWO_TILES])
def test_the_kernel_s_products_against_the_pairs_and_the_compiler_s(
        name, hidden_tile, monkeypatch):
    case = CASES[name]
    held, first, k, layer = (case[key] for key in
                             ("held", "first", "k", "layer"))
    seed = sorted(CASES).index(name)
    flat = _from_sizes(case["sizes"], case["rows"], first or 0, seed)
    picks, weights, x = _routing(flat, k, seed)
    stack = _experts(held, seed)
    experts = dict(stack, layer=jnp.int32(layer)) if layer is not None \
        else {key: w[1] for key, w in stack.items()}
    w_in, w_out = (stack[key][1 if layer is None else layer]
                   for key in ("w_in", "w_out"))
    monkeypatch.setattr(moe_ops, "KERNEL_BLOCK_BYTES",
                        3 * DIM * hidden_tile * 2)

    def call():
        return jax.jit(lambda ex, x, p, w: moe_ops.dropless_experts(
            ex, x, p, w, first, OF))(experts, x, picks, weights)

    assert moe_ops.kernel_hidden_tile(
        case["rows"], DIM, HIDDEN, jnp.bfloat16) == hidden_tile
    kernels = str(jax.make_jaxpr(
        lambda ex, x, p, w: moe_ops.dropless_experts(
            ex, x, p, w, first, OF))(experts, x, picks, weights))
    assert "pallas_call" in kernels and "ragged_dot" not in kernels
    out, sizes, handed, reads = call()
    np.testing.assert_array_equal(np.asarray(sizes), case["sizes"])
    assert int(handed) == case["handed"]
    # the kernel's grid visits each (row tile, group) pair that holds a
    # row; a step that takes a whole expert finds a straddling group's
    # weights in place at its second visit and reads each touched expert
    # once, a step that takes a hidden tile of two reads it a visit
    touched = int((np.asarray(case["sizes"]) > 0).sum())
    visits = _reads_by_hand(np.asarray(case["sizes"]), case["rows"])
    assert int(reads) == (touched if hidden_tile == HIDDEN else visits)
    if name == "a_group_straddles_a_tile_boundary":
        assert (touched, visits) == (7, 8)          # expert 3 twice
    want = _reference(w_in, w_out, x, picks, weights, first or 0)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=0.04, rtol=0.02)
    # a token none of whose pairs is held gets nothing, to the bit
    none_held = ~np.isin(np.asarray(picks), (first or 0) + np.arange(held)
                         ).any(axis=1)
    assert not np.asarray(out, np.float32)[none_held].any()
    # the compiler's grouped product, the same call's other form
    monkeypatch.setattr(moe_ops, "kernel_hidden_tile", lambda *a: None)
    other, sizes_other, handed_other, _ = call()
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(sizes_other))
    assert int(handed_other) == int(handed)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(other, np.float32), atol=0.06,
                               rtol=0.02)


# -- the rule, from a call's shapes ----------------------------------------------

@pytest.mark.parametrize("rows,dim,hidden,dtype,tile", [
    (256, 2048, 1536, jnp.bfloat16, 1536),     # lfm2's decode step: whole
    (192, 2048, 768, jnp.bfloat16, 768),       # kanana's: whole
    (64, 3072, 3072, jnp.bfloat16, 1024),      # trinity's: a third
    (512, 4096, 2048, jnp.bfloat16, 512),      # mimo's: a quarter
    (128, 2048, 1536, jnp.bfloat16, 1536),
    (64, 128, 256, jnp.bfloat16, 256),         # the least widths
    (4096, 2048, 1536, jnp.bfloat16, None),    # a 4-row prefill's rows
    (1024, 4096, 2048, jnp.bfloat16, None),
    (256, 2048, 1536, jnp.float32, None),      # the reference's precision
    (264, 2048, 1536, jnp.bfloat16, None),     # no whole number of tiles
    (256, 32, 12, jnp.bfloat16, None),         # the toy widths
    (256, 2048, 200, jnp.bfloat16, None),      # no hidden tile of lanes
    (256, 2000, 1536, jnp.bfloat16, None),
])
def test_the_rule_reads_the_rows_the_widths_and_the_type(rows, dim, hidden,
                                                         dtype, tile):
    assert moe_ops.kernel_hidden_tile(rows, dim, hidden, dtype) == tile
    if tile:
        assert hidden % tile == 0 and tile % moe_ops.NUM_LANES == 0
        assert 3 * dim * tile * 2 <= moe_ops.KERNEL_BLOCK_BYTES


@pytest.mark.parametrize("cell", [
    "lfm2-24b-a2b.serve-full", "kanana-2-30b-a3b.serve-full",
    "mimo-v2.5.serve-full", "trinity-large-preview.serve-full"])
def test_each_routed_cell_s_decode_engages_and_no_prefill_does(cell):
    cell = harness.Cell(cell)
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    cfg = cell.family.build.program_config(dims, cell.spec["flags"])
    blk, slots = cfg.transformer.block, int(cell.spec["num_slots"])
    dtype = jnp.dtype(cell.config["param_dtype"])

    def tile(tokens):
        return moe_ops.kernel_hidden_tile(
            tokens * blk.experts_per_token, cfg.transformer.dim,
            blk.expert_hidden, dtype)

    assert tile(slots)
    for rows in S.prefill_groups(slots):
        assert tile(rows * 256) is None


def test_a_toy_block_s_call_traces_no_kernel():
    """The tier-1 suites' widths (``benchmark/tiny.py``: dim 32, an
    expert's hidden 12, float32) stay on ``lax.ragged_dot``."""
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    experts = {"w_in": jax.random.normal(k[0], (8, 32, 24)),
               "w_out": jax.random.normal(k[1], (8, 12, 32))}
    picks = jax.random.randint(k[2], (64, 4), 0, 8)
    text = str(jax.make_jaxpr(moe_ops.dropless_experts)(
        experts, jax.random.normal(k[3], (64, 32)), picks,
        jnp.ones((64, 4))))
    assert "ragged_dot" in text and "pallas_call" not in text
