"""The all-held experts' grouped products meet a row tile at a time
(ops/moe.py ``row_tiles``, ``tile_sizes``, ``dropless_experts``; ISSUE
43): where a call has no ladder and its sorted pair rows are more than
the chip's ridge (``RIDGE_ROWS``) and at most the kernel's own row tile,
each tile of 64 rows runs over the groups clipped to it. The tiled products against the
one-call products and the float32 per-pair reference, the tiles from
shapes, and the load's fourth entry."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.ops import moe as moe_ops
from test_moe_row_ladder import DIM, E, K, _Blk, _experts, _reference

R = moe_ops.ROW_TILE


def _routing(flat, seed=0):
    """picks (tokens, K) laid out from ``flat`` (the pairs' experts, in any
    order), weights and x of that many tokens."""
    picks = jnp.asarray(np.asarray(flat).reshape(-1, K), jnp.int32)
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    tokens = picks.shape[0]
    return (picks, jax.random.uniform(k[0], (tokens, K), minval=0.1),
            jax.random.normal(k[1], (tokens, DIM)))


def _from_sizes(sizes, seed=0):
    """Pairs that bring expert ``g`` ``sizes[g]`` picks, shuffled."""
    flat = np.repeat(np.arange(len(sizes)), sizes)
    return np.random.default_rng(seed).permutation(flat)


def _random(tokens):
    def make(seed):
        return np.random.default_rng(seed).integers(0, E, tokens * K)
    return make


# name -> (seed -> the pairs' experts): 256 pair rows are lfm2's decode
# step; 264 is no multiple of the tile (4 tiles and 8 rows)
ROUTINGS = {
    "random_256": _random(32),
    "random_264_not_a_multiple": _random(33),
    "random_384": _random(48),
    "random_512_the_kernel_s_tile": _random(64),
    # expert 3 holds rows 60 .. 69 of the sorted pairs: both sides of 64
    "a_group_straddles_a_boundary": lambda seed: _from_sizes(
        [20, 20, 20, 10, 58, 64, 64] + [0] * (E - 7), seed),
    # one group over three tiles, and groups that end ON a boundary
    "a_group_spans_three_tiles": lambda seed: _from_sizes(
        [64, 150, 42] + [0] * (E - 3), seed),
    "every_pair_on_one_expert": lambda seed: np.full(256, 5),
    "most_experts_without_a_pick": lambda seed: _from_sizes(
        [0, 0, 100, 0, 0, 0, 156, 0, 0, 0, 0, 0, 0, 0, 0, 0], seed),
}


@pytest.mark.parametrize("stacked", [False, True], ids=["a_layer", "a_stack"])
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("name", list(ROUTINGS))
def test_tiled_products_equal_one_call_and_the_per_pair_reference(
        name, seed, stacked, monkeypatch):
    picks, weights, x = _routing(ROUTINGS[name](seed), seed)
    pairs = picks.size
    experts = _experts(3 if stacked else None, seed)
    w_in, w_out = experts["w_in"], experts["w_out"]
    if stacked:
        experts["layer"] = jnp.int32(seed + 1)
        w_in, w_out = w_in[seed + 1], w_out[seed + 1]

    def call():
        return jax.jit(lambda ex, x, p, w: moe_ops.dropless_experts(
            ex, x, p, w))(experts, x, picks, weights)

    assert moe_ops.row_tiles(pairs) == -(-pairs // R) > 1
    out, sizes, handed, reads = call()
    # over the kernel's own row tile the same call is one tile: the parent's
    monkeypatch.setattr(moe_ops, "KERNEL_ROWS", pairs - 1)
    assert moe_ops.row_tiles(pairs) == 1
    whole, sizes_whole, _, reads_whole = call()
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(sizes_whole))
    assert int(handed) == pairs and int(sizes.sum()) == pairs
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole), atol=2e-5)
    want = _reference(w_in, w_out, x, picks, weights, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    # one tile reads each touched group once; tiles read it once each
    assert int(reads_whole) == int((sizes > 0).sum())
    assert int(reads) == _reads_by_hand(np.asarray(sizes), pairs)


def _reads_by_hand(sizes, rows):
    """The (tile, group) pairs that share a row, row by row."""
    group_of_row = np.repeat(np.arange(len(sizes)), sizes)[:rows]
    return len({(row // R, g) for row, g in enumerate(group_of_row)})


# -- the tiles from shapes -----------------------------------------------------

@pytest.mark.parametrize("rows,tiles", [
    (1, 1), (32, 1), (64, 1),           # trinity's decode, mimo's first step
    (65, 1), (128, 1), (192, 1), (240, 1),      # under the ridge: kanana's
    (241, 4), (256, 4), (264, 5), (320, 5), (512, 8),       # lfm2's 256
    (513, 1), (1024, 1), (4096, 1), (6144, 1), (8192, 1),   # prefill
])
def test_the_tiles_of_a_row_count(rows, tiles):
    assert moe_ops.row_tiles(rows) == tiles


def test_the_ridge_is_the_chip_s_operations_over_its_bandwidth():
    """A row of a grouped product costs two operations a weight, the read
    two bytes a weight: the rows at which they take as long."""
    from dalle_pytorch_tpu.utils.device import chip_peaks
    v5e = chip_peaks("TPU v5 lite")
    assert moe_ops.RIDGE_ROWS == pytest.approx(
        v5e["bf16_flops"] / v5e["hbm_bytes_per_s"], rel=0.01)
    assert R < moe_ops.RIDGE_ROWS < moe_ops.KERNEL_ROWS


def test_the_tile_is_the_ladder_s_floor():
    """One constant: the least step of ``row_ladder`` and a tile's rows."""
    assert moe_ops.row_ladder(512, 16, 256)[0] == R == 64
    assert moe_ops.row_ladder(64, 32, 256) == (64,)


@pytest.mark.parametrize("rows", [192, 200, 256, 512])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_tile_s_sizes_are_the_groups_clipped_to_it(rows, seed):
    flat = np.random.default_rng(seed).integers(0, E, rows)
    sizes = np.bincount(flat, minlength=E)
    got = np.asarray(moe_ops.tile_sizes(jnp.asarray(sizes, jnp.int32), rows))
    assert got.shape == (-(-rows // R), E)
    # a tile's groups hold the tile's rows, a group's tiles the group's
    np.testing.assert_array_equal(got.sum(0), sizes)
    np.testing.assert_array_equal(
        got.sum(1), [min(R, rows - lo) for lo in range(0, rows, R)])
    group_of_row = np.sort(flat)
    for j in range(got.shape[0]):
        np.testing.assert_array_equal(
            got[j], np.bincount(group_of_row[j * R:(j + 1) * R],
                                minlength=E))


def test_a_share_s_tiles_leave_the_rows_behind_the_groups_in_none():
    """A share of few pairs has no ladder and may have tiles (256 pair
    rows where the first step is 256): the rows behind the last group lie
    in no group of any tile, and a tile of such rows reads nothing."""
    sizes = jnp.zeros((E,), jnp.int32).at[jnp.asarray([1, 4])].set(
        jnp.asarray([50, 30]))
    got = np.asarray(moe_ops.tile_sizes(sizes, 192))
    assert got.shape == (3, E)
    assert got[0, 1] == 50 and got[0, 4] == 14 and got[1, 4] == 16
    assert got.sum() == 80 and not got[2].any()
    # 32 tokens x 8 picks over the 16 held of 32: the ladder is (256,)
    assert moe_ops.row_ladder(256, E, 2 * E) == (256,)
    assert moe_ops.row_tiles(256) == 4
    rng = np.random.default_rng(0)
    picks, weights, x = _routing(rng.integers(0, 2 * E, 256))
    experts = _experts()
    out, sizes, handed, reads = moe_ops.dropless_experts(
        experts, x, picks, weights, 8, 2 * E)
    assert int(handed) == 256 and 64 < int(sizes.sum()) < 192
    want = _reference(experts["w_in"], experts["w_out"], x, picks, weights, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    assert int(reads) == _reads_by_hand(np.asarray(sizes), 256)
    assert int(reads) <= int((sizes > 0).sum()) + 2     # the fourth is empty


# -- the load's fourth entry ---------------------------------------------------

def test_group_reads_are_the_touched_and_the_straddles_by_hand():
    """256 sorted rows, boundaries at 64, 128, 192: expert 0 ends ON 64 (no
    straddle), expert 2 straddles 128, expert 3 straddles 192, experts 1, 4
    lie inside a tile: 5 touched + 2 straddles."""
    sizes = [64, 40, 50, 60, 42] + [0] * (E - 5)
    picks, weights, x = _routing(_from_sizes(sizes))
    _, got, _, reads = moe_ops.dropless_experts(_experts(), x, picks, weights)
    np.testing.assert_array_equal(np.asarray(got), sizes)
    assert int(reads) == 5 + 2


@pytest.mark.parametrize("tokens,tiles", [(8, 1), (24, 1), (32, 4), (512, 1)])
def test_the_load_of_a_block_that_holds_every_expert(tokens, tiles):
    """picks, touched, the fullest expert's, the group reads: four entries,
    the fourth the second where there is one tile and never under it."""
    blk = dataclasses.replace(_Blk(), num_experts=E, experts_held=E,
                              first_expert=0)
    assert moe_ops.holds_all(blk) and moe_ops.load_width(blk) == 4
    p = moe_ops.dropless_init(jax.random.PRNGKey(3), DIM, blk)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, DIM))
    out, load = jax.jit(lambda p, x: moe_ops.dropless_apply(p, x, blk))(p, x)
    assert out.shape == x.shape and load.shape == (4,)
    picks, touched, fullest, reads = (int(v) for v in load)
    assert picks == tokens * K and moe_ops.row_tiles(picks) == tiles
    assert touched <= reads <= touched + tiles - 1
    assert fullest * touched >= picks


def test_the_tiles_run_inside_the_scopes_the_readers_sum():
    """Every grouped product of a tiled call is under ``moe.experts``, the
    clips and the running sums under ``moe.route``."""
    picks, weights, x = _routing(_random(32)(0))
    text = jax.jit(lambda ex, x, p, w: moe_ops.dropless_experts(
        ex, x, p, w)).lower(_experts(), x, picks, weights).as_text(
            debug_info=True)
    named = [ln for ln in text.splitlines() if ln.startswith("#loc")]
    dots = [ln for ln in named if "ragged_dot" in ln]
    assert dots and all("/moe.experts/" in ln for ln in dots)
    sums = [ln for ln in named if "cumsum" in ln or "clamp" in ln]
    assert sums and all("/moe.route/" in ln for ln in sums)
