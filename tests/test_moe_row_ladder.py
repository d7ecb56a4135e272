"""The held experts' grouped products run over the rows that lie in a held
group (ops/moe.py ``row_ladder``, ``dropless_experts``): the ladder from
shapes, every step of it against a float32 per-pair reference, the load's
last entry, and the call with no ladder and one row tile left as it was
(a call without a ladder whose rows make several tiles:
tests/test_moe_row_tiles.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark import harness, seeds
from dalle_pytorch_tpu.ops import moe as moe_ops
from dalle_pytorch_tpu.serve import scheduler as S
from dalle_pytorch_tpu.serve.engine import Engine

DIM, HIDDEN, E, OF, K = 16, 8, 16, 256, 8     # 16 of 256 held, top 8


def _experts(layers=None, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    lead = (E,) if layers is None else (layers, E)
    return {"w_in": jax.random.normal(k[0], lead + (DIM, 2 * HIDDEN)) / 4,
            "w_out": jax.random.normal(k[1], lead + (HIDDEN, DIM)) / 3}


def _pairs(tokens: int, held: int, first: int, seed=1):
    """picks (tokens, K) of which exactly ``held`` pairs, anywhere, name
    an expert of ``first`` .. ``first + E``; weights; x."""
    rng = np.random.default_rng(seed)
    away = np.setdiff1d(np.arange(OF), np.arange(first, first + E))
    flat = rng.choice(away, tokens * K)
    at = rng.choice(tokens * K, held, replace=False)
    flat[at] = first + rng.integers(0, E, held)
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jnp.asarray(flat.reshape(tokens, K), jnp.int32),
            jax.random.uniform(k[0], (tokens, K), minval=0.1),
            jax.random.normal(k[1], (tokens, DIM)))


def _reference(w_in, w_out, x, picks, weights, first):
    """Each (token, pick) pair through its expert's own matrices, one by
    one, in float32; a pair whose expert is held elsewhere adds nothing."""
    here = (picks >= first) & (picks < first + E)
    at = jnp.where(here, picks - first, 0)
    hp = lax.Precision.HIGHEST
    h = jnp.einsum("td,tkdf->tkf", x, w_in[at], precision=hp)
    gate, up = jnp.split(h, 2, axis=-1)
    out = jnp.einsum("tkf,tkfd->tkd", jax.nn.silu(gate) * up, w_out[at],
                     precision=hp)
    return jnp.sum(jnp.where(here[..., None], weights[..., None] * out, 0.0),
                   axis=1)


# (tokens, [(held pairs, the step that holds them)]): none, under the
# first step, exactly a step, between steps, exactly the second, over it,
# every row
CASES = [(64, held, step) for held, step in (
             (0, 64), (40, 64), (64, 64), (100, 128), (128, 128), (300, 512),
             (512, 512))] \
    + [(1024, held, step) for held, step in (
        (0, 1024), (700, 1024), (1024, 1024), (1500, 2048), (2048, 2048),
        (5000, 8192), (8192, 8192))]


@pytest.mark.parametrize("stacked", [False, True], ids=["a_layer", "a_stack"])
@pytest.mark.parametrize("tokens,held,step", CASES)
def test_every_step_of_the_ladder_against_the_per_pair_reference(
        tokens, held, step, stacked):
    first = 32
    assert moe_ops.row_ladder(tokens * K, E, OF)[:2] == (
        (64, 128) if tokens == 64 else (1024, 2048))
    picks, weights, x = _pairs(tokens, held, first, seed=held + 1)
    experts = _experts(3 if stacked else None)
    w_in, w_out = experts["w_in"], experts["w_out"]
    if stacked:
        experts["layer"] = jnp.int32(1)
        w_in, w_out = w_in[1], w_out[1]
    out, sizes, handed, reads = jax.jit(
        lambda ex, x, p, w: moe_ops.dropless_experts(ex, x, p, w, first, OF)
    )(experts, x, picks, weights)
    assert int(handed) == step and int(sizes.sum()) == held
    # a ladder's steps are one tile each: every touched group read once
    assert int(reads) == int((sizes > 0).sum())
    want = _reference(w_in, w_out, x, picks, weights, first)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    if not held:
        assert not np.asarray(out).any()


def _parent_dropless_experts(experts, x, picks, weights, first=None):
    """``dropless_experts`` as it stood before the ladder (PR 40), for the
    jaxpr of a call that has none; and, last, the load's entry of PR 43:
    the groups that hold a row, which one tile reads once each."""
    t, k = picks.shape
    w_in, w_out = experts["w_in"], experts["w_out"]
    e = w_in.shape[-3]
    with jax.named_scope("moe.route"):
        flat = picks.reshape(-1)
        if first is not None:
            here = (flat >= first) & (flat < first + e)
            flat = jnp.where(here, flat - first, e)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
        rows = jnp.take(x, order // k, axis=0)
        pair_weights = jnp.take(weights.reshape(-1), order)
        groups = sizes
        if "layer" in experts:
            w_in = w_in.reshape((-1,) + w_in.shape[2:])
            w_out = w_out.reshape((-1,) + w_out.shape[2:])
            groups = lax.dynamic_update_slice(
                jnp.zeros((w_in.shape[0],), jnp.int32), sizes,
                (experts["layer"] * e,))
    with jax.named_scope("moe.experts"):
        gate, up = jnp.split(
            lax.ragged_dot(rows, w_in.astype(x.dtype), groups), 2, axis=-1)
        out = lax.ragged_dot(jax.nn.silu(gate) * up, w_out.astype(x.dtype),
                             groups)
        out = out.astype(jnp.float32) * pair_weights[:, None]
        if first is not None:
            out = jnp.where((jnp.take(flat, order) < e)[:, None], out, 0.0)
    with jax.named_scope("moe.route"):
        out = jnp.take(out, jnp.argsort(order), axis=0).reshape(t, k, -1)
        out = jnp.sum(out, axis=1)
        reads = jnp.sum(sizes > 0).astype(jnp.int32)
    return out.astype(x.dtype), sizes, reads


@pytest.mark.parametrize("stacked", [False, True], ids=["a_layer", "a_stack"])
@pytest.mark.parametrize("tokens,first", [
    (8, None),      # every expert held, one tile of pair rows
    (24, None),     # the same under the ridge (kanana's decode: 192 rows)
    (65, None),     # the same just over the kernel's own row tile
    (512, None),    # the same in a prefill
    (8, 32),        # a share, the pairs under the first step (trinity's 64)
], ids=["all_held", "all_held_under_the_ridge",
        "all_held_over_the_kernel_s_tile", "all_held_prefill",
        "a_share_of_few_pairs"])
def test_a_call_without_a_ladder_traces_as_the_parent_s(tokens, first,
                                                        stacked):
    """No more rows than the chip's ridge, or more than the kernel's own
    tile: the parent's jaxpr but for the load's new entry."""
    assert moe_ops.row_tiles(tokens * K) == 1
    picks, weights, x = _pairs(tokens, tokens, 32)
    if first is None:
        picks = picks % E
    experts = _experts(3 if stacked else None)
    if stacked:
        experts["layer"] = jnp.int32(2)
    args = (experts, x.astype(jnp.bfloat16), picks, weights)
    def but_handed(*a):
        out, sizes, _, reads = moe_ops.dropless_experts(*a, first, OF)
        return out, sizes, reads

    now = jax.make_jaxpr(but_handed)(*args)
    then = jax.make_jaxpr(lambda *a: _parent_dropless_experts(
        *a, first))(*args)
    assert str(now) == str(then)
    assert "cond" not in str(now)
    handed = moe_ops.dropless_experts(*args, first, OF)[2]
    assert int(handed) == tokens * K


def test_a_call_with_a_ladder_has_one_conditional_of_its_steps():
    picks, weights, x = _pairs(64, 40, 32)
    text = str(jax.make_jaxpr(lambda *a: moe_ops.dropless_experts(
        *a, 32, OF))(_experts(), x, picks, weights))
    # a step's row count is the cut already: no row tiles inside a ladder
    assert text.count("cond[") == 1 and text.count("= ragged_dot_general[") == 6
    for rows in (64, 128, 512):
        assert f"f32[{rows},{2 * HIDDEN}] = ragged_dot_general[" in text


# -- the load's last entry, and the engine's counter ---------------------------

@dataclasses.dataclass(frozen=True)
class _Blk:
    num_experts: int = OF
    experts_held: int = E
    first_expert: int = 32
    experts_per_token: int = K
    routed_scale: float = 1.0
    route_eps: float = 0.0
    route_scores: str = "sigmoid"
    expert_hidden: int = HIDDEN
    shared_hidden: int = 0
    shared_gate: bool = False


@pytest.mark.parametrize("bias,step", [(-1.0, 64), (0.0, 64), (0.06, 128),
                                       (1.0, 512)])
def test_the_load_s_last_entry_is_the_step_taken(bias, step):
    """A selection bias on the held experts moves the picks onto them: the
    products are handed the least step that holds the held picks."""
    blk = _Blk()
    p = moe_ops.dropless_init(jax.random.PRNGKey(3), DIM, blk)
    held = (jnp.arange(OF) >= 32) & (jnp.arange(OF) < 32 + E)
    p["router"]["bias"] = jnp.where(held, bias, 0.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 16, DIM))
    out, load = jax.jit(lambda p, x: moe_ops.dropless_apply(p, x, blk))(p, x)
    assert moe_ops.load_width(blk) == 6 and load.shape == (6,)
    picks, handed = int(load[4]), int(load[5])
    assert int(load[0]) == 512 and handed == step
    assert picks <= handed and not any(
        picks <= r < handed for r in moe_ops.row_ladder(512, E, OF))
    assert out.shape == x.shape
    whole = dataclasses.replace(blk, experts_held=OF, first_expert=0)
    assert moe_ops.load_width(whole) == 4


def test_the_ladder_of_a_quarter_held_of_512_top_10():
    """ISSUE 47's shape, 128 of 512 experts held under top 10: 64 slots'
    640 pairs, and the 48 slots' that the cell runs (its fallback: the
    64-row wave's prefill does not fit the chip)."""
    assert moe_ops.row_ladder(640, 128, 512) == (512, 640)
    assert moe_ops.row_ladder(480, 128, 512) == (256, 480)


@pytest.mark.parametrize("every_pair", [False, True],
                         ids=["half_the_first_step", "every_pair"])
@pytest.mark.parametrize("pairs,first_step", [(640, 512), (480, 256)])
def test_a_step_s_rows_decide_its_products(pairs, first_step, every_pair):
    """A ladder that straddles ``KERNEL_ROWS``, or ends in a step that is
    no whole row tiles: the first step's rows run in the repo's kernel,
    the step of every pair on the compiler's product, under the ladder's
    conditional; both give the per-pair reference's sums, and the load's
    fourth entry is each touched expert once either way (a grid step
    takes a whole expert)."""
    dim, hidden, e, of, k = 128, 128, 4, 16, 10
    assert moe_ops.row_ladder(pairs, e, of) == (first_step, pairs)
    assert moe_ops.kernel_hidden_tile(first_step, dim, hidden,
                                      jnp.bfloat16) == hidden
    assert moe_ops.kernel_hidden_tile(pairs, dim, hidden,
                                      jnp.bfloat16) is None
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    experts = {"w_in": (jax.random.normal(ks[0], (e, dim, 2 * hidden))
                        / 12).astype(jnp.bfloat16),
               "w_out": (jax.random.normal(ks[1], (e, hidden, dim))
                         / 12).astype(jnp.bfloat16)}
    x = jax.random.normal(ks[2], (pairs // k, dim)).astype(jnp.bfloat16)
    weights = jax.random.uniform(ks[3], (pairs // k, k), minval=0.1)
    held, step = (pairs, pairs) if every_pair else (first_step // 2,
                                                    first_step)
    rng = np.random.default_rng(3)
    flat = rng.integers(e, of, pairs)
    flat[rng.choice(pairs, held, replace=False)] = rng.integers(0, e, held)
    picks = jnp.asarray(flat.reshape(-1, k), jnp.int32)
    args = (experts, x, picks, weights)
    text = str(jax.make_jaxpr(lambda *a: moe_ops.dropless_experts(
        *a, 0, of))(*args))
    assert "pallas_call" in text and "ragged_dot_general" in text
    out, sizes, handed, reads = moe_ops.dropless_experts(*args, 0, of)
    assert int(handed) == step and int(jnp.sum(sizes)) == held
    assert int(reads) == int(jnp.sum(sizes > 0))
    here = picks < e
    at = jnp.where(here, picks, 0)
    h = jnp.einsum("td,tkdf->tkf", x.astype(jnp.float32),
                   experts["w_in"].astype(jnp.float32)[at])
    gate, up = jnp.split(h, 2, axis=-1)
    want = jnp.einsum("tkf,tkfd->tkd", jax.nn.silu(gate) * up,
                      experts["w_out"].astype(jnp.float32)[at])
    want = jnp.sum(jnp.where(here[..., None], weights[..., None] * want,
                             0.0), axis=1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=0.05, rtol=0.05)


def _toy_engine(family: str, config: str, depth: int) -> Engine:
    fam = harness.load_family(family)
    conf = dict(harness.load_json(
        f"{harness.ROOT}/benchmark/configs/{config}.json"), **fam.tiny)
    dims = fam.weights.dims_of(conf, depth)
    cfg = fam.build.program_config(dims, {})
    p = jax.jit(lambda h: fam.weights.tree(h, dims, jnp.float32))(
        seeds.split_seed(5))
    return Engine(p, cfg, S.RequestQueue(max_depth=2), num_slots=1,
                  kv="paged", page_size=4)


@pytest.mark.parametrize("family,config,depth,there", [
    ("mimo_v2", "mimo-v2.5", 7, True),
    ("afmoe", "trinity-large-preview", 5, True),
    ("mla_moe", "kanana-2-30b-a3b", 3, False),
])
def test_the_engine_counts_the_rows_where_a_share_is_held(family, config,
                                                          depth, there):
    stats = _toy_engine(family, config, depth).stats()
    assert "moe_picks" in stats and "moe_group_reads" in stats
    assert ("moe_rows_computed" in stats) == there
    assert ("moe_picks_held" in stats) == there


# -- the ladder and the tiles from shapes, at the routed cells' sizes -----------

@pytest.mark.parametrize("cell,decode,prefill,tiles", [
    ("mimo-v2.5.serve-full", (64, 128, 512), (1024, 2048, 8192), 1),
    ("trinity-large-preview.serve-full", None, (1024, 2048, 4096), 1),
    ("kanana-2-30b-a3b.serve-full", None, None, 1),
    ("lfm2-24b-a2b.serve-full", None, None, 4),
    ("qwen3-next-80b-a3b.serve-full", (256, 480), (8192, 10240), 4),
])
def test_the_ladder_of_each_routed_cell_s_programs(cell, decode, prefill,
                                                   tiles):
    """Decode hands the function a pair row a slot a pick, an admission a
    group of 4 rows x the 256-token bucket x the picks. ``tiles``: the row
    tiles of the step that a decode step of the cell takes (mimo's, by
    ``moe_rows_computed_pct`` 12.5, its first; kanana's 192 pair rows lie
    under the ridge); every step of every admission is one tile, the rows
    being more than the kernel's own."""
    cell = harness.Cell(cell)
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    blk = cell.family.build.program_config(
        dims, cell.spec["flags"]).transformer.block
    slots = int(cell.spec["num_slots"])
    assert min(S.prefill_groups(slots)) == 4

    def steps(tokens):
        pairs = tokens * blk.experts_per_token
        if moe_ops.holds_all(blk):
            return (pairs,)
        return moe_ops.row_ladder(pairs, blk.experts_held, blk.num_experts)

    def ladder(tokens):
        return steps(tokens) if len(steps(tokens)) > 1 else None

    assert ladder(slots) == decode
    assert ladder(4 * 256) == prefill
    assert moe_ops.row_tiles(steps(slots)[0]) == tiles
    for rows in S.prefill_groups(slots):
        assert {moe_ops.row_tiles(r) for r in steps(rows * 256)} == {1}
