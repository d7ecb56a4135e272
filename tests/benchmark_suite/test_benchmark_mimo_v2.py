"""The ``mimo_v2`` family's cell under the contract's checks and rehearsed
on the CPU at its ``tiny.json`` widths (seven layers in the published
pattern, full layers of one key/value head and window layers of two, K
heads of 12 and V heads of 8 numbers, a window of two pages in a sequence
of six: the ring turns): a whole run of the benchmark's own serve driver
against the family's plain reference (float32 toy weights, so that the
sound program agrees token for token), the control failing, an altered
served token failing, the program's int8 path refused for this block, the
new reader on fixture counters, and the family's byte counts. Limits here
are toy-width limits; they say nothing about a speed. The cell's programs
at the published widths are compiled for a described chip by
``test_benchmark_aot.py``, which finds every cell of ``BENCHMARK.json`` by
name (one file holds the TPU compiler: see the on-chip-measurement guide)
and reads the memory of this cell's decode and prefill programs there."""

import json
import os
import types

import pytest

from benchmark import harness, serve_cell, tiny

from test_benchmark_contract import (check_cell, check_declared,
                                     check_declared_for_some, check_moves)

CELL = "mimo-v2.5.serve-full"
LIMITS = {"served_logit_gap_max": 1e-4, "served_logit_gap_mean": 1e-7,
          "served_not_best_share": 5e-4}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
# the block metrics whose readers find something to read in this cell
BLOCK_METRICS = ("decode_window_view_ms", "decode_window_attend_ms",
                 "window_cache_saved_pct", "moe_held_pick_share_pct",
                 "decode_moe_experts_ms", "decode_moe_route_ms",
                 "moe_experts_touched_per_layer", "moe_load_max_over_mean",
                 "moe_experts_roofline", "gqa_read_roofline",
                 "window_sink_mass_pct",
                 # its scopes hold this block's projections (the partial
                 # rotary turn, the value scale), the dense layer, the head
                 "decode_weights_ms")
# and those that find nothing: no shared expert, no latent pool, no state,
# and no layer that reads by the width rule (both full layers are runs of
# one)
NOT_HERE = ("decode_moe_shared_ms", "decode_latent_ms",
            "latent_read_roofline", "decode_ssm_ms", "decode_gmu_ms",
            "ssm_step_roofline", "kv_view_columns_read_pct")


@pytest.fixture(scope="module")
def listener():
    return harness.CompileListener()


def _cell(tmp_path):
    root = tiny.make(str(tmp_path), dtype="float32")
    path = os.path.join(root, "benchmark", "cells", CELL + ".json")
    spec = harness.load_json(path)
    spec["limits"] = LIMITS
    json.dump(spec, open(path, "w"))
    harness.OUT_DIR = os.path.join(root, "benchmark_out")
    return harness.Cell(CELL, root=root)


def _args(**kw):
    base = dict(seed=2 ** 31 + 5, seconds=1.0, trace=0, control="none",
                broken="", sync_every_step=0, more_seeds=0)
    base.update(kw)
    return types.SimpleNamespace(**base)


# -- the declarations, by name -------------------------------------------------

def test_the_cell_passes_the_contract_s_checks():
    check_cell(CELL)
    bench = harness.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == ("mimo-v2.5", "serve-full", 1)
    spec = harness.Cell(CELL).spec
    assert (spec["depth"], spec["num_slots"]) == (7, 64)
    assert spec["engine"] == {"kv": "paged", "paged_attn": "gather",
                              "chunk_steps": 8}
    e2e = {m["name"] for m in harness.Cell(CELL).metrics("end_to_end")}
    assert e2e == {"images_per_s", "tpot_ms", "tpot_ms_p95", "setup_s"}


@pytest.mark.parametrize("metric", ["decode_step_device_ms",
                                    "decode_scoped_pct", "decode_sample_ms",
                                    "decode_kv_view_ms", "decode_attend_ms",
                                    "compiles_in_window.serve",
                                    "chunk_interval_ms", "loop_stall_ms"])
def test_a_metric_of_every_serve_cell_lists_the_cell(metric):
    assert CELL in check_declared(metric)["workloads"]


@pytest.mark.parametrize("metric", BLOCK_METRICS)
def test_a_block_metric_lists_the_cell(metric):
    check_declared_for_some(metric, cells=(CELL,), layer="decode math",
                            moves="tpot_ms")
    check_moves(metric)


@pytest.mark.parametrize("metric", NOT_HERE)
def test_a_metric_with_nothing_to_read_does_not_list_the_cell(metric):
    check_declared_for_some(metric, but=(CELL,))


def test_the_new_metric_is_declared_for_this_cell():
    """(Which further cells list it is theirs to say: a later cell whose
    window layers hold a sink lists it and edits no test.)"""
    check_declared_for_some(
        "window_sink_mass_pct", cells=(CELL,), unit="%",
        source="program_counter", layer="decode math", moves="tpot_ms")


# -- the CPU rehearsal of the cell ---------------------------------------------

@pytest.mark.parametrize("case, correct", [
    ("sound", True),
    ("token_altered", False),
    ("reference_fp8", False),
])
def test_tiny_cell_against_the_family_s_reference(tmp_path, listener, case,
                                                  correct):
    cell = _cell(tmp_path)
    assert cell.family.name == "mimo_v2"
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    # the toy wraps its window: two pages of window in six of sequence
    assert dims.window == 32 and dims.seq_len == 96 and dims.depth == 7
    assert (dims.full_kv_heads, dims.kv_heads, dims.head_dim,
            dims.v_head_dim, dims.rotary_dim) == (1, 2, 12, 8, 4)
    assert (dims.experts, dims.experts_held, dims.first_expert) == (16, 4, 4)
    args = _args(broken=case if case == "token_altered" else "",
                 control=case if case == "reference_fp8" else "none")
    out = json.loads(serve_cell.run(cell, args, dict(DEVICE), listener))
    assert out["correct"] is correct
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["checks"]) == set(LIMITS)
    assert set(out["metrics"]) == {"images_per_s", "tpot_ms", "tpot_ms_p95",
                                   "setup_s"}


def test_program_int8_control_is_refused_for_the_block(tmp_path, listener):
    from dalle_pytorch_tpu.ops.transformer import BlockOptionError
    with pytest.raises(BlockOptionError, match="quantize"):
        serve_cell.run(_cell(tmp_path), _args(control="program_int8"),
                       dict(DEVICE), listener)


# -- the readers ---------------------------------------------------------------

def _ctx(cell_name, stats0, stats1, kind="serve"):
    cell = harness.Cell(cell_name)
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    return {"kind": kind, "cell": cell, "dims": dims, "trace": None,
            "stats0": stats0, "stats1": stats1,
            "peaks": {"hbm_bytes_per_s": 8.19e11}}


def test_the_sink_s_reader_on_fixture_counters():
    read = harness.load_reader("window_sink_mass_pct")
    # 10 steps of 64 slots x 64 heads x 5 window layers, a third to the sink
    reads = 10 * 64 * 64 * 5
    s0 = {"window_sink_mass": 100.0, "window_sink_reads": 300,
          "decode_steps": 0}
    s1 = {"window_sink_mass": 100.0 + reads / 3.0,
          "window_sink_reads": 300 + reads, "decode_steps": 10}
    assert read(_ctx(CELL, s0, s1)) == pytest.approx(100.0 / 3.0)
    # a train cell; a window in which no window softmax ran; a program
    # without the counters (the parent of this PR, another family's cell)
    assert read(_ctx(CELL, s0, s1, kind="train")) is None
    assert read(_ctx(CELL, s0, dict(s0, decode_steps=10))) is None
    assert read(_ctx("trinity-large-preview.serve-full",
                     {"decode_steps": 0, "moe_picks": 0},
                     {"decode_steps": 80, "moe_picks": 80})) is None
    assert read(_ctx(CELL, None, None)) is None


def test_counter_readers_read_the_engine_s_counters():
    cell = harness.Cell(CELL)
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    assert (dims.full_layers, dims.window_layers, dims.moe_layers) == (2, 5,
                                                                      6)
    # 64 slots at their sequences' end: 272 pages of each full layer, 9 of
    # each window layer
    end = {"layer_pages_in_use": 64 * (2 * 272 + 5 * 9),
           "layer_pages_all_full": 64 * 7 * 272, "moe_picks": 0,
           "moe_picks_held": 0, "decode_steps": 0}
    ctx = _ctx(CELL, end, dict(end, moe_picks=10 * 6 * 512,
                               moe_picks_held=10 * 6 * 32, decode_steps=10))
    assert harness.load_reader("window_cache_saved_pct")(ctx) == \
        pytest.approx(100 * (1 - (2 * 272 + 5 * 9) / (7 * 272)))    # 69%
    assert harness.load_reader("moe_held_pick_share_pct")(ctx) == 6.25
    flops = cell.family.flops
    assert flops.expert_bytes(dims) == 3 * 4096 * 2048 * 2      # 50.3 MB
    # a page of each pool at its OWN K and V widths: 4 heads (full) or 8
    # (window) of 192 + 128 numbers a row
    assert flops.kv_page_bytes(dims, 16, True) == 16 * (768 + 512) * 2
    assert flops.kv_page_bytes(dims, 16, False) == 16 * (1536 + 1024) * 2
    assert flops.gqa_read_bytes(dims, 100, 10, 16) == \
        2 * 100 * 16 * 1280 * 2 + 5 * 10 * 16 * 2560 * 2
    # a slot's cache as the issue reckons it: 22.3 MB + 3.7 MB
    assert flops.gqa_read_bytes(dims, 272, 9, 16) == pytest.approx(
        22.3e6 + 3.7e6, rel=0.01)


def test_the_configuration_states_its_cut_beside_the_published_counts():
    conf = harness.Cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):         # every catalog key, unchanged
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "MiMo-V2.5")
        assert conf["source"] == row["source_url"]
        assert {k: conf[k] for k in row["config"]} == row["config"]
    assert (conf["num_hidden_layers"], conf["n_routed_experts"],
            conf["vocab_size"]) == (48, 256, 152576)        # as published
    assert (conf["depth"], conf["first_layer"], conf["experts_held"],
            conf["first_expert"], conf["vocab_held"],
            conf["deployment_chips"]) == (7, 0, 16, 0, 19072, 16)
    assert set(conf["reduced"]) == {"depth", "experts_held", "vocab_held"}
    for key in ("assumed", "departures", "deployment"):
        assert conf[key]
    dims = harness.Cell(CELL).family.weights.dims_of(conf, 7)
    assert dims.layer_types == ("full", "sliding", "sliding", "sliding",
                                "sliding", "full", "sliding")
    assert dims.dense_layers == 1
    assert dims.seq_len == 4352 and dims.total_tokens == 19072
    # the floors of the cut: a whole period and four layers after the
    # dense one, at least 8 experts, an eighth of the vocabulary
    assert dims.moe_layers >= 4 and dims.experts_held >= 8
    assert conf["vocab_held"] * 8 >= conf["vocab_size"]
    assert sum(conf["hybrid_layer_pattern"]) == 39      # 9 full of 48


def test_the_weights_are_6_86_gb():
    """The issue's arithmetic, from the shapes of the tree."""
    import jax
    import jax.numpy as jnp

    from benchmark import seeds
    cell = harness.Cell(CELL)
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    shapes = jax.eval_shape(lambda: cell.family.weights.tree(
        seeds.split_seed(0), dims, jnp.bfloat16))
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert nbytes == pytest.approx(6.86e9, rel=0.005)
    tr = shapes["transformer"]
    assert set(tr) == {"dense_full", "moe", "moe_full"}
    assert tr["moe"]["attn"]["k"]["w"].shape == (5, 4096, 8 * 192)
    assert tr["moe_full"]["attn"]["k"]["w"].shape == (1, 4096, 4 * 192)
    assert tr["moe"]["attn"]["v"]["w"].shape == (5, 4096, 8 * 128)
    assert tr["moe"]["attn"]["out"]["w"].shape == (5, 64 * 128, 4096)
    assert tr["moe"]["attn"]["sink"].shape == (5, 64)
    assert "sink" not in tr["moe_full"]["attn"]
    assert tr["moe"]["ff"]["experts"]["w_in"].shape == (5, 16, 4096, 4096)
    assert tr["moe"]["ff"]["router"]["w"].shape == (5, 4096, 256)
