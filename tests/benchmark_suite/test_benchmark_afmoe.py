"""The ``afmoe`` family's cell rehearsed on the CPU at its ``tiny.json``
widths (a window of two pages in a sequence of six: the ring turns): a
whole run of the benchmark's own serve driver against the family's plain
reference (float32 toy weights, so that the sound program agrees token
for token), the control failing, an altered served token failing, the
program's int8 path refused for this block, and the new readers. Limits
here are toy-width limits; they say nothing about a speed."""

import json
import os
import types

import pytest

from benchmark import harness, serve_cell, tiny

CELL = "trinity-large-preview.serve-full"
LIMITS = {"served_logit_gap_max": 1e-4, "served_logit_gap_mean": 1e-7,
          "served_not_best_share": 5e-4}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
NEW_READERS = ("decode_window_view_ms", "decode_window_attend_ms",
               "window_cache_saved_pct", "moe_held_pick_share_pct",
               "gqa_read_roofline")


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


@pytest.mark.parametrize("case, correct", [
    ("sound", True),
    ("token_altered", False),
    ("reference_fp8", False),
])
def test_tiny_cell_against_the_family_s_reference(tmp_path, listener, case,
                                                  correct):
    cell = _cell(tmp_path)
    assert cell.family.name == "afmoe"
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    # the toy wraps its window: two pages of window in six of sequence
    assert dims.window == 32 and dims.seq_len == 96
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


@pytest.mark.parametrize("reader", NEW_READERS)
@pytest.mark.parametrize("other", ["rudalle-xl.serve-full",
                                   "kanana-2-30b-a3b.serve-full"])
def test_new_readers_return_nothing_where_there_is_nothing_to_read(reader,
                                                                   other):
    """On a program without the block's scopes or counters (the parent of
    the PR that added them, another family's cell) a reader gives None
    and does not raise."""
    read = harness.load_reader(reader)
    other = harness.Cell(other)
    dims = other.family.weights.dims_of(other.config, 2)
    ctx = {"kind": "serve", "cell": other, "dims": dims, "trace": None,
           "stats0": {"decode_steps": 0, "pages_in_use": 3, "moe_picks": 0},
           "stats1": {"decode_steps": 80, "pages_in_use": 5,
                      "moe_picks": 80, "page_size": 16},
           "peaks": {"hbm_bytes_per_s": 8.19e11}}
    assert read(ctx) is None
    assert read(dict(ctx, kind="train")) is None


def test_counter_readers_read_the_engine_s_counters():
    cell = harness.Cell(CELL)
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    assert (dims.full_layers, dims.window_layers, dims.moe_layers) == (1, 4,
                                                                      4)
    # 16 slots at their sequences' end: 592 pages of the full layer, 257
    # of each window layer
    end = {"layer_pages_in_use": 16 * (592 + 4 * 257),
           "layer_pages_all_full": 16 * 5 * 592, "moe_picks": 0,
           "moe_picks_held": 0, "decode_steps": 0}
    ctx = {"kind": "serve", "cell": cell, "dims": dims, "trace": None,
           "stats0": end,
           "stats1": dict(end, moe_picks=10 * 4 * 64, moe_picks_held=10 * 4
                          * 8, decode_steps=10)}
    assert harness.load_reader("window_cache_saved_pct")(ctx) == \
        pytest.approx(100 * (1 - (592 + 4 * 257) / (5 * 592)))     # 45%
    assert harness.load_reader("moe_held_pick_share_pct")(ctx) == 12.5
    flops = cell.family.flops
    assert flops.expert_bytes(dims) == 3 * 3072 * 3072 * 2
    assert flops.kv_page_bytes(dims, 16) == 2 * 8 * 16 * 128 * 2
    assert flops.gqa_read_bytes(dims, 100, 10, 16) == \
        flops.kv_page_bytes(dims, 16) * (100 + 4 * 10)


def test_the_configuration_states_its_cut_beside_the_published_counts():
    conf = harness.Cell(CELL).config
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (60, 256, 200192)        # as published
    assert (conf["depth"], conf["first_layer"], conf["experts_held"],
            conf["first_expert"], conf["vocab_held"],
            conf["deployment_chips"]) == (5, 5, 32, 0, 25024, 8)
    assert set(conf["reduced"]) == {"depth", "experts_held", "vocab_held"}
    for key in ("assumed", "departures", "deployment"):
        assert conf[key]
    dims = harness.Cell(CELL).family.weights.dims_of(conf, 5)
    assert dims.layer_types == ("sliding", "sliding", "full", "sliding",
                                "sliding") and dims.dense_layers == 1
    assert dims.seq_len == 9472 and dims.total_tokens == 25024
    # the floors of the cut: a whole period and four layers after the
    # dense one, at least 8 experts, an eighth of the vocabulary
    assert dims.moe_layers >= 4 and dims.experts_held >= 8
    assert conf["vocab_held"] * 8 >= conf["vocab_size"]
