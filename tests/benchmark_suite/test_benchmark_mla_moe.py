"""The ``mla_moe`` family's cell rehearsed on the CPU at its ``tiny.json``
widths: a whole run of the benchmark's own serve driver against the
family's plain reference (float32 toy weights, so that the sound program
agrees token for token), the control failing, and the program's int8 path
refused for this block. Limits here are toy-width limits; they say nothing
about a speed."""

import json
import os
import types

import pytest

from benchmark import harness, serve_cell, tiny

CELL = "kanana-2-30b-a3b.serve-full"
LIMITS = {"served_logit_gap_max": 1e-4, "served_logit_gap_mean": 1e-7,
          "served_not_best_share": 5e-4}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
NEW_READERS = ("decode_moe_experts_ms", "decode_moe_route_ms",
               "decode_moe_shared_ms", "decode_latent_ms",
               "moe_experts_touched_per_layer", "moe_load_max_over_mean",
               "moe_experts_roofline", "latent_read_roofline")


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
    assert cell.family.name == "mla_moe"
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
def test_new_readers_return_nothing_where_there_is_nothing_to_read(reader):
    """On a program without the block's scopes or counters (the parent of
    the PR that added them, another family's cell) a reader gives None
    and does not raise."""
    read = harness.load_reader(reader)
    other = harness.Cell("rudalle-xl.serve-full")
    dims = other.family.weights.dims_of(other.config, 2)
    ctx = {"kind": "serve", "cell": other, "dims": dims, "trace": None,
           "stats0": {"decode_steps": 0}, "stats1": {"decode_steps": 80},
           "peaks": {"hbm_bytes_per_s": 8.19e11}}
    assert read(ctx) is None
    assert read(dict(ctx, kind="train")) is None


def test_counter_readers_read_the_engine_s_counters():
    cell = harness.Cell(CELL)
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    ctx = {"kind": "serve", "cell": cell, "dims": dims, "trace": None,
           "stats0": {"decode_steps": 0, "moe_picks": 0,
                      "moe_experts_touched": 0, "moe_load_max": 0},
           "stats1": {"decode_steps": 10, "moe_picks": 10 * 6 * 192,
                      "moe_experts_touched": 10 * 6 * 100,
                      "moe_load_max": 10 * 6 * 6}}
    assert harness.load_reader("moe_experts_touched_per_layer")(ctx) == 100
    assert harness.load_reader("moe_load_max_over_mean")(ctx) == \
        pytest.approx(6 / (192 / 128))
    assert cell.family.flops.expert_bytes(dims) == 3 * 2048 * 768 * 2
    assert cell.family.flops.latent_row_bytes(dims) == 576 * 2
