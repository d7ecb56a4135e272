"""The ``phi4flash`` family's cell rehearsed on the CPU at its
``tiny.json`` widths (12 layers: four state-space, three window, one full,
two cross, two memory units; a window of two pages in a sequence of six):
a whole run of the benchmark's own serve driver against the family's plain
reference (float32 toy weights, so that the sound program agrees token for
token), the control failing, an altered served token failing, the
program's int8 path refused for this block, and the new readers. Limits
here are toy-width limits; they say nothing about a speed. The cell's
programs at the published widths are compiled for a described chip by
``test_benchmark_aot.py``, which finds every cell of ``BENCHMARK.json`` by
name (one file holds the TPU compiler: see the on-chip-measurement
guide)."""

import json
import os
import types

import pytest

from benchmark import harness, serve_cell, tiny

CELL = "phi-4-mini-flash-reasoning.serve-full"
LIMITS = {"served_logit_gap_max": 1e-4, "served_logit_gap_mean": 1e-7,
          "served_not_best_share": 5e-4}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
NEW_READERS = ("decode_ssm_ms", "decode_gmu_ms", "ssm_step_roofline")


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
    assert cell.family.name == "phi4flash"
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    assert dims.window == 32 and dims.seq_len == 96 and dims.depth == 12
    assert dims.mixers.count("ssm") == 4 and dims.mixers.count("cross") == 2
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
                                   "trinity-large-preview.serve-full"])
def test_new_readers_return_nothing_where_there_is_nothing_to_read(reader,
                                                                   other):
    """On a program without the block's scopes (the parent of the PR that
    added them, another family's cell) a reader gives None and does not
    raise."""
    read = harness.load_reader(reader)
    other = harness.Cell(other)
    dims = other.family.weights.dims_of(other.config, other.spec["depth"])
    ctx = {"kind": "serve", "cell": other, "dims": dims, "trace": None,
           "stats0": {"decode_steps": 0}, "stats1": {"decode_steps": 80},
           "peaks": {"hbm_bytes_per_s": 8.19e11}}
    assert read(ctx) is None
    assert read(dict(ctx, kind="train")) is None


class _Trace:
    """A reduced trace that holds what the scope readers ask of one."""

    def __init__(self, seconds, runs):
        self.seconds, self.runs = seconds, runs


@pytest.mark.parametrize("reader, want", [
    ("decode_ssm_ms", 1e3 * (0.8 + 0.4) / 80),
    ("decode_gmu_ms", 1e3 * 0.2 / 80),
    # 9 x (82.66 MB + 2 x 11.47 MB: 32 slots' state) + 7 x 52.43 MB at
    # 819 GB/s over 17.5 ms of scopes a step
    ("ssm_step_roofline", 100 * (9 * (82.65728e6 + 2 * 11.4688e6)
                                 + 7 * 52.4288e6) / 8.19e11
     / (1.4 / 80)),
])
def test_new_readers_on_a_toy_trace(monkeypatch, reader, want):
    """Ten runs of a chunk of 8 steps with 0.8 s under ``ssm.scan``, 0.4
    under ``ssm.proj`` and 0.2 under ``gmu``."""
    from benchmark import scopes
    cell = harness.Cell(CELL)
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    got = {"seconds": {"ssm.scan": 0.8, "ssm.proj": 0.4, "gmu": 0.2,
                       "ff": 3.0}, "runs": 10, "total_s": 4.4}
    monkeypatch.setattr(scopes, "program_seconds", lambda ctx, pat: got)
    ctx = {"kind": "serve", "cell": cell, "dims": dims, "trace": object(),
           "peaks": {"hbm_bytes_per_s": 8.19e11}}
    assert harness.load_reader(reader)(ctx) == pytest.approx(want, rel=1e-6)


def test_counter_readers_read_the_engine_s_counters():
    cell = harness.Cell(CELL)
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    assert (len(dims.layers_of("ssm")), len(dims.layers_of("window")),
            len(dims.layers_of("full")), len(dims.layers_of("cross")),
            len(dims.layers_of("gmu"))) == (9, 8, 1, 7, 7)
    assert (dims.ssm_source, dims.kv_source) == (16, 17)
    # 32 slots at their sequences' end: 272 pages of the one full layer,
    # 33 of each window layer, where 16 unshared unwindowed layers would
    # hold 272 each
    end = {"layer_pages_in_use": 32 * (272 + 8 * 33),
           "layer_pages_all_full": 32 * 16 * 272}
    ctx = {"kind": "serve", "cell": cell, "dims": dims, "trace": None,
           "stats0": end, "stats1": end}
    assert harness.load_reader("window_cache_saved_pct")(ctx) == \
        pytest.approx(100 * (1 - (272 + 8 * 33) / (16 * 272)))     # 87.7%
    flops = cell.family.flops
    assert flops.kv_page_bytes(dims, 16) == 2 * 20 * 16 * 64 * 2
    # the full pool's pages count once for each of its 8 readers
    assert flops.gqa_read_bytes(dims, 100, 10, 16) == \
        flops.kv_page_bytes(dims, 16) * (8 * 100 + 8 * 10)
    assert flops.ssm_layer_weight_bytes(dims) == pytest.approx(82.66e6,
                                                               rel=1e-3)
    assert flops.gmu_layer_weight_bytes(dims) == 2 * 2560 * 5120 * 2
    assert flops.ssm_state_bytes(dims, 32) == 32 * (5120 * 16 * 4
                                                    + 3 * 5120 * 2)


def test_the_configuration_is_whole_and_says_what_it_assumed():
    conf = harness.Cell(CELL).config
    catalog = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
               "intermediate_size": 10240, "layer_norm_eps": 1e-05,
               "max_position_embeddings": 262144, "mb_per_layer": 2,
               "model_type": "phi4flash", "num_attention_heads": 40,
               "num_hidden_layers": 32, "num_key_value_heads": 20,
               "resid_pdrop": 0, "sliding_window": 512,
               "tie_word_embeddings": True, "mlp_bias": False,
               "lm_head_bias": False, "vocab_size": 200064}
    assert {k: conf[k] for k in catalog} == catalog
    assert conf["reduced"] == {}
    for key in ("mamba_expand", "qk_init_gain", "embedding_std",
                "text_seq_len", "image_grid", "num_text_tokens",
                "num_image_tokens", "param_dtype", "no_positions",
                "attention_biases", "lam_init", "gmu_input",
                "initialisers"):
        assert key in conf["assumed"], key
    for key in ("departures", "deployment"):
        assert conf[key]
    dims = harness.Cell(CELL).family.weights.dims_of(conf, 32)
    assert dims.seq_len == 4352 and dims.total_tokens == 200064
    assert (dims.d_inner, dims.d_state, dims.d_conv, dims.dt_rank) == \
        (5120, 16, 4, 160)
    with pytest.raises(ValueError, match="every one of its 32 layers"):
        harness.Cell(CELL).family.weights.dims_of(conf, 8)
