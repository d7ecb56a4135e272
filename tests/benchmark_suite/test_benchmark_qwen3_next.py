"""The ``qwen3_next`` family's cell under the contract's checks and
rehearsed on the CPU at its ``tiny.json`` widths (the published layers 0-7:
delta, delta, delta, full twice over, sixteen routed experts top 2 by
softmax of which four are held, a gated shared expert, an untied head): a
whole run of the benchmark's own serve driver against the family's plain
reference (float32 toy weights, so that the sound program agrees token for
token but for float32 near-ties), the control failing, an altered served
token failing, the program's int8 path refused for this block, the two new
readers on a recorded trace, and the family's byte counts. Limits here are
toy-width limits; they say nothing about a speed. The cell's programs at
the published widths are compiled for a described chip by
``test_benchmark_aot.py``, which finds every cell of ``BENCHMARK.json`` by
name (one file holds the TPU compiler: see the on-chip-measurement
guide)."""

import json
import os
import types

import pytest

from benchmark import harness, serve_cell, tiny

from test_benchmark_contract import (check_cell, check_declared,
                                     check_declared_for_some, check_moves)

CELL = "qwen3-next-80b-a3b.serve-full"
CONFIG = "qwen3-next-80b-a3b"
# float32 against float32: a served token that is not the reference's best
# is a near-tie of two orders of one float32 sum (1e-4 of logits spread
# 0.6: tests/test_qwen3_next_block.py); fp8 reads 0.1 and an altered
# token 1
LIMITS = {"served_logit_gap_max": 2e-3, "served_logit_gap_mean": 2e-6,
          "served_not_best_share": 5e-3}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
NEW_READERS = ("decode_delta_ms", "delta_step_roofline")
# the block metrics whose readers find something to read in this cell
BLOCK_METRICS = ("decode_moe_experts_ms", "decode_moe_route_ms",
                 "decode_moe_shared_ms", "moe_experts_touched_per_layer",
                 "moe_load_max_over_mean", "moe_experts_roofline",
                 "moe_expert_rereads_pct", "moe_held_pick_share_pct",
                 "moe_rows_computed_pct",
                 # its scopes hold the full layers' projections, the norms
                 # and the untied head
                 "decode_weights_ms") + NEW_READERS
# and those that find nothing: no latent pool, no window pool
# (``gqa_read_roofline`` reads ``window_pages_in_use``), no state-space
# layer, no short convolution, no sink, and no layer that reads by the
# width rule (both full layers are runs of one under expert stacks)
NOT_HERE = ("decode_latent_ms", "latent_read_roofline", "decode_ssm_ms",
            "decode_gmu_ms", "ssm_step_roofline", "decode_shortconv_ms",
            "shortconv_step_roofline", "kv_view_columns_read_pct",
            "gqa_read_roofline", "decode_window_view_ms",
            "decode_window_attend_ms", "window_cache_saved_pct",
            "window_sink_mass_pct")


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


def _dims(cell=None):
    cell = cell or harness.Cell(CELL)
    return cell.family.weights.dims_of(cell.config, cell.spec["depth"])


# -- the declarations, by name -------------------------------------------------

def test_the_cell_passes_the_contract_s_checks():
    check_cell(CELL)
    bench = harness.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "serve-full", 1)
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["depth", "experts_held", "vocab_held"] \
        and conf["file"] == f"benchmark/configs/{CONFIG}.json"
    spec = harness.Cell(CELL).spec
    # 48 slots: the issue's fallback (the 64-row wave's prefill needs 17.1
    # GB of the chip's 15.75 by AOT: PERF.md section 4)
    assert (spec["depth"], spec["num_slots"]) == (8, 48)
    assert spec["engine"] == {"kv": "paged", "paged_attn": "gather",
                              "chunk_steps": 8}
    e2e = {m["name"] for m in harness.Cell(CELL).metrics("end_to_end")}
    assert e2e == {"images_per_s", "tpot_ms", "tpot_ms_p95", "setup_s"}
    # nine cells, none on four chips
    assert len(bench["workloads"]) >= 9 and not any(
        w["chips"] == 4 for w in bench["workloads"])


@pytest.mark.parametrize("metric", ["decode_step_device_ms",
                                    "decode_scoped_pct", "decode_sample_ms",
                                    "decode_kv_view_ms", "decode_attend_ms",
                                    "decode_kv_store_ms",
                                    "compiles_in_window.serve",
                                    "chunk_interval_ms", "loop_stall_ms",
                                    "prefill_device_ms",
                                    "admit_stream_stall_ms"])
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


@pytest.mark.parametrize("metric, unit, better", [
    ("decode_delta_ms", "ms", "lower"),
    ("delta_step_roofline", "%", "higher")])
def test_the_new_metrics_are_declared_for_this_cell(metric, unit, better):
    """(Which further cells list them is theirs to say: a later cell with
    delta-rule layers lists them and edits no test.)"""
    m = check_declared_for_some(
        metric, cells=(CELL,), unit=unit, better=better,
        source="device_trace", layer="decode math", moves="tpot_ms")
    # appended: behind every entry that was there
    names = [x["name"] for x in harness.load_benchmark()["per_layer"]]
    assert names.index(m["name"]) > names.index("moe_expert_rereads_pct")


# -- the CPU rehearsal of the cell ---------------------------------------------

@pytest.mark.parametrize("case, correct", [
    ("sound", True),
    ("token_altered", False),
    ("reference_fp8", False),
])
def test_tiny_cell_against_the_family_s_reference(tmp_path, listener, case,
                                                  correct):
    cell = _cell(tmp_path)
    assert cell.family.name == "qwen3_next"
    dims = _dims(cell)
    assert dims.seq_len == 96 and dims.depth == 8 and dims.first_layer == 0
    assert dims.layer_types == ("delta", "delta", "delta", "full") * 2
    assert (dims.experts, dims.experts_held, dims.experts_per_token,
            dims.total_tokens) == (16, 4, 2, 75)
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

@pytest.mark.parametrize("reader", NEW_READERS)
@pytest.mark.parametrize("other", ["rudalle-xl.serve-full",
                                   "lfm2-24b-a2b.serve-full",
                                   "dalle-12b.train"])
def test_new_readers_return_nothing_where_there_is_nothing_to_read(
        monkeypatch, reader, other):
    """On a program without the block's scopes (the parent of the PR that
    added them, another family's cell) and for a train cell a reader gives
    None and does not raise."""
    from benchmark import scopes
    read = harness.load_reader(reader)
    other = harness.Cell(other)
    ctx = {"kind": other.kind, "cell": other, "dims": _dims(other),
           "trace": None, "stats0": {"decode_steps": 0},
           "stats1": {"decode_steps": 80},
           "peaks": {"hbm_bytes_per_s": 8.19e11}}
    assert read(ctx) is None
    assert read(dict(ctx, kind="train")) is None
    # a traced run of a program that has other scopes and not these
    got = {"seconds": {"conv.mix": 0.8, "ff": 3.0}, "runs": 10,
           "total_s": 3.8}
    monkeypatch.setattr(scopes, "program_seconds", lambda ctx, pat: got)
    assert read(dict(ctx, trace=object())) is None


@pytest.mark.parametrize("reader, want", [
    ("decode_delta_ms", 1e3 * (0.3 + 0.5) / 80),
    # 6 x (67.4 MB of weights + 2 x 103.0 MB: 48 slots' states and tails)
    # at 819 GB/s over 10 ms of scopes a step
    ("delta_step_roofline", 100 * 6 * (67.437056e6 + 2 * 103.022592e6)
     / 8.19e11 / (0.8 / 80)),
])
def test_new_readers_on_a_recorded_trace(monkeypatch, reader, want):
    """Ten runs of a chunk of 8 steps with 0.3 s under ``delta.proj`` and
    0.5 under ``delta.rule``."""
    from benchmark import scopes
    cell = harness.Cell(CELL)
    got = {"seconds": {"delta.proj": 0.3, "delta.rule": 0.5, "ff": 3.0},
           "runs": 10, "total_s": 3.8}
    monkeypatch.setattr(scopes, "program_seconds", lambda ctx, pat: got)
    ctx = {"kind": "serve", "cell": cell, "dims": _dims(cell),
           "trace": object(), "peaks": {"hbm_bytes_per_s": 8.19e11}}
    assert harness.load_reader(reader)(ctx) == pytest.approx(want, rel=1e-6)
    assert harness.load_reader("delta_step_roofline")(ctx) < 100


def test_the_family_s_byte_counts_against_hand_counts():
    cell = harness.Cell(CELL)
    dims, flops = _dims(cell), cell.family.flops
    assert (dims.full_layers, dims.delta_layers, dims.moe_layers) == (2, 6, 8)
    assert flops.expert_bytes(dims) == 3 * 2048 * 512 * 2       # 6.3 MB
    # W_in 2048 x 12288, W_ba 2048 x 64, W_out 4096 x 2048, four taps of
    # 8192, the gated norm's 128, bfloat16; A_log and dt_bias float32
    assert flops.delta_layer_weight_bytes(dims) == (
        2048 * 12288 + 2048 * 64 + 4096 * 2048 + 4 * 8192 + 128) * 2 \
        + 2 * 32 * 4 == 67437056
    # 32 x 128 x 128 float32 and three rows of 8192 bfloat16 a slot:
    # 2.15 MB a layer, where lfm2's tail is 8 kB and phi's state 358 kB
    assert flops.delta_state_bytes(dims, 1) \
        == 32 * 128 * 128 * 4 + 3 * 8192 * 2 == 2146304
    assert flops.delta_state_bytes(dims, 48) == 103022592
    assert flops.delta_step_bytes(dims, 48) == 6 * (
        67437056 + 2 * 48 * 2146304)                            # 1.64 GB
    # the issue's arithmetic at 64 slots: 0.40 GB + 1.65 GB
    assert 6 * 67437056 == pytest.approx(0.40e9, rel=0.02)
    assert flops.delta_step_bytes(dims, 64) - 6 * 67437056 \
        == pytest.approx(1.65e9, rel=0.01)
    # the counters the routed readers read
    s0 = {"moe_experts_touched": 0, "moe_picks": 0, "moe_picks_held": 0,
          "moe_load_max": 0, "decode_steps": 0}
    s1 = {"moe_experts_touched": 10 * 8 * 78, "moe_picks": 10 * 8 * 480,
          "moe_picks_held": 10 * 8 * 120, "moe_load_max": 10 * 8 * 5,
          "decode_steps": 10}
    ctx = {"kind": "serve", "cell": cell, "dims": dims, "trace": None,
           "stats0": s0, "stats1": s1}
    assert harness.load_reader("moe_experts_touched_per_layer")(ctx) == 78
    assert harness.load_reader("moe_held_pick_share_pct")(ctx) == 25.0


def test_the_configuration_states_its_cut_beside_the_published_counts():
    conf = harness.Cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):         # every catalog key, unchanged
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert conf["source"] == row["source_url"]
        assert {k: conf[k] for k in row["config"]} == row["config"]
    # every published width
    assert (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            conf["linear_num_key_heads"], conf["linear_num_value_heads"],
            conf["linear_key_head_dim"], conf["linear_value_head_dim"],
            conf["linear_conv_kernel_dim"], conf["moe_intermediate_size"],
            conf["num_experts_per_tok"], conf["num_experts"],
            conf["shared_expert_intermediate_size"],
            conf["partial_rotary_factor"], conf["rope_theta"]) == (
        2048, 16, 2, 256, 16, 32, 128, 128, 4, 512, 10, 512, 512, 0.25, 1e7)
    assert (conf["num_hidden_layers"], conf["full_attention_interval"],
            conf["vocab_size"]) == (48, 4, 151936)
    assert (conf["depth"], conf["first_layer"], conf["experts_held"],
            conf["first_expert"], conf["vocab_held"]) == (8, 0, 128, 0, 37984)
    assert set(conf["reduced"]) == {"depth", "experts_held", "vocab_held"}
    for key in ("projections", "convolution", "l2_norms",
                "decay_and_write_strength", "A_log_and_dt_bias",
                "gated_norm", "zero_centred_norms", "gate_in_q_proj",
                "query_key_norms", "rope", "router", "shared_expert_gate",
                "text_seq_len", "image_grid", "num_image_tokens",
                "param_dtype", "qk_norm_gain", "embedding_std",
                "initialisers"):
        assert key in conf["assumed"], key
    assert any("multi-token-prediction" in d for d in conf["departures"])
    assert "4 chips" in conf["deployment"] \
        and "six hosts" in conf["deployment"]
    dims = _dims()
    assert dims.layer_types == ("delta", "delta", "delta", "full") * 2
    assert dims.seq_len == 4352 and dims.total_tokens == 37984
    # the floors of the cut: whole periods, at least four layers, at
    # least 8 experts a layer, at least an eighth of the vocabulary
    assert dims.depth % conf["full_attention_interval"] == 0
    assert dims.experts_held >= 8 and dims.experts == conf["num_experts"]
    assert 8 * dims.total_tokens >= conf["vocab_size"]


def test_the_weights_are_7_33_gb():
    """The issue's arithmetic, from the shapes of the tree."""
    import jax
    import jax.numpy as jnp

    from benchmark import seeds
    cell = harness.Cell(CELL)
    dims = _dims(cell)
    shapes = jax.eval_shape(lambda: cell.family.weights.tree(
        seeds.split_seed(0), dims, jnp.bfloat16))
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert nbytes == pytest.approx(7.33e9, rel=0.003)
    tr = shapes["transformer"]
    assert set(tr) == {"moe", "moe_full"}
    assert tr["moe"]["attn"]["in"]["w"].shape == (6, 2048, 12288)
    assert tr["moe"]["attn"]["ba"]["w"].shape == (6, 2048, 64)
    assert tr["moe"]["attn"]["conv"]["w"].shape == (6, 4, 8192)
    assert tr["moe"]["attn"]["out"]["w"].shape == (6, 4096, 2048)
    assert tr["moe"]["attn"]["a_log"].shape == (6, 16, 2)
    assert tr["moe"]["attn"]["a_log"].dtype == jnp.float32
    assert tr["moe"]["attn"]["norm"]["g"].shape == (6, 128)
    assert tr["moe_full"]["attn"]["q"]["w"].shape == (2, 2048, 16 * 256)
    assert tr["moe_full"]["attn"]["gate"]["w"].shape == (2, 2048, 16 * 256)
    assert tr["moe_full"]["attn"]["k"]["w"].shape == (2, 2048, 2 * 256)
    assert tr["moe_full"]["attn"]["q_ln"]["g"].shape == (2, 256)
    assert tr["moe"]["ff"]["experts"]["w_in"].shape == (6, 128, 2048, 1024)
    assert tr["moe_full"]["ff"]["experts"]["w_out"].shape \
        == (2, 128, 512, 2048)
    assert tr["moe"]["ff"]["router"]["w"].shape == (6, 2048, 512)
    assert set(tr["moe"]["ff"]["router"]) == {"w"}      # no selection bias
    assert tr["moe"]["ff"]["shared"]["w_in"].shape == (6, 2048, 1024)
    assert tr["moe"]["ff"]["shared_gate"]["w"].shape == (6, 2048, 1)
    # untied: a head of its own over the held rows
    assert shapes["to_logits"]["proj"]["w"].shape == (2048, 37984)
    assert sum(shapes[n]["w"].shape[0] for n in ("text_emb", "image_emb")) \
        == 37983
