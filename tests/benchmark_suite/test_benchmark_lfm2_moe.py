"""The ``lfm2_moe`` family's cell under the contract's checks and rehearsed
on the CPU at its ``tiny.json`` widths (the published layers 1-9: a dense
short-convolution layer, then full, conv, conv, conv twice over, eight
routed experts top 2, all held, a tied head): a whole run of the
benchmark's own serve driver against the family's plain reference (float32
toy weights, so that the sound program agrees token for token), the
control failing, an altered served token failing, the program's int8 path
refused for this block, the two new readers on fixture scopes, and the
family's byte counts. Limits here are toy-width limits; they say nothing
about a speed. The cell's programs at the published widths are compiled
for a described chip by ``test_benchmark_aot.py``, which finds every cell
of ``BENCHMARK.json`` by name (one file holds the TPU compiler: see the
on-chip-measurement guide) and reads the memory of this cell's decode and
prefill programs there."""

import json
import os
import types

import pytest

from benchmark import harness, serve_cell, tiny

from test_benchmark_contract import (check_cell, check_declared,
                                     check_declared_for_some, check_moves)

CELL = "lfm2-24b-a2b.serve-full"
LIMITS = {"served_logit_gap_max": 1e-4, "served_logit_gap_mean": 1e-7,
          "served_not_best_share": 5e-4}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
NEW_READERS = ("decode_shortconv_ms", "shortconv_step_roofline")
# the block metrics whose readers find something to read in this cell
BLOCK_METRICS = ("decode_moe_experts_ms", "decode_moe_route_ms",
                 "moe_experts_touched_per_layer", "moe_load_max_over_mean",
                 "moe_experts_roofline",
                 # its scopes hold the full layers' projections, the dense
                 # layer, the norms and the tied head
                 "decode_weights_ms") + NEW_READERS
# and those that find nothing: no shared expert, no latent pool, no window
# pool (``gqa_read_roofline`` reads ``window_pages_in_use``), no
# state-space layer, every expert held, and no layer that reads by the
# width rule (both full layers are runs of one under expert stacks)
NOT_HERE = ("decode_moe_shared_ms", "decode_latent_ms",
            "latent_read_roofline", "decode_ssm_ms", "decode_gmu_ms",
            "ssm_step_roofline", "kv_view_columns_read_pct",
            "gqa_read_roofline", "decode_window_view_ms",
            "decode_window_attend_ms", "window_cache_saved_pct",
            "moe_held_pick_share_pct", "moe_rows_computed_pct",
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
        == ("lfm2-24b-a2b", "serve-full", 1)
    conf = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    assert conf["reduced"] == ["depth"] \
        and conf["file"] == "benchmark/configs/lfm2-24b-a2b.json"
    spec = harness.Cell(CELL).spec
    assert (spec["depth"], spec["num_slots"]) == (9, 64)
    assert spec["engine"] == {"kv": "paged", "paged_attn": "gather",
                              "chunk_steps": 8}
    e2e = {m["name"] for m in harness.Cell(CELL).metrics("end_to_end")}
    assert e2e == {"images_per_s", "tpot_ms", "tpot_ms_p95", "setup_s"}


@pytest.mark.parametrize("metric", ["decode_step_device_ms",
                                    "decode_scoped_pct", "decode_sample_ms",
                                    "decode_kv_view_ms", "decode_attend_ms",
                                    "decode_kv_store_ms",
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


@pytest.mark.parametrize("metric, unit, better", [
    ("decode_shortconv_ms", "ms", "lower"),
    ("shortconv_step_roofline", "%", "higher")])
def test_the_new_metrics_are_declared_for_this_cell(metric, unit, better):
    """(Which further cells list them is theirs to say: a later cell with
    short-convolution layers lists them and edits no test.)"""
    check_declared_for_some(
        metric, cells=(CELL,), unit=unit, better=better,
        source="device_trace", layer="decode math", moves="tpot_ms")


# -- the CPU rehearsal of the cell ---------------------------------------------

@pytest.mark.parametrize("case, correct", [
    ("sound", True),
    ("token_altered", False),
    ("reference_fp8", False),
])
def test_tiny_cell_against_the_family_s_reference(tmp_path, listener, case,
                                                  correct):
    cell = _cell(tmp_path)
    assert cell.family.name == "lfm2_moe"
    dims = _dims(cell)
    assert dims.seq_len == 96 and dims.depth == 9 and dims.first_layer == 1
    assert dims.layer_types == ("conv", "full", "conv", "conv", "conv",
                                "full", "conv", "conv", "conv")
    assert (dims.dense_layers, dims.experts, dims.experts_per_token,
            dims.total_tokens) == (1, 8, 2, 75)
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
                                   "phi-4-mini-flash-reasoning.serve-full",
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
    got = {"seconds": {"ssm.scan": 0.8, "ff": 3.0}, "runs": 10,
           "total_s": 3.8}
    monkeypatch.setattr(scopes, "program_seconds", lambda ctx, pat: got)
    assert read(dict(ctx, trace=object())) is None


@pytest.mark.parametrize("reader, want", [
    ("decode_shortconv_ms", 1e3 * (0.3 + 0.1) / 80),
    # 7 x (33.57 MB of weights + 2 x 0.52 MB: 64 slots' tails) at 819 GB/s
    # over 5 ms of scopes a step
    ("shortconv_step_roofline", 100 * 7 * (33.566720e6 + 2 * 0.524288e6)
     / 8.19e11 / (0.4 / 80)),
])
def test_new_readers_on_a_toy_trace(monkeypatch, reader, want):
    """Ten runs of a chunk of 8 steps with 0.3 s under ``conv.proj`` and
    0.1 under ``conv.mix``."""
    from benchmark import scopes
    cell = harness.Cell(CELL)
    got = {"seconds": {"conv.proj": 0.3, "conv.mix": 0.1, "ff": 3.0},
           "runs": 10, "total_s": 3.4}
    monkeypatch.setattr(scopes, "program_seconds", lambda ctx, pat: got)
    ctx = {"kind": "serve", "cell": cell, "dims": _dims(cell),
           "trace": object(), "peaks": {"hbm_bytes_per_s": 8.19e11}}
    assert harness.load_reader(reader)(ctx) == pytest.approx(want, rel=1e-6)


def test_the_family_s_byte_counts_against_hand_counts():
    cell = harness.Cell(CELL)
    dims, flops = _dims(cell), cell.family.flops
    assert (dims.full_layers, dims.conv_layers, dims.moe_layers) == (2, 7, 8)
    assert flops.expert_bytes(dims) == 3 * 2048 * 1536 * 2      # 18.9 MB
    # W_in 2048 x 6144, W_out 2048 x 2048, three taps of 2048, bfloat16
    assert flops.shortconv_layer_weight_bytes(dims) == \
        (2048 * 6144 + 2048 * 2048 + 3 * 2048) * 2 == 33566720
    # two rows of 2048 a slot: 8 kB, where phi's state is 358 kB
    assert flops.shortconv_tail_bytes(dims, 1) == 2 * 2048 * 2 == 8192
    assert flops.shortconv_step_bytes(dims, 64) == 7 * (
        33566720 + 2 * 64 * 8192)
    assert flops.shortconv_step_bytes(dims, 64, 4) == 2 * \
        flops.shortconv_step_bytes(dims, 64)
    # the counters the routed readers read
    s0 = {"moe_experts_touched": 0, "moe_picks": 0, "moe_load_max": 0,
          "decode_steps": 0}
    s1 = {"moe_experts_touched": 10 * 8 * 63, "moe_picks": 10 * 8 * 256,
          "moe_load_max": 10 * 8 * 9, "decode_steps": 10}
    ctx = {"kind": "serve", "cell": cell, "dims": dims, "trace": None,
           "stats0": s0, "stats1": s1}
    assert harness.load_reader("moe_experts_touched_per_layer")(ctx) == 63
    assert harness.load_reader("moe_load_max_over_mean")(ctx) == \
        pytest.approx(9 * 64 / 256)


def test_the_configuration_states_its_cut_beside_the_published_counts():
    conf = harness.Cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):         # every catalog key, unchanged
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "LFM2-24B-A2B")
        assert conf["source"] == row["source_url"]
        assert {k: conf[k] for k in row["config"]} == row["config"]
    assert (conf["num_hidden_layers"], conf["num_dense_layers"],
            conf["num_experts"], conf["num_experts_per_tok"],
            conf["conv_L_cache"], conf["vocab_size"],
            len(conf["layer_types"])) == (40, 2, 64, 4, 3, 65536, 40)
    assert [i for i, t in enumerate(conf["layer_types"])
            if t == "full_attention"] == list(range(2, 40, 4))
    assert (conf["depth"], conf["first_layer"]) == (9, 1)
    assert set(conf["reduced"]) == {"depth"}
    for key in ("tie_word_embeddings", "head_dim", "short_conv", "qk_norm",
                "rope", "two_pre_norms", "router", "route_eps",
                "router_bias_std", "qk_norm_gain", "embedding_std",
                "initialisers", "text_seq_len", "image_grid",
                "num_image_tokens", "param_dtype"):
        assert key in conf["assumed"], key
    for key in ("departures", "deployment"):
        assert conf[key]
    dims = _dims()
    assert dims.layer_types == ("conv", "full", "conv", "conv", "conv",
                                "full", "conv", "conv", "conv")
    assert dims.dense_layers == 1
    assert dims.seq_len == 4352 and dims.total_tokens == 65536
    # the floors of the cut: whole periods and at least four layers after
    # the dense one; every expert and every row of the vocabulary held
    assert dims.moe_layers == 8 and dims.experts == conf["num_experts"]
    assert dims.total_tokens == conf["vocab_size"]


def test_the_weights_are_10_36_gb():
    """The issue's arithmetic, from the shapes of the tree."""
    import jax
    import jax.numpy as jnp

    from benchmark import seeds
    cell = harness.Cell(CELL)
    dims = _dims(cell)
    shapes = jax.eval_shape(lambda: cell.family.weights.tree(
        seeds.split_seed(0), dims, jnp.bfloat16))
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert nbytes == pytest.approx(10.36e9, rel=0.002)
    tr = shapes["transformer"]
    assert set(tr) == {"dense", "moe_full", "moe"}
    assert tr["dense"]["attn"]["in"]["w"].shape == (1, 2048, 3 * 2048)
    assert tr["dense"]["ff"]["w_in"].shape == (1, 2048, 2 * 11776)
    assert tr["moe"]["attn"]["conv"]["w"].shape == (6, 3, 2048)
    assert tr["moe"]["attn"]["out"]["w"].shape == (6, 2048, 2048)
    assert tr["moe_full"]["attn"]["q"]["w"].shape == (2, 2048, 32 * 64)
    assert tr["moe_full"]["attn"]["k"]["w"].shape == (2, 2048, 8 * 64)
    assert tr["moe_full"]["attn"]["q_ln"]["g"].shape == (2, 64)
    assert tr["moe"]["ff"]["experts"]["w_in"].shape == (6, 64, 2048, 3072)
    assert tr["moe_full"]["ff"]["experts"]["w_out"].shape \
        == (2, 64, 1536, 2048)
    assert tr["moe"]["ff"]["router"]["w"].shape == (6, 2048, 64)
    assert tr["moe"]["ff"]["router"]["bias"].dtype == jnp.float32
    # tied: the embedding rows are the head's, and there is no projection
    assert set(shapes["to_logits"]) == {"ln"}
    assert sum(shapes[n]["w"].shape[0] for n in
               ("text_emb", "image_emb", "eos_emb")) == 65536
