"""``correct`` at toy widths on the CPU: the system against the plain
reference for both configurations, and the same comparison failing for
the lower precision and for a timed path that is broken underneath.

The limits of the real cells are set from chip readings (PERF.md); the
toy cells here carry limits read the same way at toy widths: above what
sound runs give, below what the control gives. They say nothing about a
speed.
"""

import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, reference, serve_cell, tiny, train_cell
from benchmark import weights as W

# toy-width limits. Train (bfloat16 toy weights): sound runs read 4e-5,
# 7e-3 and 7e-3; the broken paths read 1.0 on a norm or 5e-3 on the loss.
# Serve (float32 toy weights, every request that ends in the window
# compared): the sound program agrees with the reference token for token
# (0, or one near-tie under 1e-6 = a share of 3e-4); int8 puts other
# tokens first (share over 1e-3, gaps of 1e-3 to 1e-2). That separation
# holds for a float32 program only. At the cells' own type, bfloat16, the
# program's int8 path reads inside the sound runs' range on the chip
# (PERF.md section 2 and section 6 item 3), so these cases prove the
# wiring of the check and of ``--control program_int8``, NOT that the
# cells' limits tell int8 from bfloat16: they do not.
TRAIN_LIMITS = {"loss_rel_gap": 1e-3, "grad_norm_worst_leaf_gap": 0.05,
                "change_norm_worst_leaf_gap": 0.25}
SERVE_LIMITS = {"served_logit_gap_max": 1e-4, "served_logit_gap_mean": 1e-7,
                "served_not_best_share": 5e-4}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="module")
def listener():
    return harness.CompileListener()


def _cell(tmp_path, name, limits, dtype="bfloat16"):
    root = tiny.make(str(tmp_path), dtype=dtype)
    path = os.path.join(root, "benchmark", "cells", name + ".json")
    spec = harness.load_json(path)
    spec["limits"] = limits
    json.dump(spec, open(path, "w"))
    harness.OUT_DIR = os.path.join(root, "benchmark_out")
    return harness.Cell(name, root=root)


def _args(**kw):
    base = dict(seed=2 ** 31 + 5, seconds=1.0, trace=0, control="none",
                broken="", sync_every_step=0, more_seeds=0)
    base.update(kw)
    return types.SimpleNamespace(**base)


def _line(driver, cell, listener, **kw):
    return json.loads(driver.run(cell, _args(**kw), dict(DEVICE), listener))


@pytest.mark.parametrize("case, correct", [
    ("sound", True),
    ("state_unchanged", False),         # a step that returns its state
    ("batch_part_left_out", False),     # half the rows never trained on
])
def test_train_cell_against_the_reference(tmp_path, listener, case, correct):
    cell = _cell(tmp_path, "dalle-12b.train", TRAIN_LIMITS)
    broken = "" if case == "sound" else case
    out = _line(train_cell, cell, listener, broken=broken)
    assert out["correct"] is correct
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    readings = harness.load_json(os.path.join(
        harness.OUT_DIR, f"{cell.name}.seed{2 ** 31 + 5}.trace0.json"))
    assert len(readings["window_readings_s"]) >= 1
    assert readings["median_of_readings_tokens_per_s"] > 0
    # the end-to-end rate is every token over the whole window
    n = len(readings["window_readings_s"])
    assert out["metrics"]["train_tokens_per_s"]["value"] == pytest.approx(
        n * readings["tokens_per_reading"]
        / sum(readings["window_readings_s"]))


def test_train_reference_in_fp8_is_not_correct(tmp_path, listener):
    """The control: the reference itself with every matmul operand (and
    the gradient through it) rounded to float8_e4m3fn must fail one of the
    cell's numbers: unscaled fp8 loses the small gradients outright."""
    cell = _cell(tmp_path, "dalle-12b.train", TRAIN_LIMITS)
    out = _line(train_cell, cell, listener, control="reference_fp8")
    assert out["correct"] is False


@pytest.mark.parametrize("name", ["rudalle-xl.serve-full",
                                  "dalle-12b.serve-full"])
@pytest.mark.parametrize("case, correct", [
    ("sound", True),
    ("token_altered", False),           # a token changed where delivered
    ("float32_program_in_int8", False),  # int8 weights and int8 KV pages
    ("drain_cut_short", False),         # in-flight requests never end
])
def test_serve_cell_against_the_reference(tmp_path, listener, name, case,
                                          correct):
    # float32 toy weights: the sound program then agrees with the float32
    # reference token for token, and int8 is the lower precision
    cell = _cell(tmp_path, name, SERVE_LIMITS, dtype="float32")
    kw = {"sound": {}, "token_altered": {"broken": "token_altered"},
          "float32_program_in_int8": {"control": "program_int8"},
          "drain_cut_short": {}}[case]
    if case == "drain_cut_short":
        cell.spec["drain_timeout_s"] = 0.0
    out = _line(serve_cell, cell, listener, seconds=2.0, **kw)
    assert out["correct"] is correct
    assert out["attempted"] > 0
    # a request whose tokens the window counted and that does not end
    # ``ok`` with its whole stream is failed, whatever the reference says
    assert (out["failed"] > 0) is (case == "drain_cut_short")
    assert set(out["metrics"]) == {"images_per_s", "tpot_ms", "tpot_ms_p95",
                                   "setup_s"}
    readings = harness.load_json(os.path.join(
        harness.OUT_DIR, f"{cell.name}.seed{2 ** 31 + 5}.trace0.json"))
    assert readings["requests_attempted"] == out["attempted"] \
        > readings["requests_ended"] > 0
    # all the tokens over all the time, never above the median harvest by
    # more than rounding (the toy's harvests are too uneven to say more)
    assert readings["whole_window_tokens_per_s"] > 0


def test_reference_matches_the_program_in_float32():
    """The reference is the repository's block: in float32 both give the
    same logits, loss and gradient, sparse and dense layers alike."""
    import jax
    import jax.numpy as jnp

    from benchmark import build
    from dalle_pytorch_tpu.models import dalle as D
    d = W.Dims(dim=32, depth=2, heads=2, dim_head=16, ff_mult=4,
               text_seq_len=32, image_grid=8, num_text_tokens=50,
               num_image_tokens=24, pattern=("sparse", "dense"))
    p = build.init_fn(d, "float32")(W.split_seed(5))
    cfg = build.dalle_config({}, d, {"sparse_impl": "windowed"})
    rng = np.random.default_rng(0)
    text = rng.integers(1, 50, (2, 32))
    image = rng.integers(0, 24, (2, 64))
    got = np.asarray(D.dalle_apply(p, jnp.asarray(text), jnp.asarray(image),
                                   cfg=cfg))[:, :-1]
    ref = np.asarray(reference.served_logits(
        5, d, jnp.dtype("float32"), np.concatenate([text, image], 1)))
    fin = np.isfinite(ref)
    assert (got[~fin] < -1e30).all()
    assert np.abs(got[fin] - ref[fin]).max() < 1e-5

    def loss(pp):
        return D.dalle_apply(pp, jnp.asarray(text), jnp.asarray(image),
                             cfg=cfg, return_loss=True, train=True,
                             mask=jnp.ones((2, 32), bool))

    val, grads = jax.value_and_grad(loss)(p)
    two = [{"text": text, "image": image}] * 2
    out = reference.train_two_steps(5, d, jnp.dtype("float32"), two, 1e-3)
    assert abs(float(val) - out["loss"][0]) < 1e-5
    norms = jax.tree.map(lambda g: float(jnp.sqrt(jnp.sum(g * g))), grads)
    assert train_cell.worst_leaf_gap(norms, out["grad_norm"]) < 1e-4
    assert out["loss"][1] < out["loss"][0]      # the update was applied


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    assert train_cell.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 2e-9}, ref) \
        == pytest.approx(0.1)
    assert train_cell.worst_leaf_gap({"a": 0.0, "b": 0.0, "c": 0.0}, ref) \
        == pytest.approx(1.0)


def test_train_cell_over_a_mesh_with_sharded_state(tmp_path, listener):
    """The harness's sharded path (a cell file with ``mesh`` and
    ``param_axes``, as the four-chip cell of PERF.md's Open questions will
    bring), on four of the suite's virtual CPU devices: a cell added as
    files, the same checks, parameters really sharded."""
    import jax
    cell = _cell(tmp_path, "dalle-12b.train", TRAIN_LIMITS)
    here = os.path.join(cell.root, "benchmark")
    spec = dict(cell.spec, mesh={"tp": 2, "fsdp": 2},
                param_axes={"tp": "tp", "fsdp": "fsdp"}, batch_axis="fsdp")
    json.dump(spec, open(os.path.join(here, "cells", "tiny.train-mesh4.json"),
                         "w"))
    json.dump(cell.traffic, open(os.path.join(here, "traffic",
                                              "train-mesh4.json"), "w"))
    bench = harness.load_benchmark(cell.root)
    bench["workloads"].append({"name": "tiny.train-mesh4",
                               "config": "dalle-12b",
                               "traffic": "train-mesh4", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "dalle-12b.train" in m.get("workloads", []):
            m["workloads"].append("tiny.train-mesh4")
    json.dump(bench, open(os.path.join(cell.root, "BENCHMARK.json"), "w"))
    mesh_cell = harness.Cell("tiny.train-mesh4", root=cell.root)
    trainer = train_cell.Trainer(mesh_cell, 7)
    w1 = trainer.params["transformer"]["ff"]["w1"]["w"]
    assert trainer.rows == 8 and len(jax.devices()) >= 4
    assert w1.addressable_shards[0].data.size * 4 == w1.size
    trainer.free()
    out = _line(train_cell, mesh_cell, listener)
    assert out["correct"] is True
    assert _line(train_cell, mesh_cell, listener,
                 broken="state_unchanged")["correct"] is False
