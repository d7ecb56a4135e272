"""BENCHMARK.json and the files its names lead to: the harness is data."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import harness, tiny, traffic
from benchmark import weights as W

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_and_units_use_only_the_allowed_characters(group):
    names = [e["name"] for e in BENCH[group]]
    assert len(names) == len(set(names))
    for e in BENCH[group]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
        for key in ("config", "traffic"):
            if key in e:
                assert NAME.match(e[key])


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files_by_name(name):
    cell = harness.Cell(name)
    assert cell.kind in ("train", "serve")
    dims = W.dims_of(cell.config, cell.spec["depth"])
    assert dims.depth % len(dims.pattern) == 0
    # widths are the published ones whatever the depth
    assert dims.dim == cell.config["heads"] * cell.config["dim_head"] \
        or cell.config["name"] == "rudalle-xl"
    assert cell.metrics("end_to_end") and cell.metrics("per_layer")
    for m in cell.metrics("per_layer"):
        assert callable(harness.load_reader(m["name"]))


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_moves_a_metric_its_cells_report(metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    e2e = {x["name"]: x for x in BENCH["end_to_end"]}
    assert m["moves"] in e2e
    target = e2e[m["moves"]]
    reported_in = set(target.get("workloads", CELLS))
    cells = set(m.get("workloads", reported_in))
    assert cells and cells <= reported_in
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")


def test_config_files_hold_published_widths():
    by_name = {c["name"]: harness.load_json(os.path.join(harness.ROOT,
                                                         c["file"]))
               for c in BENCH["configs"]}
    x, d = by_name["rudalle-xl"], by_name["dalle-12b"]
    assert (x["dim"], x["depth"], x["heads"], x["dim_head"]) == \
        (2048, 24, 16, 128)
    assert (x["text_seq_len"], x["image_seq_len"], x["num_image_tokens"]) \
        == (128, 1024, 8192)
    assert (d["dim"], d["depth"], d["heads"], d["dim_head"]) == \
        (3968, 64, 62, 64)
    assert (d["text_seq_len"], d["num_text_tokens"], d["num_image_tokens"]) \
        == (256, 16384, 8192)
    for c in BENCH["configs"]:
        file = by_name[c["name"]]
        assert sorted(c["reduced"]) == sorted(file["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) or k in
                       ("dim", "heads", "dim_head", "ff_mult")
                       for k in c["reduced"])
        assert file["departures"] and "assumed" in file


def test_a_cell_a_config_and_a_metric_added_as_files_are_found(tmp_path):
    root = tiny.make(str(tmp_path))
    here = os.path.join(root, "benchmark")
    before = {}
    for base, _, files in os.walk(root):
        for f in files:
            p = os.path.join(base, f)
            before[p] = open(p, "rb").read()
    # new files only: a configuration, a traffic mix, a cell, a reader
    conf = harness.load_json(os.path.join(here, "configs", "rudalle-xl.json"))
    conf.update(name="other-xl", dim=48, heads=3)
    json.dump(conf, open(os.path.join(here, "configs", "other-xl.json"), "w"))
    mix = harness.load_json(os.path.join(here, "traffic", "train.json"))
    mix["rows_per_group"] = 2
    json.dump(mix, open(os.path.join(here, "traffic", "train-small.json"),
                        "w"))
    shutil.copy(os.path.join(here, "cells", "dalle-12b.train.json"),
                os.path.join(here, "cells", "other-xl.train-small.json"))
    with open(os.path.join(here, "metrics", "rows_per_step.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['readings']['rows']\n")
    bench = harness.load_benchmark(root)
    bench["configs"].append({"name": "other-xl", "source": "test",
                             "file": "benchmark/configs/other-xl.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "other-xl.train-small",
                               "config": "other-xl",
                               "traffic": "train-small", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "rows_per_step", "unit": "count",
                               "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": "setup_s",
                               "workloads": ["other-xl.train-small"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    cell = harness.Cell("other-xl.train-small", root=root)
    assert cell.config["dim"] == 48 and cell.traffic["rows_per_group"] == 2
    ctx = {"readings": {"rows": 2}, "setup_compile": {"compile_s": 1.0},
           "kind": "train", "compiles_in_window": 0, "trace": None,
           "end_to_end": {}, "chips": 1}
    got = harness.read_per_layer(cell, ctx)
    assert got["rows_per_step"] == {"value": 2.0, "unit": "count"}
    assert "compile_s" in got           # no workloads key: every cell
    for p, data in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert open(p, "rb").read() == data, f"{p} was edited"


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_metric():
    out = _run(["benchmark/run.py", "--workload", CELLS[0], "--seed", "1",
                "--seconds", "1", "--trace", "0"], harness.ROOT)
    assert out.returncode != 0
    assert "metrics" not in out.stdout and "correct" not in out.stdout


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(harness.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    out = _run(["benchmark/run.py", "--workload", CELLS[0], "--seed", "1",
                "--seconds", "1", "--trace", "0"], str(tmp_path))
    assert out.returncode != 0
    assert "metrics" not in out.stdout


def test_traffic_is_a_pure_function_of_the_seed():
    cell = harness.Cell("rudalle-xl.serve-full")
    dims = W.dims_of(cell.config, 2)
    big = 2 ** 31 + 12345
    a = traffic.requests(cell.traffic, big, 40, dims)
    assert a == traffic.requests(cell.traffic, big, 40, dims)
    b = traffic.requests(cell.traffic, big + 1, 40, dims)
    assert a != b
    # every seed: the same multiset of lengths, in another order
    lens = lambda rs: sorted(len(r["codes"]) for r in rs)  # noqa: E731
    assert lens(a) == lens(b)
    assert min(lens(a)) > dims.text_seq_len // 2      # one prefill bucket
    assert max(lens(a)) == dims.text_seq_len
    assert all(1 <= c < dims.num_text_tokens for r in a for c in r["codes"])
    t = harness.Cell("dalle-12b.train")
    x = traffic.train_batch(t.traffic, big, 3, 4, dims)
    y = traffic.train_batch(t.traffic, big, 3, 4, dims)
    assert (x["text"] == y["text"]).all() and (x["image"] == y["image"]).all()
    assert len({r.tobytes() for r in x["image"]}) == 4    # rows all differ
    assert (traffic.train_batch(t.traffic, big, 4, 4, dims)["text"]
            != x["text"]).any()


def test_weights_are_a_pure_function_of_the_seed_and_fit_large_seeds():
    import jax
    import jax.numpy as jnp
    d = W.Dims(dim=16, depth=2, heads=2, dim_head=8, ff_mult=4,
               text_seq_len=8, image_grid=2, num_text_tokens=10,
               num_image_tokens=6, pattern=("sparse", "dense"))
    big = 2 ** 31 + 7
    a = W.tree(W.split_seed(big), d, jnp.bfloat16)
    b = jax.jit(lambda h: W.tree(h, d, jnp.bfloat16))(W.split_seed(big))
    c = W.tree(W.split_seed(7), d, jnp.bfloat16)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["text_emb"]["w"] == c["text_emb"]["w"]).all())
    one = W.layer(W.layer_key(W.seed_key(big), 1), d, jnp.bfloat16)
    assert bool((one["ff"]["w1"]["w"]
                 == a["transformer"]["ff"]["w1"]["w"][1]).all())
