"""BENCHMARK.json and the files its names lead to: the harness is data."""

import json
import os
import re
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

from benchmark import harness, seeds, tiny, traffic

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


# all that ``traffic.py`` and the drivers read of a family's sizes
# (``benchmark/families/README.md``)
SIZES = ("text_seq_len", "image_seq_len", "seq_len", "num_text_tokens",
         "num_image_tokens")


def check_cell(name: str, root: str = harness.ROOT) -> None:
    """What every cell owes the harness, whatever its family: nothing
    here names a block's key, so a cell of a new family passes it as it
    is (``test_benchmark_families.py`` holds a second family's cells to
    it under a toy root)."""
    cell = harness.Cell(name, root=root)
    assert cell.kind in ("train", "serve")
    family = cell.family
    assert family.name == cell.config["family"]
    for module in harness.FAMILY_MODULES:
        assert getattr(family, module).__file__.startswith(family.directory)
    assert isinstance(family.tiny["depth"], int)
    assert jnp.dtype(cell.config["param_dtype"]).itemsize in (1, 2, 4)
    dims = family.weights.dims_of(cell.config, cell.spec["depth"])
    assert dims == family.weights.dims_of(cell.config, cell.spec["depth"])
    hash(dims)                          # a static argument of the reference
    for key in SIZES:
        assert isinstance(getattr(dims, key), int) and getattr(dims, key) > 0
    assert dims.seq_len == dims.text_seq_len + dims.image_seq_len
    assert callable(family.weights.tree)
    assert callable(family.build.program_config)
    assert callable(family.reference.served_gaps if cell.kind == "serve"
                    else family.reference.train_two_steps)
    assert cell.metrics("end_to_end") and cell.metrics("per_layer")
    assert "setup_s" in [m["name"] for m in cell.metrics("end_to_end")]
    for m in cell.metrics("per_layer"):
        assert callable(harness.load_reader(m["name"], root))


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files_by_name(name):
    check_cell(name)


def cells_of_kind(kind: str, root: str = harness.ROOT) -> list:
    """The names of a root's train or serve cells, in its order: a cell's
    kind is its traffic file's, whatever the file is called."""
    return [w["name"] for w in harness.load_benchmark(root)["workloads"]
            if harness.Cell(w["name"], root=root).kind == kind]


def _entry(metric: str, root: str, **keys) -> dict:
    """The one per-layer entry of that NAME, with these keys."""
    found = [m for m in harness.load_benchmark(root)["per_layer"]
             if m["name"] == metric]
    assert len(found) == 1, metric
    for key, value in keys.items():
        assert found[0][key] == value, (metric, key)
    return found[0]


def check_declared(metric: str, root: str = harness.ROOT, **keys) -> dict:
    """A per-layer metric that every serve cell reports is declared once,
    with these keys, for exactly those cells. It is found by its NAME: a
    PR appends its entries at the end of ``per_layer`` and adds its cell
    to such a metric's list, so no position in either list means anything
    (``test_benchmark_families.py`` holds a root with both to this).
    -> the entry."""
    m = _entry(metric, root, **keys)
    assert m["workloads"] == cells_of_kind("serve", root), metric
    return m


def check_declared_for_some(metric: str, root: str = harness.ROOT,
                            cells=(), but=(), **keys) -> dict:
    """A per-layer metric that only SOME serve cells report: ``cells`` are
    listed, ``but`` are not, and whatever else is listed is a serve cell
    of the root. Which further cells list it is theirs to say: a later
    PR's cell whose reader finds nothing to read stays out and edits no
    test (``later_pr``'s second serve cell is one). -> the entry."""
    m = _entry(metric, root, **keys)
    assert set(cells) <= set(m["workloads"]), metric
    assert not set(but) & set(m["workloads"]), metric
    assert set(m["workloads"]) <= set(cells_of_kind("serve", root)), metric
    return m


# the chunk ledger's per-layer metrics (PR 37), in the order of their entries
CHUNK_LEDGER = ["admit_delayed_delivery_pct", "admit_stream_stall_ms",
                "chunk_interval_ms", "loop_stall_ms"]
# the per-layer metrics that every serve cell reports and a test of this
# suite holds to that, each with the keys its test asks of it
EVERY_SERVE_CELL = {
    "decode_sample_ms": {"layer": "decode math", "moves": "tpot_ms",
                         "source": "device_trace"},
    **{name: {"layer": "engine", "better": "lower"} for name in CHUNK_LEDGER},
}
# those that only some serve cells report: the cells a test of this suite
# holds it to, the cells that may not list it, and its keys
SOME_SERVE_CELLS = {
    # trinity's ordered reads are runs of one full layer, read whole: its
    # counters stay 0 by design (``docs/OBSERVABILITY.md`` "Loop counters")
    "kv_view_columns_read_pct": {
        "cells": ("rudalle-xl.serve-full", "dalle-12b.serve-full",
                  "kanana-2-30b-a3b.serve-full",
                  "phi-4-mini-flash-reasoning.serve-full"),
        "but": ("trinity-large-preview.serve-full",),
        "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "decode math", "moves": "tpot_ms"},
}


def check_moves(metric: str, root: str = harness.ROOT) -> None:
    """A per-layer entry moves an end-to-end metric that each of its
    cells reports, and holds the contract's keys and no other."""
    bench = harness.load_benchmark(root)
    m = next(x for x in bench["per_layer"] if x["name"] == metric)
    e2e = {x["name"]: x for x in bench["end_to_end"]}
    assert m["moves"] in e2e
    target = e2e[m["moves"]]
    reported_in = set(target.get(
        "workloads", [w["name"] for w in bench["workloads"]]))
    cells = set(m.get("workloads", reported_in))
    assert cells and cells <= reported_in
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_moves_a_metric_its_cells_report(metric):
    check_moves(metric)


def _config_file(name: str) -> tuple:
    conf = next(c for c in BENCH["configs"] if c["name"] == name)
    return conf, harness.load_json(os.path.join(harness.ROOT, conf["file"]))


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_file_names_its_family_and_reduces_no_width(name):
    conf, file = _config_file(name)
    assert os.path.isdir(os.path.join(harness.HERE, "families",
                                      file["family"]))
    assert any(w["config"] == name for w in BENCH["workloads"])
    assert sorted(conf["reduced"]) == sorted(file.get("reduced",
                                                      conf["reduced"]))
    assert not any(k.endswith(("_dim", "_rank", "_size")) or k in
                   ("dim", "heads", "dim_head", "ff_mult")
                   for k in conf["reduced"])


def test_dalle_config_files_hold_published_widths():
    (_, x), (_, d) = _config_file("rudalle-xl"), _config_file("dalle-12b")
    assert (x["dim"], x["depth"], x["heads"], x["dim_head"]) == \
        (2048, 24, 16, 128)
    assert (x["text_seq_len"], x["image_seq_len"], x["num_image_tokens"]) \
        == (128, 1024, 8192)
    assert (d["dim"], d["depth"], d["heads"], d["dim_head"]) == \
        (3968, 64, 62, 64)
    assert (d["text_seq_len"], d["num_text_tokens"], d["num_image_tokens"]) \
        == (256, 16384, 8192)
    for file in (x, d):
        assert file["family"] == "dalle"
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
    # a metric with no ``workloads`` key is every cell's, a later one's too
    with open(os.path.join(here, "metrics", "everywhere.py"), "w") as f:
        f.write("def read(ctx):\n    return 7\n")
    bench["per_layer"].append({"name": "everywhere", "unit": "count",
                               "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": "setup_s"})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    cell = harness.Cell("other-xl.train-small", root=root)
    assert cell.config["dim"] == 48 and cell.traffic["rows_per_group"] == 2
    ctx = {"readings": {"rows": 2}, "setup_compile": {"compile_s": 1.0},
           "kind": "train", "compiles_in_window": 0, "trace": None,
           "end_to_end": {}, "chips": 1}
    got = harness.read_per_layer(cell, ctx)
    assert got["rows_per_step"] == {"value": 2.0, "unit": "count"}
    assert got["everywhere"] == {"value": 7.0, "unit": "count"}
    assert "compile_s" not in got       # it lists its cells, and not this one
    for name in CELLS:                  # the cells that were there report it
        assert "everywhere" in [m["name"] for m in harness.Cell(
            name, root=root).metrics("per_layer")]
    check_cell("other-xl.train-small", root)
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
    dims = cell.family.weights.dims_of(cell.config, 2)
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
    W = harness.load_family("dalle").weights
    d = W.Dims(dim=16, depth=2, heads=2, dim_head=8, ff_mult=4,
               text_seq_len=8, image_grid=2, num_text_tokens=10,
               num_image_tokens=6, pattern=("sparse", "dense"))
    big = 2 ** 31 + 7
    a = W.tree(seeds.split_seed(big), d, jnp.bfloat16)
    b = jax.jit(lambda h: W.tree(h, d, jnp.bfloat16))(seeds.split_seed(big))
    c = W.tree(seeds.split_seed(7), d, jnp.bfloat16)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["text_emb"]["w"] == c["text_emb"]["w"]).all())
    one = W.layer(seeds.layer_key(seeds.seed_key(big), 1), d, jnp.bfloat16)
    assert bool((one["ff"]["w1"]["w"]
                 == a["transformer"]["ff"]["w1"]["w"][1]).all())
