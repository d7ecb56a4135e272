"""The one seam of the harness: a configuration names its family, and
whatever knows a block's parameters lives in ``benchmark/families/<family>/``
and is found by path (``benchmark/families/README.md``).

Proved here on the CPU at toy widths: the block that was moved into the
first family gives the numbers it gave before the move; a second family
enters as new files only and its own reference decides its ``correct``;
a configuration without a family, or with one that is not there, is
refused by name; and no harness module knows a block.
"""

import ast
import glob
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, seeds, serve_cell, tiny, traffic, train_cell
from test_benchmark_contract import (EVERY_SERVE_CELL, SOME_SERVE_CELLS,
                                     cells_of_kind, check_cell,
                                     check_declared, check_declared_for_some,
                                     check_moves)

HERE = harness.HERE
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
TRAIN_LIMITS = {"loss_rel_gap": 1e-3, "grad_norm_worst_leaf_gap": 0.05,
                "change_norm_worst_leaf_gap": 0.25}
SERVE_LIMITS = {"served_logit_gap_max": 1e-4, "served_logit_gap_mean": 1e-7,
                "served_not_best_share": 5e-4}

# -- (a) the moved code gives the numbers it gave before the move -------------
# Recorded at the parent commit (278645a, ``benchmark/weights.py`` and
# ``benchmark/reference.py`` as they then were) on the CPU: dalle-12b's
# configuration at the family's toy widths, depth 2 (one sparse and one
# dense layer), the training mix's batches 0 and 1 of two rows; the one
# sequence is batch 0's first row. A weight is a pure function of the seed
# and is held to its bytes; logits and losses are float32 sums that another
# CPU may order otherwise, so they are held to 1e-6 of themselves.
RECORDED = {
    1: {
        "bfloat16": "7a094ee22c5d144c4cae30ea86d6795f"
                    "0a1d8d5795708339143827924c534696",
        "float32": "70fe20ac1a60fe91c206ae26a1b973d1"
                   "47e90d14a2a47a1d043ec954cf8512cd",
        "logits": (3086, 23.551518455147743, 1469.294837012887),
        "losses": (3.625222682952881, 3.555742025375366)},
    2 ** 31 + 5: {
        "bfloat16": "0a1dbadb3230153dbf097c9ed7fc8620"
                    "34e8ea662e6eae59aa829f1436e092e8",
        "float32": "1dbb6a06f854ac0aefc357a0ff4d8422"
                   "d9503a38fd0055989fbcec4c2c713390",
        "logits": (3086, -33.64449056237936, 1487.9675060734153),
        "losses": (3.5593771934509277, 3.539562225341797)},
}


@pytest.fixture(scope="module")
def dalle():
    family = harness.load_family("dalle")
    conf = harness.load_json(os.path.join(HERE, "configs", "dalle-12b.json"))
    conf.update(family.tiny)
    dims = family.weights.dims_of(conf, family.tiny["depth"])
    mix = harness.load_json(os.path.join(HERE, "traffic", "train.json"))
    return family, dims, mix


def _digest(tree) -> str:
    import jax
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), a.dtype, a.shape):
            h.update(str(part).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("seed", sorted(RECORDED))
def test_moved_weights_are_the_parents_bit_for_bit(dalle, seed, dtype):
    import jax.numpy as jnp
    family, dims, _ = dalle
    tree = family.weights.tree(seeds.split_seed(seed), dims, jnp.dtype(dtype))
    assert _digest(tree) == RECORDED[seed][dtype]


@pytest.mark.parametrize("seed", sorted(RECORDED))
def test_moved_reference_gives_the_parents_logits(dalle, seed):
    import jax.numpy as jnp
    family, dims, mix = dalle
    batch = traffic.train_batch(mix, seed, 0, 2, dims)
    seq = np.concatenate([batch["text"][:1], batch["image"][:1]], 1)
    lg = np.asarray(family.reference.served_logits(
        seed, dims, jnp.dtype("bfloat16"), seq))
    fin = lg[np.isfinite(lg)].astype(np.float64)
    count, total, absolute = RECORDED[seed]["logits"]
    assert fin.size == count
    assert np.abs(fin).sum() == pytest.approx(absolute, rel=1e-6)
    assert fin.sum() == pytest.approx(total, abs=1e-6 * absolute)


@pytest.mark.parametrize("seed", sorted(RECORDED))
def test_moved_reference_gives_the_parents_two_losses(dalle, seed):
    import jax.numpy as jnp
    family, dims, mix = dalle
    batches = [traffic.train_batch(mix, seed, i, 2, dims) for i in (0, 1)]
    two = family.reference.train_two_steps(
        seed, dims, jnp.dtype("bfloat16"), batches, 3e-3)
    assert two["loss"] == pytest.approx(RECORDED[seed]["losses"], rel=1e-6)


# -- (b), (c) a second family, added as files only -----------------------------

@pytest.fixture(scope="module")
def listener():
    return harness.CompileListener()


def _snapshot(root: str) -> dict:
    out = {}
    for base, _, files in os.walk(root):
        if "__pycache__" in base:
            continue
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[p] = fh.read()
    return out


def _dump(obj, *path) -> None:
    with open(os.path.join(*path), "w") as f:
        json.dump(obj, f)


def _rewrite(path: str, old: str, new: str) -> None:
    with open(path) as f:
        text = f.read()
    assert text.count(old) == 1, (path, old)
    with open(path, "w") as f:
        f.write(text.replace(old, new))


# the second family's own names for two of the first's configuration keys
# and for one of its sizes: nothing outside a family may know either
RENAMED_KEYS = {"dim_head": "head_size", "attention_pattern": "layer_kinds"}


def _rename(obj: dict) -> dict:
    return {RENAMED_KEYS.get(k, k): v for k, v in obj.items()}


def _second_family(tmp_path, alter_reference: bool = False):
    """A toy root with a second family, ``other``, written as new files:
    the first family's files with one changed constant in its weights (so
    its parameters differ and its reference follows) and with names of its
    own for two configuration keys and one size, a configuration that
    names it, a train and a serve cell and a reader that counts through
    it. -> (root, every file that was there before, with its bytes)."""
    root = tiny.make(str(tmp_path), dtype="float32")
    here = os.path.join(root, "benchmark")
    for name, limits in (("dalle-12b.train", TRAIN_LIMITS),
                         ("rudalle-xl.serve-full", SERVE_LIMITS)):
        path = os.path.join(here, "cells", name + ".json")
        _dump(dict(harness.load_json(path), limits=limits), path)
    before = _snapshot(root)
    other = os.path.join(here, "families", "other")
    shutil.copytree(os.path.join(here, "families", "dalle"), other)
    weights = os.path.join(other, "weights.py")
    _rewrite(weights,
             "jax.random.fold_in(key, 3)", "jax.random.fold_in(key, 4)")
    for old, new in RENAMED_KEYS.items():
        _rewrite(weights, f'config["{old}"]', f'config["{new}"]')
    with open(weights) as f:
        text = f.read()
    with open(weights, "w") as f:
        f.write(re.sub(r"\bpattern\b", "kinds", text))
    _dump(_rename(harness.load_json(os.path.join(other, "tiny.json"))),
          other, "tiny.json")
    if alter_reference:
        _rewrite(os.path.join(other, "reference.py"),
                 "dots = dots * (d.dim ** -0.5)",
                 "dots = dots * (d.dim ** -0.25)")
    conf = harness.load_json(os.path.join(here, "configs", "dalle-12b.json"))
    _dump(dict(_rename(conf), name="other-12b", family="other"),
          here, "configs", "other-12b.json")
    for cell, like in (("other-12b.train", "dalle-12b.train"),
                       ("other-12b.serve-full", "rudalle-xl.serve-full")):
        shutil.copy(os.path.join(here, "cells", like + ".json"),
                    os.path.join(here, "cells", cell + ".json"))
    with open(os.path.join(here, "metrics", "flops_per_token.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['cell'].family.flops."
                "train_flops_per_token(ctx['dims'])\n")
    bench = harness.load_benchmark(root)
    bench["configs"].append({"name": "other-12b", "source": "test",
                             "file": "benchmark/configs/other-12b.json",
                             "reduced": [], "why": "test"})
    for cell, like in (("other-12b.train", "dalle-12b.train"),
                       ("other-12b.serve-full", "rudalle-xl.serve-full")):
        bench["workloads"].append({"name": cell, "config": "other-12b",
                                   "traffic": like.split(".")[1], "chips": 1,
                                   "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "flops_per_token", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "test",
        "moves": "train_tokens_per_s", "workloads": ["other-12b.train"]})
    _dump(bench, root, "BENCHMARK.json")
    harness.OUT_DIR = os.path.join(root, "benchmark_out")
    return root, before


def _correct(root, name, listener) -> bool:
    cell = harness.Cell(name, root=root)
    args = types.SimpleNamespace(
        seed=2 ** 31 + 5, seconds=1.0 if cell.kind == "train" else 2.0,
        trace=0, control="none", broken="", sync_every_step=0, more_seeds=0)
    driver = train_cell if cell.kind == "train" else serve_cell
    out = json.loads(driver.run(cell, args, dict(DEVICE), listener))
    assert out["attempted"] > 0 and out["failed"] == 0
    return out["correct"]


def test_a_family_added_as_files_only_runs_and_is_correct(tmp_path, listener):
    import jax.numpy as jnp
    root, before = _second_family(tmp_path)
    cell = harness.Cell("other-12b.train", root=root)
    assert cell.family.name == "other"
    assert cell.family.directory.startswith(root)
    # its configuration and its sizes go by names of their own
    assert not set(RENAMED_KEYS) & set(cell.config)
    assert not set(RENAMED_KEYS) & set(cell.family.tiny)
    # every cell of the root, the new family's too, passes the checks that
    # ``test_benchmark_contract.py`` makes of every cell of BENCHMARK.json
    for w in cell.bench["workloads"]:
        check_cell(w["name"], root)
    first = harness.Cell("dalle-12b.train", root=root).family
    assert first.weights is not cell.family.weights
    # the changed constant is in the second family's weights and no other's
    dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
    assert dims.kinds == ("sparse", "dense") and not hasattr(dims, "pattern")
    halves = seeds.split_seed(3)
    a = cell.family.weights.tree(halves, dims, jnp.float32)
    b = first.weights.tree(halves, first.weights.dims_of(
        harness.Cell("dalle-12b.train", root=root).config,
        cell.spec["depth"]), jnp.float32)
    assert not bool((a["text_emb"]["w"] == b["text_emb"]["w"]).all())
    same = a["transformer"]["ff"]["w1"]["w"] == b["transformer"]["ff"]["w1"]["w"]
    assert bool(same.all())
    # a reader that is a new file counts through the cell's own family
    assert "flops_per_token" in [m["name"] for m in cell.metrics("per_layer")]
    read = harness.load_reader("flops_per_token", root)
    assert read({"cell": cell, "dims": dims}) \
        == cell.family.flops.train_flops_per_token(dims)
    assert _correct(root, "other-12b.train", listener) is True
    assert _correct(root, "other-12b.serve-full", listener) is True
    for p, data in before.items():
        if not p.endswith("BENCHMARK.json"):
            with open(p, "rb") as f:
                assert f.read() == data, f"{p} was edited"


def test_a_familys_own_reference_decides_its_correct(tmp_path, listener):
    root, _ = _second_family(tmp_path, alter_reference=True)
    assert _correct(root, "other-12b.train", listener) is False
    assert _correct(root, "other-12b.serve-full", listener) is False
    # the first family beside it is held to its own reference, untouched
    assert _correct(root, "dalle-12b.train", listener) is True
    assert _correct(root, "rudalle-xl.serve-full", listener) is True


# -- (c') what a later PR's BENCHMARK.json owes the suite's declaration checks ---

@pytest.fixture(scope="module")
def later_pr(tmp_path_factory):
    """The root of ``_second_family`` (a copy of the committed
    BENCHMARK.json with a configuration, a train and a serve cell added,
    each cell listed wherever the first family's cell of its kind is, and
    a per-layer entry appended at the END) with one more serve cell, whose
    traffic file has a name of its own and which lists only the metrics
    that EVERY serve cell reports (the README's rule; not
    ``kv_view_columns_read_pct``, ``decode_weights_ms`` or another that
    only some list: trinity's case): what the next ``model_config`` PR
    brings, which may edit no test of this directory."""
    root, _ = _second_family(tmp_path_factory.mktemp("later_pr"))
    here = os.path.join(root, "benchmark")
    shutil.copy(os.path.join(here, "traffic", "serve-full.json"),
                os.path.join(here, "traffic", "serve-long.json"))
    shutil.copy(os.path.join(here, "cells", "other-12b.serve-full.json"),
                os.path.join(here, "cells", "other-12b.serve-long.json"))
    bench = harness.load_benchmark(root)
    serve = set(cells_of_kind("serve", root))
    bench["workloads"].append({"name": "other-12b.serve-long",
                               "config": "other-12b", "traffic": "serve-long",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if serve <= set(m.get("workloads", [])):
            m["workloads"].append("other-12b.serve-long")
    _dump(bench, root, "BENCHMARK.json")
    return root


@pytest.mark.parametrize("metric", sorted(EVERY_SERVE_CELL))
def test_a_later_prs_serve_cells_pass_every_serve_cells_declaration(later_pr,
                                                                    metric):
    check_declared(metric, later_pr, **EVERY_SERVE_CELL[metric])


@pytest.mark.parametrize("metric", sorted(SOME_SERVE_CELLS))
def test_a_later_prs_serve_cell_may_leave_out_what_only_some_report(later_pr,
                                                                    metric):
    m = check_declared_for_some(metric, later_pr, **SOME_SERVE_CELLS[metric])
    # the cell under the shared traffic file lists it, the other does not
    assert "other-12b.serve-full" in m["workloads"]
    assert "other-12b.serve-long" not in m["workloads"]


def test_a_later_prs_entries_stand_at_the_end_and_pass_the_moves_check(
        later_pr):
    bench, committed = harness.load_benchmark(later_pr), harness.load_benchmark()
    # appended: what was there stands first and in its order, in both lists
    assert [m["name"] for m in bench["per_layer"]] \
        == [m["name"] for m in committed["per_layer"]] + ["flops_per_token"]
    assert cells_of_kind("serve", later_pr) == cells_of_kind("serve") \
        + ["other-12b.serve-full", "other-12b.serve-long"]
    assert cells_of_kind("train", later_pr) \
        == cells_of_kind("train") + ["other-12b.train"]
    for m in bench["per_layer"]:
        check_moves(m["name"], later_pr)
    check_cell("other-12b.serve-long", later_pr)


# -- (d) a configuration has to name a family that is there --------------------

@pytest.mark.parametrize("case, named", [
    ("no_family_key", '"family"'),
    ("family_not_there", os.path.join("benchmark", "families", "nowhere")),
])
def test_run_refuses_a_configuration_without_its_family(tmp_path, case, named):
    bench = harness.load_benchmark()
    for p in bench["paths"][:1]:
        shutil.copytree(os.path.join(harness.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    cell = bench["workloads"][0]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    obj = harness.load_json(os.path.join(harness.ROOT, conf["file"]))
    if case == "no_family_key":
        del obj["family"]
    else:
        obj["family"] = "nowhere"
    _dump(obj, str(tmp_path), conf["file"])
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=str(tmp_path),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert named in out.stderr
    assert "metrics" not in out.stdout and "correct" not in out.stdout


# -- (e) no harness module knows a block ---------------------------------------

BLOCK_WORDS = ("qkv", "w1", "GEGLU", "dim_head", "ff_mult")
HARNESS_FILES = sorted(glob.glob(os.path.join(HERE, "*.py")))
READER_FILES = sorted(glob.glob(os.path.join(HERE, "metrics", "*.py")))
FAMILY_FILES = sorted(glob.glob(os.path.join(HERE, "families", "*", "*.py")))
FAMILIES = sorted(d for d in os.listdir(os.path.join(HERE, "families"))
                  if os.path.isdir(os.path.join(HERE, "families", d)))


def _imports(path: str) -> list:
    """(module, level, names) of every import statement in a file."""
    with open(path) as f:
        tree = ast.parse(f.read())
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out += [(a.name, 0, ()) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            out.append((node.module or "", node.level,
                        tuple(a.name for a in node.names)))
    return out


def _names_a_family(module: str, names: tuple) -> bool:
    """An import that reaches a block's code by name: the families'
    directory, a loaded family's package, or one of the four old paths."""
    parts = set(module.split(".")) | set(names)
    old = set(harness.FAMILY_MODULES)
    return ("families" in parts or module.startswith("benchmark_family_")
            or (module == "benchmark" and bool(set(names) & old))
            or module in {"benchmark." + name for name in old})


def _rel(path: str) -> str:
    return os.path.relpath(path, HERE)


@pytest.mark.parametrize("path", HARNESS_FILES, ids=_rel)
def test_harness_module_knows_no_block(path):
    with open(path) as f:
        words = set(re.findall(r"[A-Za-z_][A-Za-z_0-9]*", f.read()))
    assert not words & set(BLOCK_WORDS), sorted(words & set(BLOCK_WORDS))
    for module, _level, names in _imports(path):
        assert not _names_a_family(module, names), (module, names)


@pytest.mark.parametrize("path", READER_FILES, ids=_rel)
def test_reader_reaches_a_block_only_through_its_cell(path):
    for module, _level, names in _imports(path):
        assert not _names_a_family(module, names), (module, names)


@pytest.mark.parametrize("path", FAMILY_FILES, ids=_rel)
def test_family_module_imports_no_other_family(path):
    for module, level, names in _imports(path):
        assert level <= 1, "a family imports its own modules only"
        assert not _names_a_family(module, names), (module, names)
        assert not set(module.split(".")) & set(FAMILIES), module
        if level == 0 and module.split(".")[0] == "benchmark":
            assert module in ("benchmark.seeds", "benchmark.harness") or (
                module == "benchmark" and set(names) <= {"seeds", "harness"})
        if os.path.basename(path) in ("weights.py", "reference.py"):
            assert not module.startswith("dalle_pytorch_tpu"), \
                "the reference and the weights import nothing of the program"


def test_the_four_old_paths_are_gone_and_every_family_is_whole():
    for name in harness.FAMILY_MODULES:
        assert not os.path.exists(os.path.join(HERE, name + ".py"))
    for family in FAMILIES:
        loaded = harness.load_family(family)
        assert isinstance(loaded.tiny["depth"], int)
        for name in harness.FAMILY_MODULES:
            assert getattr(loaded, name).__file__.startswith(
                loaded.directory)
    for conf in harness.load_benchmark()["configs"]:
        file = harness.load_json(os.path.join(harness.ROOT, conf["file"]))
        assert file["family"] in FAMILIES
