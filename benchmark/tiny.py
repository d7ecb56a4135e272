"""A copy of the benchmark's data files at toy widths, for rehearsing a
whole run on the CPU (tests and the builder's own dry runs). Only data is
copied and shrunk; the code that runs is the benchmark's own."""

from __future__ import annotations

import json
import os
import shutil

from benchmark import harness

WIDTHS = dict(dim=32, heads=2, dim_head=16, text_seq_len=32, image_grid=8,
              image_seq_len=64, num_text_tokens=50, num_image_tokens=24,
              depth=2)


def _edit(path: str, fn) -> None:
    with open(path) as f:
        obj = json.load(f)
    fn(obj)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make(root: str, dtype: str = "bfloat16", lr: float = 3e-3) -> str:
    """Write the tiny copy under ``root`` and return it."""
    src = harness.ROOT
    os.makedirs(os.path.join(root, "benchmark"), exist_ok=True)
    for d in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(os.path.join(src, "benchmark", d),
                        os.path.join(root, "benchmark", d),
                        dirs_exist_ok=True)
    for f in ("peaks.json",):
        shutil.copy(os.path.join(src, "benchmark", f),
                    os.path.join(root, "benchmark", f))
    shutil.copy(os.path.join(src, "BENCHMARK.json"),
                os.path.join(root, "BENCHMARK.json"))
    bench = harness.load_benchmark(root)
    for conf in bench["configs"]:
        _edit(os.path.join(root, conf["file"]),
              lambda c: c.update(WIDTHS, param_dtype=dtype))
    for w in bench["workloads"]:
        def shrink(c):
            c["depth"] = WIDTHS["depth"]
            c["flags"]["attn_impl"] = "xla"
            c["trace_seconds"] = 1
            if "num_slots" in c:
                c["num_slots"] = 4
                c["check_window_s"] = 2
                c["check_requests"] = 160
                c["harvest_gap_ms"] = 0.05      # a toy chunk takes ~1 ms
            else:
                c["flags"].update(remat="none", lr=lr)
                c["warm_max_readings"] = 5
                if "mesh" in c:
                    c["mesh"] = {k: 2 for k in c["mesh"]}
        _edit(os.path.join(root, "benchmark", "cells", w["name"] + ".json"),
              shrink)
    for name in os.listdir(os.path.join(root, "benchmark", "traffic")):
        def small(t):
            if t["kind"] == "serve":
                t.update(requests_drawn=20000, warm_draft=4)
        _edit(os.path.join(root, "benchmark", "traffic", name), small)
    return root
