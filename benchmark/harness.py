"""What every cell's run shares: finding a cell's files by name, the
refusal of anything but the chips the cell asks for, the compile
listeners, the arithmetic over readings, the per-layer readers and the
one result line. Imports nothing of the program at module level."""

from __future__ import annotations

import importlib.util
import json
import os
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
OUT_DIR = os.path.join(ROOT, "benchmark_out")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with the files its names lead to:
    ``cells/<name>.json`` (depth, flags, limits), the configuration's
    file as ``BENCHMARK.json`` gives it, ``traffic/<traffic>.json``."""

    def __init__(self, name: str, root: str = ROOT):
        bench = load_benchmark(root)
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
        here = os.path.join(root, "benchmark")
        self.name = name
        self.root = root
        self.bench = bench
        self.chips = int(entry["chips"])
        self.spec = load_json(os.path.join(here, "cells", name + ".json"))
        self.config = load_json(os.path.join(root, conf["file"]))
        self.traffic = load_json(os.path.join(
            here, "traffic", entry["traffic"] + ".json"))
        self.kind = self.traffic["kind"]

    def metrics(self, group: str) -> list:
        """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


def require_tpu(chips: int) -> dict:
    """The device as jax reports it, or exit without a result."""
    import jax
    try:
        devs = jax.devices()
    except Exception as e:  # noqa: BLE001 - any backend failure is a refusal
        print(f"benchmark: no accelerator: {e}", file=sys.stderr)
        raise SystemExit(3)
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmark: need {chips} TPU chip(s), jax gave "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        raise SystemExit(3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise SystemExit(f"no published peaks for device_kind {kind!r} in "
                         f"benchmark/peaks.json")
    return table[kind]


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


class CompileListener:
    """jax's backend-compile seconds and compile-cache hits and misses,
    with a mark so that compiles inside the window can be counted."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += duration_secs
                self.compiles += 1

    def _event(self, event, **_):
        for key in self.cache:
            if event.endswith(f"compilation_cache/cache_{key}"):
                with self._lock:
                    self.cache[key] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_s": self.compile_s, "compiles": self.compiles,
                    **self.cache}


# -- arithmetic over readings -------------------------------------------------

median = statistics.median


def percentile(values, q: float):
    """The q-th percentile (0..100), linear between closest ranks."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def rate_from_readings(units, seconds):
    """Units per second of the MEDIAN reading. ``units[i]`` of work were
    finished in ``seconds[i]``; one slow reading does not move it."""
    rates = [u / s for u, s in zip(units, seconds) if s > 0]
    if not rates:
        raise RuntimeError("the window held no complete reading")
    return median(rates)


def whole_window_rate(units, seconds):
    """All the work over all the time: what one stall moves in full."""
    return sum(units) / sum(seconds)


def agree(values, rel: float) -> bool:
    return (max(values) - min(values)) <= rel * median(values)


# -- per-layer readers ---------------------------------------------------------

def load_metric_module(name: str, directory: str):
    """The module of one per-layer metric, found by the metric's name (a
    name may hold dots, so it is loaded by path, not imported)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(directory, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: str = ROOT):
    return load_metric_module(
        name, os.path.join(root, "benchmark", "metrics")).read


def read_per_layer(cell: Cell, ctx: dict) -> dict:
    """Each per-layer metric of this cell by its own reader; a reader
    that finds nothing returns None and the metric is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        value = load_reader(m["name"], cell.root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- the result ----------------------------------------------------------------

def write_readings(cell: str, seed: int, trace: int, payload: dict) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{cell}.seed{seed}.trace{trace}.json")
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


def print_checks(checks: list) -> bool:
    """Each number compared, beside its limit; True if all hold."""
    ok = True
    for c in checks:
        held = c["value"] <= c["limit"]
        ok = ok and held
        print(f"check {c['name']}: {c['value']:.6g} (limit {c['limit']:.6g})"
              f" {'ok' if held else 'FAILED'}", flush=True)
    return ok


def result_line(*, correct, attempted, failed, metrics, device,
                breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    return json.dumps(out)


class Clock:
    """Process start, for ``setup_s``."""
    start = time.perf_counter()
