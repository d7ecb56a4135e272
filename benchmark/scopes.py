"""Device time by the program's own named scopes, and the engine loop's
spans, for the per-scope readers under ``metrics/``.

The profiler names an ``XLA Ops`` event by its HLO instruction text
without metadata, so the scope a ``jax.named_scope`` put into the program
is not in the reduction. The program hands out the missing half itself:
``Engine.device_scopes()`` and ``parallel.train.step_scopes()`` compile
their programs once more and map instruction name -> scope. This file
asks them after the window, on the live chip, for the cell's programs
(built as ``aot.py`` builds them), joins the trace's events by
``reduce.short_name``'s instruction name and result shape, and sums
seconds by scope for each program's runs. An event whose name is not in
the map, or whose shape differs, is ``unscoped``: the engine built here
is not the one the server built, and ``decode_scoped_pct`` /
``train_scoped_pct`` are the check that they compiled alike.

A program that has no such maps (the parent of the PR that added them)
gives None everywhere, and each reader then leaves its metric out.
"""

from __future__ import annotations

import bisect
import gc
import json
import os
import re
import statistics
import time

from benchmark import harness
from benchmark import reduce as R

UNSCOPED = "unscoped"
PROGRAM = re.compile(r"^jit_(.+?)(?:\(\d+\))?$")
# a device gap shorter than this is not laid at an admission's door: the
# profiler's device clock leads the host's by a millisecond or two, and
# the decode program's own gaps between operations are microseconds
MIN_GAP_NS = 3_000_000


def _serve_maps(cell) -> dict:
    """The decode program's and the used prefill buckets' maps, from an
    engine built at the cell's sizes (zeros for weights)."""
    from benchmark import aot, traffic
    from benchmark import weights as W
    from dalle_pytorch_tpu.serve import scheduler as S
    engine = aot.serve_engine(cell)
    try:
        dims = W.dims_of(cell.config, cell.spec["depth"])
        used = sorted({S.bucket_for(n, engine.buckets) for n in
                       traffic.prompt_lengths(cell.traffic,
                                              dims.text_seq_len)})
        return engine.device_scopes(buckets=used)
    finally:
        del engine
        gc.collect()


def _train_maps(cell) -> dict:
    """The train step's map, from shapes placed as ``train_cell.Trainer``
    places its state (``aot.compile_train_step``'s arguments)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import build
    from benchmark import weights as W
    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.parallel import make_mesh
    from dalle_pytorch_tpu.parallel.train import (dalle_param_specs,
                                                  make_train_step,
                                                  step_scopes)
    spec, mix = cell.spec, cell.traffic
    dims = W.dims_of(cell.config, spec["depth"])
    dtype = jnp.dtype(cell.config["param_dtype"])
    cfg = build.dalle_config(cell.config, dims, spec["flags"])
    mesh = make_mesh(spec.get("mesh") or {"dp": cell.chips},
                     jax.devices()[:cell.chips])
    axis = spec.get("batch_axis", "dp")
    rows = int(mix["rows_per_group"]) * int(mesh.shape[axis])
    optimizer = optax.adam(float(spec["flags"]["lr"]))
    shapes = jax.eval_shape(lambda: W.tree(W.split_seed(0), dims, dtype))
    rep = NamedSharding(mesh, P())
    axes = spec.get("param_axes")
    if axes:
        specs = dalle_param_specs(shapes, mesh=mesh, **axes)
        shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    else:
        shard = jax.tree.map(lambda _: rep, shapes)

    def sds(tree, sharding):
        if not isinstance(sharding, dict):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=sharding), tree)
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, sharding)

    adam = jax.eval_shape(optimizer.init, shapes)
    opt = (type(adam[0])(count=sds(adam[0].count, rep),
                         mu=sds(adam[0].mu, shard),
                         nu=sds(adam[0].nu, shard)),) + tuple(adam[1:])
    rows_sh = NamedSharding(mesh, P(axis))
    batch = {"text": jax.ShapeDtypeStruct((rows, dims.text_seq_len),
                                          jnp.int32, sharding=rows_sh),
             "image": jax.ShapeDtypeStruct((rows, dims.image_seq_len),
                                           jnp.int32, sharding=rows_sh)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)

    def loss_fn(p, b, r):
        return D.dalle_apply(p, b["text"], b["image"], cfg=cfg,
                             mask=jnp.ones_like(b["text"], bool), rng=r,
                             train=True, return_loss=True)

    step = make_train_step(loss_fn, optimizer)
    return {"step": step_scopes(step, sds(shapes, shard), opt, batch, rng)}


def program_maps(ctx) -> dict | None:
    """{program name: {instruction name: scope entry}} of the cell's
    programs, asked of the program once a run; None where the program
    has no such maps."""
    if "_scope_maps" not in ctx:
        t0 = time.perf_counter()
        try:
            import dalle_pytorch_tpu.obs.device  # noqa: F401
            maps = (_train_maps if ctx["kind"] == "train"
                    else _serve_maps)(ctx["cell"])
        except ImportError:
            maps = None         # the program names no scopes yet
        ctx["_scope_maps"] = maps
        ctx["_scope_maps_s"] = time.perf_counter() - t0
        print(f"scope maps of {sorted(maps) if maps else None} took "
              f"{ctx['_scope_maps_s']:.1f} s", flush=True)
    return ctx["_scope_maps"]


def lookup(scopes: dict, event_name: str) -> dict:
    """The map's entry for a trace event: joined by instruction name,
    held to the same result shape, else ``unscoped``."""
    inst, _, shape = R.short_name(event_name).partition(" ")
    entry = scopes.get(inst)
    if entry is None or entry.get("shape", "") != shape:
        return {"scope": UNSCOPED, "recompute": False, "inherited": False}
    return entry


def by_scope(red: R.Reduction, maps: dict, pattern: str) -> dict | None:
    """Seconds by scope over the first chip's runs of the programs whose
    module name matches ``pattern``: {"runs", "total_s", "seconds":
    {scope: s}, "inherited_s": {scope: s}, "recompute_s", "top": {scope:
    [[instruction and shape, s], ...]}}. None where no such program ran
    or none of those that ran has a map.

    A run that the capture's edge cut is in the trace with the part of
    its operations that fell inside, so where a program has more than
    two runs its first and its last are left out: a step's milliseconds
    are then a whole step's, whatever the edges hit."""
    first = min(red.modules) if red.modules else None
    rx = re.compile(pattern)
    named = ((s, s + d, PROGRAM.match(n)) for n, s, d in
             red.modules.get(first, []) if rx.search(n))
    runs = sorted((s, e, m.group(1)) for s, e, m in named
                  if m and m.group(1) in maps)
    for prog in {r[2] for r in runs}:
        own = [r for r in runs if r[2] == prog]
        if len(own) > 2:
            runs = [r for r in runs if r not in (own[0], own[-1])]
    if not runs:
        return None
    starts = [r[0] for r in runs]
    seconds, inherited, ops = {}, {}, {}
    recompute = 0.0
    for name, s, d in red.devices.get(first, []):
        if R.CONTAINER.match(name):
            continue                        # a loop holds its body's ops
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or s >= runs[k][1]:
            continue                        # another program's operation
        entry = lookup(maps[runs[k][2]], name)
        scope, sec = entry["scope"], d / 1e9
        seconds[scope] = seconds.get(scope, 0.0) + sec
        if entry.get("inherited"):
            inherited[scope] = inherited.get(scope, 0.0) + sec
        if entry["recompute"]:
            recompute += sec
        key = (scope, R.short_name(name))
        ops[key] = ops.get(key, 0.0) + sec
    top: dict = {}
    for (scope, op), sec in sorted(ops.items(), key=lambda kv: -kv[1]):
        if len(top.setdefault(scope, [])) < 8:
            top[scope].append([op, sec])
    return {"runs": len(runs), "total_s": sum(seconds.values()),
            "seconds": seconds, "inherited_s": inherited,
            "recompute_s": recompute, "top": top}


def program_seconds(ctx, pattern: str) -> dict | None:
    """``by_scope`` of this run's trace, kept in ``ctx`` and written
    beside the run's readings for ``PERF.md``'s breakdowns."""
    red = ctx.get("trace")
    if red is None:
        return None
    cache = ctx.setdefault("_by_scope", {})
    if pattern not in cache:
        maps = program_maps(ctx)
        cache[pattern] = by_scope(red, maps, pattern) if maps else None
        _write_side_file(ctx)
    return cache[pattern]


def scope_ms(ctx, pattern: str, scopes, per: int = 1) -> float | None:
    """Milliseconds of ``scopes`` in one run of the program, over ``per``
    steps."""
    got = program_seconds(ctx, pattern)
    if got is None:
        return None
    sec = sum(got["seconds"].get(s, 0.0) for s in scopes)
    return 1e3 * sec / (got["runs"] * per)


def scoped_pct(ctx, pattern: str) -> float | None:
    got = program_seconds(ctx, pattern)
    if got is None or not got["total_s"]:
        return None
    return 100.0 * (1.0 - got["seconds"].get(UNSCOPED, 0.0)
                    / got["total_s"])


# -- the engine loop's spans ----------------------------------------------------

def admissions(red: R.Reduction) -> list:
    """One record for each ``engine.admit`` host event of the capture
    that lies inside an ``engine.step``: the step's and the admission's
    seconds, its parts (``plan``, ``put``, ``prefill``) and the device's
    idle seconds inside the step, gaps under ``MIN_GAP_NS`` left out.
    The step is laid on the device's clock by the skew that
    ``host_minus_device_ms`` reads off the same capture.

    The profiler keeps a host event only if it began and ended inside
    the capture, and such a step lasts up to a second of the five. So a
    prefill run of the capture that no such step holds is read on the
    device's side alone (``cut``): the idle between the end of the
    decode run before it and the start of the decode run after it, which
    is where a whole step has its idle too. One whose neighbours the
    capture does not hold either is left out."""
    skew = int((host_minus_device_ms(red) or 0.0) * 1e6)
    steps = sorted((s, s + d) for n, s, d in red.host
                   if n == "engine.step")
    first = min(red.devices) if red.devices else None
    busy = R._union((s, s + d) for _, s, d in red.devices.get(first, []))
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])
            if b[0] - a[1] >= MIN_GAP_NS]
    out = []
    for n, s, d in red.host:
        if n != "engine.admit":
            continue
        step = next(((a, b) for a, b in steps if a <= s and s + d <= b),
                    None)
        if step is None:
            continue
        parts = {p: sum(pd for pn, ps, pd in red.host
                        if pn == "engine.admit." + p
                        and s <= ps and ps + pd <= s + d) / 1e9
                 for p in ("plan", "put", "prefill")}
        out.append({"step_s": (step[1] - step[0]) / 1e9, "admit_s": d / 1e9,
                    **parts, "on_device": (step[0] - skew, step[1] - skew),
                    "idle_s": R._covered(gaps, step[0] - skew,
                                         step[1] - skew) / 1e9})
    runs = sorted((s, s + d, n) for n, s, d in red.modules.get(first, []))
    decode = [r for r in runs if "decode_impl" in r[2]]
    for s, e, n in runs:
        if not re.search(r"jit_prefill_b\d+", n) or any(
                a["on_device"][0] <= s and e <= a["on_device"][1]
                for a in out if "on_device" in a):
            continue
        before = [r[1] for r in decode if r[1] <= s]
        after = [r[0] for r in decode if r[0] >= e]
        if before and after:
            out.append({"cut": True, "idle_s": R._covered(
                gaps, before[-1], after[0]) / 1e9})
    for a in out:
        a.pop("on_device", None)
    return out


def host_minus_device_ms(red: R.Reduction) -> float | None:
    """Median of (end of an ``engine.harvest_wait`` on the host's line)
    minus (end of the decode run it waited for on the device's): the
    profiler's two clocks' skew plus the fetch's latency."""
    first = min(red.modules) if red.modules else None
    ends = sorted(s + d for n, s, d in red.modules.get(first, [])
                  if "decode_impl" in n)
    lags = []
    for n, s, d in red.host:
        if n == "engine.harvest_wait" and ends:
            k = bisect.bisect_right(ends, s + d + 20_000_000) - 1
            if k >= 0 and abs(s + d - ends[k]) < 20_000_000:
                lags.append((s + d - ends[k]) / 1e6)
    return statistics.median(lags) if lags else None


def counter_delta(ctx, key: str) -> float | None:
    s0, s1 = ctx.get("stats0") or {}, ctx.get("stats1") or {}
    if key not in s0 or key not in s1:
        return None
    return s1[key] - s0[key]


def _write_side_file(ctx) -> None:
    red, readings = ctx["trace"], ctx.get("readings") or {}
    first = min(red.modules) if red.modules else None
    payload = {
        "cell": ctx["cell"].name, "seed": readings.get("seed"),
        "maps_s": ctx.get("_scope_maps_s"),
        "programs": ctx.get("_by_scope"),
        "module_runs_s": {}, "admissions": admissions(red),
        "host_minus_device_ms": host_minus_device_ms(red),
        # the loop's spans and the program runs, in ms from the first
        # device operation: what an idle gap is read against
        "engine_spans": sorted(
            [n, (s - red.t0) / 1e6, d / 1e6] for n, s, d in red.host
            if n.startswith("engine.")),
        "module_runs": [[n, (s - red.t0) / 1e6, d / 1e6]
                        for n, s, d in red.modules.get(first, [])],
        "min_gap_ms": MIN_GAP_NS / 1e6,
    }
    for n, _, d in red.modules.get(first, []):
        m = PROGRAM.match(n)
        payload["module_runs_s"].setdefault(
            m.group(1) if m else n, []).append(d / 1e9)
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    path = os.path.join(
        harness.OUT_DIR,
        f"{ctx['cell'].name}.seed{readings.get('seed')}.scopes.json")
    with open(path, "w") as f:
        json.dump(payload, f)
