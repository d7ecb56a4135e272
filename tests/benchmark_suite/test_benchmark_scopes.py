"""The per-scope readers (ISSUE 24): the join of a trace's device events
with the program's instruction -> scope maps (``benchmark/scopes.py``),
the engine loop's spans and counters, on hand-built reductions and maps
and on a small trace recorded on a TPU v5e with its ``scopes.json``
(a tiny paged engine, two requests, the second admitted mid-decode;
``.chipwork`` probe of PR 24)."""

import json
import os
import types

import pytest

from benchmark import harness
from benchmark import reduce as R
from benchmark import scopes

NEW = ["decode_kv_view_ms", "decode_kv_store_ms", "decode_attend_ms",
       "decode_weights_ms", "decode_scoped_pct", "prefill_device_ms",
       "admit_dispatch_ms", "engine_host_pct",
       "train_ff_ms", "train_attn_ms", "train_optimizer_ms",
       "train_recompute_pct", "train_scoped_pct"]
MS = 1_000_000      # nanoseconds


def entry(scope, shape, recompute=False, inherited=False):
    return {"scope": scope, "shape": shape, "recompute": recompute,
            "inherited": inherited, "op_name": ""}


def op(inst, shape, start_ms, dur_ms, kind="fusion"):
    return (f"%{inst} = {shape}{{0}} {kind}(%p)", int(start_ms * MS),
            int(dur_ms * MS))


# two decode chunks of two steps each with a prefill between them; both
# programs have a ``fusion.1``, under different scopes
DECODE_MAP = {
    "fusion.1": entry("kv.view", "bf16[4,8]"),
    "copy.9": entry("kv.view", "bf16[4,8]", inherited=True),
    "fusion.2": entry("kv.store", "bf16[4,8]"),
    "fusion.3": entry("attn.read", "bf16[4]"),
    "fusion.4": entry("ff", "bf16[4]"),
    "fusion.5": entry("norm", "bf16[4]"),
    "fusion.6": entry("sample", "s32[4]"),
    "fusion.7": entry("ff", "bf16[16]"),     # traced with another shape
    "while.1": entry("unscoped", "s32[]"),
}
PREFILL_MAP = {"fusion.1": entry("prefill.scatter", "bf16[4,8]"),
               "fusion.8": entry("attn.read", "bf16[4,4]")}
MAPS = {"_decode_impl_paged": DECODE_MAP, "prefill_b8": PREFILL_MAP}


def chunk(at):
    return [op("while.1", "s32[]", at, 20, "while"),     # holds the rest
            op("fusion.1", "bf16[4,8]", at, 6),
            op("copy.9", "bf16[4,8]", at + 6, 2, "copy"),
            op("fusion.2", "bf16[4,8]", at + 8, 2),
            op("fusion.3", "bf16[4]", at + 10, 3),
            op("fusion.4", "bf16[4]", at + 13, 2),
            op("fusion.5", "bf16[4]", at + 15, 1),
            op("fusion.6", "s32[4]", at + 16, 1),
            op("fusion.7", "bf16[4]", at + 17, 1),       # shape differs
            op("fusion.99", "bf16[4]", at + 18, 2)]      # not in the map


@pytest.fixture()
def red():
    devices = {0: chunk(0) + [op("fusion.1", "bf16[4,8]", 30, 4),
                              op("fusion.8", "bf16[4,4]", 34, 6)]
               + chunk(50) + [op("fusion.1", "bf16[4,8]", 80, 5)]}
    modules = {0: [("jit__decode_impl_paged(11)", 0, 20 * MS),
                   ("jit_prefill_b8(12)", 30 * MS, 10 * MS),
                   ("jit__decode_impl_paged(11)", 50 * MS, 20 * MS),
                   ("jit_convert_element_type(13)", 80 * MS, 5 * MS)]}
    host = [("engine.step", 0, 48 * MS),
            ("engine.admit", 25 * MS, 20 * MS),
            ("engine.admit.plan", 25 * MS, 1 * MS),
            ("engine.admit.put", 26 * MS, 2 * MS),
            ("engine.admit.prefill", 28 * MS, 16 * MS),
            ("engine.harvest_wait", 45 * MS, int(26.5 * MS)),
            # a second admission whose step the capture cut: left out
            ("engine.admit", 90 * MS, 5 * MS),
            ("engine.step", 49 * MS, 30 * MS)]
    return R.Reduction(devices, modules, host)


def serve_ctx(red, maps=MAPS, **kw):
    cell = types.SimpleNamespace(
        name="toy.serve", spec={"engine": {"chunk_steps": 2}})
    ctx = {"kind": "serve", "cell": cell, "trace": red, "_scope_maps": maps,
           "_scope_maps_s": 0.0, "readings": {"seed": 5}, "t_open": 100.0,
           "t_close": 110.0, "stats0": {}, "stats1": {}}
    ctx.update(kw)
    return ctx


@pytest.fixture(autouse=True)
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))


def read(name, ctx):
    return harness.load_reader(name)(ctx)


def test_lookup_joins_by_instruction_name_and_result_shape():
    ev = "%fusion.1 = bf16[4,8]{1,0:T(8,128)(2,1)} fusion(%a), kind=kLoop"
    assert scopes.lookup(DECODE_MAP, ev)["scope"] == "kv.view"
    assert scopes.lookup(DECODE_MAP, ev.replace("[4,8]", "[4,9]"))["scope"] \
        == "unscoped"
    assert scopes.lookup(DECODE_MAP, ev.replace("fusion.1", "fusion.77")) \
        == {"scope": "unscoped", "recompute": False, "inherited": False}
    # a tuple result is held to its first element's shape
    tup = "%fusion.3 = (bf16[4]{0}, f32[4]{0}) fusion(%a)"
    assert scopes.lookup(DECODE_MAP, tup)["scope"] == "attn.read"


def test_by_scope_sums_each_programs_runs_by_its_own_map(red):
    got = scopes.by_scope(red, MAPS, r"decode_impl")
    assert got["runs"] == 2
    assert got["seconds"] == pytest.approx({
        "kv.view": 0.016, "kv.store": 0.004, "attn.read": 0.006,
        "ff": 0.004, "norm": 0.002, "sample": 0.002, "unscoped": 0.006})
    assert got["total_s"] == pytest.approx(0.040)   # the loop is not added
    assert got["inherited_s"] == pytest.approx({"kv.view": 0.004})
    assert got["top"]["kv.view"][0] == ["fusion.1 bf16[4,8]",
                                        pytest.approx(0.012)]
    assert {o for o, _ in got["top"]["unscoped"]} \
        == {"fusion.7 bf16[4]", "fusion.99 bf16[4]"}
    # the prefill's ``fusion.1`` is the prefill's, and the stray program's
    # operation after the last run is nobody's
    pre = scopes.by_scope(red, MAPS, r"prefill_b")
    assert pre["seconds"] == pytest.approx({"prefill.scatter": 0.004,
                                            "attn.read": 0.006})
    assert scopes.by_scope(red, MAPS, r"jit_step") is None
    # a third chunk, cut by the capture's end to its first operation:
    # with more than two runs the first and the last are left out
    cut = R.Reduction({0: red.devices[0] + chunk(90)[:2]},
                      {0: red.modules[0] + [("jit__decode_impl_paged(11)",
                                             90 * MS, 6 * MS)]}, [])
    whole = scopes.by_scope(cut, MAPS, r"decode_impl")
    assert whole["runs"] == 1 and whole["total_s"] == pytest.approx(0.020)
    assert scopes.by_scope(red, {"prefill_b8": PREFILL_MAP},
                           r"decode_impl") is None


@pytest.mark.parametrize("name, value", [
    ("decode_kv_view_ms", 4.0), ("decode_kv_store_ms", 1.0),
    ("decode_attend_ms", 1.5), ("decode_weights_ms", 1.5),
    ("decode_scoped_pct", 85.0), ("prefill_device_ms", 10.0),
])
def test_decode_readers_give_milliseconds_a_step(red, name, value):
    ctx = serve_ctx(red)
    assert read(name, ctx) == pytest.approx(value)
    # the breakdown is written beside the run's readings
    side = harness.load_json(os.path.join(harness.OUT_DIR,
                                          "toy.serve.seed5.scopes.json")) \
        if name != "prefill_device_ms" else None
    if side:
        assert side["programs"]["decode_impl"]["runs"] == 2
        assert side["module_runs_s"]["prefill_b8"] == [0.01]


def test_admissions_inside_the_capture_and_the_device_idle_in_them(red):
    found = scopes.admissions(red)
    assert len(found) == 1                  # the cut one is left out
    a = found[0]
    assert (a["step_s"], a["admit_s"]) == pytest.approx((0.048, 0.020))
    assert (a["plan"], a["put"], a["prefill"]) \
        == pytest.approx((0.001, 0.002, 0.016))
    # the wait's end on the host against the run's end on the device:
    # the host's clock reads 1.5 ms later, so the step is 0..46.5 ms there
    assert scopes.host_minus_device_ms(red) == pytest.approx(1.5)
    # idle inside it: 20..30 and 40..46.5 ms (the gap after the prefill
    # ends with the step); the decode's own gaps do not count
    assert a["idle_s"] == pytest.approx(0.0165)
    # the step's event cut by the capture's edge: the same admission
    # read between its neighbouring decode runs, 20..30 and 40..50 ms
    cut = R.Reduction(red.devices, red.modules, [])
    assert scopes.admissions(cut) == [{"cut": True,
                                       "idle_s": pytest.approx(0.020)}]
    # and one whose neighbours the capture does not hold: nothing
    quiet = R.Reduction(red.devices, {0: red.modules[0][:2]}, [])
    assert scopes.admissions(quiet) == []
    assert read("prefill_device_ms", serve_ctx(R.Reduction(
        red.devices, {0: red.modules[0][:1]}, []))) is None


def test_short_device_gaps_are_not_an_admissions():
    devices = {0: [op("fusion.1", "bf16[4,8]", 0, 10),
                   op("fusion.1", "bf16[4,8]", 12.9, 10),   # 2.9 ms gap
                   op("fusion.1", "bf16[4,8]", 26, 4)]}     # 3.1 ms gap
    host = [("engine.step", 0, 30 * MS), ("engine.admit", 1 * MS, 2 * MS)]
    red = R.Reduction(devices, {0: []}, host)
    assert scopes.admissions(red)[0]["idle_s"] == pytest.approx(0.0031)


def test_counter_readers_difference_the_window(red):
    s0 = {"admit_prefill_s": 1.0, "prefill_runs": 3, "warm_admits": 1,
          "engine_loop_s": 20.0, "harvest_wait_s": 19.0}
    s1 = {"admit_prefill_s": 1.8, "prefill_runs": 6, "warm_admits": 2,
          "engine_loop_s": 29.5, "harvest_wait_s": 28.0}
    ctx = serve_ctx(None, stats0=s0, stats1=s1)
    assert read("admit_dispatch_ms", ctx) == pytest.approx(200.0)
    assert read("engine_host_pct", ctx) == pytest.approx(5.0)
    # a program without the counters: the metrics are left out
    old = serve_ctx(None, stats0={"harvests": 1}, stats1={"harvests": 9})
    assert read("admit_dispatch_ms", old) is None
    assert read("engine_host_pct", old) is None
    # a window without an admission has no quotient
    flat = serve_ctx(None, stats0=s0, stats1=dict(s0))
    assert read("admit_dispatch_ms", flat) is None


STEP_MAP = {
    "fusion.1": entry("ff", "bf16[8]"),
    "fusion.2": entry("ff", "bf16[8]", recompute=True),
    "fusion.3": entry("attn.flash_fwd", "bf16[8]", recompute=True),
    "fusion.4": entry("attn.flash_bwd", "bf16[8]"),
    "fusion.5": entry("attn.read", "bf16[8]"),
    "fusion.6": entry("attn.proj", "bf16[8]"),
    "fusion.7": entry("optimizer", "bf16[8]"),
    "fusion.8": entry("unscoped", "bf16[8]"),
}


def train_ctx():
    ops, at = [], 0
    for step in range(2):
        for i, dur in enumerate((30, 10, 4, 6, 2, 20, 8, 20), start=1):
            ops.append(op(f"fusion.{i}", "bf16[8]", at, dur))
            at += dur
    red = R.Reduction({0: ops}, {0: [("jit_step(7)", 0, 100 * MS),
                                     ("jit_step(7)", 100 * MS, 100 * MS)]},
                      [])
    return {"kind": "train", "cell": types.SimpleNamespace(name="toy.train"),
            "trace": red, "_scope_maps": {"step": STEP_MAP},
            "_scope_maps_s": 0.0, "readings": {"seed": 6}}


@pytest.mark.parametrize("name, value", [
    ("train_ff_ms", 40.0),
    ("train_attn_ms", 12.0),        # not the projections
    ("train_optimizer_ms", 8.0),
    ("train_recompute_pct", 14.0),
    ("train_scoped_pct", 80.0),
])
def test_train_readers_give_milliseconds_a_step(name, value):
    assert read(name, train_ctx()) == pytest.approx(value)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_where_there_is_nothing_to_read(name):
    """The other kind of cell, an untraced run, a program that hands out
    no maps (the parent) and a trace that holds none of its programs:
    None each time, never an exception."""
    bench = harness.load_benchmark()
    m = next(x for x in bench["per_layer"] if x["name"] == name)
    kind = "train" if name.startswith("train_") else "serve"
    other = "serve" if kind == "train" else "train"
    empty = R.Reduction({}, {}, [])
    base = serve_ctx(None) if kind == "serve" else train_ctx()
    assert read(name, dict(base, kind=other)) is None
    assert read(name, dict(base, trace=None)) is None
    assert read(name, dict(base, trace=empty, _scope_maps=None)) is None
    assert read(name, dict(base, trace=empty)) is None
    assert m["workloads"] and all(
        harness.Cell(w).kind == kind for w in m["workloads"])


@pytest.mark.parametrize("name, program, scoped", [
    ("dalle-12b.train", "step", {"ff", "attn.proj", "optimizer", "loss"}),
    ("rudalle-xl.serve-full", "_decode_impl_paged", {"ff", "kv.store",
                                                     "sample"}),
])
def test_a_toy_cells_programs_hand_out_their_maps(tmp_path, name, program,
                                                  scoped):
    """The maps of a cell's own programs, built through its family as
    ``aot.py`` builds them (the train step's by the one builder that the
    AOT compile uses), at toy widths on this backend."""
    from benchmark import tiny
    cell = harness.Cell(name, root=tiny.make(str(tmp_path)))
    ctx = {"kind": cell.kind, "cell": cell}
    maps = scopes.program_maps(ctx)
    assert program in maps and ctx["_scope_maps_s"] > 0
    assert scoped <= {e["scope"] for e in maps[program].values()}


def test_a_program_without_the_module_has_no_maps(monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "dalle_pytorch_tpu.obs.device", None)
    ctx = {"kind": "serve", "cell": None}
    assert scopes.program_maps(ctx) is None
    assert scopes.program_maps(ctx) is None and "_scope_maps_s" in ctx


# -- the recorded trace ---------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(reduction, maps) of the v5e probe: the capture gunzipped, and the
    ``scopes.json`` the same engine's ``device_scopes()`` gave."""
    import gzip
    import shutil
    here = os.path.join(harness.HERE, "testdata")
    path = str(tmp_path_factory.mktemp("probe") / "probe.xplane.pb")
    with gzip.open(os.path.join(here, "probe_scopes_v5e.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(os.path.join(here, "probe_scopes_v5e.scopes.json")) as f:
        return R.reduce_file(path), json.load(f)


def test_recorded_trace_joins_its_own_scopes(recorded):
    red, maps = recorded
    assert set(maps) == {"_decode_impl_paged", "prefill_b8"}
    assert [n.split("(")[0] for n, _ in red.module_runs("")] == [
        "jit_prefill_b8", "jit__decode_impl_paged", "jit__decode_impl_paged",
        "jit_prefill_b8"] + ["jit__decode_impl_paged"] * 4
    decode = scopes.by_scope(red, maps, r"decode_impl")
    # six runs; the first and the last may be cut by the capture's edge
    assert decode["runs"] == 4
    assert decode["total_s"] == pytest.approx(1.87049e-4, rel=1e-5)
    us = {k: round(v * 1e6, 2) for k, v in decode["seconds"].items()}
    assert us == {"norm": 15.08, "embed": 16.47, "attn.proj": 4.18,
                  "sample": 95.27, "kv.store": 14.02, "kv.view": 10.82,
                  "unscoped": 2.74, "ff": 8.04, "attn.read": 16.67,
                  "head": 3.75}
    # the served program is the one the maps were compiled from: all but
    # the loop's own counters has a scope
    assert 1 - decode["seconds"]["unscoped"] / decode["total_s"] > 0.98
    prefill = scopes.by_scope(red, maps, r"prefill_b")
    assert prefill["runs"] == 2
    assert prefill["seconds"]["prefill.scatter"] \
        == pytest.approx(13.74e-6, abs=1e-8)
    # the pool copies the compiler added are the view's and the store's
    assert decode["inherited_s"]["kv.view"] > 0
    assert decode["inherited_s"]["kv.store"] > 0


def test_recorded_trace_through_the_readers(recorded):
    red, maps = recorded
    ctx = serve_ctx(red, maps=maps)
    steps = 4 * 2                       # whole runs x chunk_steps 2
    assert read("decode_kv_view_ms", ctx) \
        == pytest.approx(10.82e-3 / steps, rel=1e-3)
    assert read("decode_scoped_pct", ctx) == pytest.approx(98.54, abs=0.01)
    assert read("prefill_device_ms", ctx) \
        == pytest.approx((24.362e-3 + 24.676e-3) / 2, rel=0.02)
    # both admissions lie inside the capture; the host's clock reads
    # 1.2 ms EARLIER than the device's here
    found = scopes.admissions(red)
    assert len(found) == 2 and all(a["put"] > a["prefill"] > a["plan"] > 0
                                   for a in found)
    assert scopes.host_minus_device_ms(red) == pytest.approx(-1.20682)
    # a tiny engine's chip is idle most of the time: gaps over the floor
    assert [a["idle_s"] for a in found] \
        == pytest.approx([0.001270315, 0.001441869], rel=1e-6)
    # the maps of ANOTHER program's compile name nothing here
    other = {"_decode_impl_paged": {k + ".x": v for k, v in
                                    maps["_decode_impl_paged"].items()}}
    assert read("decode_scoped_pct", serve_ctx(red, maps=other)) == 0.0
