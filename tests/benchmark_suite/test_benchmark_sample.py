"""``decode_sample_ms``: the scope ``sample`` of the decode program, a step;
nothing where there is nothing to read."""

import types

import pytest

from benchmark import harness
from benchmark import reduce as R
from test_benchmark_contract import EVERY_SERVE_CELL, check_declared

MS = 1_000_000      # nanoseconds


def entry(scope, shape):
    return {"scope": scope, "shape": shape, "recompute": False,
            "inherited": False, "op_name": ""}


def op(inst, shape, start_ms, dur_ms, kind="fusion"):
    return (f"%{inst} = {shape}{{0}} {kind}(%p)", int(start_ms * MS),
            int(dur_ms * MS))


# a conditional holds its branch's operations as a loop holds its body's
MAPS = {"_decode_impl_paged": {
    "fusion.1": entry("ff", "bf16[4]"),
    "fusion.2": entry("sample", "u32[4,1]"),
    "conditional.1": entry("sample", "f32[4,1]"),
    "sort.3": entry("sample", "f32[4,9]"),
    "fusion.4": entry("sample", "s32[4]")}}


def chunk(at):
    return [op("fusion.1", "bf16[4]", at, 10),
            op("fusion.2", "u32[4,1]", at + 10, 2),
            op("conditional.1", "f32[4,1]", at + 12, 4, "conditional"),
            op("sort.3", "f32[4,9]", at + 12, 4, "sort"),
            op("fusion.4", "s32[4]", at + 16, 1)]


def ctx_of(red, **kw):
    cell = types.SimpleNamespace(
        name="toy.serve", spec={"engine": {"chunk_steps": 4}})
    ctx = {"kind": "serve", "cell": cell, "trace": red, "_scope_maps": MAPS,
           "_scope_maps_s": 0.0, "readings": {"seed": 5}}
    ctx.update(kw)
    return ctx


@pytest.fixture(autouse=True)
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))


def test_reader_gives_the_scopes_milliseconds_a_step():
    red = R.Reduction(
        {0: chunk(0) + chunk(30)},
        {0: [("jit__decode_impl_paged(7)", 0, 20 * MS),
             ("jit__decode_impl_paged(7)", 30 * MS, 20 * MS)]}, [])
    # 2 + 4 + 1 ms a chunk of four steps; the conditional itself not added
    assert harness.load_reader("decode_sample_ms")(ctx_of(red)) \
        == pytest.approx(7 / 4)


def test_reader_finds_nothing_where_there_is_nothing_to_read():
    read = harness.load_reader("decode_sample_ms")
    empty = R.Reduction({}, {}, [])
    assert read(ctx_of(empty, kind="train")) is None
    assert read(ctx_of(None)) is None
    assert read(ctx_of(empty, _scope_maps=None)) is None
    assert read(ctx_of(empty)) is None


def test_the_metric_is_declared_for_the_serve_cells():
    check_declared("decode_sample_ms", **EVERY_SERVE_CELL["decode_sample_ms"])
