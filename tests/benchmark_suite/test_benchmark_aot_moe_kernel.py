"""The routed experts' kernel in the four routed cells' programs (ISSUE 44).
Traced, every cell: a decode step's products are the repo's kernel
(``ops/moe.py expert_products``) and none of the compiler's grouped
products; an admission's rows are over the kernel's and its program holds
the compiler's grouped products and no kernel; a cell without a routed
layer never reaches the function. Compiled for a described v5e, the two
forms (lfm2's: every expert held, a whole expert a grid step, scanned
runs and lone layers; mimo's: a share, the three steps of a ladder inside
a conditional; kanana's and trinity's decode programs are held by
``test_benchmark_aot_moe_tiles.py``'s and ``test_benchmark_aot.py``'s
cases): the ``tpu_custom_call``s named ``moe.experts`` are counted, the
kernel indexes the expert stacks in place, the program needs what its
parent needed, and the scope map puts every kernel call in
``moe.experts``. Nothing runs; no number from here is a device metric.
The fixtures and the engine over shapes are ``test_benchmark_aot.py``'s
and ``test_benchmark_aot_moe_rows.py``'s."""

import re

import pytest

from benchmark import aot, harness
from dalle_pytorch_tpu.obs import device as obs_device
from dalle_pytorch_tpu.ops import moe as moe_ops
from test_benchmark_aot import quiet_cache, topo  # noqa: F401 - fixtures
from test_benchmark_aot_moe_rows import _engine_over_shapes, _shape

# cell -> the kernel calls of its traced decode step: one a routed layer's
# place (a scan's body, or a lone layer's), times the steps of the ladder
# around the products (mimo's three)
ROUTED = {"lfm2-24b-a2b.serve-full": 4, "kanana-2-30b-a3b.serve-full": 1,
          "mimo-v2.5.serve-full": 9, "trinity-large-preview.serve-full": 3}
# the cells compiled -> what the parent's decode program needs by this
# compiler, PR 43, compiled beside this one (the kernel's programs need
# 11,557,126,656 / 10,022,016,512; kanana's and trinity's 10,398,302,208
# / 10,687,604,224 for 10,398,495,744 / 10,688,282,112)
COMPILED = {"lfm2-24b-a2b.serve-full": 11_560_993_792,
            "mimo-v2.5.serve-full": 10_023_210_496}
DENSE = ["dalle-12b.train", "rudalle-xl.serve-full", "dalle-12b.serve-full",
         "phi-4-mini-flash-reasoning.serve-full"]
KERNEL = re.compile(r"%(moe\.experts[\w.]*) = [^\n]*custom-call\([^\n]*"
                    r'custom_call_target="tpu_custom_call"')


class _TraceOnly:
    """Stands in for a jitted function under ``aot.compile_decode`` /
    ``compile_prefill``, which build its arguments: traces it, keeps the
    jaxpr's text, lowers and compiles nothing. Which products a call runs
    is decided as it is traced."""

    def __init__(self, fn):
        self.fn, self.jaxpr = fn, None

    def trace(self, *args):
        self.jaxpr = str(self.fn.trace(*args).jaxpr)
        return self

    def lower(self, **kwargs):
        return self

    def compile(self):
        return self


def _traced_prefill(engine, device) -> str:
    stand_in = _TraceOnly(engine._prefill_fn(max(engine.buckets)))
    engine._prefill_fn = lambda bucket: stand_in
    return aot.compile_prefill(engine, max(engine.buckets), device).jaxpr


@pytest.mark.parametrize("name", list(ROUTED))
def test_a_decode_step_traces_the_kernel_and_an_admission_does_not(
        name, topo):  # noqa: F811
    """An admission's thousands of pair rows are over ``KERNEL_ROWS``: its
    program is the parent's (line for line by ``benchmark/aot.py``'s
    compiled text when the kernel came: PERF.md section 6, PR 44)."""
    engine = _engine_over_shapes(harness.Cell(name))
    engine._decode_fn = _TraceOnly(engine._decode_fn)
    decode = aot.compile_decode(engine, topo.devices[0]).jaxpr
    assert decode.count("pallas_call[") == ROUTED[name]
    assert "ragged_dot" not in decode
    prefill = _traced_prefill(engine, topo.devices[0])
    assert "ragged_dot" in prefill and "pallas_call" not in prefill


@pytest.fixture(scope="module", params=list(COMPILED))
def programs(request, topo, quiet_cache):  # noqa: F811
    """(the cell's name, its compiled decode program's text, the bytes it
    needs, the shapes of its expert stacks)."""
    engine = _engine_over_shapes(harness.Cell(request.param))
    decode = aot.compile_decode(engine, topo.devices[0])
    stacks = [tuple(v["ff"]["experts"][k].shape)
              for v in engine.params["transformer"].values()
              if "experts" in v["ff"] for k in ("w_in", "w_out")]
    return (request.param, decode.as_text(), aot.bytes_needed(decode),
            stacks)


def test_every_routed_layer_s_products_are_the_kernel(programs):
    name, text, _, _ = programs
    assert len(KERNEL.findall(text)) == ROUTED[name]
    assert "ragged-dot" not in text


def test_the_kernel_reads_the_expert_stacks_in_place(programs):
    """Whatever has the shape of an expert stack, of the stack as the
    groups the kernel indexes, or of a layer's experts is an argument, a
    tuple's element or a bitcast of one."""
    _, text, _, stacks = programs
    assert stacks
    for dims in stacks:
        for shape in (dims, (dims[0] * dims[1],) + dims[2:], dims[1:],
                      (1,) + dims[1:]):
            made_by = set(re.findall(
                r"= " + re.escape(_shape(shape)) + r"\S* ([\w\-]+)\(", text))
            assert made_by <= {"parameter", "get-tuple-element", "bitcast"}, (
                shape, made_by)
        for shape in (dims, (dims[0] * dims[1],) + dims[2:]):
            assert re.search(r"= " + re.escape(_shape(shape)), text), shape


def test_the_program_needs_what_its_parent_needed(programs):
    name, _, needed, _ = programs
    assert abs(needed - COMPILED[name]) <= COMPILED[name] // 100


def test_the_scope_map_puts_every_kernel_call_in_moe_experts(programs):
    """``moe_experts_roofline`` divides by the device time of the scope:
    a kernel call that fell out of it would read the share over 100%."""
    _, text, _, _ = programs
    scopes = obs_device.scopes_of_hlo(text)
    calls = KERNEL.findall(text)
    assert calls
    for call in calls:
        assert scopes[call]["scope"] == "moe.experts", scopes[call]
        assert not scopes[call]["inherited"]


@pytest.mark.parametrize("name", DENSE)
def test_a_cell_without_a_routed_layer_never_reaches_the_products(
        name, topo, monkeypatch):  # noqa: F811
    """Its programs are the parent's whatever the rule says: traced with
    a ``dropless_experts`` that refuses to be called."""
    def refuse(*args, **kwargs):
        raise AssertionError("a dense cell reached the routed experts")
    monkeypatch.setattr(moe_ops, "dropless_experts", refuse)
    cell = harness.Cell(name)
    if cell.kind == "train":
        with aot.as_on_tpu():
            step, args = aot.train_step(cell, topo.devices)
            text = str(step.trace(*args).jaxpr)
    else:
        engine = _engine_over_shapes(cell)
        engine._decode_fn = _TraceOnly(engine._decode_fn)
        text = aot.compile_decode(engine, topo.devices[0]).jaxpr \
            + _traced_prefill(engine, topo.devices[0])
    assert "ragged_dot" not in text
