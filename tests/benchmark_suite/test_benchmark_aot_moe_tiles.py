"""The all-held experts' row tiles in the decode programs of
``lfm2-24b-a2b.serve-full`` and ``kanana-2-30b-a3b.serve-full``, compiled
for a described v5e (ISSUE 43): lfm2's 256 pair rows are over the chip's
ridge and every grouped product is handed one tile of 64 sorted rows,
kanana's 192 are under it and handed whole; the products read the expert
stacks in place, and the programs need what their parents needed. Nothing runs; no number from
here is a device metric. The fixtures and the engine over shapes are
``test_benchmark_aot.py``'s and ``test_benchmark_aot_moe_rows.py``'s."""

import re

import pytest

from benchmark import aot, harness
from dalle_pytorch_tpu.ops import moe as moe_ops
from test_benchmark_aot import quiet_cache, topo  # noqa: F401 - fixtures
from test_benchmark_aot_moe_rows import _engine_over_shapes, _shape

# cell -> (what the parent's decode program needs by this compiler, PR 42;
# lfm2's tiled program needs 11,560,993,792; the products' widths:
# ``w_in``'s output and ``w_out``'s)
CELLS = {"lfm2-24b-a2b.serve-full": (11_559_090_688, (3072, 2048)),
         "kanana-2-30b-a3b.serve-full": (10_398_495_744, (1536, 2048))}
ROOM = 16 << 20         # a layer's experts are 1.2 GB and 0.6 GB


@pytest.fixture(scope="module", params=list(CELLS))
def decode_text(request, topo, quiet_cache):  # noqa: F811
    """(the cell's name, its compiled decode program's text, the bytes it
    needs, the shapes of its expert stacks, its tiles a routed layer)."""
    cell = harness.Cell(request.param)
    engine = _engine_over_shapes(cell)
    compiled = aot.compile_decode(engine, topo.devices[0])
    stacks = [tuple(v["ff"]["experts"][k].shape)
              for v in engine.params["transformer"].values()
              if "experts" in v["ff"] for k in ("w_in", "w_out")]
    pairs = int(cell.spec["num_slots"]) \
        * engine.cfg.transformer.block.experts_per_token
    return (request.param, compiled.as_text(), aot.bytes_needed(compiled),
            stacks, moe_ops.row_tiles(pairs))


def test_every_grouped_product_is_handed_one_tile_of_rows(decode_text):
    name, text, _, _, tiles = decode_text
    rows = re.findall(r"%ragged-dot-none[\w.]* = bf16\[(\d+),(\d+)\]", text)
    assert tiles == {"lfm2-24b-a2b.serve-full": 4,
                     "kanana-2-30b-a3b.serve-full": 1}[name]
    handed = moe_ops.ROW_TILE if tiles > 1 else 192
    assert rows and {int(r) for r, _ in rows} == {handed}
    assert {int(w) for _, w in rows} == set(CELLS[name][1])
    # a routed layer's place in the program (a scan's body, or a lone
    # layer's) has a pair of products a tile
    assert len(rows) % (2 * tiles) == 0


def test_no_tile_copies_an_expert_stack_or_a_layer_s_experts(decode_text):
    """Whatever has the shape of an expert stack, of the stack as the
    groups the products index, or of a layer's experts is an argument, a
    tuple's element or a bitcast of one."""
    _, text, _, stacks, _ = decode_text
    assert stacks
    for dims in stacks:
        for shape in (dims, (dims[0] * dims[1],) + dims[2:], dims[1:],
                      (1,) + dims[1:]):
            made_by = set(re.findall(
                r"= " + re.escape(_shape(shape)) + r"\S* ([\w\-]+)\(", text))
            assert made_by <= {"parameter", "get-tuple-element", "bitcast"}, (
                shape, made_by)
        made_by = set(re.findall(
            r"= " + re.escape(_shape(dims)) + r"\S* ([\w\-]+)\(", text))
        assert made_by, dims


def test_the_program_needs_what_its_parent_needed(decode_text):
    name, _, needed, _, _ = decode_text
    assert needed <= CELLS[name][0] + ROOM
