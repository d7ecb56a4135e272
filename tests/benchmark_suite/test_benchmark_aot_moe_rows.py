"""The held experts' row ladder in ``mimo-v2.5.serve-full``'s decode
program, compiled for a described v5e (ISSUE 41): the grouped products
of the first branch are handed 64 rows, the branches read the expert
stacks in place, and the program needs what its parent needed. Nothing
runs; no number from here is a device metric. Only one process may hold
the TPU compiler: ``test_benchmark_aot.py``'s fixtures, used here, skip
where that file's worker already does and several loads are not allowed
(the driver's command allows them)."""

import re

import pytest

from benchmark import aot, harness
from test_benchmark_aot import quiet_cache, topo  # noqa: F401 - fixtures

CELL = "mimo-v2.5.serve-full"
# what the parent's decode program needs (PR 40, this compiler:
# 10,021,984,768 B; the ladder's program 10,023,210,496) and the room a
# step's (512, 4096) float32 rows may take; a layer's experts are 0.8 GB
PARENT_BYTES = 10_021_984_768
ROOM = 16 << 20


def _engine_over_shapes(cell):
    """``aot.serve_engine`` with the weights as shapes: the decode program
    is traced from shapes alone, and this file runs beside
    ``test_benchmark_aot.py`` on another worker, so it does not hold 6.9
    GB of zeros as well (3.3 GB at the peak for 10.1)."""
    import jax
    import jax.numpy as jnp

    from benchmark import seeds
    from dalle_pytorch_tpu.serve import engine as engine_mod
    from dalle_pytorch_tpu.serve import scheduler as S
    spec, family = cell.spec, cell.family
    dims = family.weights.dims_of(cell.config, spec["depth"])
    cfg = family.build.program_config(dims, spec["flags"])
    shapes = jax.eval_shape(lambda: family.weights.tree(
        seeds.split_seed(0), dims, jnp.dtype(cell.config["param_dtype"])))
    eng = spec["engine"]
    with aot.as_on_tpu():
        return engine_mod.Engine(
            shapes, cfg, S.RequestQueue(max_depth=8,
                                        max_prompt_len=cfg.text_seq_len),
            num_slots=int(spec["num_slots"]),
            chunk_steps=int(eng["chunk_steps"]), kv=eng["kv"],
            paged_attn=eng["paged_attn"])


@pytest.fixture(scope="module")
def decode_text(topo, quiet_cache):  # noqa: F811
    """(the compiled decode program's text, the bytes it needs, the
    shapes of the scanned expert stacks)."""
    engine = _engine_over_shapes(harness.Cell(CELL))
    compiled = aot.compile_decode(engine, topo.devices[0])
    experts = engine.params["transformer"]["moe"]["ff"]["experts"]
    return (compiled.as_text(), aot.bytes_needed(compiled),
            {k: tuple(experts[k].shape) for k in ("w_in", "w_out")})


def _shape(dims) -> str:
    return "bf16[" + ",".join(str(d) for d in dims) + "]"


def test_the_first_branch_hands_the_products_64_rows(decode_text):
    text = decode_text[0]
    # a grouped product is the compiler's ``ragged-dot`` custom call: its
    # operands are the group metadata, then the rows, then the weights
    calls = re.findall(
        r"%(ragged-dot-none[\w.]*) = (bf16\[\d+,\d+\])[^\n]*custom-call\("
        r"([^)]*)\)", text)
    rows = {}
    for name, out, operands in calls:
        lhs = operands.split(",")[-2].split("%")[-1].strip()
        shape = re.search(
            r"%" + re.escape(lhs) + r" = (bf16\[\d+,\d+\])", text).group(1)
        rows.setdefault(shape, []).append(out)
    # every routed layer's place in the program has the three steps, each
    # with its ``w_in`` product (rows x 4096 in) and ``w_out`` (x 2048 in)
    assert set(rows) == {f"bf16[{r},{w}]" for r in (64, 128, 512)
                         for w in (4096, 2048)}
    assert len(rows["bf16[64,4096]"]) == len(rows["bf16[512,4096]"]) >= 1
    assert len(re.findall(r"\bconditional\(", text)) >= len(
        rows["bf16[64,4096]"])


def test_no_branch_copies_an_expert_stack_or_a_layer_s_experts(decode_text):
    """Whatever has the shape of an expert stack, of the stack as the
    groups the products index, or of a layer's experts is an argument, a
    tuple's element or a bitcast of one: no copy, fusion or slice makes
    such a value."""
    text, _, stacks = decode_text
    assert stacks == {"w_in": (5, 16, 4096, 4096),
                      "w_out": (5, 16, 2048, 4096)}
    for dims in stacks.values():
        for shape in (dims, (dims[0] * dims[1],) + dims[2:], dims[1:],
                      (1,) + dims[1:]):
            made_by = set(re.findall(
                r"= " + re.escape(_shape(shape)) + r"\S* ([\w\-]+)\(", text))
            assert made_by and made_by <= {
                "parameter", "get-tuple-element", "bitcast"}, (shape, made_by)


def test_the_program_needs_what_its_parent_needed(decode_text):
    assert decode_text[1] <= PARENT_BYTES + ROOM
