"""Each cell's program compiles for a described v5e at its published
widths and stated depth and slots, and fits the chip: a later PR that
breaks a cell's fit fails here, on the CPU. Nothing runs; no number from
here is a device metric. One file, one worker: only one process may hold
the TPU compiler (see the on-chip-measurement guide)."""

import pytest

from benchmark import aot, harness

HBM = 15.75e9      # what the v5e's compiler allows a program


@pytest.fixture(scope="module")
def topo():
    try:
        return aot.topology("v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def quiet_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


TRAIN = [w["name"] for w in harness.load_benchmark()["workloads"]
         if w["traffic"] == "train"]
SERVE = [w["name"] for w in harness.load_benchmark()["workloads"]
         if w["traffic"] != "train"]


@pytest.mark.parametrize("name", TRAIN)
def test_train_step_compiles_and_fits(topo, quiet_cache, name):
    cell = harness.Cell(name)
    compiled = aot.compile_train_step(cell, topo.devices)
    text = compiled.as_text()
    assert "tpu_custom_call" in text            # the flash kernel is in
    if cell.chips > 1:
        assert "all-reduce" in text and "all-gather" in text
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes < HBM


@pytest.mark.parametrize("name", SERVE)
def test_decode_and_prefill_programs_compile_and_fit(topo, quiet_cache, name):
    cell = harness.Cell(name)
    engine = aot.serve_engine(cell)
    dev = topo.devices[0]
    decode = aot.compile_decode(engine, dev)
    m = decode.memory_analysis()
    assert m.alias_size_in_bytes > 0            # the pool is donated
    assert m.argument_size_in_bytes < HBM
    longest = max(engine.buckets)
    pre = aot.compile_prefill(engine, longest, dev)
    # admission copies the pool (it is not donated there): both fit
    pm = pre.memory_analysis()
    assert pm.argument_size_in_bytes + pm.output_size_in_bytes < HBM
    assert engine.decode_traces == 1
