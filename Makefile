# Canonical invocations for dalle_pytorch_tpu development.
#
# CPU targets pin JAX_PLATFORMS=cpu (tests, harness smokes: kernels
# interpreted, 8 virtual devices). Chip targets run where jax finds a TPU —
# from a sandbox without one, through the chip tool (PERF.md).

# No XLA_FLAGS device forcing here: tests/conftest.py and
# __graft_entry__.dryrun_multichip set up the 8-device CPU mesh themselves
CPU_ENV := JAX_PLATFORMS=cpu

.PHONY: test test-fast dryrun chip-smoke demo-rehearsal demo lint \
	serve-stream

test:            ## full suite on the virtual 8-device CPU mesh (~25 min)
	$(CPU_ENV) python -m pytest tests/ -q

test-fast:       ## kernels + transformer + parallel only (~5 min)
	$(CPU_ENV) python -m pytest tests/test_kernels.py \
	    tests/test_transformer.py tests/test_parallel.py -q

dryrun:          ## the driver's multi-chip validation (8 virtual devices)
	$(CPU_ENV) python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

chip-smoke:      ## the main path end to end on the chip (PERF.md)
	python chip_smoke.py

serve-stream:    ## streaming/fan-out tier
	$(CPU_ENV) python -m pytest tests/test_stream.py tests/test_fanout.py \
	    tests/test_ipc.py -q

demo-rehearsal:  ## end-to-end demo pipeline, tiny knobs, scratch dirs
	$(CPU_ENV) OUT=/tmp/demo_rehearsal/out DATA=/tmp/demo_rehearsal/data \
	    MODELS=/tmp/demo_rehearsal/models IMG_N=48 IMG_SIZE=32 \
	    VAE_EPOCHS=1 DALLE_EPOCHS=1 CFG_EPOCHS=1 CLIP_EPOCHS=1 DIM=32 \
	    DEPTH=2 TOKENS=64 CDIM=32 HID=16 LAYERS=2 bash scripts/tpu_demo.sh

demo:            ## the real trained demo on the chip
	bash scripts/tpu_demo.sh

lint:            ## syntax check + jaxlint + racelint (AST rule gates)
	$(CPU_ENV) python -m compileall -q dalle_pytorch_tpu tests scripts \
	    chip_smoke.py __graft_entry__.py
	for f in scripts/*.sh; do bash -n $$f || exit 1; done
	$(CPU_ENV) python -m dalle_pytorch_tpu.analysis.jaxlint \
	    dalle_pytorch_tpu tests scripts chip_smoke.py
	$(CPU_ENV) python -m dalle_pytorch_tpu.analysis.racelint \
	    dalle_pytorch_tpu tests scripts chip_smoke.py
