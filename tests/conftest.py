"""Test configuration: force an 8-device CPU platform BEFORE jax initialises.

Multi-chip behaviour (DP/TP/SP meshes, collectives) is tested on a virtual
8-device CPU mesh — the standard JAX substitute for a pod (SURVEY.md §4e).
Must run before any jax import in the test process.
"""

import os

# Force, don't setdefault: tests run on the virtual CPU mesh whatever
# platform the session environment names (the chip is exercised by
# chip_smoke.py through the chip tool, never by pytest).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

from dalle_pytorch_tpu.utils.device import enable_compile_cache  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

# Share XLA executables across the run — and across runs — via the
# persistent compilation cache, placed by the one helper every entry point
# uses: JAX_COMPILATION_CACHE_DIR when the environment sets it, else the
# fixed <checkout>/.jax_cache (the path is part of the cache key, so a
# per-run temporary name could never be warm). Many tests build identical
# programs from DISTINCT jit objects (every serve test constructs its own
# Engine, whose fused decode program re-traces but compiles to the same
# HLO), and on the CPU backend XLA compilation dominates tier-1 wall time.
# Trace-count contracts are unaffected: guards.compile_count and
# Engine.decode_traces count TRACES, which still happen once per jit
# object. Process-isolated serving tests spawn child workers
# (serve/worker.py) that call the same helper, so they land on the same
# directory without any hand-off.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

# ... thresholds through the ENVIRONMENT too: the child workers build their
# own jax from env vars, not from this process's jax.config
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.5"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
