"""What device the process got, what it can do, and where compiles go.

Three facts every entry point (the training CLIs, the server, its worker
processes, benchmark/run.py, chip_smoke.py, the test suite)
needs the same answer to — kept in one module so none of them can hide
the device behind a default:

  * ``describe_device()`` — platform / device_kind / count as jax reports
    them; logged once at start-up and carried by ``/healthz``, so a jax
    that fell back to the CPU is visible in the first line of every log;
  * ``chip_peaks()`` — published per-chip peaks keyed by ``device_kind``
    WITH their source; a device that is not in the table is an error,
    never a default (a utilization against a guessed peak is not a
    measurement);
  * ``enable_compile_cache()`` — the persistent compilation cache, placed
    from OUTSIDE when ``JAX_COMPILATION_CACHE_DIR`` is set and at one
    fixed path otherwise (the path is part of the cache key: a directory
    that moves never hits).
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# per-chip published peaks, keyed by jax's ``device_kind``
_TPU_DOCS = "Google Cloud TPU documentation, system architecture, "
CHIP_PEAKS = {
    "TPU v4": {"bf16_flops": 275e12, "hbm_bytes_per_s": 1228e9,
               "source": _TPU_DOCS + "'TPU v4'"},
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": _TPU_DOCS + "'TPU v5e'"},
    "TPU v5": {"bf16_flops": 459e12, "hbm_bytes_per_s": 2765e9,
               "source": _TPU_DOCS + "'TPU v5p'"},
    "TPU v6 lite": {"bf16_flops": 918e12, "hbm_bytes_per_s": 1640e9,
                    "source": _TPU_DOCS + "'TPU v6e'"},
}


class UnknownDeviceError(LookupError):
    """``device_kind`` has no row in ``CHIP_PEAKS``: refuse to compute a
    utilization or a roofline share against a guessed peak."""

    def __init__(self, device_kind: str):
        super().__init__(
            f"no published peaks on record for device_kind "
            f"{device_kind!r} (known: {sorted(CHIP_PEAKS)}); add a row "
            f"with its source to utils/device.py CHIP_PEAKS rather than "
            f"defaulting to another chip's")
        self.device_kind = device_kind


def chip_peaks(device_kind: str) -> dict:
    """``{bf16_flops, hbm_bytes_per_s, source}`` for ``device_kind``, or
    the typed ``UnknownDeviceError``."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(device_kind) from None


def describe_device() -> dict:
    """The device jax actually gave this process, as jax reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — fixed, so every process of a command
    (and the next command on the same disk) finds the same entries."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)),
                        ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache before the first compile
    and return its directory. With ``JAX_COMPILATION_CACHE_DIR`` set the
    placement is the environment's (jax reads the variable itself) and
    nothing is changed in code; otherwise the cache lives at
    ``default_cache_dir()``. The ONLY writer of
    ``jax_compilation_cache_dir`` in the tree."""
    outside = os.environ.get(CACHE_ENV)
    if outside:
        return outside
    import jax
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
