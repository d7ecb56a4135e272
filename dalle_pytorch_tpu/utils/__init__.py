"""Aux subsystems: metrics, profiling, debug toggles (SURVEY.md §5.1/2/5).

Lazy exports (mirroring the root package): ``utils.metrics`` must be
importable without jax — resilience.retry emits structured bring-up
failure records from threads that must not import jax themselves (the
jax import stays inside the deadline-bounded claim thread).
"""

__all__ = ["MetricsLogger", "StepProfiler", "enable_nan_checks",
           "check_finite_tree", "guard_loss", "structured_event"]

_EXPORTS = {
    "MetricsLogger": ("dalle_pytorch_tpu.utils.metrics", "MetricsLogger"),
    "structured_event": ("dalle_pytorch_tpu.utils.metrics",
                         "structured_event"),
    "StepProfiler": ("dalle_pytorch_tpu.utils.profiling", "StepProfiler"),
    "enable_nan_checks": ("dalle_pytorch_tpu.utils.debug",
                          "enable_nan_checks"),
    "check_finite_tree": ("dalle_pytorch_tpu.utils.debug",
                          "check_finite_tree"),
    "guard_loss": ("dalle_pytorch_tpu.utils.debug", "guard_loss"),
}


def __getattr__(name):
    if name in _EXPORTS:
        import importlib
        module, attr = _EXPORTS[name]
        return getattr(importlib.import_module(module), attr)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
