"""Profiling hooks: jax.profiler trace capture around train steps.

SURVEY.md §5.1 — the reference has no profiler at all; the TPU build exposes
XLA's own tracer so a Perfetto/TensorBoard trace of the compiled train step
(matmul tiling, collective overlap, host gaps) is one flag away in every CLI
(``--profile_dir``).
"""

from __future__ import annotations

from typing import Optional

import jax


class StepProfiler:
    """Captures a trace window [start, start+steps) inside a training loop:

        prof = StepProfiler(log_dir, start=10, steps=3)
        for i, batch in ...:
            prof.maybe_start(i)
            ...train step...
            prof.maybe_stop(i)
    """

    def __init__(self, log_dir: Optional[str], start: int = 10,
                 steps: int = 3):
        self.log_dir = log_dir
        self.start = start
        self.stop_at = start + steps
        self._active = False

    def maybe_start(self, step: int) -> None:
        if self.log_dir and not self._active and step == self.start:
            jax.profiler.start_trace(self.log_dir)
            self._active = True

    def maybe_stop(self, step: int) -> None:
        if self._active and step + 1 >= self.stop_at:
            jax.profiler.stop_trace()
            self._active = False

    def close(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
