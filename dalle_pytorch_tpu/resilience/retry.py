"""Backend/cluster bring-up under a deadline, with backoff + jitter.

A backend or cluster join can pend forever (``jax.devices()`` inside a
wedged claim has no deadline of its own, and nothing retries it). This
module is the single bring-up discipline the entry points share —
``parallel.multihost.initialize`` (the training CLIs) and
``InferenceServer.start`` both route through it:

  * ``call_with_deadline`` — run a claim in a daemon thread; if it does not
    finish by the deadline, raise ``DeadlineExceeded`` (the wedged thread is
    abandoned — a pending claim cannot be cancelled, but the PROCESS stays
    in control of its window).
  * ``retry_with_backoff`` — exponential backoff with jitter between
    attempts (jitter desynchronizes a pod's workers re-claiming a shared
    coordinator after an outage), emitting a structured retry record per
    failure so post-hoc analysis can tell "stale because wedged" from
    "retried and recovered".
  * ``BringupError`` — the terminal failure, carrying the structured record
    (label, attempts, per-attempt errors, elapsed) that callers log through
    utils.metrics instead of hanging past their deadline.

Every knob lives in ``RetryPolicy`` so tests inject milliseconds where
production uses minutes.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time
from typing import Callable, Optional, Sequence


class DeadlineExceeded(TimeoutError):
    """A bring-up attempt did not finish inside its deadline."""


class BringupError(RuntimeError):
    """Terminal bring-up failure. ``record`` is the structured event dict
    (``utils.metrics.structured_event`` shape) describing every attempt."""

    def __init__(self, record: dict):
        super().__init__(
            f"{record.get('label', 'bring-up')} failed after "
            f"{record.get('attempts')} attempt(s): "
            f"{(record.get('errors') or ['?'])[-1]}")
        self.record = record


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Deadline + exponential-backoff-with-jitter parameters.

    ``deadline_s`` bounds each ATTEMPT (None = no per-attempt deadline);
    backoff between attempt ``a`` and ``a+1`` is
    ``min(base * multiplier**a, max_backoff)`` scaled by a uniform
    ``[1-jitter, 1+jitter]`` draw."""
    max_attempts: int = 3
    deadline_s: Optional[float] = 600.0
    base_backoff_s: float = 5.0
    backoff_multiplier: float = 2.0
    max_backoff_s: float = 120.0
    jitter: float = 0.25

    def backoff(self, attempt: int,
                rng: Optional[random.Random] = None) -> float:
        base = min(self.base_backoff_s * self.backoff_multiplier ** attempt,
                   self.max_backoff_s)
        if self.jitter <= 0:
            return base
        r = rng if rng is not None else random
        return base * r.uniform(1.0 - self.jitter, 1.0 + self.jitter)


def failure_record(label: str, errors: Sequence[str], attempts: int,
                   elapsed_s: float, **extra) -> dict:
    """The one structured shape for terminal bring-up failures (shared by
    multihost init, the server's claim, and the tests that assert on it)."""
    from dalle_pytorch_tpu.utils.metrics import structured_event
    return structured_event("bringup_failure", label=label,
                            attempts=attempts, errors=list(errors),
                            elapsed_s=round(elapsed_s, 3), **extra)


def call_with_deadline(fn: Callable, deadline_s: Optional[float],
                       label: str = "bring-up"):
    """Run ``fn()`` in a daemon thread, waiting at most ``deadline_s``.

    Returns ``fn``'s result; re-raises its exception. On timeout raises
    ``DeadlineExceeded`` and ABANDONS the thread (daemon: it cannot keep
    the process alive) — the standard move for an uncancellable pending
    claim.
    ``deadline_s`` None or <= 0 calls ``fn`` inline."""
    if not deadline_s or deadline_s <= 0:
        return fn()
    box: dict = {}

    def _run():
        try:
            box["result"] = fn()
        except BaseException as e:          # noqa: BLE001 — re-raised below
            box["error"] = e

    t = threading.Thread(target=_run, daemon=True,
                         name=f"deadline:{label}")
    t.start()
    t.join(deadline_s)
    if t.is_alive():
        raise DeadlineExceeded(
            f"{label} did not finish within {deadline_s:g} s")
    if "error" in box:
        raise box["error"]
    return box.get("result")


def retry_with_backoff(fn: Callable, policy: RetryPolicy, *,
                       label: str = "bring-up",
                       on_event: Optional[Callable[[dict], None]] = None,
                       rng: Optional[random.Random] = None,
                       sleep: Callable[[float], None] = time.sleep):
    """``fn(attempt)`` under ``policy``: each attempt deadline-bounded,
    failures retried with jittered exponential backoff.

    ``on_event`` receives a structured record per retry (kind
    ``bringup_retry``) so the metrics stream shows "retried and recovered"
    runs distinctly from clean ones. Exhausted attempts raise
    ``BringupError`` carrying the terminal ``failure_record``."""
    from dalle_pytorch_tpu.utils.metrics import structured_event
    errors: list = []
    t0 = time.monotonic()
    for attempt in range(max(policy.max_attempts, 1)):
        try:
            return call_with_deadline(lambda: fn(attempt),
                                      policy.deadline_s, label)
        except (KeyboardInterrupt, SystemExit):
            # an operator abort must exit NOW, not be recorded as a
            # retryable bring-up failure and slept through max_attempts
            # times over
            raise
        except BaseException as e:          # noqa: BLE001 — recorded, rethrown
            errors.append(f"{type(e).__name__}: {e}")
            last = attempt == max(policy.max_attempts, 1) - 1
            if not last:
                delay = policy.backoff(attempt, rng)
                if on_event is not None:
                    on_event(structured_event(
                        "bringup_retry", label=label, attempt=attempt + 1,
                        error=errors[-1], backoff_s=round(delay, 3)))
                sleep(delay)
    record = failure_record(label, errors, max(policy.max_attempts, 1),
                            time.monotonic() - t0,
                            deadline_s=policy.deadline_s)
    if on_event is not None:
        on_event(record)
    raise BringupError(record)
