"""Fault-tolerance runtime (ISSUE 1): the failure modes that dominate long
pod runs — wedged backend bring-up, preemption, loss-spike divergence, and
flaky data paths — handled as first-class, *tested* behavior instead of
lost runs.

Layout:
  * ``retry``      — deadline + exponential backoff + jitter bring-up,
                     shared by parallel.multihost, the server and the CLIs;
                     failures degrade to a structured record, never a hang.
  * ``supervisor`` — the supervised train-step wrapper the training CLIs
                     use: SIGTERM/SIGINT preemption checkpoints, cadence
                     checkpoints with retention/GC, auto-resume from the
                     newest *valid* checkpoint, NaN/loss-spike rollback
                     with optional LR re-warm.
  * ``faults``     — deterministic fault injection (hung init, mid-run
                     SIGTERM, NaN batches, corrupt checkpoints, crashing
                     iterators) so every behavior above runs on CPU in
                     tier-1 tests (pytest -m faults).

Policy and contracts: docs/RESILIENCE.md.
"""

from dalle_pytorch_tpu.resilience.retry import (BringupError,
                                                DeadlineExceeded,
                                                RetryPolicy,
                                                call_with_deadline,
                                                failure_record,
                                                retry_with_backoff)
from dalle_pytorch_tpu.resilience.supervisor import (Preempted,
                                                     TrainingDiverged,
                                                     TrainSupervisor,
                                                     find_auto_resume)

__all__ = ["RetryPolicy", "BringupError", "DeadlineExceeded",
           "call_with_deadline", "retry_with_backoff", "failure_record",
           "TrainSupervisor", "Preempted", "TrainingDiverged",
           "find_auto_resume"]
