"""Multi-host initialization — the DCN-scale entry point of the distributed
backend.

The reference reaches no communication backend at all (SURVEY.md §5.8:
DeepSpeed is built solely for its sparse-attention op; no process groups are
ever initialized). Here multi-host is the standard JAX runtime contract:
every host runs the SAME program, ``initialize()`` wires the processes into
one cluster (coordinator + process id), after which ``jax.devices()`` is the
GLOBAL device list — every mesh/pjit/shard_map in this package then spans
hosts automatically, with XLA routing collectives over ICI within a slice
and DCN across slices (mesh.py's axis-order convention keeps only the dp
psum on DCN).

On Cloud TPU pods ``jax.distributed.initialize()`` autodetects everything
from the metadata server; elsewhere (CPU/GPU clusters, tests) pass
coordinator/process counts explicitly or via the standard env vars. The
data layer is already host-sharded (data.prefetch reads 1/process_count of
the stream per host), so the CLIs become pod-ready by calling this first.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax

_ENV_COORD = "JAX_COORDINATOR_ADDRESS"
_ENV_NPROC = "JAX_NUM_PROCESSES"
_ENV_PID = "JAX_PROCESS_ID"

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids: Optional[Sequence[int]] = None,
               deadline_s: Optional[float] = None,
               max_attempts: int = 3,
               on_event=None) -> bool:
    """Join (or form) the multi-host cluster. Returns True iff distributed
    mode was initialized.

    Resolution order per field: explicit argument, then the standard env
    var (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID),
    then TPU-pod autodetection (when no coordinator is known but jax was
    launched on a pod, ``jax.distributed.initialize()`` with no arguments
    resolves from the metadata server). With neither arguments, env vars,
    nor a pod environment this is a single-process no-op returning False.

    ``deadline_s`` bounds the cluster join (a wedged coordinator otherwise
    pends it indefinitely — the round-5 failure mode): each of
    ``max_attempts`` attempts runs under the deadline with jittered
    exponential backoff between them (resilience.retry), retry records
    flowing to ``on_event``; exhausted attempts raise
    ``resilience.BringupError`` carrying the structured failure record
    instead of hanging. None (default) keeps the legacy unbounded join.

    Caveat: a deadline-cut attempt ABANDONS its daemon thread, which may
    still be blocked inside ``jax.distributed.initialize``; a retry then
    races it against a fresh call. That is acceptable for the wedge this
    defends against (the abandoned call is stuck in connect and never
    mutates the client), but a retried init that merely *straggles* can
    interleave with its successor — a fresh process per attempt is the
    right model for anything beyond a launcher.

    Idempotent: a second call (same process) is a no-op returning True.
    """
    global _initialized
    if _initialized:
        return True
    coord = coordinator_address or os.environ.get(_ENV_COORD)
    nproc = num_processes if num_processes is not None else (
        int(os.environ[_ENV_NPROC]) if _ENV_NPROC in os.environ else None)
    pid = process_id if process_id is not None else (
        int(os.environ[_ENV_PID]) if _ENV_PID in os.environ else None)

    if coord is None and nproc is None:
        # bare single-process run (the common laptop/test case): stay local
        # unless we're visibly on a multi-worker pod (TPU pod env
        # autodetects). A single entry in TPU_WORKER_HOSTNAMES is one host
        # (some runtimes set it to "localhost" even on a single chip) —
        # nothing to join.
        hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
        if len([h for h in hosts.split(",") if h.strip()]) <= 1:
            return False

    def _join(attempt: int = 0):
        from dalle_pytorch_tpu.resilience import faults
        faults.on_backend_init(attempt)
        try:
            jax.distributed.initialize(coordinator_address=coord,
                                       num_processes=nproc,
                                       process_id=pid,
                                       local_device_ids=local_device_ids)
        except RuntimeError as e:
            # someone initialized jax.distributed without going through
            # this module ("distributed.initialize should only be called
            # once")
            msg = str(e).lower()
            if "already" not in msg and "only be called once" not in msg:
                raise

    if deadline_s and deadline_s > 0:
        from dalle_pytorch_tpu.resilience import retry as rretry
        policy = rretry.RetryPolicy(max_attempts=max(max_attempts, 1),
                                    deadline_s=deadline_s)
        rretry.retry_with_backoff(_join, policy, label="multihost_init",
                                  on_event=on_event)
    else:
        _join()
    _initialized = True
    return True


def is_primary() -> bool:
    """True on the process that should write checkpoints/logs (process 0 —
    the multi-host analogue of the reference's single-process scripts
    writing unconditionally)."""
    return jax.process_index() == 0


def fetch_local(x):
    """Materialize a (possibly cross-host-sharded) array as numpy on EVERY
    process — a collective in multi-host mode (all processes must call it
    together), a plain ``np.asarray`` otherwise.

    For epoch-end diagnostics (recon grids, samples) that need concrete
    values: ``np.asarray`` on a dp-sharded global array raises on shards
    owned by other hosts, and feeding per-host-different data into a jit
    over the global mesh would break SPMD consistency — allgathering first
    solves both."""
    import numpy as np
    if jax.process_count() == 1:
        return np.asarray(x)
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(x, tiled=True))
