"""Child-process engine worker — the other end of ``serve/ipc.py``.

``worker_main`` is the spawn entrypoint one process-isolated replica
runs: build a private ``Engine`` (own jax client, pinned to this
replica's device), then loop — drain parent frames, step the engine,
ship completed results and heartbeat snapshots back. The worker holds
no authority: every request it runs also lives in the parent's shadow
bookkeeping, so this process can die AT ANY INSTRUCTION — SIGKILL,
SIGSEGV, OOM — and the supervisor replays its open work byte-identically
on a survivor.

The worker is TRANSPORT-AGNOSTIC (``serve/transport.py``): a spawned
child over a duplex pipe (``worker_main``), a spawned child that dials
back over TCP (``worker_main_dial``), and a worker started by hand on
another host (``python -m dalle_pytorch_tpu.serve.worker --connect
HOST:PORT --index N``, token in the ``DALLE_WORKER_TOKEN`` env var) all
run the SAME loop — a dialing worker authenticates with a HELLO and
receives its spec (params + config) over the socket, then is supervised
exactly like a local child. The invariants the worker owns:

  * **Results and the counters that count them ride the same frame.**
    A completion is shipped in a harvest frame whose snapshot already
    includes it; the parent absorbs results before the snapshot. The
    prefix of frames that survives a mid-write kill is therefore always
    a consistent state (see ipc.py's module docstring).
  * **A dead parent means exit, not a leak.** Every transport
    read/write and every idle nap goes through the connection; when the
    parent dies the transport EOFs/resets and the worker ``os._exit``\\ s
    — no orphaned interpreters pinning devices after a parent crash.
    Over a socket this covers the network deaths too: a reset or a
    stalled parent that stops reading surfaces as a transport error and
    the worker dies rather than running unsupervised.
  * **Every frame is sequenced.** The worker numbers its frames and
    verifies the parent's; a transport that loses, duplicates, or
    reorders delivery is caught as a typed protocol error on whichever
    side sees it first — never absorbed into the replay state.
  * **Local handles are stand-ins.** Admitted requests become child-
    local ``RequestHandle``\\ s (same request_id/queue_seq — replay
    identity survives the boundary); the engine fulfils them locally
    and the worker observes+ships the terminal result. The caller's
    real future never leaves the parent.
  * **The RSS watchdog dies loudly.** With ``rss_limit_mb`` set, the
    worker checks its real RSS (/proc/self/statm) every iteration and
    ``os._exit(137)``\\ s past the limit — the container OOM-kill
    convention, and exactly the abrupt no-goodbye death the supervisor
    must handle from a kernel OOM killer.
  * **Known compiles announce themselves.** A cold decode program or
    prefill bucket blocks this loop for seconds with no frames; the
    worker sends a compiling=True heartbeat BEFORE such a step
    (``Engine.compile_pending``), so the parent's hang deadline doesn't
    read warm-up as a wedge and hard-kill a healthy child.
"""

from __future__ import annotations

import os
import time
from typing import Dict

from dalle_pytorch_tpu.serve import ipc
from dalle_pytorch_tpu.serve import scheduler as S
from dalle_pytorch_tpu.serve import transport as T

_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096

# exit codes are protocol (the parent decodes them): 0 clean, 1 crash
# (after a best-effort CRASH frame), 3 parent/transport gone, 4 the
# parent rejected this worker's HELLO (bad token / index / version),
# 5 the spec's local checkpoint is missing/invalid (ipc.BAD_CKPT_EXIT),
# 137 RSS watchdog
PARENT_GONE_EXIT = 3
REJECTED_EXIT = 4


class WorkerCheckpointError(RuntimeError):
    """Typed local-checkpoint failure for a checkpoint-path attach spec
    (``ReplicaSet(worker_ckpt=...)``): the path the spec named is
    missing, fails ``checkpoint.validate`` (truncated payload, crc
    mismatch, absent manifest), or — in ``latest:`` form — no valid
    epoch exists at all. The worker ships the reason in a CRASH frame
    and dies with ``ipc.BAD_CKPT_EXIT`` (5), so the parent's /healthz
    shows an operator-actionable exit instead of a generic crash.
    ``record`` is the structured event."""

    def __init__(self, record: dict):
        super().__init__(
            f"worker checkpoint rejected: {record.get('reason')} "
            f"(path {record.get('path')!r})")
        self.record = record


def load_ckpt_params(spec: dict):
    """Resolve + validate + restore the params a checkpoint-path spec
    names. Two forms: a concrete checkpoint directory (gated by
    ``checkpoint.validate`` — never trust a checkpoint that a partial
    rsync may have torn), or ``latest:<models_dir>:<name>`` resolved
    through ``checkpoint.latest_valid`` (newest epoch that validates —
    the same trust rule auto-resume uses).

    The spec's serving TRANSFORMS then apply worker-side, in the same
    order the in-process CLI applies them — ``ckpt_use_ema`` swaps in
    the checkpoint's EMA weights (``cli.common.ema_as``, restored from
    the SAME resolved directory; a checkpoint without EMA is a typed
    rejection, exit 5), ``ckpt_quantize`` int8-quantizes the decode
    path (``models.dalle.quantize_for_decode``) — so a checkpoint-path
    attach serves weights byte-identical to ``--use_ema``/
    ``--quantize`` applied on the parent, without those weights ever
    crossing the wire."""
    from dalle_pytorch_tpu import checkpoint as ckpt
    from dalle_pytorch_tpu.utils.metrics import structured_event

    path = str(spec["ckpt_path"])
    if path.startswith("latest:"):
        try:
            _, models_dir, name = path.split(":", 2)
        except ValueError:
            raise WorkerCheckpointError(structured_event(
                "serve_worker_ckpt_invalid", path=path,
                reason="malformed latest:<models_dir>:<name> spec")) \
                from None
        found = ckpt.latest_valid(models_dir, name)
        if found is None:
            raise WorkerCheckpointError(structured_event(
                "serve_worker_ckpt_invalid", path=path,
                reason=f"no valid checkpoint for {name!r} under "
                       f"{models_dir!r}"))
        path = found[0]
    else:
        ok, reason = ckpt.validate(path)
        if not ok:
            raise WorkerCheckpointError(structured_event(
                "serve_worker_ckpt_invalid", path=path, reason=reason))
    params, _manifest = ckpt.restore_params(path)
    if spec.get("ckpt_use_ema"):
        ema = ckpt.restore_ema(path)
        if ema is None:
            raise WorkerCheckpointError(structured_event(
                "serve_worker_ckpt_invalid", path=path,
                reason="spec asks for EMA weights but the checkpoint "
                       "carries none (train with --ema_decay)"))
        from dalle_pytorch_tpu.cli.common import ema_as
        params = ema_as(ema, params)
    quantize = str(spec.get("ckpt_quantize") or "none")
    if quantize not in ("none", "int8", "int8_kv"):
        raise WorkerCheckpointError(structured_event(
            "serve_worker_ckpt_invalid", path=path,
            reason=f"unknown ckpt_quantize {quantize!r} (expected "
                   f"'none', 'int8', or 'int8_kv')"))
    if quantize != "none":
        from dalle_pytorch_tpu.models import dalle as D
        params = D.quantize_for_decode(params)
    return params


def rss_mb() -> int:
    """Resident set size in MiB — /proc on Linux; elsewhere, the
    ru_maxrss (PEAK, the best portable stand-in) with the platform's
    units: bytes on macOS, KiB on the rest."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * _PAGE_SIZE // (1 << 20)
    except (OSError, IndexError, ValueError):
        import resource
        import sys
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return peak >> 20 if sys.platform == "darwin" else peak >> 10


class _FrameSender:
    """The worker's one frame-writing point: every frame out carries
    the next tx sequence number, so delivery-order violations are
    detectable on the parent's side of any transport."""

    def __init__(self, transport, start_seq: int):
        self.transport = transport
        self.seq = int(start_seq)

    def send(self, kind: str, payload: dict) -> None:
        self.transport.send_bytes(ipc.encode_frame(kind, payload,
                                                   self.seq))
        self.seq += 1


def worker_main(spec: dict, conn) -> None:
    """Pipe-transport spawn entrypoint (``multiprocessing`` 'spawn'
    context — never fork a live jax runtime)."""
    _worker_shell(spec, T.PipeTransport(conn), start_seq=0)


def worker_main_dial(host: str, port: int, token: str,
                     index: int) -> None:
    """Socket-transport spawn entrypoint: dial the parent's listener,
    HELLO (token + protocol version + index), receive the spec over the
    authenticated socket, then run the same loop. Also the body of the
    hand-started remote worker (``main`` below)."""
    try:
        transport, spec = T.dial_parent(host, port, token, index)
    except T.IPCError as e:
        print(f"serve-worker[{index}]: attach rejected: {e}",
              flush=True)
        os._exit(REJECTED_EXIT)
    except OSError as e:
        print(f"serve-worker[{index}]: cannot reach parent "
              f"{host}:{port}: {e}", flush=True)
        os._exit(PARENT_GONE_EXIT)
    # seq 0 of each direction was spent on HELLO/HELLO_OK
    _worker_shell(spec, transport, start_seq=1)


def _worker_shell(spec: dict, transport, start_seq: int) -> None:
    """Run the loop; translate every way it can end into the exit-code
    protocol. Signals show up as negative exitcodes for the parent to
    decode."""
    sender = _FrameSender(transport, start_seq)
    try:
        _run(spec, transport, sender, rx_seq=start_seq)
    except (EOFError, BrokenPipeError, ConnectionResetError,
            ConnectionAbortedError):
        os._exit(PARENT_GONE_EXIT)  # parent/transport died: leak nothing
    except MemoryError:
        os._exit(ipc.OOM_EXIT)
    except WorkerCheckpointError as e:
        # typed, operator-actionable: ship the reason, die with the
        # checkpoint exit code (the parent decodes 5 as 'fix the path /
        # rsync the checkpoint', not as a crash to diff)
        try:
            sender.send(ipc.CRASH, {"error": repr(e)})
        except Exception:   # noqa: BLE001 — the transport may be gone
            pass
        os._exit(ipc.BAD_CKPT_EXIT)
    except BaseException as e:  # noqa: BLE001 — ship the reason, then die
        try:
            sender.send(ipc.CRASH, {"error": repr(e)})
        except Exception:   # noqa: BLE001 — the transport may be gone too
            pass
        os._exit(1)
    os._exit(0)


def _run(spec: dict, conn, sender: _FrameSender, rx_seq: int) -> None:
    from dalle_pytorch_tpu.resilience import faults

    # the parent decides which plan (if any) this child gets — NOT the
    # env var: fire-once for hard kills must outlive the child, so
    # faults.child_plan_for hands a plan to a replica's first spawn
    # only and a restarted child comes up clean
    if spec.get("faults"):
        faults.activate(faults.FaultPlan(**spec["faults"]))
    rss_limit = int(spec.get("rss_limit_mb") or 0)
    index = int(spec["index"])

    import jax

    from dalle_pytorch_tpu.serve.engine import Engine, MigrationError
    from dalle_pytorch_tpu.utils.device import enable_compile_cache

    # every worker of a fleet compiles the same engine programs: share
    # them through the one persistent cache the parent uses
    enable_compile_cache()
    devices = jax.devices()
    params = spec["params"]
    if params is None:
        # checkpoint-path attach: the spec carried a path, not weights —
        # load + validate LOCALLY (a remote host's own checkpoint store,
        # never a multi-GB pickle over the wire)
        params = load_ckpt_params(spec)
    queue = S.RequestQueue(max_depth=1 << 30, clock=time.perf_counter)
    mesh_m = int(spec.get("devices_per_replica") or 1)
    if mesh_m > 1:
        # replica = mesh slice, in-child: same Engine surface, params +
        # KV sharded over this worker's local device slice
        from dalle_pytorch_tpu.parallel import serve_specs as SS
        from dalle_pytorch_tpu.serve.mesh_engine import MeshEngine
        device = SS.slice_devices(devices, int(spec["device_index"]),
                                  mesh_m)
        engine = MeshEngine(params, spec["cfg"], queue, complete=None,
                            clock=time.perf_counter, devices=device,
                            **spec["engine_kwargs"])
    else:
        device = (devices[int(spec["device_index"]) % len(devices)]
                  if spec.get("place") else None)
        if device is None:
            # Engine device_puts params itself when placed; unplaced, do
            # it here so the numpy pytree isn't re-uploaded every jit
            # call
            params = jax.device_put(params)
        engine = Engine(params, spec["cfg"], queue, complete=None,
                        clock=time.perf_counter, device=device,
                        **spec["engine_kwargs"])

    open_handles: Dict[int, S.RequestHandle] = {}
    # READY announces the weights generation this worker actually
    # serves: during a rolling upgrade the parent re-spawns workers on
    # a NEW ckpt path/params, and the announcement lets the supervisor
    # (and /healthz) verify the attach landed on the generation it
    # asked for — a stale worker dialing a reshaped fleet advertises
    # itself instead of silently serving old weights
    sender.send(ipc.READY, {"pid": os.getpid(), "device": str(device),
                            "rss_mb": rss_mb(),
                            "weights_version": engine.weights_version})

    hb_interval = float(spec.get("heartbeat_interval_s", 0.05))
    idle_sleep = float(spec.get("idle_sleep_s", 0.002))
    last_hb = 0.0
    flight_seq = 0      # ring increments already shipped to the parent

    def send_snapshot(kind: str, results=None,
                      compiling: bool = False) -> None:
        nonlocal last_hb, flight_seq
        chunks = engine.decode_steps // engine.chunk_steps
        snap = ipc.engine_snapshot(engine, chunks, rss_mb(), compiling)
        payload = {"snap": snap}
        # the flight ring's INCREMENTS ride every snapshot frame: the
        # parent's mirror is therefore as fresh as the last frame that
        # landed, which is exactly what a SIGKILL post-mortem can
        # honestly have (spans stamped after the last frame die with
        # this process — a consistent prefix, never a lie)
        flight_seq, events = engine.flight.since(flight_seq)
        if events:
            payload["events"] = events
        if results is not None:
            payload["results"] = results
        sender.send(kind, payload)
        last_hb = time.perf_counter()

    while True:
        # 1. parent frames (admission + control). recv raising EOF /
        # reset here IS the parent-death path _worker_shell handles;
        # a broken sequence from the parent is a protocol error the
        # worker dies loudly on (CRASH frame + exit 1).
        while conn.poll(0):
            kind, payload, seq = ipc.decode_frame(conn.recv_bytes())
            rx_seq = ipc.seq_check(seq, rx_seq)
            if kind == ipc.ADMIT:
                now = time.perf_counter()
                for d in payload["requests"]:
                    h = S.RequestHandle.from_wire(d, now)
                    open_handles[h.request.request_id] = h
                    # requeue, not submit: the handle keeps the parent-
                    # assigned request_id and arrival seq — replay
                    # identity and ordering survive the boundary
                    queue.requeue(h, count=False)
            elif kind == ipc.FENCE:
                engine.fence()
                sender.send(ipc.BYE, {"reason": "fenced"})
                return
            elif kind == ipc.SHUTDOWN:
                engine.cancel_active("server shutdown")
                for h in queue.drain():
                    h.fulfill(S.Result(
                        status=S.CANCELLED,
                        request_id=h.request.request_id,
                        reason="server shutdown"))
                sender.send(ipc.BYE, {"reason": "shutdown"})
                return
            elif kind == ipc.STATS_REQ:
                sender.send(ipc.STATS, {"stats": engine.stats()})
            elif kind == ipc.MIGRATE_OUT:
                # export the named request's live slot and ship the
                # snapshot back. Success VACATES the slot: the request
                # leaves this worker un-fulfilled (the parent moves its
                # shadow to the target), so it is dropped from
                # open_handles WITHOUT a result frame — the target's
                # completion ships it.
                rid = int(payload["request_id"])
                try:
                    snap, _h = engine.export_request(rid)
                except MigrationError as e:
                    sender.send(ipc.MIGRATE_OUT, {
                        "request_id": rid, "ok": False,
                        "reason": e.reason, "error": str(e)})
                except Exception as e:    # noqa: BLE001 — typed fallback
                    sender.send(ipc.MIGRATE_OUT, {
                        "request_id": rid, "ok": False,
                        "reason": "transfer", "error": repr(e)})
                else:
                    open_handles.pop(rid, None)
                    sender.send(ipc.MIGRATE_OUT, {
                        "request_id": rid, "ok": True, "snap": snap})
            elif kind == ipc.MIGRATE_IN:
                # install an exported slot here; the stand-in handle
                # import_slot builds from the payload's wire form joins
                # open_handles so its completion ships as a normal
                # harvest result. A failed import leaves this engine
                # untouched (import_slot discards partial state) — the
                # NACK tells the parent to fall back to replay.
                snap = payload["snap"]
                rid = int(snap.get("request_id", -1))
                try:
                    slot_i = engine.import_slot(snap)
                except MigrationError as e:
                    sender.send(ipc.MIGRATE_ACK, {
                        "request_id": rid, "ok": False,
                        "reason": e.reason, "error": str(e)})
                except Exception as e:    # noqa: BLE001 — typed fallback
                    sender.send(ipc.MIGRATE_ACK, {
                        "request_id": rid, "ok": False,
                        "reason": "transfer", "error": repr(e)})
                else:
                    open_handles[rid] = engine.slots[slot_i].handle
                    sender.send(ipc.MIGRATE_ACK,
                                {"request_id": rid, "ok": True})
            else:
                raise ipc.IPCError(
                    f"unexpected frame kind {kind!r} from parent")

        chunks = engine.decode_steps // engine.chunk_steps
        # the soft catalog (crash raises -> CRASH frame + exit 1; hang
        # sleeps -> missed heartbeats -> the parent hard-kills), the
        # hard catalog (real self-SIGKILL/SIGSEGV, OOM against the
        # watchdog, a corrupt frame), and the NETWORK catalog (reset
        # mid-frame, torn frame, stalled socket, duplicate/reordered
        # frames) all run here, making every serve fault
        # process-drivable
        faults.on_replica_chunk(index, chunks)
        faults.on_worker_chunk(index, chunks,
                               emit_frame=conn.send_bytes,
                               rss_limit_mb=rss_limit, rss_mb=rss_mb,
                               transport=conn, sender=sender)

        # 2. RSS watchdog: die the way a container memory kill does —
        # abruptly, with no goodbye frame, exit 137
        if rss_limit and rss_mb() > rss_limit:
            os._exit(ipc.OOM_EXIT)

        # 3. announce a known-blocking compile BEFORE entering it
        if engine.compile_pending():
            send_snapshot(ipc.HEARTBEAT, compiling=True)

        busy = engine.step_once()

        # 4. ship completions. Batched under the pipe's atomic-write
        # size; ONLY the final batch carries the snapshot, because the
        # snapshot counts every completion in the sweep — a counter
        # must never arrive ahead of the result it counted.
        done = [rid for rid, h in open_handles.items() if h.done()]
        if done:
            wires = []
            for rid in done:
                h = open_handles.pop(rid)
                w = h.result(timeout=0).to_wire()
                if h.trace is not None:
                    # the stand-in trace's spans go home with the
                    # result — the parent merges them into the
                    # caller's timeline (scheduler.RequestHandle
                    # .from_wire seeded the same trace_id)
                    w["spans"] = h.trace.wire_spans()
                wires.append(w)
            for i in range(0, len(wires), ipc.HARVEST_BATCH):
                batch = wires[i:i + ipc.HARVEST_BATCH]
                if i + ipc.HARVEST_BATCH >= len(wires):
                    send_snapshot(ipc.HARVEST, results=batch)
                else:
                    sender.send(ipc.HARVEST,
                                {"results": batch, "snap": None})
        elif time.perf_counter() - last_hb >= hb_interval:
            send_snapshot(ipc.HEARTBEAT)

        # 5. idle nap ON THE TRANSPORT: wakes early for new admissions
        # and notices a dead parent even with nothing to do
        if not busy and engine.idle():
            conn.poll(idle_sleep)


def main(argv=None) -> None:
    """The hand-started / launcher-started worker (remote attach):

        DALLE_WORKER_TOKEN=<token> python -m dalle_pytorch_tpu.serve.worker \\
            --connect HOST:PORT --index N

    Dials the serving parent's ``--transport socket`` listener,
    authenticates, receives its spec over the socket, and serves as
    replica N until the parent fences it, shuts it down, or dies (any
    of which ends this process — a worker never outlives its parent's
    interest in it)."""
    import argparse

    p = argparse.ArgumentParser(
        description="dial into a serve_dalle --transport socket parent "
                    "as one engine-replica worker")
    p.add_argument("--connect", required=True, metavar="HOST:PORT",
                   help="the parent's worker endpoint "
                        "(serve_dalle --worker_endpoint)")
    p.add_argument("--index", type=int, required=True,
                   help="the replica index this worker serves as")
    p.add_argument("--token", default="",
                   help=f"HELLO token (prefer the {T.TOKEN_ENV} env "
                        f"var — argv is visible in `ps`)")
    args = p.parse_args(argv)
    token = args.token or os.environ.get(T.TOKEN_ENV, "")
    if not token:
        raise SystemExit(f"no attach token: set {T.TOKEN_ENV} or pass "
                         f"--token")
    host, port = T.parse_endpoint(args.connect)
    worker_main_dial(host, port, token, args.index)


if __name__ == "__main__":
    main()
