"""Typed IPC for process-isolated replica serving.

``serve/replica.py``'s fence/reclaim/replay protocol was built process-
shape-agnostic; this module is the process shape. One replica = one
child process (``serve/worker.py``) running its own Python interpreter,
its own jax client, its own ``Engine`` — so a segfault in XLA, a host
OOM kill, or an operator ``kill -9`` takes down ONE replica, not the
set. Parent and child share nothing but a transport
(``serve/transport.py``: a duplex pipe, or a dial-back TCP socket for
host-per-engine isolation and remote attach) carrying framed,
versioned, sequence-numbered, checksummed messages:

  parent -> child:  ADMIT (request batches), FENCE, SHUTDOWN, STATS_REQ,
                    MIGRATE_OUT (export one request's slot snapshot),
                    MIGRATE_IN (install a snapshot exported elsewhere)
  child -> parent:  READY, HEARTBEAT, HARVEST (completed-result batches
                    + the engine-state snapshot), STATS, CRASH, BYE,
                    MIGRATE_OUT (the export reply: snapshot or typed
                    refusal), MIGRATE_ACK (the import verdict)

Design rules, each load-bearing for the zero-loss contract:

  * **The parent never trusts the child.** Every handle routed to a
    child stays in the parent-side *shadow* (``ChildEngineClient
    .shadow``) until its result frame lands. Reclaim-on-death reads the
    shadow, never asks the corpse — a SIGKILLed child answers nothing.
  * **Counters ride the frames that explain them.** A harvest frame
    carries the child's lifetime counters and per-request progress AS
    OF that frame, and completions are never counted ahead of the
    frame that ships their result. Whatever prefix of frames the
    parent managed to read before the child died is therefore a
    CONSISTENT state: salvaged results fulfil their handles, everything
    still open is reclaimed, and the retire math (counters minus
    reclaimed requests' progress) keeps the set's aggregates counting
    distinct delivered tokens — exactly through a `kill -9`.
  * **Corruption fences, never hangs.** Every frame is
    magic+version+kind+CRC32-checked before its payload is parsed; a
    truncated or garbage frame raises a typed ``IPCError``, the client
    marks itself poisoned, and the supervisor fences the replica (kill
    + reclaim + replay) — the one safe response to a peer whose stream
    can no longer be believed.
  * **Delivery order is verified, not assumed.** Every frame carries a
    per-connection sequence number; a gap (lost frame) or a duplicate/
    reordered delivery raises ``IPCError`` and fences the replica. A
    pipe cannot reorder, but the zero-loss replay contract must not
    depend on that accident of transport: a lossy or re-delivering
    path (a proxy, a broken relay, a resumed stream) is caught at the
    protocol layer, where fencing is the defined response — counters
    and results can never be silently double-absorbed or skipped.
  * **Two clocks never cross the pipe raw.** Deadlines ship as
    remaining budget; latency is restamped against the parent's clock
    at fulfilment. The only cross-process timestamps are the snapshot
    stamps used for the IPC-lag metric, taken from ``perf_counter`` —
    CLOCK_MONOTONIC on Linux, one epoch machine-wide.

The client is SINGLE-OWNER by design: only the replica set's control
thread (threaded mode) or the sync driver (tests/bench) may touch
``route``/``pump``/``fence``/``reclaim`` — the same no-reentrancy
discipline as ``Engine.step_once``, which is what lets the whole
protocol run lock-free in the parent.
"""

from __future__ import annotations

import json
import multiprocessing as mp
import os
import pickle
import signal
import struct
import subprocess
import time
import zlib
from collections import deque
from typing import Callable, Dict, List, Optional

from dalle_pytorch_tpu.obs import flight as oflight
from dalle_pytorch_tpu.serve import scheduler as S
from dalle_pytorch_tpu.serve import transport as T
from dalle_pytorch_tpu.serve.engine import COUNTERS
from dalle_pytorch_tpu.serve.transport import IPCError  # noqa: F401
#                       (re-export: the typed error every layer fences on)

# v2: the header grew a per-connection frame sequence number, and the
# handshake kinds (HELLO/HELLO_OK) joined for socket-transport attach.
# The header version pins the FRAME LAYOUT only; payload schema evolves
# by field tolerance instead (Request/Result.from_wire `.get` defaults
# — e.g. the streaming/fan-out fields stream/n_samples/
# image_seq_len_override decode from a pre-streaming peer's frames as
# their defaults), so a rolling upgrade can mix peers without a flag
# day. Bump this ONLY when the header itself changes shape.
PROTOCOL_VERSION = 2

# frame kinds — parent -> child
ADMIT = "admit"
FENCE = "fence"
SHUTDOWN = "shutdown"
STATS_REQ = "stats_req"
# frame kinds — child -> parent
READY = "ready"
HEARTBEAT = "heartbeat"
HARVEST = "harvest"
STATS = "stats"
CRASH = "crash"
BYE = "bye"
# handshake (socket transport only; see transport.WorkerListener)
HELLO = "hello"
HELLO_OK = "hello_ok"
# live migration (serve/engine.py export_slot/import_slot): MIGRATE_OUT
# is bidirectional — the parent's export request and the child's reply
# (snapshot or typed refusal); MIGRATE_IN ships a snapshot to a target
# child, answered by MIGRATE_ACK. Appended AFTER the v2 kinds so every
# existing frame keeps its positional id on the wire.
MIGRATE_OUT = "migrate_out"
MIGRATE_IN = "migrate_in"
MIGRATE_ACK = "migrate_ack"

KINDS = (ADMIT, FENCE, SHUTDOWN, STATS_REQ,
         READY, HEARTBEAT, HARVEST, STATS, CRASH, BYE,
         HELLO, HELLO_OK,
         MIGRATE_OUT, MIGRATE_IN, MIGRATE_ACK)
_KIND_ID = {k: i for i, k in enumerate(KINDS)}

_MAGIC = 0xD5
# magic, version, kind, pad, seq, crc32(payload)
_HEADER = struct.Struct("<BBBxII")

# results per harvest frame: keeps every frame comfortably under the
# pipe's atomic-write buffer (a frame torn across writes by a kill
# mid-send must be the rare case the CRC catches, not the common one)
HARVEST_BATCH = 8

# exit code the worker dies with when its RSS watchdog trips — the
# 128+SIGKILL convention container runtimes use for memory kills, so
# operators read it the same way in either environment
OOM_EXIT = 137

# exit code for a worker whose LOCAL checkpoint (ckpt-path attach specs,
# serve/worker.py) is missing or fails checkpoint.validate — a typed,
# operator-actionable death distinct from a crash: fix the path / rsync
# the checkpoint, the circuit breaker retries meanwhile
BAD_CKPT_EXIT = 5


def encode_frame(kind: str, payload: dict, seq: int = 0) -> bytes:
    body = json.dumps(payload, separators=(",", ":")).encode()
    return _HEADER.pack(_MAGIC, PROTOCOL_VERSION, _KIND_ID[kind],
                        seq & 0xFFFFFFFF, zlib.crc32(body)) + body


def decode_frame(data: bytes):
    """-> (kind, payload, seq). Raises ``IPCError`` on anything
    untrustworthy."""
    if len(data) < _HEADER.size:
        raise IPCError(f"truncated frame: {len(data)} bytes < "
                       f"{_HEADER.size}-byte header")
    magic, version, kind_id, seq, crc = _HEADER.unpack_from(data)
    if magic != _MAGIC:
        raise IPCError(f"bad magic 0x{magic:02x}")
    if version != PROTOCOL_VERSION:
        raise IPCError(f"protocol version skew: peer speaks v{version}, "
                       f"this process v{PROTOCOL_VERSION}")
    if kind_id >= len(KINDS):
        raise IPCError(f"unknown frame kind id {kind_id}")
    body = data[_HEADER.size:]
    if zlib.crc32(body) != crc:
        raise IPCError("payload checksum mismatch (corrupt or torn frame)")
    try:
        payload = json.loads(body.decode())
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise IPCError(f"unparseable payload: {e}") from None
    if not isinstance(payload, dict):
        raise IPCError(f"payload must be an object, got "
                       f"{type(payload).__name__}")
    return KINDS[kind_id], payload, seq


def seq_check(got: int, expected: int) -> int:
    """Verify one received frame's sequence number; returns the next
    expected. A mismatch is a transport that lost, duplicated, or
    reordered delivery — typed ``IPCError``, and the peer is fenced:
    replay correctness cannot survive a stream whose order or
    exactly-once delivery is broken. The wire field is u32; the
    comparison masks so a counter past 2^32 doesn't false-fence."""
    if got != (expected & 0xFFFFFFFF):
        how = ("duplicate or reordered delivery"
               if got < (expected & 0xFFFFFFFF)
               else "gap: lost frame(s)")
        raise IPCError(f"frame sequence broken: got seq {got}, "
                       f"expected {expected & 0xFFFFFFFF} ({how})")
    return expected + 1


def engine_snapshot(engine, chunks: int, rss_mb: int,
                    compiling: bool) -> dict:
    """The child's engine state as one wire dict — counters, per-request
    progress, occupancy and kv facts — built by the worker and absorbed
    by ``ChildEngineClient``. Progress keys are stringified (JSON
    objects key on strings); the client converts them back."""
    snap = {
        "counters": engine.counters(),
        "progress": {str(k): int(v)
                     for k, v in engine.progress_snapshot().items()},
        "active_slots": int(engine.active_slots()),
        "queued": int(engine.queue.depth()),
        "chunks": int(chunks),
        "compiling": bool(compiling),
        "rss_mb": int(rss_mb),
        "t": time.perf_counter(),
        "pages_free": (int(engine.alloc.free)
                       if engine.kv == "paged" else -1),
        # the engine's head-of-line page reservation, if any: the oldest
        # page-deferred request's (id, pages needed). Mirrored so the
        # parent can hand the reservation back to the shared queue when
        # this replica is fenced/drained — a retiring replica must not
        # take a waiting request's page claim to the grave with it.
        "hol": (None if engine.kv != "paged"
                or getattr(engine, "_hol_rid", None) is None
                else [int(engine._hol_rid), int(engine._hol_need)]),
    }
    return snap


def _snap_fields(payload: dict):
    """Validate + convert a snapshot payload; IPCError on wrong shapes."""
    try:
        raw = payload["counters"]
        if not isinstance(raw, dict):
            raise TypeError(f"counters must be a dict, got "
                            f"{type(raw).__name__}")
        # .get: a worker built before a COUNTERS key existed (version
        # skew on a hand-started remote attach) reports 0 for it — the
        # same decode-as-default tolerance Request.from_wire gives
        # unknown request fields, instead of poisoning every heartbeat
        counters = {k: int(raw.get(k, 0)) for k in COUNTERS}
        progress = {int(k): int(v)
                    for k, v in payload["progress"].items()}
        # .get: a pre-elastic worker's snapshots carry no hol field —
        # decode as "no reservation" instead of poisoning the stream
        raw_hol = payload.get("hol")
        hol = (None if raw_hol is None
               else (int(raw_hol[0]), int(raw_hol[1])))
        return (counters, progress, int(payload["active_slots"]),
                int(payload["queued"]), int(payload["chunks"]),
                bool(payload["compiling"]), int(payload["rss_mb"]),
                float(payload["t"]), int(payload["pages_free"]), hol)
    except (KeyError, TypeError, ValueError, IndexError) as e:
        raise IPCError(f"malformed snapshot: {e!r}") from None


class ChildEngineClient:
    """Parent-side endpoint for one child-process engine replica.

    Quacks enough like ``Engine`` for the replica set's supervisor,
    router, and stats aggregation to stay mode-agnostic: the
    ``COUNTERS`` show through as attributes (mirrored from the last
    frame), plus ``num_slots`` / ``kv`` / ``active_slots()`` /
    ``last_heartbeat`` / ``compiling`` / ``fenced`` /
    ``inflight_handles()``. What it adds is the process half: PID
    liveness, exit decoding, the shadow bookkeeping, and hard-kill.

    Three LAUNCH shapes, picked by ``transport`` + ``worker_cmd``:

      * ``transport='pipe'`` (default): spawn a local child over a
        duplex pipe — PR 8's shape, unchanged;
      * ``transport='socket'``, ``worker_cmd=None``: spawn a local
        child that DIALS BACK to the parent's ``WorkerListener`` and
        receives its spec over the authenticated socket — same
        supervision, network transport;
      * ``transport='socket'``, ``worker_cmd=<template>``: launch the
        worker via an operator command (``{endpoint}``/``{index}``/
        ``{token}`` placeholders; ``{endpoint}`` is the advertised —
        dialable — address, and the token also ships via the
        ``DALLE_WORKER_TOKEN`` env var for local launchers) — e.g.
        ``ssh otherhost env DALLE_WORKER_TOKEN={token} python -m
        dalle_pytorch_tpu.serve.worker --connect {endpoint} --index
        {index}``; ``worker_cmd=''`` launches NOTHING and waits for a
        hand-started worker to dial in (remote attach). Either way the
        attached worker is supervised exactly like a spawned child:
        shadow bookkeeping, heartbeat deadline, fence→reclaim→replay.
        Without a local PID, the socket itself is the liveness signal —
        a reset or EOF on it declares the replica dead."""

    def __init__(self, params, cfg, *, index: int,
                 engine_kwargs: dict,
                 device_index: int = 0,
                 place: bool = False,
                 devices_per_replica: int = 1,
                 ckpt_path: Optional[str] = None,
                 ckpt_use_ema: bool = False,
                 ckpt_quantize: str = "none",
                 heartbeat_interval_s: float = 0.05,
                 rss_limit_mb: int = 0,
                 fault_plan: Optional[dict] = None,
                 idle_sleep_s: float = 0.002,
                 clock: Callable[[], float] = time.perf_counter,
                 on_done: Optional[Callable] = None,
                 transport: str = "pipe",
                 listener: Optional[T.WorkerListener] = None,
                 worker_cmd: Optional[str] = None):
        from dalle_pytorch_tpu.serve import worker as worker_mod

        self.clock = clock
        self.index = int(index)
        self.num_slots = int(engine_kwargs.get("num_slots", 4))
        self.chunk_steps = int(engine_kwargs.get("chunk_steps", 8))
        self.kv = str(engine_kwargs.get("kv", "dense"))
        self.on_done = on_done
        self.transport_kind = str(transport)
        if ckpt_path is None and params is None:
            raise ValueError("ChildEngineClient needs params or a "
                             "ckpt_path for the worker to load from")
        spec = {
            "index": self.index,
            # numpy pytree (picklable) — or, with ckpt_path, NOTHING:
            # the worker loads + validates the checkpoint locally
            # (serve/worker.py), and the attach spec shrinks from the
            # weight pytree to a path string
            "params": None if ckpt_path is not None else params,
            "ckpt_path": ckpt_path,
            # worker-side serving transforms for ckpt-path specs: the
            # worker applies EMA swap / int8 quantization AFTER its
            # local load (serve/worker.py load_ckpt_params), so remote
            # workers serve the same weights --use_ema/--quantize give
            # the in-process engine
            "ckpt_use_ema": bool(ckpt_use_ema),
            "ckpt_quantize": str(ckpt_quantize),
            "cfg": cfg,
            "engine_kwargs": dict(engine_kwargs),
            "device_index": int(device_index),
            "place": bool(place),
            "devices_per_replica": int(devices_per_replica),
            "heartbeat_interval_s": float(heartbeat_interval_s),
            "rss_limit_mb": int(rss_limit_mb),
            "faults": fault_plan,
            "idle_sleep_s": float(idle_sleep_s),
        }
        self._listener = listener
        self._proc = None
        self._popen = None
        self._conn = None
        self.pid: Optional[int] = None
        self.peer = ""
        self.remote_host = ""
        self.awaiting_operator = False
        if transport == "pipe":
            # spawn, not fork: the parent holds a live jax runtime, and
            # a forked copy of it is undefined behaviour — the child
            # builds its own interpreter and its own jax client from
            # scratch, which is the entire point of the isolation
            ctx = mp.get_context("spawn")
            parent_end, child_end = ctx.Pipe(duplex=True)
            self._conn = T.PipeTransport(parent_end)
            self._proc = ctx.Process(
                target=worker_mod.worker_main, args=(spec, child_end),
                daemon=True, name=f"serve-worker-{index}")
            self._proc.start()
            # the parent MUST close its copy of the child's end: the
            # child detects parent death as EOF on the pipe, which only
            # happens when no live process holds a write handle
            child_end.close()
            self.pid = self._proc.pid
            self.peer = f"pipe:pid={self.pid}"
        elif transport == "socket":
            if listener is None:
                raise ValueError("transport='socket' needs a "
                                 "WorkerListener")
            # the spec travels over the authenticated socket AFTER the
            # HELLO, so a hand-started remote worker needs nothing but
            # endpoint + token + index
            listener.expect(self.index, pickle.dumps(spec))
            if worker_cmd is None:
                ctx = mp.get_context("spawn")
                self._proc = ctx.Process(
                    target=worker_mod.worker_main_dial,
                    args=(listener.dial_host, listener.port,
                          listener.token, self.index),
                    daemon=True, name=f"serve-worker-{index}")
                self._proc.start()
                self.pid = self._proc.pid
            elif worker_cmd == "":
                # remote attach: an operator (or an external launcher)
                # starts the worker by hand; no spawn deadline applies
                self.awaiting_operator = True
            else:
                import shlex
                # {endpoint} is the ADVERTISED address (a 0.0.0.0 bind
                # is not a destination a remote host can dial); {token}
                # is for launchers that cross a host boundary — a plain
                # env var does not survive ssh (no SendEnv), so the
                # documented ssh form inlines it via `env` on the far
                # side. The env var still covers local launchers.
                cmd = worker_cmd.format(
                    endpoint=listener.advertise_endpoint,
                    index=self.index, token=listener.token)
                env = dict(os.environ)
                env[T.TOKEN_ENV] = listener.token
                self._popen = subprocess.Popen(shlex.split(cmd), env=env)
                self.pid = self._popen.pid
        else:
            raise ValueError(f"unknown transport {transport!r}")
        self.started_t = self.clock()
        # per-connection frame sequencing: over a socket, seq 0 of each
        # direction was spent on HELLO/HELLO_OK during the handshake
        self._tx_seq = 1 if transport == "socket" else 0
        self._rx_seq = 1 if transport == "socket" else 0

        # lifecycle flags (single-owner: control thread / sync driver)
        self.ready = False
        self.fenced = False
        self.crashed = False            # child shipped a CRASH frame
        self.poisoned = False           # protocol error: fence me
        self.bye = False                # clean goodbye received
        self.last_error = ""
        self.worker_weights_version = ""    # READY announcement

        # the shadow: every handle routed here and not yet resolved —
        # the reclaim surface, owned and trusted by the parent only
        self.shadow: Dict[int, S.RequestHandle] = {}

        # parent-side MIRROR of the child engine's flight recorder:
        # heartbeat/harvest frames carry the child ring's increments,
        # so the last-N events of a SIGKILLed child survive here — the
        # fence dump reads this mirror, never asks the corpse
        self.flight = oflight.FlightRecorder(capacity=512)

        # last-frame mirror of the child engine's state
        self.counter_state = {k: 0 for k in COUNTERS}
        self.progress: Dict[int, int] = {}
        self.active = 0
        self.queued = 0
        self.chunks = 0
        self.compiling = True           # bring-up IS a compile phase
        self.rss_mb = 0
        self.pages_free = -1
        self.hol = None                 # (rid, need) per the last frame
        self.last_heartbeat = self.clock()
        self.last_frame_t = self.clock()    # ANY decoded frame stamps it
        self.stats_reply: Optional[dict] = None
        # the child's parked answer to the ONE in-flight migration
        # (export reply or import ack) — single-owner control thread,
        # migrations run serially, so one slot suffices
        self.migrate_reply: Optional[dict] = None
        # child-stamp -> parent-absorb lag per frame (what isolation
        # adds to a harvest); perf_counter is
        # CLOCK_MONOTONIC on Linux — one epoch across processes
        self.ipc_lag_s: deque = deque(maxlen=10_000)

    def __getattr__(self, name):
        # the COUNTERS surface (tokens_decoded, decode_traces, ...)
        # mirrors the last frame — this is what lets the replica set's
        # _agg()/stats() read a client exactly like an Engine
        counters = self.__dict__.get("counter_state")
        if counters is not None and name in counters:
            return counters[name]
        raise AttributeError(name)

    # -- transport adoption (socket dial-back) ------------------------------

    def _maybe_attach(self) -> None:
        """Adopt the transport a dialing worker completed the HELLO
        handshake on (socket mode; the listener parks it under this
        replica's index). Spawned-socket children, launcher-started
        workers, and hand-started remote workers all arrive here."""
        if self._conn is not None or self._listener is None:
            return
        t = self._listener.take(self.index)
        if t is None:
            return
        # short send bound from here on: this transport is now driven
        # by the control thread that supervises EVERY replica, and one
        # worker that stops reading must cost a recorded send failure
        # (fence + replay), never stall the others' heartbeat deadlines
        t.set_send_timeout(2.0)
        self._conn = t
        self.peer = t.peer
        hello = t.hello or {}
        if self.pid is None:
            # a remote worker's pid: triage info for /healthz, never a
            # liveness signal — the socket is the liveness signal
            pid = hello.get("pid")
            self.pid = int(pid) if isinstance(pid, int) else None
        self.remote_host = str(hello.get("host") or "")
        if self.awaiting_operator:
            self.awaiting_operator = False
            # the wait for an operator was open-ended; supervision
            # deadlines (attach -> READY) start now
            self.started_t = self.clock()

    # -- sending ------------------------------------------------------------

    def _send(self, kind: str, payload: dict) -> bool:
        self._maybe_attach()
        if self._conn is None:
            if not self.last_error:
                self.last_error = "no worker transport attached yet"
            return False
        try:
            self._conn.send_bytes(encode_frame(kind, payload,
                                               self._tx_seq))
            self._tx_seq += 1
            return True
        except (OSError, ValueError) as e:
            if not self.last_error:
                self.last_error = f"transport write failed: {e!r}"
            # a write failure over a STILL-LIVE stream (a peer that
            # stopped reading, a send timeout) leaves routed handles
            # stranded unless someone fences: the dropped frame also
            # un-syncs our tx sequence, so this stream can never be
            # trusted again — poison, and the supervisor fences +
            # replays the shadow. When the transport itself is dead,
            # liveness (PID, or the socket state for a remote worker)
            # already tells the story and fences the same way.
            if self._conn.alive():
                self.poisoned = True
            return False

    def route(self, handles: List[S.RequestHandle]) -> None:
        """Hand requests to the child. They enter the shadow FIRST: if
        the write fails (child mid-death), the reclaim sweep still owns
        them and they replay on a survivor — routed work is never lost
        to a torn pipe."""
        now = self.clock()
        for h in handles:
            self.shadow[h.request.request_id] = h
        self._send(ADMIT, {"requests": [h.to_wire(now) for h in handles]})

    def request_stats(self) -> None:
        self._send(STATS_REQ, {})

    # -- live migration (parent side) ----------------------------------------

    def _await_migrate(self, timeout: float) -> Optional[dict]:
        """Pump until the child answers the in-flight migration frame
        (or the stream dies / the deadline passes — None). Absorbs
        every other frame kind normally while waiting, so heartbeats
        and harvests keep landing mid-transfer."""
        deadline = self.clock() + timeout
        while True:
            self.pump(0.01)
            reply, self.migrate_reply = self.migrate_reply, None
            if reply is not None:
                return reply
            if self.poisoned or self.crashed or self.fenced \
                    or not self.alive_proc() \
                    or self.clock() >= deadline:
                return None

    def export_request(self, request_id: int,
                       timeout: float = 30.0) -> dict:
        """Ask the child to export ``request_id``'s slot (MIGRATE_OUT)
        and return the snapshot payload. On success the child has
        already vacated the slot — the parent-side handle stays in THIS
        client's shadow until the caller hands it to the target. Raises
        the typed ``MigrationError`` when the child refuses, dies
        mid-transfer, or never answers (the replay-fallback signal:
        the handle is still shadow-owned, so nothing is lost)."""
        from dalle_pytorch_tpu.serve.engine import MigrationError
        if int(request_id) not in self.shadow:
            raise MigrationError(
                "not_found", f"request {request_id} is not routed here")
        if not self._send(MIGRATE_OUT, {"request_id": int(request_id)}):
            raise MigrationError(
                "source_dead",
                self.last_error or "transport write failed")
        reply = self._await_migrate(timeout)
        if reply is None:
            raise MigrationError(
                "source_dead",
                self.last_error or "source died or went silent "
                "mid-transfer")
        if not reply.get("ok"):
            raise MigrationError(str(reply.get("reason") or "transfer"),
                                 str(reply.get("error") or ""))
        snap = reply.get("snap")
        if not isinstance(snap, dict):
            raise MigrationError("transfer", "malformed export reply "
                                 "(no snapshot object)")
        return snap

    def import_request(self, snap: dict, handle: S.RequestHandle,
                       timeout: float = 30.0) -> None:
        """Ship an exported snapshot to this child (MIGRATE_IN) and
        wait for its MIGRATE_ACK. The handle enters the shadow FIRST —
        ``route``'s rule: if the child dies mid-import, the reclaim
        sweep still owns the request and it replays. A refused or
        unanswered import pops the handle back out and raises the
        typed ``MigrationError`` so the caller's fallback ladder
        (requeue-for-replay) runs."""
        from dalle_pytorch_tpu.serve.engine import MigrationError
        rid = int(snap.get("request_id", -1))
        self.shadow[rid] = handle
        sent = self._send(MIGRATE_IN, {"snap": snap})
        reply = self._await_migrate(timeout) if sent else None
        if reply is None or not reply.get("ok"):
            self.shadow.pop(rid, None)
            if reply is None:
                raise MigrationError(
                    "target_dead",
                    self.last_error or "target died or went silent "
                    "mid-import")
            raise MigrationError(str(reply.get("reason") or "transfer"),
                                 str(reply.get("error") or ""))

    # -- receiving ----------------------------------------------------------

    def pump(self, poll_s: float = 0.0) -> bool:
        """Drain and dispatch every complete frame the child has sent.
        Returns True when any frame was processed. A fenced client
        never pumps (late frames from a zombie must not fulfil
        anything); a frame that fails to decode poisons the client —
        the supervisor fences it on the next sweep."""
        if self.fenced:
            return False
        self._maybe_attach()
        if self._conn is None:
            return False
        did = False
        first = True
        while True:
            try:
                if not self._conn.poll(poll_s if first else 0):
                    break
                data = self._conn.recv_bytes()
            except IPCError as e:
                # the transport itself caught a lie: a torn frame, a
                # reset mid-frame, an oversize length — fence material
                self.poisoned = True
                self.last_error = f"protocol error: {e}"
                break
            except (EOFError, OSError):
                # clean close at a frame boundary: liveness (PID for a
                # local child, the socket state for a remote worker)
                # tells the story
                break
            first = False
            did = True
            try:
                kind, payload, seq = decode_frame(data)
                self._rx_seq = seq_check(seq, self._rx_seq)
                self.last_frame_t = self.clock()
                self._dispatch(kind, payload)
            except IPCError as e:
                self.poisoned = True
                self.last_error = f"protocol error: {e}"
                break
        return did

    def _dispatch(self, kind: str, payload: dict) -> None:
        if kind == READY:
            self.ready = True
            self.compiling = True       # first chunks still compile
            self.last_heartbeat = self.clock()
            try:
                self.rss_mb = int(payload.get("rss_mb", 0))
            except (TypeError, ValueError):
                raise IPCError(f"malformed READY: {payload!r}") from None
            # what generation the worker SAYS it serves (rolling
            # upgrades re-spawn workers on new weights; the replica
            # set verifies the attach landed on the one it asked for).
            # .get: a pre-elastic worker simply doesn't announce.
            self.worker_weights_version = \
                str(payload.get("weights_version") or "")
        elif kind in (HEARTBEAT, HARVEST):
            # flight-ring increments first (the mirror should already
            # hold the spans/events that EXPLAIN a result when it
            # lands), then results, then the snapshot that counts them
            # — absorbing in this order keeps parent state consistent
            # even if a later frame never arrives. .get + isinstance:
            # a pre-obs worker ships no events; a malformed entry is
            # advisory observability, dropped rather than fenced over.
            for ev in payload.get("events") or ():
                if isinstance(ev, dict):
                    self.flight.record(ev)
            if kind == HARVEST:
                for d in payload.get("results", ()):
                    self._absorb_result(d)
            if payload.get("snap") is not None:
                self._absorb_snapshot(payload["snap"])
            self.last_heartbeat = self.clock()
        elif kind == STATS:
            reply = payload.get("stats")
            if not isinstance(reply, dict):
                raise IPCError(f"malformed STATS: {payload!r}")
            self.stats_reply = reply
        elif kind == CRASH:
            self.crashed = True
            self.last_error = str(payload.get("error", "child crash"))
        elif kind == BYE:
            self.bye = True
        elif kind in (MIGRATE_OUT, MIGRATE_ACK):
            # the child's verdict on the in-flight export/import —
            # parked for the control thread's _await_migrate
            self.migrate_reply = payload
        else:
            raise IPCError(f"unexpected frame kind {kind!r} from child")

    def _absorb_result(self, d: dict) -> None:
        try:
            result = S.Result.from_wire(d)
        except (KeyError, TypeError, ValueError) as e:
            raise IPCError(f"malformed result: {e!r}") from None
        handle = self.shadow.pop(result.request_id, None)
        if handle is None or handle.done():
            return      # reclaimed+replayed already, or a stale echo
        # the child's span records ride the result frame: merge them
        # into the parent trace (same machine, one CLOCK_MONOTONIC
        # epoch, so they tile against the parent's route span) and
        # re-anchor the tiling pointer at the absorb instant — the
        # postprocess span starts here. Advisory: malformed spans are
        # skipped inside merge_wire, never fence material.
        if handle.trace is not None and d.get("spans"):
            handle.trace.merge_wire(d["spans"], self.clock())
        # honest caller-observed latency: restamp against the PARENT
        # clock and the caller's real submit time (the child's stamps
        # are relative to its own admission)
        result.total_s = round(self.clock() - handle.request.submit_t, 6)
        if self.on_done is not None:
            self.on_done(handle, result)
        else:
            handle.fulfill(result)

    def _absorb_snapshot(self, snap: dict) -> None:
        (self.counter_state, self.progress, self.active, self.queued,
         self.chunks, self.compiling, self.rss_mb, stamp,
         self.pages_free, self.hol) = _snap_fields(snap)
        self.ipc_lag_s.append(max(time.perf_counter() - stamp, 0.0))

    # -- supervision surface ------------------------------------------------

    def active_slots(self) -> int:
        return self.active

    def inflight_handles(self) -> List[S.RequestHandle]:
        return list(self.shadow.values())

    def alive_proc(self) -> bool:
        """The replica's liveness, by the strongest signal available.
        Over a socket, a dead CONNECTION means a dead replica whatever
        the process state — an unreachable engine cannot serve, and a
        remote worker has no PID to ask. With a local process (spawn)
        or a launcher child (Popen), PID liveness layers on top. A
        worker not yet attached counts as alive: the spawn/attach
        deadline, not this check, bounds that phase."""
        if self._conn is not None and self._conn.kind == "socket" \
                and not self._conn.alive():
            return False
        if self._proc is not None:
            return self._proc.is_alive()
        if self._popen is not None:
            if self._popen.poll() is None:
                return True
            # the launcher exited (an ssh relay dropping out): the
            # worker may still be up — believe the live socket
            return self._conn is not None and self._conn.alive()
        if self._conn is None:
            return True         # attach mode, still awaiting dial-in
        return self._conn.alive()

    @staticmethod
    def _decode_exit(code: Optional[int]) -> str:
        if code is None:
            return "running"
        if code < 0:
            try:
                name = signal.Signals(-code).name
            except ValueError:
                name = f"signal {-code}"
            return f"killed by {name}"
        if code == OOM_EXIT:
            return f"oom-killed (exit {OOM_EXIT}: child RSS limit)"
        if code == BAD_CKPT_EXIT:
            return (f"invalid checkpoint (exit {BAD_CKPT_EXIT}: the "
                    f"worker's local checkpoint failed validation)")
        return f"exit code {code}"

    def exit_desc(self) -> str:
        """Decode how the child died — the second liveness signal. A
        negative exitcode is the terminating signal (SIGKILL for a host
        OOM killer or `kill -9`, SIGSEGV for an XLA crash); exit 137 is
        the worker's own RSS watchdog (container OOM convention). A
        worker with no local process (remote attach) has only the
        connection's state to report."""
        if self._proc is not None:
            return self._decode_exit(self._proc.exitcode)
        if self._popen is not None:
            return self._decode_exit(self._popen.poll())
        if self._conn is None:
            return "no worker attached"
        return f"remote worker: {self._conn.state_desc()}"

    def transport_info(self, now: Optional[float] = None) -> dict:
        """The per-replica transport block /healthz and /stats carry:
        transport kind, peer address, and seconds since the last
        decoded frame (the staleness an operator actually triages
        with; heartbeat_age tracks only HEARTBEAT/HARVEST)."""
        now = self.clock() if now is None else now
        info = {"transport": self.transport_kind,
                "peer": self.peer or "unattached",
                "last_frame_age_s": round(
                    max(now - self.last_frame_t, 0.0), 4)}
        if self.remote_host:
            info["worker_host"] = self.remote_host
        return info

    # -- fencing / teardown -------------------------------------------------

    def fence(self) -> None:
        """One-way: after this, no frame from the child is ever
        processed again — its requests belong to the reclaim sweep.
        The transport is released too (a fenced client never reads or
        writes again; holding the fd would leak one per failover on a
        long-lived server), and any dial-in expectation this replica
        registered is cancelled so a stale worker cannot attach to a
        fenced slot. Closing the socket is also what tells a live
        remote worker its parent is gone — it EOFs and exits on its
        own (the worker's no-leak contract)."""
        self.fenced = True
        if self._listener is not None:
            try:
                self._listener.cancel(self.index)
            except Exception:   # noqa: BLE001 — teardown best-effort
                pass
        if self._conn is not None:
            try:
                self._conn.close()
            except OSError:
                pass

    def hard_kill(self, join_s: float = 5.0) -> None:
        """SIGKILL the child (idempotent; a corpse stays dead). No
        grace: by the time a replica is being fenced, its child is
        crashed, wedged, or lying — all three deserve -9. A remote
        worker has no process to signal — frames it already wrote
        remain salvageable, and the fence's transport close is what
        reaches it."""
        if self._proc is not None:
            if self._proc.is_alive():
                try:
                    self._proc.kill()
                except (OSError, ValueError):
                    pass
            self._proc.join(join_s)
        elif self._popen is not None:
            try:
                self._popen.kill()
            except OSError:
                pass
            try:
                self._popen.wait(join_s)
            except (OSError, subprocess.TimeoutExpired):
                pass

    def salvage(self) -> None:
        """After the child is down: drain every complete frame it wrote
        before dying. Results that made it into the pipe fulfil their
        handles (they will NOT be replayed); the final snapshot brings
        the counter mirror to the last consistent state. Call BEFORE
        ``fence`` — a fenced client drops frames."""
        while self.pump():
            pass

    def reclaim(self) -> List[S.RequestHandle]:
        """Every routed, still-open handle — the replay set. Clears the
        shadow; call exactly once, after ``salvage`` + ``fence``."""
        out = [h for h in self.shadow.values() if not h.done()]
        self.shadow.clear()
        return out

    def retire_counters(self,
                        reclaimed: List[S.RequestHandle]) -> Dict[str, int]:
        """The dead child's counters minus the reclaimed requests'
        harvested prefixes (per the last frame's progress map): replay
        re-credits every token, so this keeps the set's aggregates
        counting distinct delivered tokens across a hard kill."""
        out = dict(self.counter_state)
        for h in reclaimed:
            n = self.progress.get(h.request.request_id, 0)
            out["tokens_decoded"] -= n
            out["occupancy_sum"] -= n
        return out

    def close(self, timeout: float = 10.0) -> None:
        """Graceful shutdown: ask, wait, then kill. Frames written
        before the child exited are salvaged either way. A remote
        worker (nothing to join) gets the SHUTDOWN frame and a bounded
        pump for its BYE before the transport closes under it."""
        if self._proc is not None:
            # only wait for a child that actually HEARD the shutdown:
            # a socket child still dialing (no transport attached) or
            # a dead pipe would make this join burn its whole timeout
            # on a worker with no reason to exit
            if self._proc.is_alive() and self._send(SHUTDOWN, {}):
                self._proc.join(timeout)
        elif self._popen is not None:
            if self._popen.poll() is None and self._send(SHUTDOWN, {}):
                try:
                    self._popen.wait(timeout)
                except (OSError, subprocess.TimeoutExpired):
                    pass
        elif self._conn is not None and self._conn.alive():
            self._send(SHUTDOWN, {})
            deadline = time.perf_counter() + timeout
            while not self.bye and time.perf_counter() < deadline:
                # a worker that died or lied mid-shutdown will never
                # BYE — stop waiting the moment the stream can say so
                if self.poisoned or not self._conn.alive():
                    break
                if not self.pump(0.05):
                    time.sleep(0.01)
        self.hard_kill()
        self.salvage()
        self.fence()
