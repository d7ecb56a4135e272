"""Transport layer for process- and host-isolated replica serving.

``serve/ipc.py`` speaks a framed, versioned, sequence-numbered,
CRC-checksummed protocol; this module is everything UNDER the frames —
how frame bytes move between the parent and a worker. Two transports
share one contract (``send_bytes`` / ``poll`` / ``recv_bytes``, the
``multiprocessing.Connection`` surface the IPC layer was already written
against):

  * ``PipeTransport`` — a duplex ``multiprocessing`` pipe. The OS
    delivers each write whole, the peer is a local child by
    construction, and there is no network to lie about. This is
    ``--transport pipe``, the process-isolation default (PR 8).
  * ``SocketTransport`` — a TCP stream, which is what host-per-engine
    isolation actually crosses. A stream transport has failure modes a
    pipe can never exhibit, and each one must surface as a TYPED error
    rather than a hang or a silent mis-parse:

      - **short reads**: a frame legally arrives in arbitrary byte
        fragments; the receive path buffers and loops to the exact
        length-prefixed frame boundary before handing bytes up;
      - **mid-frame EOF / torn frames**: a peer dying between two
        writes leaves a partial frame — ``IPCError``, never a partial
        parse (the CRC would catch it, but the transport refuses to
        even offer the bytes);
      - **connection reset**: an RST mid-stream is
        ``IPCError`` when it tears a frame, ``ConnectionResetError``
        at a frame boundary — either way the replica is fenced, and a
        remote worker (no PID to probe) is declared dead off exactly
        this signal;
      - **stalled peers**: every receive is buffered + non-blocking
        (``poll`` uses ``select``), so a socket that is accepted but
        never written — or a frame that stops halfway — can stall a
        HEARTBEAT deadline but never a thread; sends time out
        (``BrokenPipeError``) instead of blocking forever on a peer
        that stopped reading.

``WorkerListener`` is the parent's dial-in endpoint: workers CONNECT TO
THE PARENT (never the reverse — the parent may be behind the same
firewall, and a dialing worker composes with hand-started remote
workers), and the first frame on a new connection must be an
authenticated HELLO: the shared token (serve/auth.py's constant-time
``check_token``; ships
via the ``DALLE_WORKER_TOKEN`` env var, never argv) plus the protocol
version and the replica index the worker claims. A bad token, a version
skew, or an unexpected index closes the connection without attaching
anything. On success the parent answers HELLO_OK and streams the worker
spec (params + config, pickled) down the SAME authenticated socket —
so a remote worker needs nothing but the endpoint, the token, and an
index: ``python -m dalle_pytorch_tpu.serve.worker --connect HOST:PORT
--index N``. Only the worker ever unpickles, and only from the endpoint
its operator pointed it at; the parent parses nothing but JSON frames
off the network.
"""

from __future__ import annotations

import os
import pickle
import secrets
import select
import socket
import struct
import threading
import time
from typing import Callable, Dict, Optional, Tuple

from dalle_pytorch_tpu.serve import auth

# the env var a hand-started / launcher-started worker reads its HELLO
# token from — an env var, not argv, so the secret never shows in `ps`
TOKEN_ENV = "DALLE_WORKER_TOKEN"

# length prefix for socket framing; the cap bounds what a garbage or
# hostile length field can make the receive buffer allocate
_LEN = struct.Struct("<I")
MAX_FRAME_BYTES = 1 << 30


class IPCError(RuntimeError):
    """A frame or stream that cannot be believed: truncated, wrong
    magic, version skew, checksum mismatch, broken sequence,
    unparseable payload, mid-frame EOF, or a reset that tore a frame.
    The only safe response is to FENCE the peer — a stream that
    produced one lie may have corrupted anything."""


class PipeTransport:
    """A ``multiprocessing`` duplex pipe behind the transport contract.
    The pipe already delivers whole messages and raises ``EOFError`` /
    ``OSError`` when the peer vanishes; this wrapper only adds the
    metadata (`kind`/`peer`) the observability surface reports."""

    kind = "pipe"

    def __init__(self, conn):
        self._conn = conn
        self._closed = False
        self.peer = "pipe"

    def send_bytes(self, data: bytes) -> None:
        self._conn.send_bytes(data)

    def poll(self, timeout: float = 0.0) -> bool:
        if self._closed:
            return False
        return self._conn.poll(timeout)

    def recv_bytes(self) -> bytes:
        return self._conn.recv_bytes()

    def alive(self) -> bool:
        # a pipe's liveness is its process's liveness; the owner layers
        # PID checks on top, so the transport only reports local close
        return not self._closed

    def state_desc(self) -> str:
        return "closed" if self._closed else "open"

    def close(self) -> None:
        self._closed = True
        try:
            self._conn.close()
        except (OSError, AttributeError):
            pass


class SocketTransport:
    """A TCP stream behind the transport contract, framed as
    ``[u32 little-endian length][frame bytes]``.

    All receiving is buffered and non-blocking: ``poll`` selects, then
    drains the socket into a local buffer; ``recv_bytes`` hands back one
    complete frame from that buffer or raises — ``EOFError`` for a
    clean FIN at a frame boundary, ``IPCError`` for EOF/reset with a
    partial frame buffered (the torn-frame signal), and
    ``ConnectionResetError`` for an RST at a boundary. No call here can
    block past ``poll``'s timeout, which is what keeps a stalled peer a
    heartbeat problem instead of a wedged control thread.

    Sends loop over ``select`` with a deadline and raise
    ``BrokenPipeError`` when the peer stops draining — a worker treats
    that exactly like a dead parent (exit, leak nothing), the parent
    records it and lets supervision fence the replica."""

    kind = "socket"

    def __init__(self, sock: socket.socket, send_timeout_s: float = 30.0):
        sock.setblocking(False)
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass                      # not TCP (socketpair in tests)
        self._sock = sock
        self._send_timeout_s = float(send_timeout_s)
        self._buf = bytearray()
        self._eof = False
        self._reset: Optional[OSError] = None
        self._closed = False
        try:
            name = sock.getpeername()
            self.peer = (f"{name[0]}:{name[1]}"
                         if isinstance(name, tuple) and len(name) >= 2
                         else (str(name) or "socket"))
        except OSError:
            self.peer = "socket"
        # filled by the listener handshake: the worker's HELLO payload
        # (remote pid/host — observability, never trusted for liveness)
        self.hello: dict = {}

    # -- receive ------------------------------------------------------------

    def _fill(self) -> None:
        """Drain whatever the socket has RIGHT NOW into the buffer —
        never blocks. EOF and resets are recorded, not raised: they
        surface from ``recv_bytes`` where the partial-frame context
        (torn vs clean) is known."""
        if self._eof or self._closed:
            return
        while True:
            try:
                chunk = self._sock.recv(1 << 16)
            except (BlockingIOError, InterruptedError):
                return
            except OSError as e:
                self._eof = True
                self._reset = e
                return
            if not chunk:
                self._eof = True
                return
            self._buf += chunk

    def _ready(self) -> bool:
        """A complete frame is buffered, or an error is ready to raise."""
        if len(self._buf) >= _LEN.size:
            (n,) = _LEN.unpack_from(self._buf)
            if n > MAX_FRAME_BYTES:
                return True           # recv_bytes raises the IPCError
            if len(self._buf) >= _LEN.size + n:
                return True
        return self._eof

    def poll(self, timeout: float = 0.0) -> bool:
        """True when ``recv_bytes`` will return a frame or raise —
        never blocks past ``timeout``. The short-read loop lives here:
        however the network fragments the stream, bytes accumulate in
        the buffer until a whole length-prefixed frame is in."""
        if self._closed:
            return False
        if self._ready():
            return True
        self._fill()
        if self._ready():
            return True
        # a fragment that completes no frame is not the answer: wait out
        # the rest of the timeout for the frame's remaining bytes
        deadline = time.monotonic() + timeout
        while not self._ready():
            left = deadline - time.monotonic()
            if left <= 0:
                return False
            try:
                r, _, _ = select.select([self._sock], [], [], left)
            except (OSError, ValueError):
                return True           # fd died: recv_bytes surfaces it
            if not r:
                return False
            self._fill()
        return True

    def recv_bytes(self) -> bytes:
        if self._closed:
            raise EOFError("transport closed locally")
        if not self._ready():
            self._fill()
        if len(self._buf) >= _LEN.size:
            (n,) = _LEN.unpack_from(self._buf)
            if n > MAX_FRAME_BYTES:
                raise IPCError(
                    f"declared frame length {n} exceeds the "
                    f"{MAX_FRAME_BYTES}-byte cap (corrupt stream)")
            if len(self._buf) >= _LEN.size + n:
                frame = bytes(self._buf[_LEN.size:_LEN.size + n])
                del self._buf[:_LEN.size + n]
                return frame
        if self._eof:
            if self._buf:
                # the torn-frame / mid-frame-EOF signal: the peer died
                # (or was reset) between two writes of one frame
                how = (f"connection reset ({self._reset!r})"
                       if self._reset is not None else "peer closed")
                raise IPCError(
                    f"mid-frame EOF: {how} with {len(self._buf)} bytes "
                    f"of a partial frame buffered")
            if self._reset is not None:
                raise ConnectionResetError(str(self._reset))
            raise EOFError("peer closed the connection")
        raise BlockingIOError("no complete frame buffered (poll first)")

    # -- send ---------------------------------------------------------------

    def send_bytes(self, data: bytes) -> None:
        self._send_all(_LEN.pack(len(data)) + data)

    def send_partial_frame(self, frame: bytes, upto: int) -> None:
        """Fault-injection only: write the length prefix declaring the
        FULL frame, then just the first ``upto`` bytes of it — the
        deterministic torn frame the receive path must refuse with a
        typed error instead of waiting out or mis-parsing."""
        self._send_all((_LEN.pack(len(frame)) + frame)[:_LEN.size + upto])

    def _send_all(self, payload: bytes) -> None:
        if self._closed:
            raise BrokenPipeError("transport closed locally")
        view = memoryview(payload)
        off = 0
        deadline = time.perf_counter() + self._send_timeout_s
        while off < len(payload):
            try:
                off += self._sock.send(view[off:])
                continue
            except (BlockingIOError, InterruptedError):
                pass
            left = deadline - time.perf_counter()
            if left <= 0:
                # a peer that stopped reading: to the sender this is a
                # dead parent / dead worker, not a wait-forever
                raise BrokenPipeError(
                    f"send stalled > {self._send_timeout_s:g}s "
                    f"(peer not reading)")
            try:
                select.select([], [self._sock], [], min(left, 0.5))
            except (OSError, ValueError) as e:
                raise BrokenPipeError(f"socket died mid-send: {e!r}")

    # -- lifecycle / observability ------------------------------------------

    def set_send_timeout(self, s: float) -> None:
        """Re-bound how long a send may block. The parent sets this
        SHORT after adopting a worker's transport: its control thread
        supervises every replica, and one stalled peer must cost a
        failed send (recorded, fenced by supervision) rather than
        stalling everyone else's heartbeat deadlines. The handshake
        keeps the long default — the spec blob is large and its send
        runs on a dedicated thread."""
        self._send_timeout_s = float(s)

    def alive(self) -> bool:
        return not self._closed and not self._eof

    def state_desc(self) -> str:
        if self._closed:
            return "closed"
        if self._reset is not None:
            return "connection reset"
        if self._eof:
            return "connection closed by peer"
        return "open"

    def reset_hard(self) -> None:
        """Abort with an RST instead of a FIN (SO_LINGER 0) — the fault
        catalog's deterministic stand-in for a network-level reset."""
        try:
            self._sock.setsockopt(
                socket.SOL_SOCKET, socket.SO_LINGER,
                struct.pack("ii", 1, 0))
        except OSError:
            pass
        self.close()

    def close(self) -> None:
        self._closed = True
        try:
            self._sock.close()
        except OSError:
            pass


# ---------------------------------------------------------------------------
# handshake (worker dials the parent)
# ---------------------------------------------------------------------------


def _recv_frame_deadline(transport, timeout_s: float) -> bytes:
    """One frame with a hard deadline — handshake-only (the steady-state
    protocol never blocks on a single peer)."""
    deadline = time.perf_counter() + timeout_s
    while True:
        left = deadline - time.perf_counter()
        if left <= 0:
            raise IPCError(f"handshake timed out after {timeout_s:g}s")
        if transport.poll(min(left, 0.25)):
            return transport.recv_bytes()


def parse_endpoint(endpoint: str) -> Tuple[str, int]:
    """``"host:port"`` -> ``(host, port)``; bare ``":port"`` binds all
    interfaces (remote workers must be able to reach it)."""
    host, sep, port = endpoint.rpartition(":")
    if not sep:
        raise ValueError(f"endpoint must be HOST:PORT, got {endpoint!r}")
    return host or "0.0.0.0", int(port)


def dial_parent(host: str, port: int, token: str, index: int, *,
                timeout_s: float = 60.0):
    """Worker side of the attach handshake: connect, HELLO (token +
    protocol version + claimed index), await HELLO_OK, then receive the
    pickled worker spec over the now-authenticated stream. Returns
    ``(transport, spec)``; raises ``IPCError`` on any rejection (the
    parent answers a bad HELLO by closing, which lands here as EOF)."""
    from dalle_pytorch_tpu.serve import ipc

    sock = socket.create_connection((host, port), timeout=timeout_s)
    transport = SocketTransport(sock)
    transport.send_bytes(ipc.encode_frame(ipc.HELLO, {
        "token": token, "version": ipc.PROTOCOL_VERSION,
        "index": int(index), "pid": os.getpid(),
        "host": socket.gethostname()}, seq=0))
    try:
        kind, payload, seq = ipc.decode_frame(
            _recv_frame_deadline(transport, timeout_s))
        if kind != ipc.HELLO_OK or seq != 0:
            raise IPCError(f"expected HELLO_OK/0, got {kind}/{seq}")
        spec = pickle.loads(_recv_frame_deadline(transport, timeout_s))
    except (EOFError, ConnectionResetError, OSError):
        # a parent that closes anywhere in the handshake — before
        # HELLO_OK or mid-spec — is a rejection to this worker either
        # way: one typed error, one exit code
        transport.close()
        raise IPCError(
            "parent closed during handshake (bad token, wrong index, "
            "or version skew)") from None
    except IPCError:
        transport.close()
        raise
    return transport, spec


class WorkerListener:
    """The parent's dial-in endpoint: one listening socket shared by
    every socket-transport replica. Workers connect and HELLO; the
    accept loop (one thread; one short-lived thread per handshake, so a
    dialer that connects and says nothing — the stalled-socket fault —
    times out alone instead of blocking other attaches) authenticates
    the token, checks the protocol version, matches the claimed index
    against the expected registry, ships the spec, and parks the
    attached transport for ``ChildEngineClient`` to adopt on its next
    pump. Everything unexpected is closed and counted (``rejected``),
    never attached."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0, *,
                 token: Optional[str] = None,
                 handshake_timeout_s: float = 10.0,
                 on_event: Optional[Callable[[dict], None]] = None):
        self.token = token or secrets.token_hex(16)
        self._handshake_timeout_s = float(handshake_timeout_s)
        self._on_event = on_event
        self._sock = socket.create_server((host, port), backlog=16)
        name = self._sock.getsockname()
        self.host, self.port = name[0], int(name[1])
        self.endpoint = f"{self.host}:{self.port}"
        # a bind address is not a destination: what a LOCAL spawn
        # dials, and what a REMOTE worker is told to dial (an
        # all-interfaces bind advertises this host's name — bind a
        # concrete address instead if that name doesn't resolve from
        # the worker hosts)
        self.dial_host = "127.0.0.1" if self.host == "0.0.0.0" \
            else self.host
        self.advertise_endpoint = (
            f"{socket.gethostname()}:{self.port}"
            if self.host == "0.0.0.0" else self.endpoint)
        self._lock = threading.Lock()
        self._expected: Dict[int, bytes] = {}       # index -> spec blob
        self._attached: Dict[int, SocketTransport] = {}
        self.rejected = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._accept_loop, daemon=True,
            name="serve-worker-listener")
        self._thread.start()

    # -- registry (called by ChildEngineClient) -----------------------------

    def expect(self, index: int, spec_blob: bytes) -> None:
        """Declare that a worker for replica ``index`` may dial in, and
        what spec to hand it. Re-registering replaces (a replaced
        replica's stale expectation must not admit a stale worker),
        and any un-taken stale transport is closed — its worker EOFs
        and exits rather than idling attached to nothing."""
        with self._lock:
            self._expected[int(index)] = spec_blob
            stale = self._attached.pop(int(index), None)
        if stale is not None:
            stale.close()

    def cancel(self, index: int) -> None:
        with self._lock:
            self._expected.pop(int(index), None)
            t = self._attached.pop(int(index), None)
        if t is not None:
            t.close()

    def take(self, index: int) -> Optional[SocketTransport]:
        """The attached transport for ``index``, if a worker completed
        the handshake since the last call. Single consumer per index."""
        with self._lock:
            return self._attached.pop(int(index), None)

    def expected_indices(self) -> list:
        """Replica indices a worker may dial in as RIGHT NOW — the
        operator's 'which --index do I start' surface (/stats carries
        it). Nothing about the registry is startup-static: a replica
        born from ``add_replica`` registers its expectation through
        the same ``expect`` call as a boot-time slot, and a retired
        replica's ``cancel`` removes its entry for good — so a fleet
        reshaped at runtime always advertises exactly the slots that
        can still accept a worker."""
        with self._lock:
            return sorted(self._expected)

    # -- accept / handshake -------------------------------------------------

    def _event(self, kind: str, **fields) -> None:
        if self._on_event is not None:
            try:
                self._on_event({"kind": kind, **fields})
            except Exception:   # noqa: BLE001 — observability only
                pass

    def _accept_loop(self) -> None:
        self._sock.settimeout(0.25)
        while not self._stop.is_set():
            try:
                conn, addr = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return              # listener closed
            threading.Thread(
                target=self._handshake, args=(conn, addr), daemon=True,
                name="serve-worker-handshake").start()

    def _handshake(self, conn: socket.socket, addr) -> None:
        from dalle_pytorch_tpu.serve import ipc

        transport = SocketTransport(conn)
        peer = transport.peer
        try:
            kind, payload, seq = ipc.decode_frame(_recv_frame_deadline(
                transport, self._handshake_timeout_s))
            if kind != ipc.HELLO or seq != 0:
                raise IPCError(f"first frame must be HELLO/0, "
                               f"got {kind}/{seq}")
            token = payload.get("token")
            index = payload.get("index")
            if not auth.check_token(token, self.token):
                raise IPCError("HELLO rejected: bad token")
            if not isinstance(index, int):
                raise IPCError("HELLO rejected: no index")
        except (IPCError, EOFError, ConnectionResetError,
                OSError) as e:
            self.rejected += 1
            self._event("serve_attach_rejected", peer=peer,
                        error=repr(e))
            transport.close()
            return
        with self._lock:
            spec_blob = self._expected.get(index)
            if spec_blob is None or index in self._attached:
                self.rejected += 1
                self._event("serve_attach_rejected", peer=peer,
                            error=f"unexpected replica index {index}")
                transport.close()
                return
        try:
            transport.send_bytes(ipc.encode_frame(
                ipc.HELLO_OK, {"index": index}, seq=0))
            transport.send_bytes(spec_blob)
        except OSError as e:
            self.rejected += 1
            self._event("serve_attach_rejected", peer=peer,
                        error=f"spec hand-off failed: {e!r}")
            transport.close()
            return
        transport.hello = {k: payload.get(k) for k in ("pid", "host")}
        with self._lock:
            # attach exactly once, and only while the expectation this
            # dialer was served under is STILL current: the lock was
            # released for the spec hand-off, and in that window the
            # replica may have been fenced and re-registered (new spec)
            # or another dialer may have won — either way this worker
            # holds a stale spec and must not consume the fresh
            # expectation. Identity compare works because expect()
            # stores a new bytes object per registration.
            if index in self._attached \
                    or self._expected.get(index) is not spec_blob:
                self.rejected += 1
                stale = True
            else:
                self._expected.pop(index)
                self._attached[index] = transport
                stale = False
        if stale:
            self._event("serve_attach_rejected", peer=peer,
                        error=f"lost the attach race for replica "
                              f"{index} (stale or duplicate dialer)")
            transport.close()
            return
        self._event("serve_worker_attached", peer=peer, index=index,
                    pid=payload.get("pid"), host=payload.get("host"))

    def close(self) -> None:
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass
        with self._lock:
            attached = list(self._attached.values())
            self._attached.clear()
            self._expected.clear()
        for t in attached:
            t.close()
