"""Runtime guards: the dynamic twin of jaxlint's static rules.

The two invariants the lint can only approximate from source — "this
region performs no implicit host-device transfer" and "this program
compiled exactly N times" — are checkable exactly at runtime, and both
had ad-hoc open-coded versions in the tree (``test_serve``'s
``engine.decode_traces == 1`` asserts). These context managers are the
one shared implementation: tests fail on a violation, and any future
kernel test gets the same contract for one line.

  * ``no_transfers()`` — ``jax.transfer_guard("disallow")``: implicit
    transfers raise; EXPLICIT ``jax.device_put``/``jax.device_get``
    still pass. That split is the point: a steady-state loop wrapped in
    ``no_transfers()`` documents every intentional round-trip as an
    explicit call at the transfer site (serve/engine.py's per-step token
    fetch is the canonical allowance — ROADMAP "keep cur_tok/pos on
    device"). Note the guard bites hardest on a real accelerator; the
    CPU backend shares one memory space, so some copies never register.
  * ``compile_count(counter, expect=N)`` — asserts a trace/compile
    counter advanced by exactly N inside the block.
  * ``counting(fn)`` — wrap a function so jit-tracing it is countable:
    ``fn2 = counting(fn); jitted = jax.jit(fn2)``; ``fn2.traces``.
  * ``LockOrderRecorder`` / ``TrackedLock`` / ``instrument_locks`` —
    racelint's dynamic twin: swap an object's ``threading.Lock`` attrs
    for wrappers that record the real acquisition order at test time.
    An ACQUISITION-ORDER INVERSION (this thread acquires B→A after
    A→B was ever observed) raises immediately — the single-threaded
    witness of a deadlock that needs two threads to actually fire —
    and ``assert_consistent_with(racelint.lock_order_edges(...))``
    asserts every runtime edge was predicted by the static graph, so
    the static analysis is validated by the test suite, not trusted.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import (Callable, Dict, Iterable, Iterator, List, Optional,
                    Set, Tuple)


class CompileCountError(AssertionError):
    """A guarded region compiled a different number of programs than its
    contract allows. Carries ``expected``/``actual``."""

    def __init__(self, label: str, expected, actual: int):
        super().__init__(
            f"{label}: expected {expected} compile(s), observed {actual}")
        self.label = label
        self.expected = expected
        self.actual = actual


@contextlib.contextmanager
def compile_count(counter: Callable[[], int], *, expect: Optional[int]
                  = None, at_most: Optional[int] = None,
                  label: str = "compile_count") -> Iterator[None]:
    """Assert that ``counter`` (a zero-arg callable returning a
    monotonically increasing trace/compile count — e.g.
    ``lambda: engine.decode_traces``) advances by exactly ``expect``
    (or by at most ``at_most``) across the block. A violation is only
    checked on clean exit: if the body itself raised, that error wins."""
    if (expect is None) == (at_most is None):
        raise ValueError("pass exactly one of expect= / at_most=")
    start = counter()
    yield
    actual = counter() - start
    bad = actual != expect if expect is not None else actual > at_most
    if bad:
        want = expect if expect is not None else f"<= {at_most}"
        raise CompileCountError(label, want, actual)


@contextlib.contextmanager
def no_transfers(level: str = "disallow") -> Iterator[None]:
    """Forbid implicit host-device transfers inside the block
    (``jax.transfer_guard``). Explicit ``jax.device_put`` /
    ``jax.device_get`` calls still pass under the default ``disallow``
    level — intentional round-trips must be spelled at the site they
    happen. ``level="log"`` audits instead of failing;
    ``"disallow_explicit"`` forbids even the explicit escape hatch."""
    import jax
    with jax.transfer_guard(level):
        yield


def counting(fn: Callable) -> Callable:
    """Wrap ``fn`` so each trace (python execution) bumps
    ``wrapped.traces`` — the counter jit re-runs only when it compiles.
    Pair with ``compile_count``:

        traced = counting(step_fn)
        jitted = jax.jit(traced)
        with compile_count(lambda: traced.traces, expect=1):
            for batch in data:
                jitted(params, batch)
    """
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        wrapped.traces += 1
        return fn(*args, **kwargs)

    wrapped.traces = 0
    return wrapped


# ---------------------------------------------------------------------------
# Lock-order sanitizer — racelint RL002's runtime counterpart
# ---------------------------------------------------------------------------

class LockOrderError(AssertionError):
    """An acquisition-order inversion: this thread acquired ``second``
    while holding ``first``, but the opposite order ``second -> first``
    was already observed (possibly transitively). Two threads running
    those two paths concurrently can deadlock — the recorder surfaces
    the hazard from a single-threaded witness, no actual deadlock
    required."""

    def __init__(self, first: str, second: str,
                 chain: List[str]):
        path = " -> ".join(chain)
        super().__init__(
            f"lock-order inversion: acquiring {second!r} while holding "
            f"{first!r}, but the order {path} was already observed")
        self.first = first
        self.second = second
        self.chain = chain


class LockOrderRecorder:
    """Records the directed graph of observed lock-acquisition orders.

    Each thread keeps its own held-stack (thread-local); every acquire
    of ``b`` while ``a`` is held records the edge ``a -> b``. Before
    recording, the recorder checks whether ``b`` can already reach ``a``
    through observed edges — if so, the program has demonstrated both
    orders and ``LockOrderError`` is raised at the inverting acquire.

    Lock NAMES are racelint's lock ids (``ClassName.attr``), so edges
    here compare directly against ``racelint.lock_order_edges(paths)``:
    ``assert_consistent_with(static_edges)`` asserts every edge the
    program actually exercised was predicted by the static graph.
    Same-name edges are skipped — distinct instances of the same class
    share a name, and ordering within one id is an instance-level
    question the static graph deliberately doesn't model either.
    """

    def __init__(self) -> None:
        self._edges: Dict[str, Set[str]] = {}
        self._sites: Dict[Tuple[str, str], str] = {}
        self._tls = threading.local()
        self._graph_lock = threading.Lock()

    # -- per-thread held stack ------------------------------------------
    def _held(self) -> List[str]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _find_chain(self, src: str, dst: str) -> Optional[List[str]]:
        """A path src -> ... -> dst over observed edges, or None."""
        parents: Dict[str, str] = {}
        frontier = [src]
        seen = {src}
        while frontier:
            node = frontier.pop()
            for nxt in self._edges.get(node, ()):
                if nxt in seen:
                    continue
                parents[nxt] = node
                if nxt == dst:
                    chain = [dst]
                    while chain[-1] != src:
                        chain.append(parents[chain[-1]])
                    return chain[::-1]
                seen.add(nxt)
                frontier.append(nxt)
        return None

    def on_acquire(self, name: str) -> None:
        held = self._held()
        with self._graph_lock:
            for h in held:
                if h == name:
                    continue
                chain = self._find_chain(name, h)
                if chain is not None:
                    raise LockOrderError(h, name, chain)
                self._edges.setdefault(h, set()).add(name)
                self._sites.setdefault((h, name), threading.current_thread().name)
        held.append(name)

    def on_release(self, name: str) -> None:
        held = self._held()
        # release in LIFO discipline is the common case, but timed/early
        # releases may pop out of order — remove the most recent match
        for i in range(len(held) - 1, -1, -1):
            if held[i] == name:
                del held[i]
                return

    # -- inspection -----------------------------------------------------
    def edges(self) -> Set[Tuple[str, str]]:
        with self._graph_lock:
            return {(a, b) for a, succ in self._edges.items() for b in succ}

    def assert_consistent_with(
            self, static_edges: Iterable[Tuple[str, str]]) -> None:
        """Every observed runtime edge must appear in the static graph.

        ``static_edges`` is ``racelint.lock_order_edges(paths)`` — the
        set of held->acquired pairs the analyzer derived from source. A
        runtime edge the static pass missed means the call-graph
        resolution has a hole worth fixing (or a lock was taken through
        a path the analyzer cannot see, e.g. getattr indirection)."""
        static = set(static_edges)
        missing = sorted(e for e in self.edges() if e not in static)
        if missing:
            rendered = ", ".join(f"{a} -> {b}" for a, b in missing)
            raise AssertionError(
                f"runtime lock order not predicted by static graph: "
                f"{rendered}")


class TrackedLock:
    """A drop-in ``threading.Lock``/``RLock`` wrapper that reports
    acquisition order to a :class:`LockOrderRecorder`. Passthrough for
    the lock API the serve tier uses: ``with``, ``acquire(blocking=,
    timeout=)``, ``release``, ``locked``."""

    def __init__(self, name: str, recorder: LockOrderRecorder,
                 lock=None):
        self.name = name
        self._recorder = recorder
        self._lock = lock if lock is not None else threading.Lock()

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            self._recorder.on_acquire(self.name)
        return ok

    def release(self) -> None:
        self._lock.release()
        self._recorder.on_release(self.name)

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()


_LOCK_TYPES = (type(threading.Lock()), type(threading.RLock()))


def instrument_locks(obj, recorder: LockOrderRecorder,
                     cls_name: Optional[str] = None) -> List[str]:
    """Replace every ``threading.Lock``/``RLock`` attribute in
    ``vars(obj)`` with a :class:`TrackedLock` named with racelint's lock
    id (``ClassName.attr``). Returns the names installed.

    ``cls_name`` overrides the class part — needed when the lock is
    defined by a base class (racelint names locks after the DEFINING
    class, e.g. ``RequestQueue._lock`` even on a ``WeightedFairQueue``
    instance)."""
    base = cls_name or type(obj).__name__
    installed = []
    try:
        attrs = list(vars(obj))
    except TypeError:       # __slots__ classes (obs.Trace) have no __dict__
        attrs = [a for klass in type(obj).__mro__
                 for a in getattr(klass, "__slots__", ())]
    for attr in attrs:
        val = getattr(obj, attr, None)
        if isinstance(val, _LOCK_TYPES):
            name = f"{base}.{attr}"
            tracked = TrackedLock(name, recorder, lock=val)
            setattr(obj, attr, tracked)
            installed.append(name)
    return installed
