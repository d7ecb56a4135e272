"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, time by operation, the longest idle gaps with what the host was
doing in them, and collective time that no compute hides.

Read with ``jax.profiler.ProfileData`` alone. A TPU trace holds one plane
a chip (``/device:TPU:<n>``) whose ``XLA Ops`` line has one event for
each executed operation and whose ``XLA Modules`` line one for each
program run, and a host plane (``/host:CPU``) whose lines are threads;
``jax.profiler.TraceAnnotation`` names appear there. All share one clock.
"""

from __future__ import annotations

import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\s=]")
HLO_NAME = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.I)


def short_name(name: str) -> str:
    """An event's HLO text cut to its instruction name and result shape:
    ``%fusion.7 = bf16[4,8]{...} fusion(...)`` -> ``fusion.7 bf16[4,8]``."""
    m = HLO_NAME.match(name)
    if not m:
        return name[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def _union(intervals):
    """Sorted, merged (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _covered(merged, s, e):
    """Nanoseconds of [s, e) that the merged intervals cover."""
    total = 0
    for a, b in merged:
        if b <= s:
            continue
        if a >= e:
            break
        total += min(b, e) - max(a, s)
    return total


class Reduction:
    """``devices``: {chip: [(name, start_ns, dur_ns)]} from ``XLA Ops``;
    ``modules``: the same from ``XLA Modules``; ``host``: [(name,
    start_ns, dur_ns)] of every host event."""

    def __init__(self, devices: dict, modules: dict, host: list):
        self.devices, self.modules, self.host = devices, modules, host
        starts = [s for evs in devices.values() for _, s, _ in evs]
        ends = [s + d for evs in devices.values() for _, s, d in evs]
        self.t0 = min(starts) if starts else 0
        self.t1 = max(ends) if ends else 0
        self.window_s = (self.t1 - self.t0) / 1e9
        self._busy = {k: _union((s, s + d) for _, s, d in evs)
                      for k, evs in devices.items()}
        per_chip = [sum(e - s for s, e in m) for m in self._busy.values()]
        self.busy_s = (sum(per_chip) / len(per_chip) / 1e9) if per_chip else 0.0

    @property
    def chips(self) -> int:
        return len(self.devices)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def op_seconds(self) -> dict:
        """Seconds by operation name, averaged over the chips."""
        out: dict = {}
        for evs in self.devices.values():
            for name, _, d in evs:
                if not CONTAINER.match(name):   # a loop holds its body's ops
                    out[name] = out.get(name, 0.0) + d / 1e9 / self.chips
        return out

    def count_matching(self, pattern: str) -> int:
        """Events on the first chip whose name matches."""
        rx = re.compile(pattern)
        first = min(self.devices) if self.devices else None
        return sum(1 for n, _, _ in self.devices.get(first, [])
                   if rx.search(n))

    def seconds_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_seconds().items() if rx.search(k))

    def module_runs(self, pattern: str = "") -> list:
        """(name, seconds) of each program run on the first chip."""
        rx = re.compile(pattern)
        first = min(self.modules) if self.modules else None
        return [(n, d / 1e9) for n, _, d in self.modules.get(first, [])
                if rx.search(n)]

    def idle_gaps(self, top: int = 10) -> list:
        """The longest idle gaps of the first chip, each with the host
        event that covers most of it: [(host event name, seconds)]."""
        first = min(self._busy) if self._busy else None
        merged = self._busy.get(first, [])
        gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
        gaps.sort(reverse=True)
        out = []
        for dur, s, e in gaps[:top]:
            best, cover = "host:unattributed", 0
            for name, hs, hd in self.host:
                # the benchmark's own annotations first, then anything
                # but the interpreter's per-function events
                c = min(hs + hd, e) - max(hs, s)
                if name.startswith("$"):
                    continue
                if name.startswith("bench."):
                    c *= 2
                if c > cover:
                    best, cover = name, c
            out.append([best[:64], dur / 1e9])
        return out

    def collective_exposed_s(self) -> float:
        """Seconds, averaged over chips, in which a collective ran and no
        other operation did on that chip."""
        total = 0.0
        for evs in self.devices.values():
            coll = [(s, s + d) for n, s, d in evs if COLLECTIVE.search(n)]
            comp = _union((s, s + d) for n, s, d in evs
                          if not COLLECTIVE.search(n))
            for s, e in _union(coll):
                total += (e - s) - _covered(comp, s, e)
        return total / 1e9 / self.chips if self.chips else 0.0

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[short_name(k), v] for k, v in ops],
                "idle_gaps": self.idle_gaps(top)}


def reduce_file(path: str) -> Reduction:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events]
                (devices if line.name == OPS_LINE else modules)[
                    int(m.group(1))] = evs
            elif plane.name.startswith("/host:"):
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events)
    return Reduction(devices, modules, host)


class Capture:
    """One profiler capture into the output directory."""

    def __init__(self, cell: str, seed: int):
        from benchmark import harness
        self.dir = os.path.join(harness.OUT_DIR, "trace", f"{cell}.{seed}")

    def start(self):
        import jax
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def reduce(self) -> Reduction:
        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        try:
            return reduce_file(files[-1])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)   # tens of MB a run
