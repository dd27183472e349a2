"""Reduction of one profiler trace (`.xplane.pb`) to the numbers the
per-layer readers take: device busy and idle time, per-program device
time and launches, exposed collectives, and the idle gaps labelled by
what the host was doing.

Device planes are `/device:TPU:<n>`; their `XLA Modules` line holds one
event per program launch, named `jit_<function>(<fingerprint>)`, and
their `XLA Ops` line the HLO operations (a `while` event spans its
body's operations). The window is the host span `bench.window` that
the harness opens around the measured work.
"""

import glob
import os
import re
from bisect import bisect_right

WINDOW_SPAN = "bench.window"
_CONTAINERS = ("while", "conditional", "call")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast",
                "send", "recv")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % log_dir)
    return found[-1]


def op_name(event_name: str) -> str:
    """`%fusion.13 = s32[...] fusion(...)` -> `fusion.13`."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%").strip()


def op_kind(name: str) -> str:
    """`fusion.13` -> `fusion`, `all-reduce-start.2` -> `all-reduce-start`."""
    return re.sub(r"\.\d+$", "", name)


def program_name(module_event: str) -> str:
    """`jit_run(16258671304053783308)` -> `jit_run`."""
    return module_event.split("(", 1)[0]


def union(intervals) -> list:
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Parts of merged intervals `a` not covered by merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


class Device:
    """One device plane: program launches and operations, in ns."""

    def __init__(self, name: str):
        self.name = name
        self.modules = []   # (start, end, program)
        self.ops = []       # (start, end, op name)


class Trace:
    """The reduced trace. All times in seconds."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        self.devices = []
        self.host = []      # (start, end, name) of every host event
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                dev = Device(plane.name)
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        dev.modules = [(e.start_ns, e.start_ns + e.duration_ns,
                                        program_name(e.name))
                                       for e in line.events]
                    elif line.name == "XLA Ops":
                        dev.ops = [(e.start_ns, e.start_ns + e.duration_ns,
                                    op_name(e.name)) for e in line.events]
                self.devices.append(dev)
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    self.host += [(e.start_ns, e.start_ns + e.duration_ns,
                                   e.name) for e in line.events]
        self.devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
        spans = [h for h in self.host if h[2] == WINDOW_SPAN]
        if not spans:
            raise ValueError("trace has no %r span" % WINDOW_SPAN)
        self.lo, self.hi = spans[0][0], spans[0][1]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def _busy(self, dev: Device) -> list:
        return clip(union((s, e) for s, e, _n in dev.ops
                          or [(s, e, n) for s, e, n in dev.modules]),
                    self.lo, self.hi)

    def busy_s(self, chips: int = None) -> float:
        """Seconds in which an operation ran, averaged over the first
        `chips` devices (all when None)."""
        devs = self.devices[:chips] if chips else self.devices
        if not devs:
            return 0.0
        return sum(total(self._busy(d)) for d in devs) / len(devs) / 1e9

    def program_time_s(self, prefix: str, device: int = 0) -> float:
        """Device seconds of launches of programs named exactly
        `prefix` inside the window."""
        if device >= len(self.devices):
            return 0.0
        return total(clip([(s, e) for s, e, p in self.devices[device].modules
                           if p == prefix], self.lo, self.hi)) / 1e9

    def launches(self, names=None, device: int = 0) -> int:
        """Program launches that overlap the window (all programs when
        `names` is None). Overlap, not start: the device's clock reads
        about a millisecond ahead of the host's in the chip's traces."""
        if device >= len(self.devices):
            return 0
        return sum(1 for s, e, p in self.devices[device].modules
                   if e > self.lo and s < self.hi
                   and (names is None or p in names))

    def exposed_collective_s(self, chips: int = None) -> float:
        """Seconds per device in which a collective ran and no other
        operation did, averaged over the devices."""
        devs = self.devices[:chips] if chips else self.devices
        if not devs:
            return 0.0
        out = 0.0
        for d in devs:
            coll, comp = [], []
            for s, e, n in d.ops:
                kind = op_kind(n)
                if kind.startswith(_COLLECTIVES):
                    coll.append((s, e))
                elif not kind.startswith(_CONTAINERS):
                    comp.append((s, e))
            out += total(subtract(clip(union(coll), self.lo, self.hi),
                                  union(comp)))
        return out / len(devs) / 1e9

    def top_ops(self, n: int = 10, device: int = 0) -> list:
        """[[program/op, seconds]] of the operations that took most
        device time (containers left out: they span their bodies)."""
        if device >= len(self.devices):
            return []
        d = self.devices[device]
        mods = sorted(d.modules)
        starts = [m[0] for m in mods]
        acc = {}
        for s, e, name in d.ops:
            if op_kind(name).startswith(_CONTAINERS):
                continue
            i = bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            key = "%s/%s" % (prog, name)
            acc[key] = acc.get(key, 0.0) + max(
                0.0, min(e, self.hi) - max(s, self.lo))
        top = sorted(((k, v / 1e9) for k, v in acc.items() if v > 0),
                     key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]

    def idle_gaps(self, n: int = 10, device: int = 0) -> list:
        """[[label, seconds]] of the longest device idle gaps in the
        window, each labelled by the innermost benchmark span (`bench.`)
        around its middle and the longest other host event in it."""
        if device >= len(self.devices):
            return []
        busy = self._busy(self.devices[device])
        gaps = subtract([[self.lo, self.hi]], busy)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            bench = [h for h in self.host if h[2].startswith("bench.")
                     and h[2] != WINDOW_SPAN and h[0] <= mid < h[1]]
            label = (min(bench, key=lambda h: h[1] - h[0])[2]
                     if bench else "outside")
            other = [(min(h[1], e) - max(h[0], s), h[2]) for h in self.host
                     if not h[2].startswith("bench.") and h[0] < e and h[1] > s]
            if other:
                label += ":" + max(other)[1]
            out.append([label, (e - s) / 1e9])
        return out


def idle_pct(run, trace: Trace):
    """Share of the traced window in which no operation ran on the
    device, averaged over the cell's chips; None without a device."""
    if not trace.devices or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.cell.chips) / trace.window_s)
