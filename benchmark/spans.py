"""The program's own spans and counters in a traced run, and the
device's idle time split by what the program was doing.

The program's recorder (`gelly_streaming_tpu/utils/telemetry.py`)
writes each span as a profiler annotation with its attributes as
stats, and each counter as a zero-length event whose stats hold
`value` and the counter's attributes, on the host plane and on the
device planes' clock. This module reads the run's `.xplane.pb` again
(one parse per path) and keeps those events where they overlap the
window (`bench.window`). The window thread is the host line that holds
`bench.window`. A trace of a program that writes no such event gives
no reading: every function here then returns None, never 0.
"""

import functools
from bisect import bisect_left

from benchmark import trace as trace_mod

# name prefixes of the recorder's spans and counters; JAX's own host
# events (`np.asarray_jax.Array_`, `PjitFunction(run)`) carry none
PROGRAM = ("step.", "ingress.", "driver.", "triangles.", "cohort.",
           "fused_scan.", "reduce.", "sharded.", "sliding.", "tenant.",
           "resident.")

# what the window thread does while the device idles
INGEST = ("step.intern", "ingress.prep", "ingress.h2d")
READBACK = ("step.snapshot_wait", "step.snapshot_extract",
            "ingress.finalize")


class Event:
    """One program event: times in ns on the trace's clock; `line`
    numbers the host lines (threads) of the trace."""

    __slots__ = ("name", "line", "start", "end", "stats")

    def __init__(self, name, line, start, end, stats):
        self.name = name
        self.line = line
        self.start = start
        self.end = end
        self.stats = stats

    @property
    def counter(self) -> bool:
        return "value" in self.stats


class Capture:
    """The program events of one trace that overlap its window."""

    def __init__(self, events, line, lo, hi):
        self.events = events
        self.line = line        # the window thread's line, None without
        self.lo, self.hi = lo, hi

    @property
    def spans(self) -> list:
        return [e for e in self.events if not e.counter]

    def counter_per_window(self, name: str):
        """Σ value of counter `name` over Σ its `windows`; None when
        the trace has no such counter or it counts no window."""
        got = [e.stats for e in self.events if e.counter and e.name == name]
        windows = sum(s.get("windows", 0) for s in got)
        if not got or not windows:
            return None
        return sum(s["value"] for s in got) / windows

    def window_spans(self) -> list:
        """[(start, end, name)] of the window thread's spans, clipped
        to the window."""
        return [(max(e.start, self.lo), min(e.end, self.hi), e.name)
                for e in self.spans if e.line == self.line]


def parse(path: str) -> Capture:
    from jax.profiler import ProfileData

    events, line, lo, hi, n = [], None, 0.0, 0.0, 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                end = e.start_ns + e.duration_ns
                if e.name == trace_mod.WINDOW_SPAN:
                    line, lo, hi = n, e.start_ns, end
                elif e.name.startswith(PROGRAM):
                    events.append(Event(e.name, n, e.start_ns, end,
                                        dict(e.stats)))
            n += 1
    if line is None:
        return Capture([], None, lo, hi)
    return Capture([e for e in events if e.end >= lo and e.start <= hi],
                   line, lo, hi)


@functools.lru_cache(maxsize=2)
def load(path: str) -> Capture:
    return parse(path)


def of_run(run):
    """The run's capture; None when it wrote no trace."""
    try:
        return load(trace_mod.find_xplane(run.trace_dir))
    except FileNotFoundError:
        return None


def counter_per_window(run, name: str):
    cap = of_run(run)
    return None if cap is None else cap.counter_per_window(name)


def innermost(spans) -> list:
    """[(start, end, name)] pieces of one thread's time, each labelled
    by the innermost of `spans` open over it (the latest started);
    touching pieces of one name are merged."""
    order = sorted(spans, key=lambda s: (s[0], -s[1]))
    points = sorted({p for s, e, _n in spans for p in (s, e)})
    out, active, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(order) and order[i][0] <= a:
            active.append(order[i])
            i += 1
        active = [s for s in active if s[1] > a]
        if active:
            name = max(active, key=lambda s: (s[0], -s[1]))[2]
            if out and out[-1][1] == a and out[-1][2] == name:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def split(gaps, pieces) -> dict:
    """ns of merged `gaps` under each labelled piece (sorted, disjoint,
    as `innermost` gives them); the rest under None."""
    starts = [p[0] for p in pieces]
    out = {}
    for gs, ge in gaps:
        covered = 0.0
        j = max(bisect_left(starts, gs) - 1, 0)
        while j < len(pieces) and pieces[j][0] < ge:
            s, e, name = pieces[j]
            part = min(e, ge) - max(s, gs)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            j += 1
        out[None] = out.get(None, 0.0) + (ge - gs) - covered
    return out


def idle_split(trace, cap, device: int = 0):
    """{innermost span name, or None: seconds} of device `device`'s
    idle time in the window; None without a device or a program span
    on the window thread."""
    spans = cap.window_spans() if cap is not None else []
    if not spans or device >= len(trace.devices) or trace.window_s <= 0:
        return None
    gaps = trace_mod.subtract([[trace.lo, trace.hi]],
                              trace._busy(trace.devices[device]))
    return {k: v / 1e9 for k, v in split(gaps, innermost(spans)).items()}


def idle_pct(run, trace, names):
    """Share of the window in which device 0 runs no operation and the
    window thread's innermost program span is one of `names`."""
    got = idle_split(trace, of_run(run))
    if got is None:
        return None
    return 100.0 * sum(v for k, v in got.items() if k in names) \
        / trace.window_s
