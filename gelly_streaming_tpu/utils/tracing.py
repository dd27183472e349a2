"""Tracing / profiling.

The reference has none (SURVEY.md §5.1 — only wall-clock via
getNetRuntime, CentralizedWeightedMatching.java:62-64). Here:

- `StepTimer` — per-operator / per-window wall-time and record counts,
  collected by the runtime when `env.enable_tracing()` is on. Since
  the flight recorder landed (utils/telemetry) StepTimer is a thin
  adapter over it: `step()` measures through a telemetry span (so an
  armed recorder sees every step as a `step.<name>` span with the
  run's trace ID), while `report()`/`event_log()` and their
  accumulation semantics are unchanged for existing call sites.

A device trace is `jax.profiler.trace(log_dir)` itself: inside a
capture every telemetry span, `step.<name>` included, is a profiler
annotation on the device planes' clock (utils/telemetry).
"""

from __future__ import annotations

import contextlib
from collections import defaultdict
from typing import Dict, List

from . import telemetry


class StepTimer:
    def __init__(self):
        self.totals: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        self.records: Dict[str, int] = defaultdict(int)
        self.events: List[dict] = []  # discrete happenings (demotions)

    def event(self, name: str, info: dict = None) -> None:
        """Record a discrete runtime event (e.g. a tier demotion) into
        the trace: not a timing, a happening — surfaced by
        `event_log()` beside `report()` so a degraded run's trace says
        so explicitly."""
        self.events.append({"event": name, **(info or {})})

    def event_log(self) -> List[dict]:
        return list(self.events)

    def add(self, name: str, seconds: float, num_records: int = 0) -> None:
        """Record one already-measured step (used by the runtime's
        exclusive-time accounting)."""
        self.totals[name] += seconds
        self.counts[name] += 1
        self.records[name] += num_records

    @contextlib.contextmanager
    def step(self, name: str, num_records: int = 0):
        # the telemetry span IS the stopwatch (identical perf_counter
        # measurement armed or not); the local accumulation keeps
        # report() byte-compatible for existing consumers. Yields the
        # span so dispatch-owning steps can attach attributes before
        # it records (the driver stamps program/sig cost tags).
        sp = telemetry.span("step." + name, records=num_records)
        try:
            with sp:
                yield sp
        finally:
            self.add(name, sp.elapsed, num_records)

    def report(self) -> List[dict]:
        out = []
        for name in sorted(self.totals, key=self.totals.get, reverse=True):
            total = self.totals[name]
            recs = self.records[name]
            out.append({
                "op": name,
                "total_s": round(total, 6),
                "calls": self.counts[name],
                "records": recs,
                "records_per_s": round(recs / total) if total and recs else 0,
            })
        return out

    def __str__(self) -> str:
        lines = ["op                            total_s    calls  records  rec/s"]
        for row in self.report():
            lines.append(
                f"{row['op']:<28} {row['total_s']:>9.4f} {row['calls']:>7}"
                f" {row['records']:>8} {row['records_per_s']:>7}"
            )
        return "\n".join(lines)
