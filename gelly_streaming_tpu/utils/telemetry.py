"""Flight recorder: unified span/counter/gauge telemetry with a
crash-safe run ledger.

Three generations of ad-hoc instrumentation grew side by side —
`utils/tracing.StepTimer`, `ops/ingress_pipeline.StageTimers`,
`utils/resilience` event dicts, and raw perf_counter() spans in the
autotuned round loops — none sharing a schema, a correlation ID, or a
durable sink, so a hung session died with no post-mortem evidence.
This module is the ONE recorder they all feed:

- **Spans** (named timed intervals with attributes), **events**
  (discrete happenings: demotions, injected faults, checkpoints,
  resumes), **counters** and **gauges** — every record carries the
  process-wide run *trace ID* plus whatever correlation attributes
  the caller binds (chunk index, window range), so a chaos run reads
  as one coherent timeline across the pipeline's threads.
- Span *nesting* is tracked per thread (a span opened inside another
  records its parent span id); cross-thread stages (the ingress prep
  pool) attach to their chunk span via an explicit ctx handle instead
  (`chunk_ctx`/`close_chunk` — thread-locals do not cross the pool).
- A bounded in-memory **ring buffer** (`GS_TRACE_RING`, default 4096
  records) holds the recent history at near-zero cost.
- A **crash-safe JSONL ledger** (`GS_TRACE_DIR`): durable-class
  events (fault kills, demotions, stage timeouts, checkpoints,
  resumes) are appended AND fsync'd the moment they close
  (`GS_TRACE_DURABLE=0` drops the fsync); ordinary spans ride the
  ring and are flushed by `flush()`, `atexit`, a fatal injected
  fault (utils/faults hooks `on_fatal`), or SIGTERM — so a
  kill-adjacent wedge still leaves the last N spans on disk. The
  ledger is append-only with one JSON object per line; readers
  (tools/trace_report.py) skip a torn final line, the same
  damage-tolerant discipline as utils/checkpoint.

- The **live profiler session** is the second sink: while a
  `jax.profiler` capture records (`jax.profiler.trace`, the
  benchmark's `--trace 1`), every span also opens a
  `TraceAnnotation` carrying its attributes, and every counter emits
  a zero-length event carrying its value, so the program's own spans
  land in the capture on the device planes' clock. This sink is
  independent of `GS_TELEMETRY`; stopwatches and after-the-fact
  `record_span` intervals stay off it (`trace_scope` gives such a
  site a live scope).

Zero-overhead contract: with `GS_TELEMETRY=0` (the default) and no
profiler session, every recording call is a guarded no-op and
`span()` degrades to a bare perf_counter stopwatch — exactly the
measurement the migrated call sites performed before — so the hot
path is bit-identical armed or not (asserted by
tests/test_telemetry.py digest parity). The profiler guard is one
`TraceMe.is_enabled()` call per span or counter.

Knobs:
    GS_TELEMETRY      0 (default) = disarmed no-ops; 1 = record
    GS_TRACE_DIR      ledger directory (unset = ring only, no disk)
    GS_TRACE_RING     ring capacity in records (default 4096)
    GS_TRACE_DURABLE  1 (default) = fsync durable-class appends
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional

from . import knobs

_SAMPLE_CAP = 2048  # per-span-name duration reservoir for summary()

clock = time.perf_counter  # the one monotonic clock every record uses


# ----------------------------------------------------------------------
# env knobs (read per call through the utils/knobs registry: tests and
# tools flip them mid-process)
# ----------------------------------------------------------------------
def enabled() -> bool:
    """GS_TELEMETRY arms the recorder; off (the default) nothing
    reaches the ring or the ledger and span() is a bare stopwatch (a
    live profiler session still sees spans and counters)."""
    return knobs.get_bool("GS_TELEMETRY")


def trace_dir() -> Optional[str]:
    """Ledger directory (GS_TRACE_DIR); None = ring only."""
    return knobs.get_path("GS_TRACE_DIR")


def ring_size() -> int:
    return knobs.get_int("GS_TRACE_RING")


def durable_sync() -> bool:
    """GS_TRACE_DURABLE=0 drops the per-durable-event fsync (append
    still happens; only the power-loss window widens)."""
    return knobs.get_bool("GS_TRACE_DURABLE")


# ----------------------------------------------------------------------
# the process-global recorder
# ----------------------------------------------------------------------
class _Recorder:
    """All mutable state behind one lock: the ring, the per-name
    aggregates summary() renders, the ledger file, and the id
    counters. One instance per process (rebuilt by reset())."""

    def __init__(self):
        self.lock = threading.RLock()
        self.trace = "%x-%x" % (os.getpid(),
                                int(time.time() * 1e3) & 0xFFFFFFFF)
        self.epoch = time.time()
        self.mono = clock()
        self.ring = collections.deque(maxlen=ring_size())
        self.next_sid = 1
        self.agg: Dict[str, dict] = {}
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.ledger = None        # open file object, lazily created
        self.ledger_path = None
        self.ledger_failed = False  # sticky: disk broke, stop trying

    # -- ledger --------------------------------------------------------
    def _ensure_ledger(self):
        """Open (once) the append-only JSONL ledger under
        GS_TRACE_DIR, writing the meta anchor line readers use to map
        monotonic span timestamps back to wall time."""
        if self.ledger is not None:
            return self.ledger
        if self.ledger_failed:
            return None
        d = trace_dir()
        if d is None:
            return None
        # an unwritable/full trace dir degrades to ring-only recording:
        # the flight recorder must never take down the stream it traces
        try:
            os.makedirs(d, exist_ok=True)
            self.ledger_path = os.path.join(
                d, "trace_%s.jsonl" % self.trace)
            self.ledger = open(self.ledger_path, "a")
            self.ledger.write(json.dumps({
                "t": "meta", "trace": self.trace, "pid": os.getpid(),
                "epoch": self.epoch, "mono": self.mono,
                "ring": self.ring.maxlen}) + "\n")
            self.ledger.flush()
        except OSError:
            self._ledger_broke()
            return None
        _install_exit_hooks()
        return self.ledger

    def _ledger_broke(self) -> None:
        self.ledger_failed = True
        self.ledger_path = None
        if self.ledger is not None:
            try:
                self.ledger.close()
            except OSError:
                pass
            self.ledger = None

    def _append(self, rec: dict, sync: bool) -> None:
        f = self._ensure_ledger()
        if f is None:
            return
        try:
            f.write(json.dumps(rec, default=str) + "\n")
            rec["_w"] = True  # private written mark, stripped on flush
            if sync:
                f.flush()
                if durable_sync():
                    try:
                        os.fsync(f.fileno())
                    except OSError:
                        pass
        except OSError:
            self._ledger_broke()

    def flush(self) -> None:
        """Drain every not-yet-written ring record to the ledger (the
        atexit / fatal-fault / operator path)."""
        with self.lock:
            f = self._ensure_ledger()
            if f is None:
                return
            try:
                for rec in self.ring:
                    if not rec.get("_w"):
                        f.write(json.dumps(
                            {k: v for k, v in rec.items() if k != "_w"},
                            default=str) + "\n")
                        rec["_w"] = True
                f.flush()
                try:
                    os.fsync(f.fileno())
                except OSError:
                    pass
            except OSError:
                self._ledger_broke()

    # -- recording -----------------------------------------------------
    def add(self, rec: dict, durable: bool = False) -> None:
        with self.lock:
            self.ring.append(rec)
            if rec["t"] == "span":
                a = self.agg.setdefault(rec["name"], {
                    "count": 0, "total": 0.0,
                    "samples": collections.deque(maxlen=_SAMPLE_CAP)})
                a["count"] += 1
                a["total"] += rec["dur"]
                a["samples"].append(rec["dur"])
            elif rec["t"] == "counter":
                self.counters[rec["name"]] = (
                    self.counters.get(rec["name"], 0) + rec["value"])
            elif rec["t"] == "gauge":
                self.gauges[rec["name"]] = rec["value"]
            if durable:
                self._append(rec, sync=True)

    def sid(self) -> int:
        with self.lock:
            s = self.next_sid
            self.next_sid += 1
            return s


_REC: Optional[_Recorder] = None
_REC_LOCK = threading.Lock()
_TLS = threading.local()
_HOOKS_INSTALLED = False

# Downstream consumers of the record stream (the metrics registry,
# utils/metrics.py): each entry is (sink_fn, active_fn). A sink sees
# every record the hooks produce while ITS active_fn says so, even
# with GS_TELEMETRY=0 — the flight-recorder hooks are the one
# instrumentation surface every layer already feeds, so the metrics
# plane rides them instead of duplicating call sites. With telemetry
# AND every sink disarmed the hooks stay guarded no-ops.
_SINKS: List[tuple] = []


def register_sink(sink, active) -> None:
    """Attach `sink(record_dict)` to the record stream, consulted
    while `active()` is true. Idempotent per (sink, active) pair."""
    with _REC_LOCK:
        if (sink, active) not in _SINKS:
            _SINKS.append((sink, active))


def _sinks_active() -> bool:
    for _fn, active in _SINKS:
        if active():
            return True
    return False


def _active() -> bool:
    """True when anything consumes records: the recorder itself
    (GS_TELEMETRY) or an armed sink (the metrics registry)."""
    return enabled() or _sinks_active()


def _rec() -> _Recorder:
    global _REC
    if _REC is None:
        with _REC_LOCK:
            if _REC is None:
                _REC = _Recorder()
    return _REC


def _install_exit_hooks() -> None:
    """atexit + SIGTERM flush, installed once on first ledger open (a
    ring-only recorder has nothing to save). SIGTERM chains any prior
    handler; SIGKILL is of course uncatchable — the durable-class
    immediate appends are what bound that loss to the ring."""
    global _HOOKS_INSTALLED
    if _HOOKS_INSTALLED:
        return
    _HOOKS_INSTALLED = True
    atexit.register(flush)
    try:
        import signal

        prev = signal.getsignal(signal.SIGTERM)

        def _on_term(signum, frame):
            flush()
            # preserve the prior disposition EXACTLY: chain a callable
            # handler, die the default way for SIG_DFL, and keep the
            # process alive when it deliberately ignored SIGTERM
            # (SIG_IGN / unknown) — the flush must never change
            # whether SIGTERM is survivable
            if callable(prev):
                prev(signum, frame)
            elif prev is signal.SIG_DFL:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signal.SIGTERM)

        signal.signal(signal.SIGTERM, _on_term)
    except (ValueError, OSError):
        pass  # non-main thread / exotic platform: atexit still covers


def reset() -> None:
    """Test/tool hook: drop all recorded state and start a fresh trace
    (closes the current ledger; a new one opens on the next record)."""
    global _REC
    with _REC_LOCK:
        if _REC is not None and _REC.ledger is not None:
            try:
                _REC.flush()
                _REC.ledger.close()
            except (OSError, ValueError):
                pass
        _REC = None
    _TLS.__dict__.clear()


def trace_id() -> str:
    """The process-wide run trace ID every record carries."""
    return _rec().trace


def ledger_path() -> Optional[str]:
    """Path of this run's ledger file (None when GS_TRACE_DIR is
    unset or nothing has been recorded to disk yet)."""
    r = _rec()
    if r.ledger_path is None and trace_dir() is not None:
        with r.lock:
            r._ensure_ledger()
    return r.ledger_path


def flush() -> None:
    """Drain the ring to the ledger (no-op without GS_TRACE_DIR)."""
    if _REC is not None:
        _REC.flush()


# ----------------------------------------------------------------------
# context / correlation
# ----------------------------------------------------------------------
def _ctx_attrs() -> dict:
    return getattr(_TLS, "ctx", None) or {}


def _parent_sid() -> Optional[int]:
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def context(**attrs):
    """Bind correlation attributes (chunk=..., window=...) to every
    record made by THIS thread inside the scope; explicit per-record
    attrs win on collision. Thread-local — pool workers need their
    chunk identity passed explicitly (see chunk_ctx)."""
    prev = getattr(_TLS, "ctx", None)
    merged = dict(prev or {})
    merged.update(attrs)
    _TLS.ctx = merged
    try:
        yield
    finally:
        _TLS.ctx = prev


def _record(kind: str, name: str, durable: bool = False,
            **fields) -> Optional[dict]:
    rec = {"t": kind, "name": name, "trace": _rec().trace,
           "tid": threading.get_ident()}
    ctx = _ctx_attrs()
    if ctx:
        a = dict(ctx)
        a.update(fields.pop("a", None) or {})
        fields["a"] = a
    rec.update({k: v for k, v in fields.items() if v is not None})
    if not rec.get("a"):
        rec.pop("a", None)
    if enabled():
        if not _HOOKS_INSTALLED and trace_dir() is not None:
            # a ledger-destined run must flush its ring at exit even if
            # no durable event ever opens the file earlier
            _install_exit_hooks()
        _rec().add(rec, durable=durable)
    dropped = []
    for sink, active in list(_SINKS):
        if active():
            try:
                sink(rec)
            except Exception as exc:  # gslint: disable=except-hygiene (a broken metrics sink must never take down the stream it observes; it is dropped from the record path with a durable marker below)
                with _REC_LOCK:
                    if (sink, active) in _SINKS:
                        _SINKS.remove((sink, active))
                        dropped.append(exc)
    for exc in dropped:
        # the armed plane going dark must leave a visible scar, not
        # silently freeze its gauges: stamp a durable event (the
        # failed sink is already removed, so this re-entry terminates)
        # AND a registry counter — with GS_TELEMETRY=0 the event
        # no-ops (no ledger), but /metrics still shows the drop
        event("metrics_sink_dropped", durable=True,
              error=repr(exc)[:200])
        try:
            from . import metrics as _metrics

            _metrics.counter_inc("gs_metrics_sink_dropped_total")
        except Exception:  # gslint: disable=except-hygiene (the scar write itself must never take down the record path it marks)
            pass
    return rec


# ----------------------------------------------------------------------
# the live profiler session (the second sink)
# ----------------------------------------------------------------------
_ANNOTATION = None  # jax.profiler.TraceAnnotation, bound on first use
_UNSET = object()


def _profiling():
    """The `TraceAnnotation` class while a jax.profiler session
    records, else None: one `is_enabled()` call off the capture."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION if _ANNOTATION.is_enabled() else None


def trace_scope(name: str, **attrs):
    """A profiler-only scope (nothing reaches the ring or the ledger):
    for sites that time themselves and report through `record_span`
    after the work, so the capture still sees the work as it runs."""
    ann = _profiling()
    return ann(name, **attrs) if ann is not None \
        else contextlib.nullcontext()


# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------
class _Span:
    """Context manager AND stopwatch. Always measures (callers like
    the autotune round loops need `.elapsed` whether or not telemetry
    is armed); records only when armed at __exit__ time, and opens a
    profiler annotation while a session records. Nesting is tracked
    per thread via the span-id stack."""

    __slots__ = ("name", "attrs", "t0", "elapsed", "sid", "_pushed",
                 "_ann", "_ann_attrs")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.t0 = clock()
        self.elapsed = 0.0
        self.sid = None
        self._pushed = False
        self._ann = None
        self._ann_attrs = None

    def __enter__(self):
        ann = _profiling()
        if ann is not None:
            self._ann_attrs = dict(self.attrs)
            self._ann = ann(self.name, **self._ann_attrs)
            self._ann.__enter__()
        self.t0 = clock()
        if enabled():
            self.sid = _rec().sid()
            stack = getattr(_TLS, "stack", None)
            if stack is None:
                stack = _TLS.stack = []
            stack.append(self.sid)
            self._pushed = True
        return self

    def __exit__(self, exc_type, exc, tb):
        self.elapsed = clock() - self.t0
        if self._pushed:
            _TLS.stack.pop()
            self._pushed = False
        if self._ann is not None:
            self._close_annotation(exc_type, exc, tb)
        if _active():
            par = _parent_sid()
            a = dict(self.attrs) if self.attrs else {}
            if exc_type is not None:
                a["error"] = exc_type.__name__
            _record("span", self.name, ts=self.t0, dur=self.elapsed,
                    sid=self.sid, par=par, a=a or None)
        return False

    def _close_annotation(self, exc_type, exc, tb) -> None:
        """Attributes set inside the span (the driver's dispatch tags)
        and the error go on as metadata; the annotation closes on
        every path."""
        ann, self._ann = self._ann, None
        try:
            late = {k: v for k, v in self.attrs.items()
                    if self._ann_attrs.get(k, _UNSET) is not v}
            if exc_type is not None:
                late["error"] = exc_type.__name__
            if late:
                ann.set_metadata(**late)
        finally:
            self._ann_attrs = None
            ann.__exit__(exc_type, exc, tb)


def span(name: str, **attrs) -> _Span:
    """A named span: `with telemetry.span("step.intern", records=n)
    as sp: ...`; sp.elapsed holds the measured seconds either way."""
    return _Span(name, attrs)


class _Stopwatch:
    """Deferred span: started at construction, recorded by stop() —
    for intervals that cross scopes (the driver's dispatch-to-dispatch
    autotune rounds). Unstopped stopwatches record nothing."""

    __slots__ = ("name", "attrs", "t0", "_done")

    def __init__(self, name: Optional[str], attrs: dict):
        self.name = name
        self.attrs = attrs
        self.t0 = clock()
        self._done = False

    def stop(self, **extra) -> float:
        """Close the interval; returns elapsed seconds (idempotent —
        later calls return the first measurement without
        re-recording)."""
        if self._done:
            return self.attrs.get("_elapsed", 0.0)
        self._done = True
        elapsed = clock() - self.t0
        self.attrs["_elapsed"] = elapsed
        if self.name is not None and _active():
            a = dict(self.attrs)
            a.pop("_elapsed", None)
            a.update(extra)
            _record("span", self.name, ts=self.t0, dur=elapsed,
                    sid=_rec().sid() if enabled() else None,
                    par=_parent_sid(), a=a or None)
        return elapsed


def stopwatch(name: Optional[str] = None, **attrs) -> _Stopwatch:
    return _Stopwatch(name, attrs)


def record_span(name: str, t0: float, dur: float,
                parent: Optional[int] = None,
                sid: Optional[int] = None, **attrs) -> None:
    """Record an already-measured interval (the worker-side ingress
    stages time themselves and report after the fact)."""
    if not _active():
        return
    if sid is None and enabled():
        sid = _rec().sid()
    _record("span", name, ts=t0, dur=dur, sid=sid,
            par=parent if parent is not None else _parent_sid(),
            a=attrs or None)


# -- program-signature dispatch tags (the cost observatory) ------------
def tag_dispatch(**tags) -> None:
    """Bind program-identity attributes (program=..., sig=...) to THIS
    thread's next dispatch-span record. Set by the dispatch wrappers
    (utils/costmodel via metrics.wrap_jit / costmodel.wrap_exec, which
    run INSIDE the dispatch call), consumed by the dispatch-span
    record sites (ops/ingress_pipeline, the driver's snapshot-scan
    step) via pop_dispatch_tags — so ledger spans carry the program
    and abstract-shape signature the cost registry is keyed by."""
    _TLS.dispatch_tags = tags


def pop_dispatch_tags() -> dict:
    """Take (and clear) the pending dispatch tags of this thread; {}
    when none are bound. Cheap enough for disarmed hot paths: one
    thread-local read."""
    tags = getattr(_TLS, "dispatch_tags", None)
    if tags is None:
        return {}
    _TLS.dispatch_tags = None
    return tags


# -- cross-thread chunk correlation (the ingress pipeline) -------------
def chunk_ctx(chunk) -> Optional[dict]:
    """Open a chunk span handle the pool workers can parent their
    stage spans to (thread-local nesting cannot cross the pool). The
    span itself is recorded by close_chunk once the chunk's finalize
    lands."""
    if not enabled():
        return None
    return {"sid": _rec().sid(), "chunk": chunk, "t0": clock()}


def close_chunk(ctx: Optional[dict], **attrs) -> None:
    if ctx is None or not enabled():
        return
    _record("span", "ingress.chunk", ts=ctx["t0"],
            dur=clock() - ctx["t0"], sid=ctx["sid"],
            par=_parent_sid(),
            a=dict(attrs, chunk=ctx["chunk"]))


def chunk_key(item):
    """A compact correlation id for a pipeline chunk descriptor:
    ints (window starts) pass through; anything else is opaque."""
    import numbers

    if isinstance(item, numbers.Integral):
        return int(item)
    if isinstance(item, tuple) and item \
            and isinstance(item[0], numbers.Integral):
        return int(item[0])  # gslint: disable=host-sync (a chunk descriptor's host int, never a device value)
    return None


# ----------------------------------------------------------------------
# events / counters / gauges
# ----------------------------------------------------------------------
def event(name: str, durable: bool = False, **attrs) -> None:
    """A discrete happening. durable=True appends + fsyncs the record
    to the ledger immediately (demotions, kills, checkpoints, resumes
    — the post-mortem class that must survive a wedge)."""
    if not _active():
        return
    _record("event", name, ts=clock(), durable=durable,
            a=attrs or None)


def counter(name: str, value: float = 1, **attrs) -> None:
    """Add `value` to counter `name`. A live profiler session gets a
    zero-length event with `value` and the attrs, armed or not."""
    ann = _profiling()
    if ann is not None:
        with ann(name, value=value, **attrs):
            pass
    if not _active():
        return
    _record("counter", name, ts=clock(), value=value, a=attrs or None)


def gauge(name: str, value: float, **attrs) -> None:
    if not _active():
        return
    _record("gauge", name, ts=clock(), value=value, a=attrs or None)


def on_fatal(site: str = "") -> None:
    """The simulated-hard-kill hook (utils/faults fatal InjectedFault):
    stamp a durable event and flush the ring, so the post-kill ledger
    still holds the pre-kill spans — the flight-recorder contract
    tools/chaos_run.py asserts end-to-end."""
    if not enabled():
        return
    event("fatal", durable=True, site=site)
    flush()


# ----------------------------------------------------------------------
# aggregation (PERF.json `telemetry` section; shared histogram math)
# ----------------------------------------------------------------------
def percentiles(samples, ps=(50, 95, 99)) -> Dict[int, float]:
    """Nearest-rank percentiles over `samples` (exact, no
    interpolation: the p-th percentile is the ceil(p/100*n)-th
    smallest sample) — the one histogram definition the recorder,
    tools/trace_report.py, and the tests all share."""
    xs = sorted(samples)
    if not xs:
        return {p: 0.0 for p in ps}
    n = len(xs)
    out = {}
    for p in ps:
        rank = max(1, -(-p * n // 100))  # ceil(p*n/100), 1-based
        out[p] = float(xs[min(rank, n) - 1])  # gslint: disable=host-sync (recorded host durations, never device values)
    return out


def summary(top: int = 0) -> List[dict]:
    """Per-span-name latency rows (count, total, p50/p95/p99 over the
    bounded sample reservoir), sorted by total time — the
    schema-validated `telemetry` section tools commit to PERF.json."""
    r = _rec()
    with r.lock:
        rows = []
        for name, a in r.agg.items():
            pct = percentiles(a["samples"])
            rows.append({
                "span": name,
                "count": a["count"],
                "total_ms": round(a["total"] * 1e3, 3),
                "p50_ms": round(pct[50] * 1e3, 3),
                "p95_ms": round(pct[95] * 1e3, 3),
                "p99_ms": round(pct[99] * 1e3, 3),
            })
    rows.sort(key=lambda x: -x["total_ms"])
    return rows[:top] if top else rows


def records() -> List[dict]:
    """Snapshot of the ring (tests / diagnostics), private marks
    stripped."""
    r = _rec()
    with r.lock:
        return [{k: v for k, v in rec.items() if k != "_w"}
                for rec in r.ring]
