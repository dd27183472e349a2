"""Three-stage host-ingress pipeline: parallel window prep ‖ h2d
transfer ‖ device compute.

PERF.md's verified chip ladder pins the end-to-end stream rate at
~500-770K edges/s across every scale — flat while the baseline falls
10× — i.e. the wall is serialized single-core host prep plus
transfer/dispatch, not the K×K device compare. The reference delegates
exactly this ingest/shuffle layer to Flink's network stack
(SimpleEdgeStream.java:60-90); this module is its TPU-native
replacement, generalizing the depth-2 producer thread that used to
live inline in TriangleWindowKernel._run_stack_loop into ONE reusable
pipeline every streaming kernel routes through (triangles,
windowed_reduce, the fused scan engines, and the sharded kernels —
which keep their own table contract but share this loop).

Stages, per chunk of windows:

  1. PREP     — build the padded host stacks (seg_ops.window_stack /
                compact_ingress slicing / cell-id packing). Runs on a
                process-wide worker POOL (`prep_pool`), so several
                chunks prep concurrently — numpy copies and the native
                parser drop the GIL, so the parallelism is real. Prep
                results are consumed strictly in chunk order; worker
                scheduling can never reorder (or change) results, so
                counts are identical at every pool size.
  2. H2D      — convert/enqueue the host stacks to device arrays on
                the SAME worker, immediately after that chunk's prep
                (timed as its own stage). Running a blocking
                device_put on the worker is what lets chunk i+1's
                transfer overlap both device execution and the main
                thread's blocking d2h wait on chunk i-1 — the overlap
                the round-5 producer thread provided.
  3. DISPATCH — enqueue the chunk's device program (async, main
                thread, chunk order) and, one chunk later,
                MATERIALIZE the previous chunk's outputs (d2h +
                overflow recounts), so the d2h round trip of chunk i
                hides behind chunk i+1's execution — the same depth-2
                discipline as before, now with a parallel front end.
                `dispatch_ahead` splits the loop at its read-back
                instead: every chunk dispatched now, all finalized
                when the caller asks (the driver's triangle stack,
                queued behind its scan before the scan's d2h wait).

Per-stage wall time accumulates in a `StageTimers` (prep/h2d/compute
ms per chunk) that tools/profile_kernels.py commits to PERF.json, so
a chip run can decompose the chip-side wall without new
instrumentation. With the flight recorder armed (utils/telemetry,
GS_TELEMETRY=1) each chunk additionally records a correlated span
tree — an `ingress.chunk` span with prep/h2d/dispatch/finalize child
spans, worker-side stages included — into the run ledger. Inside a
jax.profiler capture each stage also runs under a live scope of the
same name, on the thread that does it (`telemetry.trace_scope`).

Env knobs:
  GS_STREAM_PREFETCH=0  — force the fully synchronous single-threaded
                          form (no pool, prep inline; dispatch keeps
                          its depth-2 overlap). Same counts.
  GS_PIPELINE_WORKERS=N — prep pool size (default min(4, cpus-1);
                          0 behaves like GS_STREAM_PREFETCH=0 for the
                          pool while keeping the API).
  GS_PIPELINE_INFLIGHT=N — max prepped+TRANSFERRED chunks in flight
                          ahead of dispatch in run_pipeline (default
                          3): the host+HBM footprint bound the old
                          depth-2 producer queue provided, kept
                          independent of the pool width so capping
                          device memory never requires shrinking prep
                          parallelism for the host-tier map_ordered
                          users.
  GS_STAGE_TIMEOUT_S=T  — per-STAGE watchdog deadline (utils/
                          resilience): a prep/h2d/dispatch/finalize
                          call that exceeds T surfaces as a typed
                          StageTimeout naming the chunk instead of
                          stalling the stream forever (a hung
                          transfer). 0 (default) disables.
  GS_STAGE_RETRIES=N    — bounded retry for the re-runnable stages
                          (prep and h2d are pure/idempotent) with
                          deterministic jitterless exponential
                          backoff (GS_STAGE_BACKOFF_S base, default
                          0.05 s). Side-effecting stages (dispatch,
                          finalize) never retry — a deadline/failure
                          there is typed and raised at once. Default
                          0; with both knobs unset the guard is inert
                          and the legacy inline path (and its exact
                          exception types) runs.

On ANY failure escaping the loop, in-flight device work is DRAINED:
the already-dispatched previous chunk's finalize runs best-effort
before the error re-raises, so its device buffers and d2h are never
silently abandoned mid-stream.
"""

from __future__ import annotations

import os
import threading
import time
import traceback
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import Callable, Iterable, List, Optional

from ..utils import faults
from ..utils import knobs
from ..utils import metrics
from ..utils import resilience
from ..utils import telemetry
from ..utils.resilience import StageFailed, StageTimeout

_MAX_DEFAULT_WORKERS = 4
_POLL_S = 0.02  # watchdog poll tick while awaiting a guarded stage


class StageTimers:
    """Per-stage wall-time accumulators of one pipelined run (or a
    kernel's lifetime): milliseconds spent in prep (summed across
    workers — CPU time, not critical-path time, when prep runs
    parallel), h2d conversion/enqueue, and compute (the blocking
    materialize wait: device execute + d2h as observed by the host).
    `snapshot()` renders the per-chunk means PERF.json commits."""

    __slots__ = ("chunks", "prep_ms", "h2d_ms", "compute_ms", "_lock")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        # under the lock: a concurrent worker's add() between the
        # field writes would otherwise be partially erased
        with self._lock:
            self.chunks = 0
            self.prep_ms = 0.0
            self.h2d_ms = 0.0
            self.compute_ms = 0.0

    def add(self, stage: str, seconds: float) -> None:
        with self._lock:  # prep accumulates from several workers
            setattr(self, stage + "_ms",
                    getattr(self, stage + "_ms") + seconds * 1e3)

    def snapshot(self) -> dict:
        n = max(self.chunks, 1)
        return {
            "chunks": self.chunks,
            "prep_ms_per_chunk": round(self.prep_ms / n, 3),
            "h2d_ms_per_chunk": round(self.h2d_ms / n, 3),
            "compute_ms_per_chunk": round(self.compute_ms / n, 3),
        }


class PrepError(RuntimeError):
    """A prep-stage worker failed. The message carries the worker's
    FORMATTED traceback (the raw re-raise used to surface only the
    consumer-side frames, losing where in make_chunk the producer
    actually died); the original exception rides as __cause__."""


_POOL = None
_POOL_WORKERS = None
_POOL_LOCK = threading.Lock()
_FORCE_SYNC = 0  # nesting depth of forced_sync() contexts


def worker_count() -> int:
    """Prep pool width: GS_PIPELINE_WORKERS, defaulting to
    min(4, cpus-1) — one core stays with the main thread's
    h2d/dispatch stage."""
    env = knobs.get_int("GS_PIPELINE_WORKERS")
    if env is not None:
        return env
    return max(1, min(_MAX_DEFAULT_WORKERS, (os.cpu_count() or 2) - 1))


def inflight_limit() -> int:
    """Max prepped+transferred chunks run_pipeline keeps in flight
    ahead of dispatch (GS_PIPELINE_INFLIGHT, default 3) — the bounded-
    footprint contract of the old depth-2 queue, decoupled from the
    pool width."""
    return knobs.get_int("GS_PIPELINE_INFLIGHT")


def pipeline_enabled() -> bool:
    """False when the caller (or env) pinned the synchronous form."""
    if _FORCE_SYNC:
        return False
    if not knobs.get_bool("GS_STREAM_PREFETCH"):
        return False
    return worker_count() > 0


def forced_sync_active() -> bool:
    """True while any forced_sync() context is live — the explicit A/B
    measurement lever. The dispatch autotuner FREEZES under it (exploit
    only, no recording), so a sync-baseline rep can neither pollute the
    tuner's rate estimates nor be measured at a different configuration
    than the pipelined rep it is compared against."""
    return _FORCE_SYNC > 0


class forced_sync:
    """Context manager pinning the synchronous single-threaded form —
    the A/B lever bench.py and the profiler use to measure the
    pipeline against its own sync baseline without env juggling.
    Process-global (and lock-guarded, so nested/concurrent contexts
    can't corrupt the depth): while any context is active, EVERY
    pipelined call in the process runs sync — measurement harnesses
    must not run unrelated pipelined work concurrently."""

    def __enter__(self):
        global _FORCE_SYNC
        with _POOL_LOCK:
            _FORCE_SYNC += 1
        return self

    def __exit__(self, *exc):
        global _FORCE_SYNC
        with _POOL_LOCK:
            _FORCE_SYNC -= 1
        return False


def prep_pool():
    """The process-wide prep ThreadPoolExecutor (lazily built, rebuilt
    when GS_PIPELINE_WORKERS changes); None when pipelining is off."""
    global _POOL, _POOL_WORKERS
    if not pipeline_enabled():
        return None
    w = worker_count()
    with _POOL_LOCK:
        if _POOL is None or _POOL_WORKERS != w:
            from concurrent.futures import ThreadPoolExecutor

            # a superseded pool is ABANDONED, never shut down: a
            # concurrent run still holding it must be able to finish
            # submitting (ThreadPoolExecutor's workers exit on their
            # own once the dropped executor is garbage collected)
            _POOL = ThreadPoolExecutor(
                max_workers=w, thread_name_prefix="gs-ingress-prep")
            _POOL_WORKERS = w
        return _POOL


def reset_pool() -> None:
    """Test hook: drop the memoized pool (e.g. after changing
    GS_PIPELINE_WORKERS mid-process). The old pool is abandoned, not
    shut down — see prep_pool."""
    global _POOL, _POOL_WORKERS
    with _POOL_LOCK:
        _POOL = None
        _POOL_WORKERS = None


def _mark(cell: Optional[dict], stage: str) -> None:
    """Record which stage a worker task is in (and since when) so the
    consumer-side watchdog can enforce a per-STAGE deadline and name
    the actual hung stage. Plain dict writes: each key is written by
    one thread and read by one other — torn reads are impossible for
    the float/str values involved."""
    if cell is not None:
        cell["since"] = time.perf_counter()
        cell["stage"] = stage


def _span_cell(cell: Optional[dict], item):
    """(parent span id, chunk correlation id) of a worker stage: the
    chunk ctx rides the worker cell (thread-local span nesting cannot
    cross the pool); without one, the item's own key still correlates
    the stage records of one chunk."""
    ctx = cell.get("tctx") if cell else None
    if ctx is not None:
        return ctx["sid"], ctx["chunk"]
    return None, telemetry.chunk_key(item)


def _timed_prep(prep: Callable, item, timers: Optional[StageTimers],
                cell: Optional[dict] = None):
    """Worker-side prep wrapper: times the call and converts a failure
    into a PrepError carrying the formatted worker traceback."""
    _mark(cell, "prep")
    par, ck = _span_cell(cell, item)
    t0 = time.perf_counter()
    try:
        with telemetry.trace_scope("ingress.prep", chunk=ck):
            faults.fire("prep")
            out = prep(item)
    except Exception as e:
        # Exception only: KeyboardInterrupt/SystemExit must abort the
        # run unwrapped (a broad caller-side `except RuntimeError`
        # fallback must never eat an interrupt as a prep failure);
        # pool futures re-raise those at .result() regardless
        raise PrepError(
            "ingress prep stage failed for chunk %r:\n%s"
            % (item, traceback.format_exc())) from e
    dt = time.perf_counter() - t0
    if timers is not None:
        timers.add("prep", dt)
    telemetry.record_span("ingress.prep", t0, dt, parent=par,
                          chunk=ck)
    return out


def _prep_then_h2d(prep: Callable, h2d: Callable, item,
                   timers: Optional[StageTimers],
                   cell: Optional[dict] = None):
    """One worker task = prep + h2d of one chunk, each stage timed
    separately; h2d failures carry the worker traceback too."""
    payload = _timed_prep(prep, item, timers, cell)
    _mark(cell, "h2d")
    par, ck = _span_cell(cell, item)
    t0 = time.perf_counter()
    try:
        with telemetry.trace_scope("ingress.h2d", chunk=ck):
            faults.fire("h2d")
            dev = h2d(payload)
    except Exception as e:  # see _timed_prep: interrupts pass through
        raise PrepError(
            "ingress h2d stage failed for chunk %r:\n%s"
            % (item, traceback.format_exc())) from e
    dt = time.perf_counter() - t0
    if timers is not None:
        timers.add("h2d", dt)
    telemetry.record_span("ingress.h2d", t0, dt, parent=par, chunk=ck)
    _mark(cell, "done")
    return dev


def _is_fatal(exc: BaseException) -> bool:
    """True for the chaos harness's simulated hard kill
    (faults.InjectedFault(fatal=True)), possibly wrapped in PrepError
    by the worker: never retried, re-raised as-is."""
    cause = exc.__cause__ if isinstance(exc, PrepError) else exc
    return isinstance(cause, faults.InjectedFault) and cause.fatal


def _await_attempt(wait_tick: Callable, outcome: Callable,
                   cell: dict, timeout: float, queued_since: float):
    """Shared wait loop of one guarded prep+h2d attempt. `wait_tick(t)`
    blocks up to t seconds and returns True once the attempt finished;
    `outcome()` then returns its value or raises. Enforces `timeout`
    per STAGE via the worker-updated cell; a task no worker has picked
    up yet counts its QUEUE wait (since `queued_since`) against the
    same deadline — with every pool worker wedged on abandoned hangs,
    the queue itself is the hung stage, and the retry's dedicated
    thread is what routes around the dead pool. Returns
    (True, value, None) | (False, exception, stage) |
    (False, None, stage) — the last meaning a stage deadline expired
    (the attempt's thread is abandoned)."""
    while True:
        if wait_tick(_POLL_S if timeout > 0 else None):
            try:
                return True, outcome(), None
            except BaseException as e:  # gslint: disable=except-hygiene (captured: caller raises or retries it)
                return False, e, cell.get("stage")
        stage = cell.get("stage", "queued")
        since = cell.get("since", queued_since)
        if (timeout > 0 and stage in ("queued", "prep", "h2d")
                and time.perf_counter() - since > timeout):
            return False, None, stage


def _guarded_prep_h2d(prep: Callable, h2d: Callable, item,
                      timers: Optional[StageTimers],
                      first_future=None, first_cell=None):
    """Resolve one chunk's prep+h2d under the stage guard
    (GS_STAGE_TIMEOUT_S / GS_STAGE_RETRIES): a per-stage deadline with
    bounded deterministic-backoff retry. Attempt 1 consumes
    `first_future` (already submitted to the pool) when given; retry
    attempts run on DEDICATED daemon threads so a hung pool worker is
    abandoned rather than re-poisoned. Prep and h2d are safe to re-run
    by contract (prep is pure, h2d an idempotent transfer).

    This is the cell-aware twin of resilience.call_guarded (which
    deadlines a whole call): the per-STAGE deadline and the
    pooled-first-attempt handoff need the worker-updated cell, which
    the generic guard has no notion of. A change to retry semantics
    (fatal pass-through, attempt accounting, backoff) must land in
    BOTH."""
    retries = resilience.stage_retries()
    timeout = resilience.stage_timeout_s()
    backoff = resilience.stage_backoff_s()
    attempts: List[dict] = []
    last_stage = "prep"
    for attempt in range(retries + 1):
        t0 = time.perf_counter()
        if attempt == 0 and first_future is not None:
            cell = first_cell if first_cell is not None else {}
            ok, res, stage = _await_attempt(
                lambda t: _future_wait(first_future, t),
                first_future.result, cell, timeout,
                cell.get("submitted", t0))
        elif timeout > 0:
            # retry attempts keep the chunk-span correlation of the
            # pooled first attempt (telemetry): same parent, so the
            # ledger shows the retries under one chunk
            cell = {"tctx": (first_cell or {}).get("tctx")}
            box, done = {}, threading.Event()

            def _runner(cell=cell, box=box, done=done):
                try:
                    box["value"] = _prep_then_h2d(prep, h2d, item,
                                                  timers, cell)
                except BaseException as e:  # gslint: disable=except-hygiene (captured: _outcome re-raises on the waiter)
                    box["error"] = e
                finally:
                    done.set()

            threading.Thread(target=_runner, daemon=True,
                             name="gs-ingress-retry").start()

            def _outcome(box=box):
                if "error" in box:
                    raise box["error"]
                return box["value"]

            ok, res, stage = _await_attempt(done.wait, _outcome, cell,
                                            timeout, t0)
        else:  # retries without a deadline: run inline
            cell = {"tctx": (first_cell or {}).get("tctx")}
            try:
                return _prep_then_h2d(prep, h2d, item, timers, cell)
            except Exception as e:  # gslint: disable=except-hygiene (captured: the retry loop re-raises as StageFailed)
                ok, res, stage = False, e, cell.get("stage")
        if ok:
            return res
        if res is not None and (not isinstance(res, Exception)
                                or _is_fatal(res)):
            raise res  # interrupts and the simulated kill: unretried
        last_stage = stage or last_stage
        attempts.append({
            "stage": last_stage,
            "outcome": "timeout" if res is None else type(res).__name__,
            "elapsed_s": round(time.perf_counter() - t0, 6)})
        if attempt >= retries:
            if res is None:
                raise StageTimeout(
                    "%s stage of chunk %r exceeded its %.3gs deadline "
                    "(GS_STAGE_TIMEOUT_S) on %d attempt(s)"
                    % (last_stage, item, timeout, len(attempts)),
                    last_stage, item, attempts)
            raise StageFailed(
                "%s stage of chunk %r failed after %d attempt(s): %s"
                % (last_stage, item, len(attempts), res),
                last_stage, item, attempts) from res
        telemetry.event("stage_retry", stage=last_stage,
                        chunk=telemetry.chunk_key(item),
                        attempt=attempt + 1,
                        outcome=attempts[-1]["outcome"])
        time.sleep(backoff * (2 ** attempt))


def _future_wait(fut, t: Optional[float]) -> bool:
    """Event.wait-shaped adapter over Future: True once done."""
    if t is None:
        try:
            fut.exception()  # blocks to completion; outcome re-raises
        except BaseException:  # gslint: disable=except-hygiene (wait only: outcome() re-raises the real error)
            pass
        return True
    try:
        fut.exception(timeout=t)
    except _FutureTimeout:
        return fut.done()
    except BaseException:  # gslint: disable=except-hygiene (wait only: outcome() re-raises the real error)
        pass
    return True


def _chunk_ctx(item):
    """Chunk span handle (telemetry): stage spans of this chunk —
    including the pool worker's prep/h2d — parent to it; closed when
    the chunk's finalize lands. None when disarmed."""
    return telemetry.chunk_ctx(telemetry.chunk_key(item))


def _dispatch_stage(dispatch: Callable, item, dev, tctx, guard: bool):
    """The dispatch stage of one chunk: its fault site, guard and
    span. Returns dispatch(dev)'s raw outputs."""
    # the wrapped program called inside dispatch() binds its
    # program/signature tags (utils/costmodel) in the TLS of
    # WHICHEVER thread runs it — under an armed stage watchdog that
    # is the gs-stage-watchdog helper, not this thread — so the tags
    # are captured inside the callable and carried back through the
    # closure for the span record below
    disp_tags = {}

    def _call():
        faults.fire("dispatch")
        out = dispatch(dev)
        disp_tags.update(telemetry.pop_dispatch_tags())
        return out

    par, ck = _span_cell({"tctx": tctx}, item)
    t0 = time.perf_counter()
    telemetry.pop_dispatch_tags()  # drop any stale pre-dispatch tag
    # dispatch is retries=0 too: engines fold the chunk into a
    # device-resident carry inside it, so re-running would
    # double-fold the chunk
    with telemetry.trace_scope("ingress.dispatch", chunk=ck):
        raw = (resilience.call_guarded("dispatch", item, _call,
                                       retries=0)
               if guard else _call())
    telemetry.record_span("ingress.dispatch", t0,
                          time.perf_counter() - t0, parent=par,
                          chunk=ck, **disp_tags)
    return raw


def _finalize_stage(finalize: Callable, item, raw, tctx,
                    timers: Optional[StageTimers], guard: bool) -> None:
    """The finalize stage of one chunk: its fault site, guard, timer
    and span; closes the chunk span."""
    par, ck = _span_cell({"tctx": tctx}, item)
    t0 = time.perf_counter()

    def _call():
        faults.fire("finalize")
        finalize(raw)

    with telemetry.trace_scope("ingress.finalize", chunk=ck):
        if guard:
            # deadline only, NEVER retried: finalize mutates consumer
            # state (appends results, advances carried mirrors), so a
            # re-run would double-apply; a hang still surfaces as a
            # typed StageTimeout instead of stalling the stream
            resilience.call_guarded("finalize", item, _call, retries=0)
        else:
            _call()
    dt = time.perf_counter() - t0
    if timers is not None:
        timers.add("compute", dt)
        timers.chunks += 1
    telemetry.record_span("ingress.finalize", t0, dt, parent=par,
                          chunk=ck)
    telemetry.close_chunk(tctx)


def dispatch_ahead(items: Iterable, prep: Callable, h2d: Callable,
                   dispatch: Callable, finalize: Callable,
                   timers: Optional[StageTimers] = None) -> Callable:
    """run_pipeline split at its read-back: prep, h2d and dispatch
    every item now, finalize them later. For a caller that has other
    host work to run under the queued programs before it reads them
    back. Stages run inline on the calling thread, in item order,
    under run_pipeline's fault sites, guards and spans. Returns the
    read-back: a call with no arguments that finalizes each item in
    order; an error stops it at the failing item, those before it
    finalized."""
    guard = resilience.guard_active()
    flights = []
    for item in items:
        tctx = _chunk_ctx(item)
        cell = {"tctx": tctx}
        dev = (_guarded_prep_h2d(prep, h2d, item, timers, first_cell=cell)
               if guard else _prep_then_h2d(prep, h2d, item, timers, cell))
        flights.append(
            (item, _dispatch_stage(dispatch, item, dev, tctx, guard), tctx))

    def read_back() -> None:
        for item, raw, tctx in flights:
            _finalize_stage(finalize, item, raw, tctx, timers, guard)

    return read_back


def run_pipeline(items: Iterable, prep: Callable, h2d: Callable,
                 dispatch: Callable, finalize: Callable,
                 timers: Optional[StageTimers] = None,
                 inflight: Optional[int] = None) -> None:
    """Run `items` (ordered chunk descriptors) through the three
    stages. Contracts:

      prep(item)     -> host payload (pure; runs on the pool, any
                        worker, but results are consumed in item
                        order — parallelism never reorders effects)
      h2d(payload)   -> device payload (runs on the SAME worker right
                        after that chunk's prep, so a blocking
                        transfer overlaps device execute
                        and the previous chunk's d2h wait; must be
                        thread-safe — jnp.asarray/device_put are)
      dispatch(dev)  -> raw outputs (main thread, item order; must be
                        ASYNC — do not block on device results here)
      finalize(raw)  -> None (materializes d2h + any recount; called
                        one item BEHIND dispatch so the round trip of
                        chunk i hides behind chunk i+1, then once more
                        at the end)

    A prep/h2d failure surfaces in the caller as PrepError
    (RuntimeError) carrying the worker traceback — or, with the stage
    guard armed (GS_STAGE_TIMEOUT_S / GS_STAGE_RETRIES), as a typed
    StageTimeout/StageFailed naming the chunk and stage once the
    attempt budget is exhausted. Pending futures are cancelled, and the
    already-dispatched previous chunk is DRAINED (its finalize runs
    best-effort) before any error re-raises, so device buffers and the
    d2h in flight are never silently abandoned. With pipelining
    disabled (`forced_sync`, GS_STREAM_PREFETCH=0, or zero workers)
    both stages run inline — identical results either way.

    `inflight` narrows the prepped+transferred look-ahead below the
    global GS_PIPELINE_INFLIGHT for callers with their own ring
    contract (the resident tier's GS_RESIDENT_SLOTS ingest ring);
    None keeps the global bound.
    """
    items = list(items)
    pool = prep_pool() if len(items) > 1 else None
    pending = None  # (item, raw, chunk ctx) one chunk behind dispatch
    guard = resilience.guard_active()
    futures = ()

    def _finalize(item, raw, tctx=None):
        _finalize_stage(finalize, item, raw, tctx, timers, guard)

    def _consume(item, dev, tctx=None):
        nonlocal pending
        raw = _dispatch_stage(dispatch, item, dev, tctx, guard)
        if pending is not None:
            done_chunk, pending = pending, None
            _finalize(*done_chunk)
        pending = (item, raw, tctx)

    def _submit(it):
        # `submitted` anchors the queue-wait deadline: a task no
        # wedged-pool worker ever picks up must still time out
        cell = {"submitted": time.perf_counter(),
                "tctx": _chunk_ctx(it)}
        return (it, cell,
                pool.submit(_prep_then_h2d, prep, h2d, it, timers,
                            cell))

    try:
        if pool is None:
            for item in items:
                tctx = _chunk_ctx(item)
                cell = {"tctx": tctx}
                dev = (_guarded_prep_h2d(prep, h2d, item, timers,
                                         first_cell=cell)
                       if guard
                       else _prep_then_h2d(prep, h2d, item, timers,
                                           cell))
                _consume(item, dev, tctx)
        else:
            from collections import deque

            # bounded look-ahead caps host memory AND in-flight device
            # buffers at inflight_limit() prepped+transferred chunks
            # (default 3) — the footprint bound of the old depth-2
            # queue, independent of the pool width
            lookahead = min(len(items), worker_count() + 1,
                            min(inflight, inflight_limit())
                            if inflight else inflight_limit())
            futures = deque(_submit(it) for it in items[:lookahead])
            nxt = lookahead
            while futures:
                item, cell, fut = futures.popleft()
                dev = (_guarded_prep_h2d(prep, h2d, item, timers,
                                         first_future=fut,
                                         first_cell=cell)
                       if guard else fut.result())
                if nxt < len(items):
                    futures.append(_submit(items[nxt]))
                    nxt += 1
                # backlog gauges for the health plane (no-op
                # disarmed): prepped+transferred chunks waiting on
                # dispatch, plus the AGE of the oldest one — depth
                # says how much is queued, age says how long the head
                # of the line has already waited (the pipeline-level
                # twin of the per-tenant queue-age gauge)
                metrics.gauge_set("gs_inflight_chunks", len(futures))
                if metrics.enabled():
                    oldest = (futures[0][1].get("submitted")
                              if futures else None)
                    # 0.0 when drained: a scrape after the stream
                    # finishes must not show age for work that no
                    # longer exists
                    metrics.gauge_set(
                        "gs_inflight_oldest_s",
                        time.perf_counter() - oldest
                        if oldest is not None else 0.0)
                _consume(item, dev, cell.get("tctx"))
    except Exception:
        # drain in-flight device work before surfacing the failure:
        # the previous chunk was already dispatched, so its outputs
        # (device buffers + the pending d2h) are materialized
        # best-effort instead of abandoned (a hung drain is bounded by
        # the same finalize deadline when the guard is armed)
        if pending is not None:
            done_chunk, pending = pending, None
            try:
                _finalize(*done_chunk)
            except Exception as drain_err:
                try:
                    telemetry.event(
                        "drain_failed", durable=True,
                        component="ingress_pipeline",
                        error="%s: %s" % (type(drain_err).__name__,
                                          drain_err))
                except Exception:  # gslint: disable=except-hygiene (a failing ledger write must not replace the typed StageError the demotion ladder keys on)
                    pass
        raise
    finally:
        for _it, _cell, f in futures:
            f.cancel()
    if pending is not None:
        _finalize(*pending)


def submit_prep(fn: Callable, item, timers: Optional[StageTimers] = None):
    """Submit ONE prep task to the pool, or None when pipelining is
    disabled (caller then preps inline) — the single-lookahead form
    for consumers whose dispatches carry sequential state the full
    run_pipeline loop doesn't model (the driver's snapshot scan). The
    future's result() raises PrepError with the worker traceback on
    failure, same as run_pipeline."""
    pool = prep_pool()
    if pool is None:
        return None
    return pool.submit(_timed_prep, fn, item, timers)


def map_ordered(fn: Callable, items: Iterable) -> List:
    """Ordered parallel map over the prep pool — the host-tier form of
    the prep stage (per-window numpy/native counting, per-window
    first-occurrence uniques for interning). Results are returned in
    item order regardless of worker scheduling, and the sequential
    form runs when pipelining is disabled, so outputs are identical at
    every pool size (the worker-pool determinism contract).

    Honors the stage guard like every other prep consumer: with
    GS_STAGE_RETRIES/GS_STAGE_TIMEOUT_S armed, a failed or hung pooled
    item is re-run under resilience.call_guarded (fn is pure by the
    prep contract); inert knobs keep the legacy zero-overhead path."""
    items = list(items)
    pool = prep_pool() if len(items) > 1 else None
    guard = resilience.guard_active()

    def _rerun(i, it):
        return resilience.call_guarded(
            "prep", i, lambda: _timed_prep(fn, it, None))

    if pool is None:
        if not guard:
            return [_timed_prep(fn, it, None) for it in items]
        return [_rerun(i, it) for i, it in enumerate(items)]
    futures = [pool.submit(_timed_prep, fn, it, None) for it in items]
    try:
        if not guard:
            return [f.result() for f in futures]
        out = []
        timeout = resilience.stage_timeout_s()
        for i, (it, fut) in enumerate(zip(items, futures)):
            try:
                out.append(fut.result(
                    timeout=2 * timeout if timeout > 0 else None))
            except BaseException as e:
                if not isinstance(e, Exception) or _is_fatal(e):
                    raise  # interrupts / the simulated kill
                # pooled attempt failed (or its 2×deadline wait
                # expired — the worker is abandoned): re-run under the
                # guard's own watchdog/retry budget
                out.append(_rerun(i, it))
        return out
    finally:
        for f in futures:
            f.cancel()
