"""Stage watchdogs, bounded retry, and the tier-demotion registry.

A device stream's worst failure mode is a HANG, not an exception: an
h2d or dispatch that never returns stalls the whole stream forever
unless something in the ingress pipeline owns a deadline. This module
is the shared guard machinery:

- Typed stage errors. `StageTimeout` / `StageFailed` carry which chunk,
  which stage, and the per-attempt timings, so an operator (or
  tools/chaos_run.py) can tell a wedged transfer from a poisoned prep
  without parsing tracebacks.
- `call_guarded` — run one stage under a configurable deadline
  (`GS_STAGE_TIMEOUT_S`) with bounded retry and DETERMINISTIC
  (jitterless) exponential backoff (`GS_STAGE_RETRIES`,
  `GS_STAGE_BACKOFF_S`). With both knobs at their defaults (0) the
  guard is inert and callers run their legacy inline path — zero
  threads, zero overhead, bit-identical behavior.
- The demotion registry — a process-global log of tier demotions
  (device→native→host) the driver records and
  tools/profile_kernels.py commits to PERF.json as a `degradations`
  section, so a degraded run is visibly labeled and can never
  masquerade as a device-tier measurement.

Deadline mechanics: the guarded callable runs on a helper thread and
the caller waits `timeout` seconds. On expiry the helper is ABANDONED
(daemon; Python cannot safely interrupt a thread blocked in a ctypes
or network call — exactly the hung-transfer shape) and the attempt is
retried or surfaced as `StageTimeout`. A guarded stage must therefore
be safe to re-run: prep is pure and h2d is an idempotent transfer;
side-effecting stages (finalize, carry-mutating dispatch) are guarded
with `retries=0` — deadline only — by their callers.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional

from . import faults
from . import knobs
from . import telemetry


class StageError(RuntimeError):
    """Base of the typed stage failures. `stage` is the pipeline stage
    name ('prep' / 'h2d' / 'dispatch' / 'finalize'), `chunk` the chunk
    descriptor the caller passed, `attempts` one dict per attempt:
    {"outcome": "timeout" | exception class name, "elapsed_s": float}.

    Construction stamps a durable flight-recorder event
    (utils/telemetry): a typed stage failure is exactly the
    post-mortem evidence the run ledger exists for, and putting the
    stamp here covers BOTH guard implementations (call_guarded and
    ingress_pipeline._guarded_prep_h2d) by construction."""

    def __init__(self, message: str, stage: str, chunk,
                 attempts: Optional[List[dict]] = None):
        super().__init__(message)
        self.stage = stage
        self.chunk = chunk
        self.attempts = attempts or []
        telemetry.event(
            {"StageTimeout": "stage_timeout",
             "StageFailed": "stage_failed"}.get(type(self).__name__,
                                                "stage_error"),
            durable=True, stage=stage,
            chunk=telemetry.chunk_key(chunk),
            attempts=len(self.attempts))


class StageTimeout(StageError):
    """A stage exceeded its GS_STAGE_TIMEOUT_S deadline on every
    allowed attempt (the hung-transfer shape)."""


class StageFailed(StageError):
    """A stage raised on every allowed attempt; the last exception
    rides as __cause__."""


# ----------------------------------------------------------------------
# env knobs (read per call through the utils/knobs registry: tests and
# tools/chaos_run.py flip them mid-process)
# ----------------------------------------------------------------------
def stage_timeout_s() -> float:
    """Per-stage watchdog deadline in seconds (GS_STAGE_TIMEOUT_S);
    0 (default) disables the watchdog entirely."""
    return knobs.get_float("GS_STAGE_TIMEOUT_S")


def stage_retries() -> int:
    """Extra attempts after the first failure/timeout
    (GS_STAGE_RETRIES, default 0 = fail on first error)."""
    return knobs.get_int("GS_STAGE_RETRIES")


def stage_backoff_s() -> float:
    """Base of the deterministic exponential backoff between retry
    attempts: sleep base·2^attempt, NO jitter (GS_STAGE_BACKOFF_S,
    default 0.05). Jitter exists to de-correlate fleets; a single
    streaming process gains nothing from it and loses reproducibility.
    """
    return knobs.get_float("GS_STAGE_BACKOFF_S")


def backoff_s(attempt: int) -> float:
    """The deterministic (jitterless) backoff ladder: base·2^attempt
    seconds with the GS_STAGE_BACKOFF_S base. The stage guard sleeps
    it between retries, and the serving front-end (core/serve.py)
    returns it as the `retry_after_s` hint on a typed backpressure
    response — one discipline, so a polite client and the in-process
    retry pace identically."""
    return stage_backoff_s() * (2 ** max(0, attempt))


def guard_active() -> bool:
    """True when either knob arms the guard; callers keep their legacy
    inline path (and exact legacy exception types) otherwise."""
    return stage_timeout_s() > 0 or stage_retries() > 0


_TIMEOUT = object()  # sentinel: deadline expired


def _run_with_deadline(fn: Callable, timeout: float):
    """Run fn() on a daemon helper thread, waiting at most `timeout`
    seconds. Returns fn's value, re-raises its exception, or returns
    the _TIMEOUT sentinel (the helper is abandoned — see module
    docstring)."""
    box = {}
    done = threading.Event()

    def runner():
        try:
            box["value"] = fn()
        except BaseException as e:  # gslint: disable=except-hygiene (captured: _run_with_deadline re-raises on the caller)
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=runner, daemon=True,
                         name="gs-stage-watchdog")
    t.start()
    if not done.wait(timeout):
        return _TIMEOUT
    if "error" in box:
        raise box["error"]
    return box["value"]


def call_guarded(stage: str, chunk, fn: Callable, *,
                 retries: Optional[int] = None,
                 timeout: Optional[float] = None):
    """Run `fn()` (one stage of one chunk) under the watchdog/retry
    policy. retries/timeout default to the env knobs; pass retries=0
    for side-effecting stages that must not re-run.

    Raises StageTimeout/StageFailed with per-attempt timings once the
    attempt budget is exhausted. KeyboardInterrupt/SystemExit and
    FATAL injected faults (faults.InjectedFault(fatal=True) — the
    chaos harness's simulated kill) pass through unwrapped and
    unretried."""
    if retries is None:
        retries = stage_retries()
    if timeout is None:
        timeout = stage_timeout_s()
    attempts: List[dict] = []
    for attempt in range(retries + 1):
        t0 = time.perf_counter()
        try:
            if timeout > 0:
                out = _run_with_deadline(fn, timeout)
            else:
                out = fn()
        except faults.InjectedFault as e:
            if e.fatal:
                raise  # the simulated hard kill: never retried
            attempts.append({"outcome": type(e).__name__,
                             "elapsed_s": time.perf_counter() - t0})
            if attempt >= retries:
                raise StageFailed(
                    "%s stage failed for chunk %r after %d attempt(s): %s"
                    % (stage, chunk, len(attempts), e),
                    stage, chunk, attempts) from e
        except Exception as e:
            attempts.append({"outcome": type(e).__name__,
                             "elapsed_s": time.perf_counter() - t0})
            if attempt >= retries:
                raise StageFailed(
                    "%s stage failed for chunk %r after %d attempt(s): %s"
                    % (stage, chunk, len(attempts), e),
                    stage, chunk, attempts) from e
        else:
            if out is not _TIMEOUT:
                return out
            attempts.append({"outcome": "timeout",
                             "elapsed_s": time.perf_counter() - t0})
            if attempt >= retries:
                raise StageTimeout(
                    "%s stage of chunk %r exceeded its %.3gs deadline "
                    "on %d attempt(s) (GS_STAGE_TIMEOUT_S; per-attempt "
                    "timings on .attempts)"
                    % (stage, chunk, timeout, len(attempts)),
                    stage, chunk, attempts)
        telemetry.event("stage_retry", stage=stage,
                        chunk=telemetry.chunk_key(chunk),
                        attempt=attempt + 1,
                        outcome=attempts[-1]["outcome"])
        time.sleep(backoff_s(attempt))


# ----------------------------------------------------------------------
# tier-demotion registry
# ----------------------------------------------------------------------
_DEMOTIONS: List[dict] = []
_DEMOTIONS_LOCK = threading.Lock()


def _clip(reason: str, limit: int = 500) -> str:
    """Bound a reason to `limit` chars keeping its head AND its tail:
    a wrapped error puts the cause's own message (a traceback's last
    line) at the end, and that is the part a reader needs."""
    if len(reason) <= limit:
        return reason
    half = (limit - 5) // 2
    return reason[:half] + " ... " + reason[-half:]


def record_demotion(component: str, from_tier: str, to_tier: str,
                    window: int, reason: str,
                    mesh_shape: Optional[list] = None,
                    shard_id: Optional[int] = None,
                    tenant: Optional[str] = None) -> dict:
    """Log one tier demotion (or a failed re-promotion probe). The
    process-global log is what tools/profile_kernels.py snapshots into
    PERF.json's `degradations` section, so a run that silently fell
    off the device tier is labeled in the committed evidence.

    `mesh_shape` (device counts per mesh axis; None = single-chip) and
    `shard_id` (the implicated shard of a mesh failure, when known —
    e.g. faults.InjectedFault.shard) are ALWAYS present in the event:
    a demoted mesh run must carry its mesh provenance into the
    degradations evidence, so it can never masquerade as a healthy
    sharded-tier row (tools/perf_schema.py enforces the key)."""
    event = {
        "component": component,
        "from": from_tier,
        "to": to_tier,
        "window": int(window),
        "reason": _clip(reason),
        "mesh_shape": (None if mesh_shape is None
                       else [int(x) for x in mesh_shape]),
        "shard_id": None if shard_id is None else int(shard_id),
        # multi-tenant provenance (core/tenancy.py): a demoted tenant's
        # event names WHICH stream fell off the cohort tier, so the
        # degradations evidence (and /healthz's demotion tail) can
        # never blame the whole cohort for one sick stream
        "tenant": None if tenant is None else str(tenant),
    }
    with _DEMOTIONS_LOCK:
        _DEMOTIONS.append(event)
    # durable flight-recorder stamp: a demotion must survive whatever
    # killed the tier (the whole point of the run ledger)
    telemetry.event("tier_demotion", durable=True, **event)
    return event


def demotion_events() -> List[dict]:
    with _DEMOTIONS_LOCK:
        return list(_DEMOTIONS)


def reset_demotions() -> None:
    """Test/tool hook: clear the process-global demotion log."""
    with _DEMOTIONS_LOCK:
        _DEMOTIONS.clear()


def tier_retry_windows() -> int:
    """Probation length for re-promotion after a tier demotion
    (GS_TIER_RETRY_WINDOWS): after this many windows finalized on the
    demoted tier without failure, the driver retries the higher tier
    once; a repeat failure demotes again (and restarts probation).
    0 (default) = a demotion is permanent for the process."""
    return knobs.get_int("GS_TIER_RETRY_WINDOWS")


def tier_demotion_enabled() -> bool:
    """GS_TIER_DEMOTE=0 pins the resolved tier: failures raise instead
    of degrading — what a measurement harness wants (a silently
    demoted bench row is worse than a failed one; the profiler also
    labels any demotion that does happen)."""
    return knobs.get_bool("GS_TIER_DEMOTE")


def mesh_demotion_enabled() -> bool:
    """GS_MESH_DEMOTE=0 pins a sharded session to the mesh: a
    persistent mesh failure raises instead of demoting
    sharded → single-chip scan → native → host (subordinate to
    GS_TIER_DEMOTE, which pins EVERY rung). Default 1: a dead shard
    degrades the stream to one device instead of wedging it — the
    multi-chip leg of the core/driver demotion ladder."""
    return knobs.get_bool("GS_MESH_DEMOTE")


def mesh_wire_check_enabled() -> bool:
    """GS_MESH_WIRE_CHECK=1 arms the sharded h2d wire validation
    (parallel/sharded.guard_wire): every mesh-bound window stack is
    range-checked per shard slice before dispatch, so a corrupt shard
    wire (torn transfer, faults.py's corrupt_shard drill) surfaces as
    a typed stage failure naming the shard instead of scattering
    out-of-range ids into carried state. Default 0: the hot path
    stays byte-identical to the unguarded form."""
    return knobs.get_bool("GS_MESH_WIRE_CHECK")
