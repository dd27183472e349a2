"""Deterministic fault injection.

The resilient runtime (ops/ingress_pipeline stage guards, the driver's
tier demotion, utils/checkpoint rotation) is only trustworthy if its
failure paths are EXERCISED deterministically — the reference leans on
Flink's restart strategies and never tests them in-repo; a common
real failure mode is a hang, which no exception-based mock
reproduces. This module is a
process-global, context-manager-scoped fault plan that the runtime's
hook points consult:

    with faults.inject(
            faults.FaultSpec(site="prep", on_call=3),          # raise
            faults.FaultSpec(site="h2d", on_call=2,
                             action="hang", seconds=5.0),      # stall
            faults.FaultSpec(site="ckpt_save",
                             action="truncate_file")):         # damage
        engine.process(src, dst)

Sites are plain strings fired by the runtime (`fire(site)`); the
active plan counts calls per site and triggers each spec on its
1-based `on_call`-th firing, `times` times. No randomness anywhere —
the same plan against the same stream injects the same faults, which
is what lets tools/chaos_run.py assert fault-run counts equal the
fault-free run bit-for-bit.

Hooked sites (all no-ops when no plan is active — the hooks are one
dict lookup on the hot path):

    prep          ops/ingress_pipeline._timed_prep (worker side)
    h2d           ops/ingress_pipeline._prep_then_h2d (worker side)
    dispatch      ops/ingress_pipeline.run_pipeline + the driver's
                  snapshot-scan dispatch
    finalize      ops/ingress_pipeline.run_pipeline + the driver's
                  snapshot materialize
    ckpt_save     utils/checkpoint.save (fires AFTER the atomic
                  replace, payload=final path — truncate_file here
                  models external damage to a completed checkpoint)
    ckpt_restore  utils/checkpoint.restore (before the load)
    parse         io/sources edge-chunk parse (payload=bytes;
                  corrupt_bytes garbles one line)
    admit         every admission boundary — TenantCohort.feed,
                  SummaryEngineBase.process, driver.run_arrays —
                  BEFORE the sanitizer (utils/sanitize) and the
                  journal see the batch; payload=(tenant, src, dst),
                  so a `call` spec can poison the parsed arrays the
                  way corrupt_bytes tears file bytes (chaos targets
                  the sanitizer through exactly this hook)
    wal_enqueue   between the journal append and the queue/fold (the
                  kill window the WAL contract pins)

Mesh-scoped sites (fired only by the sharded engines and the driver's
mesh path — parallel/sharded.py; a single-chip run never fires them,
which is what lets a demoted stream keep running through a plan that
keeps killing the mesh):

    shard_dispatch  every sharded shard_map dispatch (the SPMD program
                    covering ALL shards — a dead chip fails the whole
                    dispatch, so `raise` here with FaultSpec.shard=k
                    models shard k dying: the InjectedFault carries
                    the shard id for the demotion record; `hang`
                    models an ICI stall the GS_STAGE_TIMEOUT_S
                    watchdog must cut)
    shard_gather    the d2h gather of replicated sharded outputs /
                    engine state slabs
    shard_wire      the mesh h2d wire; payload=(arrays, n_shards).
                    corrupt_shard garbles FaultSpec.shard's slice of
                    each array's edge axis — GS_MESH_WIRE_CHECK=1
                    (utils/resilience.mesh_wire_check_enabled) is the
                    guard that must catch it before dispatch.

Actions:
    raise          raise InjectedFault (or `exc` if given). fatal=True
                   marks the fault non-retryable: the stage guards
                   re-raise it immediately instead of burning retries
                   — the deterministic "kill" for crash/resume drills.
    hang           time.sleep(seconds) inside the stage — the watchdog
                   deadline (GS_STAGE_TIMEOUT_S) is what must cut it.
    truncate_file  payload is a path: cut the file to half its bytes.
    corrupt_bytes  payload is bytes: garble the first line-break-free
                   span (models a torn/overwritten edge line).
    corrupt_shard  payload is (arrays, n_shards): poison shard
                   `spec.shard`'s contiguous slice of each array's
                   trailing (edge) axis with out-of-range vertex ids —
                   a torn/garbled ICI wire that MUST be caught by the
                   wire check, never silently folded.
    call           invoke `fn(payload)` and return its result — the
                   escape hatch for bespoke corruption.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Callable, List, Optional

from . import telemetry


class InjectedFault(RuntimeError):
    """A fault raised by the active plan. `site` names the hook that
    fired; `fatal` marks it exempt from stage-guard retries (the
    simulated hard kill); `shard` (mesh-scoped sites) names the shard
    the fault implicates — the driver's demotion record carries it
    into the `degradations` evidence as `shard_id`."""

    def __init__(self, message: str, site: str, fatal: bool = False,
                 shard: Optional[int] = None):
        super().__init__(message)
        self.site = site
        self.fatal = fatal
        self.shard = shard


@dataclasses.dataclass
class FaultSpec:
    """One planned fault: fire at the `on_call`-th firing of `site`
    (1-based, counted per plan), `times` consecutive firings."""

    site: str
    on_call: int = 1
    times: int = 1
    action: str = "raise"
    seconds: float = 0.0          # hang duration
    exc: Optional[type] = None    # raise: exception class to use
    fatal: bool = False           # raise: exempt from guard retries
    fn: Optional[Callable] = None  # call: bespoke payload transform
    shard: Optional[int] = None   # mesh sites: implicated shard id

    def _matches(self, call_no: int) -> bool:
        return self.on_call <= call_no < self.on_call + self.times


class FaultPlan:
    """An ordered set of FaultSpecs plus per-site call counters.
    Thread-safe: stages fire from pool workers and watchdog threads."""

    def __init__(self, specs):
        self.specs: List[FaultSpec] = list(specs)
        self.calls = {}   # site -> firings so far
        self.fired = []   # (site, call_no, action) log, for assertions
        self._lock = threading.Lock()

    def fire(self, site: str, payload=None):
        with self._lock:
            n = self.calls.get(site, 0) + 1
            self.calls[site] = n
            hits = [s for s in self.specs
                    if s.site == site and s._matches(n)]
            for s in hits:
                self.fired.append((site, n, s.action))
        # injected faults are part of the run's timeline: the flight
        # recorder (utils/telemetry) stamps each firing so a chaos
        # run's ledger interleaves faults with the spans they poisoned
        for s in hits:
            telemetry.event("fault_injected", durable=s.fatal,
                            site=site, call=n, action=s.action,
                            fatal=s.fatal, shard=s.shard)
        # act OUTSIDE the lock: a hang must not serialize other sites
        for s in hits:
            payload = _act(s, site, n, payload)
        return payload


def _act(spec: FaultSpec, site: str, call_no: int, payload):
    if spec.action == "raise":
        if spec.fatal:
            # the simulated hard kill: flush the telemetry ring FIRST,
            # so the post-kill ledger still holds the pre-kill spans —
            # the flight-recorder durability contract
            # tools/chaos_run.py and tests/test_telemetry.py assert
            telemetry.on_fatal(site)
        exc = spec.exc
        where = ("site %r (call %d)" % (site, call_no)
                 if spec.shard is None else
                 "site %r (call %d, shard %d)"
                 % (site, call_no, spec.shard))
        if exc is None:
            raise InjectedFault("injected fault at " + where, site,
                                fatal=spec.fatal, shard=spec.shard)
        raise exc("injected fault at " + where)
    if spec.action == "hang":
        time.sleep(spec.seconds)
        return payload
    if spec.action == "truncate_file":
        path = payload
        with open(path, "r+b") as f:
            f.seek(0, 2)
            f.truncate(f.tell() // 2)
        return payload
    if spec.action == "corrupt_shard":
        import numpy as np

        arrays, n = payload
        k = spec.shard or 0
        poisoned = []
        for a in arrays:
            a = np.array(a)  # fresh copy: never poison caller state
            width = a.shape[-1] // n
            if width and np.issubdtype(a.dtype, np.integer):
                # out-of-range vertex ids (far above any bucket's
                # sentinel): the wire check must trip, the scatter
                # kernels must never silently fold them
                a[..., k * width:(k + 1) * width] = np.iinfo(
                    a.dtype).max
            poisoned.append(a)
        return tuple(poisoned), n
    if spec.action == "corrupt_bytes":
        data = bytearray(payload)
        # garble the first line: digits -> 'x' makes the parser drop
        # it (a torn write), never silently misread it
        end = data.find(b"\n")
        end = len(data) if end < 0 else end
        for i in range(end):
            data[i] = ord("x")
        return bytes(data)
    if spec.action == "call":
        return spec.fn(payload)
    raise ValueError("unknown fault action %r" % spec.action)


_ACTIVE: List[FaultPlan] = []  # stack; innermost plan wins
_ACTIVE_LOCK = threading.Lock()


@contextlib.contextmanager
def inject(*specs):
    """Activate a fault plan for the dynamic extent of the context.
    Nestable (innermost plan fires); process-global, so concurrently
    running measurement harnesses must not overlap an injection."""
    plan = FaultPlan(specs)
    with _ACTIVE_LOCK:
        _ACTIVE.append(plan)
    try:
        yield plan
    finally:
        with _ACTIVE_LOCK:
            _ACTIVE.remove(plan)


def active() -> Optional[FaultPlan]:
    return _ACTIVE[-1] if _ACTIVE else None


def fire(site: str, payload=None):
    """Runtime hook: consult the active plan (no-op without one). May
    raise, sleep, or transform `payload`; returns the (possibly
    transformed) payload."""
    plan = active()
    if plan is None:
        return payload
    return plan.fire(site, payload)
