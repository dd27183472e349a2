"""Multi-tenant cohort scheduler: N independent graph streams, ONE
vmapped dispatch per window cohort.

The observatory's committed verdict (PERF_cpu.json `cost_model`, ISSUE
10) is that every hot program is bytes/launch-bound — the fused scan
runs at 0.096% of roofline and the wall is per-DISPATCH, not
per-stream. The ROADMAP north star ("millions of users") is thousands
of SMALL independent streams, so the biggest available lever is
amortizing each dispatch across many of them: this module admits N
tenants, right-pads each tenant's next window(s) into a cohort slab
`[N, W, eb]`, and issues one leading-axis-vmapped dispatch over the
SAME fused scan body every summary engine runs
(ops/scan_analytics.build_cohort_scan — the trick the sharded path
already plays for panes, applied to tenants). Per-tenant results are
bit-identical to N separate StreamSummaryEngine runs by construction
(padded rows/windows fold as no-ops against the carry), asserted by
tools/tenancy_ab.py and tests/test_tenancy.py.

The serving pieces around the slab:

- **Admission & backpressure.** `admit()` is capped at GS_TENANT_MAX
  (typed `TenantRejected` + durable `tenant_rejected` event past it);
  each tenant owns a bounded ingest queue of GS_TENANT_QUEUE_WINDOWS
  windows, and `feed()` past capacity either raises a typed
  `TenantBackpressure` (policy `reject`, the default — the caller owns
  retry) or sheds the overflow with a durable event + counter (policy
  `drop`). Slab prep for the NEXT dispatch batch rides the resident
  tier's ingest ring (ops/resident_engine.IngestRing over the shared
  ingress worker pool), so its bounded slots are the admission queue
  between the host and the device — while batch k computes, batch
  k+1's slab fills.
- **Per-tenant state.** Each tenant carries its own (degrees, labels,
  cover) slabs in the engine-shared checkpoint layout, so
  `tenant_state_dict()` is interchangeable with
  StreamSummaryEngine.load_state_dict at equal buckets — the
  cohort→single demotion ladder and the per-tenant
  checkpoint/kill→resume drills (tools/chaos_run.py tenant leg) are
  layout conversions, not translations. Tenants may declare their own
  vertex bucket: the cohort groups tenants by bucket signature and
  dispatches one slab per group.
- **Per-tenant demotion.** A tenant whose slab prep fails (poisoned
  input, injected fault) demotes ALONE to its own single-tenant
  StreamSummaryEngine seeded from its live carry
  (utils/resilience.record_demotion stamps the `tenant` label); the
  cohort keeps dispatching the healthy tenants. The sick tenant's
  stream continues on the single tier — same summaries, its own
  dispatches — and its checkpoints stay engine-interchangeable.
- **Resident cohort tier.** With
  ops/resident_engine.resolve_resident_cohort selected
  (GS_COHORT_RESIDENT=on), the per-(vb, kb) group's carries live as ONE
  stacked `[N, ...]` pytree on device between rounds, updated in
  place by a donated super-batch program
  (`jax.jit(..., donate_argnums)` where the backend honors donation)
  that folds up to GS_RESIDENT_SPB windows per tenant per dispatch —
  no per-round restack, no per-tenant carry h2d. Per-tenant
  checkpoints gather their slice at super-batch boundaries
  (`_carry_of`), so the ISSUE-11/12 checkpoint, WAL-replay,
  bulkhead-bisect, and demotion contracts hold per tenant unchanged;
  membership changes (admit/close/quarantine/demote/restore) break
  residency and restack. The window body itself may additionally be
  the tenant-axis Pallas megakernel
  (ops/pallas_window.maybe_cohort_body, its own GS_COHORT_PALLAS
  gate).
- **Autotuning.** The dispatch autotuner (ops/autotune.DispatchTuner,
  family `tenant_cohort`, keyed by eb/vb AND the live cohort bucket
  Nb — a grown cohort re-keys instead of inheriting stale optima)
  gains a tenants-per-dispatch arm (× windows-per-superbatch on the
  resident tier): pump rounds chunk the ready tenants into
  `tpd`-sized vmapped dispatches and feed the measured edges/s back.
  GS_TENANT_TPD pins the arm; GS_AUTOTUNE=0 dispatches all ready
  tenants in one slab.
- **Observability.** Every finalized tenant window marks
  metrics.mark_window(tenant=...) — per-tenant window/edge counters
  and staleness rows on /healthz + /metrics under the registry's
  cardinality bound (past GS_METRICS_SERIES, new tenants collapse
  into one `overflow` row instead of growing the registry) — and
  cohort dispatches run under `cohort.dispatch` spans whose
  tenant/window attrs tools/explain_perf.py aggregates.

Windowed reduce rides the same cohort shape via
ops/windowed_reduce.WindowedEdgeReduce.cohort_step (N tenants' windows
as one [N, eb] segment-kernel stack); triangle counts (and the exact
K-overflow recount) are inside the fused scan body itself.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from ..ops import ingress_pipeline
from ..ops import resident_engine
from ..ops import scan_analytics
from ..ops import segment as seg_ops
from ..ops import triangles as tri_ops
from ..utils import checkpoint
from ..utils import faults
from ..utils import knobs
from ..utils import latency
from ..utils import metrics
from ..utils import provenance
from ..utils import resilience
from ..utils import sanitize as sanitize_mod
from ..utils import telemetry
from ..utils import wal as wal_mod


# ----------------------------------------------------------------------
# knobs (utils/knobs.py registry; live per-call reads)
# ----------------------------------------------------------------------
def max_tenants() -> int:
    """Admission cap of the cohort (GS_TENANT_MAX, default 64)."""
    return knobs.get_int("GS_TENANT_MAX")


def queue_windows() -> int:
    """Per-tenant ingest-queue depth in windows
    (GS_TENANT_QUEUE_WINDOWS, default 8): queue capacity in edges is
    depth x edge_bucket."""
    return knobs.get_int("GS_TENANT_QUEUE_WINDOWS")


def admission_policy() -> str:
    """Queue-overflow policy (GS_TENANT_ADMISSION): `reject` (default)
    raises typed TenantBackpressure accepting nothing; `drop` accepts
    what fits and sheds the rest with a durable event."""
    return knobs.get_str("GS_TENANT_ADMISSION")


def pinned_tpd() -> int:
    """GS_TENANT_TPD: tenants per vmapped dispatch; 0 = auto (the
    tuner's arm, or all ready tenants with GS_AUTOTUNE=0)."""
    return knobs.get_int("GS_TENANT_TPD")


def quarantine_windows() -> int:
    """GS_QUARANTINE_WINDOWS: clean solo probation windows before a
    quarantined tenant re-enters the cohort (0 = permanent)."""
    return knobs.get_int("GS_QUARANTINE_WINDOWS")


def ooo_bound() -> int:
    """GS_OOO_BOUND: bounded out-of-orderness (event-time ns) of the
    per-tenant reorder buffer ahead of the monotonic guard; 0 = off
    (ts must arrive non-decreasing exactly as before)."""
    return knobs.get_int("GS_OOO_BOUND")


# ----------------------------------------------------------------------
# typed admission errors (durable-stamped like StageError)
# ----------------------------------------------------------------------
class TenantError(RuntimeError):
    """Base of the typed tenancy failures; `tenant` names the stream.
    Construction stamps a durable `tenant_rejected` flight-recorder
    event — an admission refusal is exactly the serving evidence the
    run ledger exists for, and stamping here covers every raise
    site by construction."""

    EVENT = "tenant_rejected"

    def __init__(self, message: str, tenant, _record: bool = True,
                 _durable: bool = True, **attrs):
        super().__init__(message)
        self.tenant = tenant
        if _record:
            telemetry.event(self.EVENT, durable=_durable,
                            tenant=str(tenant),
                            kind=type(self).__name__, **attrs)
            metrics.counter_inc("gs_tenant_rejections_total",
                                kind=type(self).__name__)


class TenantRejected(TenantError):
    """Admission refused: the cohort is at GS_TENANT_MAX, the id is
    unknown/closed, or a duplicate admit."""


class TenantBackpressure(TenantError):
    """A feed() overflowed the tenant's bounded queue under the
    `reject` policy. Carries `queued` and `capacity` (edges) so the
    caller can size its retry. The durable (fsync'd) ledger stamp
    fires once per overflow EPISODE (reset when the queue drains) —
    a producer retry loop against a full queue must not become
    fsync-bound or flood the post-mortem ledger with identical
    records; subsequent rejections in the episode stamp buffered
    (non-durable) events."""

    def __init__(self, message: str, tenant, queued: int,
                 capacity: int, _durable: bool = True):
        super().__init__(message, tenant, _durable=_durable,
                         queued=queued, capacity=capacity)
        self.queued = queued
        self.capacity = capacity


class TenantQuarantined(TenantRejected):
    """A feed() reached a quarantined tenant — the cohort bulkhead
    suspended the stream after a poisoned dispatch, and admission
    stays refused until the probation ladder re-admits it (or forever
    with GS_QUARANTINE_WINDOWS=0). Carries `probation_left` so a
    client can tell a permanent quarantine from a recovering one.
    Events stamp buffered (non-durable): the quarantine itself already
    wrote the durable record, and a hostile client retry-flooding a
    quarantined stream must not become fsync-bound."""

    def __init__(self, message: str, tenant, probation_left: int):
        super().__init__(message, tenant, _durable=False,
                         reason="quarantined",
                         probation_left=probation_left)
        self.probation_left = probation_left


class PoisonOutput(RuntimeError):
    """A cohort dispatch finalized implausible output (negative or
    out-of-domain analytics) for specific slab rows. Internal signal
    of the bulkhead: `tenants` names the poisoned stream(s), and the
    dispatch loop quarantines exactly those and re-runs the rest —
    never raised to callers."""

    def __init__(self, message: str, tenants):
        super().__init__(message)
        self.tenants = list(tenants)


class _Tenant:
    """One admitted stream: its bounded ingest queue, carried state in
    the engine-shared layout, cursors, and (after demotion) its own
    single-tenant engine."""

    __slots__ = ("tid", "vb", "kb", "src", "dst", "carry",
                 "windows_done", "closed_partial", "closing", "closed",
                 "tier", "engine", "ckpt_policy", "dropped_edges",
                 "bp_stamped", "fed_offset", "probation",
                 "quarantine_reason", "last_report", "res_row",
                 "last_ts", "ooo_src", "ooo_dst", "ooo_ts")

    def __init__(self, tid: str, vb: int, kb: int):
        self.tid = tid
        self.vb = vb
        self.kb = kb
        self.src = np.zeros(0, np.int32)
        self.dst = np.zeros(0, np.int32)
        self.bp_stamped = False    # durable-once-per-overflow-episode
        self.carry = None          # lazy: built at first dispatch
        self.res_row = None        # row in the resident cohort stack
                                   # (carry lives THERE, not here)
        self.last_ts = None        # newest accepted event-time stamp
        # GS_OOO_BOUND reorder buffer: edges held (sorted by ts)
        # until the tenant's watermark passes them — host-side, ahead
        # of the monotonic guard, never journaled until released
        self.ooo_src = np.zeros(0, np.int64)
        self.ooo_dst = np.zeros(0, np.int64)
        self.ooo_ts = np.zeros(0, np.int64)
        self.windows_done = 0
        self.closed_partial = False
        self.closing = False
        self.closed = False
        self.tier = "cohort"       # "cohort" | "single" | "quarantined"
        self.engine = None         # demoted/probation engine
        self.ckpt_policy = None    # per-tenant CheckpointPolicy
        self.dropped_edges = 0
        self.fed_offset = 0        # cumulative fed edges incl. rejects
                                   # (the DLQ's source-offset domain)
        self.probation = 0         # clean solo windows since quarantine
        self.quarantine_reason = None
        self.last_report = None    # last feed()'s SanitizeReport

    @property
    def queued(self) -> int:
        return len(self.src)


class TenantCohort:
    """N independent graph streams through one vmapped fused-scan
    dispatch per window cohort. See the module docstring for the
    serving model; the API in driver order:

        cohort = TenantCohort(edge_bucket=4096, vertex_bucket=8192)
        cohort.admit("user-1"); cohort.admit("user-2", vertex_bucket=2048)
        cohort.feed("user-1", src, dst)     # bounded; may reject
        results = cohort.pump()             # {tenant: [summary, ...]}
        results = cohort.close("user-1")    # flush the partial window

    Summaries are the fused summary engines' dicts (max_degree /
    num_components / odd_cycle / triangles), bit-identical per tenant
    to a single StreamSummaryEngine fed the same stream."""

    # windows of ONE tenant folded per dispatch ceiling: deep queues
    # catch up wc windows per slab row instead of one round per window
    MAX_WINDOWS_PER_DISPATCH = 8

    def __init__(self, edge_bucket: int, vertex_bucket: int,
                 k_bucket: int = 0,
                 windows_per_dispatch: Optional[int] = None):
        self.eb = seg_ops.bucket_size(edge_bucket)
        self.default_vb = seg_ops.bucket_size(vertex_bucket)
        self._kb_arg = k_bucket
        self.wc = seg_ops.bucket_size(
            windows_per_dispatch if windows_per_dispatch
            else self.MAX_WINDOWS_PER_DISPATCH)
        self.tenants: Dict[str, _Tenant] = {}
        self._programs = {}        # (vb, kb, nb, wb) -> jitted cohort scan
        self._pad_carries = {}     # (vb,) -> fresh host carry template
        self._tri_redo = {}        # (vb, kb) -> escalated exact kernel
        self._tuners = {}          # (vb,) -> DispatchTuner (tpd arm)
        self._tuner_nb = {}        # (vb,) -> Nb the tuner was keyed at
        # resident cohort tier (ops/resident_engine
        # .resolve_resident_cohort): per (vb, kb) group, the stacked
        # [N, ...] carry pytree kept ON DEVICE between rounds —
        # {"nb": rows, "rows": (tid|None, ...), "carry": 3-tuple} —
        # updated in place by the donated super-batch program and
        # restacked only when membership changes; per-tenant
        # checkpoint gathers slice it at super-batch boundaries
        self._res = {}
        self._res_programs = {}    # (vb, kb, nb, wb) -> donated program
        self.resident_dispatches = 0  # dispatches through the tier
        self._round_spb = 0        # this round's windows-per-superbatch arm
        self._ring = resident_engine.IngestRing()
        self._ckpt_dir = None
        self._ckpt_every_n = 0
        self._ckpt_every_s = 0.0
        self._round_no = 0
        self._wal = None           # utils/wal.WriteAheadLog when armed
        self._wal_dir = None
        # latency plane (utils/latency.py): the serving front-end
        # flips this so finalized windows defer their latency record
        # to the results-sink write (serve._emit stamps `deliver`);
        # direct pump() callers emit at finalize (deliver = 0)
        self.defer_delivery = False
        # GS_WAL_RETAIN bookkeeping: journal truncation at the
        # checkpoint_all() flush boundary, floored per tenant at the
        # older kept generation (utils/wal.RetentionCursor)
        self._wal_retention = wal_mod.RetentionCursor()
        # the async serving pump's queue lock (GS_PUMP=async,
        # core/serve.py): feed() appends and the pump's finalize
        # prefix-drops under it, so ingest threads and ONE pump
        # thread can run concurrently — the queues are append-only
        # from feed and consume-only from pump, and every
        # read-modify-write of (src, dst) is atomic under this lock.
        # Under GS_PUMP=sync (the default) the lock is uncontended
        # and the path is bit-identical to the pre-pump build.
        self._qlock = threading.RLock()

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def admit(self, tenant_id, vertex_bucket: Optional[int] = None,
              k_bucket: Optional[int] = None) -> None:
        """Admit one stream under the GS_TENANT_MAX cap. Tenants may
        declare their own vertex bucket (the cohort groups slabs by
        bucket signature); the k bucket follows the engines' tuned
        default for the cohort's edge bucket."""
        tid = str(tenant_id)
        if tid in self.tenants:
            raise TenantRejected(
                "tenant %r is already admitted" % tid, tid,
                reason="duplicate")
        cap = max_tenants()
        live = sum(1 for t in self.tenants.values() if not t.closed)
        if live >= cap:
            raise TenantRejected(
                "cohort is at its GS_TENANT_MAX=%d admission cap; "
                "tenant %r refused" % (cap, tid), tid,
                reason="max_tenants", cap=cap)
        vb = seg_ops.bucket_size(vertex_bucket if vertex_bucket
                                 else self.default_vb)
        kb = seg_ops.bucket_size(
            k_bucket if k_bucket else
            (self._kb_arg if self._kb_arg else tri_ops._tuned_kb(self.eb)))
        t = _Tenant(tid, vb, kb)
        if self._ckpt_every_n or self._ckpt_every_s:
            t.ckpt_policy = checkpoint.CheckpointPolicy(
                every_n_windows=self._ckpt_every_n,
                every_seconds=self._ckpt_every_s)
        with self._qlock:
            # async pump: admissions land from ingest threads while
            # the pump thread iterates its _tids() snapshot — the
            # insert and every snapshot share the queue lock
            self.tenants[tid] = t
        telemetry.event("tenant_admitted", tenant=tid, vb=vb)
        metrics.on_stream_start("cohort", tenant=tid)

    def _tids(self) -> list:
        """Sorted snapshot of the tenant ids — the only safe way to
        iterate the roster once the async serving pump runs dispatch
        on its own thread while admissions keep landing (a bare
        sorted(self.tenants) can see the dict resize mid-iteration)."""
        with self._qlock:
            return sorted(self.tenants)

    def _tenant(self, tenant_id, for_feed: bool = False) -> _Tenant:
        tid = str(tenant_id)
        t = self.tenants.get(tid)
        if t is None:
            # record only on the serving surface (the feed path): a
            # typo'd id in read-only introspection must not stamp
            # ledger events or inflate the rejection counter
            raise TenantRejected("unknown tenant %r (admit() first)"
                                 % tid, tid, _record=for_feed,
                                 reason="unknown")
        if for_feed and (t.closed or t.closing):
            raise TenantRejected(
                "tenant %r is closed — its final (partial) window was "
                "already cut" % tid, tid, reason="closed")
        if for_feed and t.tier == "quarantined" \
                and quarantine_windows() <= 0:
            # permanent quarantine (GS_QUARANTINE_WINDOWS=0): refuse
            # typed — nothing would ever drain the queue. With
            # probation enabled, feeds stay ACCEPTED into the bounded
            # queue (it is what the solo probation windows consume to
            # earn re-admission); the serving front-end surfaces the
            # quarantined flag on the feed reply instead.
            raise TenantQuarantined(
                "tenant %r is quarantined (%s); "
                "GS_QUARANTINE_WINDOWS=0 — permanent for this process"
                % (tid, t.quarantine_reason), tid, probation_left=-1)
        return t

    # ------------------------------------------------------------------
    # feed / backpressure
    # ------------------------------------------------------------------
    def _check_event_time(self, t: _Tenant, src, ts):
        """Per-tenant event-time monotonicity (the cohort-aware
        guard): the optional ts column must align with the batch, be
        non-decreasing WITHIN it, and start at or after the tenant's
        newest accepted stamp. Checked independently per tenant — a
        slab interleaving tenants with disjoint time ranges is the
        normal serving shape, so nothing here ever compares clocks
        ACROSS tenants. Returns the validated int64 column (None when
        no column was given); raises ValueError naming the tenant on
        a regression, consuming nothing."""
        if ts is None:
            return None
        col = np.asarray(ts, np.int64)  # gslint: disable=host-sync (host-input normalization: feed() takes numpy/lists, never device values)
        if col.shape != (len(src),):
            raise ValueError(
                "tenant %r ts column length %d != batch length %d"
                % (t.tid, col.size, len(src)))
        if col.size == 0:
            return col
        if col.size > 1 and bool(np.any(np.diff(col) < 0)):  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary event-time check)
            raise ValueError(
                "tenant %r event-time regression WITHIN the batch: "
                "ts must be non-decreasing per tenant" % t.tid)
        if t.last_ts is not None and int(col[0]) < t.last_ts:  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary event-time check)
            raise ValueError(
                "tenant %r event-time regression: batch starts at "
                "%d but the tenant's stream already reached %d"
                % (t.tid, int(col[0]), t.last_ts))  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary event-time check)
        return col

    def feed(self, tenant_id, src, dst, ts=None) -> int:
        """Append edges to one tenant's bounded queue. Returns the
        number of edges accepted. Past capacity
        (GS_TENANT_QUEUE_WINDOWS x edge_bucket edges), the
        GS_TENANT_ADMISSION policy decides: `reject` raises typed
        TenantBackpressure accepting NOTHING (the caller owns retry —
        an atomic refusal can't split a window across a retry
        boundary), `drop` accepts what fits and sheds the rest with a
        durable event + counter.

        `ts` is an optional per-edge EVENT-TIME column (int64,
        non-decreasing). Tenants sharing a slab legitimately carry
        disjoint, interleaved time ranges, so the monotonicity guard
        keys on THE TENANT, never the batch or the slab: the column
        must be non-decreasing within the batch AND start at or after
        this tenant's newest accepted stamp. A regression refuses the
        whole batch (ValueError, nothing consumed) for that tenant
        only — other tenants' clocks are untouched.

        With the latency plane armed (GS_LATENCY=1), the accepted
        batch is stamped with a monotonic admission timestamp at THIS
        boundary — carried through the journal's ts column so
        replayed edges keep their original admission time — and the
        per-tenant queue-age gauge updates."""
        lat = latency.enabled()
        t_admit = latency.clock() if lat else 0.0
        t = self._tenant(tenant_id, for_feed=True)
        if t.closed_partial:
            # the engines' partial-window-must-be-final guard: a
            # tenant restored from a checkpoint taken after its short
            # final window was cut must not fold more windows on a
            # carry whose boundaries are already misaligned
            raise ValueError(
                "tenant %r already closed a partial window (length "
                "not a multiple of edge_bucket); it cannot accept "
                "more of the stream" % t.tid)
        # "admit" fault site: chaos/tests poison the raw parsed arrays
        # at the admission boundary — UPSTREAM of the sanitizer — the
        # way the "parse" site tears file bytes (utils/faults)
        got = faults.fire("admit", (t.tid, src, dst))
        if got is not None:
            _tid, src, dst = got
        bound = ooo_bound()
        if ts is not None and bound > 0:
            # GS_OOO_BOUND reorder buffer, AHEAD of the monotonic
            # guard: the batch merges (ts-sorted) into the tenant's
            # host-side hold, and only the prefix the watermark
            # (newest stamp − bound) has passed releases into the
            # normal admission path below — the released stream is
            # non-decreasing by construction, so the per-tenant
            # guard keeps holding. Held edges are NOT yet accepted:
            # not journaled, not queued, not in the return value.
            with self._qlock:
                src, dst, ts = self._ooo_insert(t, src, dst, ts,
                                                bound)
            if len(src) == 0:
                return 0
            try:
                return self._feed_accepted(t, src, dst, ts, lat,
                                           t_admit)
            except TenantBackpressure:
                # atomic refusal: the released prefix returns to the
                # buffer FRONT (its stamps precede every held one),
                # so the caller's retry re-releases it exactly
                with self._qlock:
                    self._ooo_unrelease(t, src, dst, ts)
                raise
        return self._feed_accepted(t, src, dst, ts, lat, t_admit)

    def _feed_accepted(self, t: _Tenant, src, dst, ts, lat,
                       t_admit) -> int:
        """The admission path past the reorder buffer: monotonic
        guard → sanitize → capacity gate → journal → enqueue. Queue
        mutations run under _qlock so the async pump's finalize can
        prefix-drop concurrently (GS_PUMP=async)."""
        # cohort-aware event-time guard: validated against THIS
        # tenant's clock before anything is consumed — a regression
        # refuses the batch atomically (last_ts advances only below,
        # once the batch clears the capacity gate)
        ts_col = self._check_event_time(t, src, ts)
        t.last_report = None
        report = None
        if sanitize_mod.enabled():
            # armed admission: structurally invalid records peel off
            # to the dead-letter journal (typed reason codes, absolute
            # source offsets) and the accepted remainder — every id
            # proven in [0, vb) — continues; the legacy hard-refusal
            # path below stays bit-identical with GS_SANITIZE=off.
            # commit=False: journaling + the offset advance happen
            # only once the batch clears the capacity gate below — a
            # backpressure-refused feed accepts NOTHING, so its
            # retry must not double-journal the rejects.
            try:
                report = sanitize_mod.sanitize(
                    src, dst, t.vb, tenant=t.tid, origin="feed",
                    offset=t.fed_offset,
                    dlq=sanitize_mod.resolve_dlq(), commit=False)
            except sanitize_mod.BatchRejected as e:
                # whole-batch refusals are terminal (never retried
                # as-is): already journaled, the offset domain moves
                t.fed_offset += e.size
                raise
            src = report.src.astype(np.int32)
            dst = report.dst.astype(np.int32)
        else:
            src = np.asarray(src, np.int32)  # gslint: disable=host-sync (host-input normalization: feed() takes numpy/lists, never device values)
            dst = np.asarray(dst, np.int32)  # gslint: disable=host-sync (host-input normalization: feed() takes numpy/lists, never device values)
            if len(src) != len(dst):
                raise ValueError("src/dst length mismatch")
            if len(src) and (int(src.max()) >= t.vb  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary id check)
                             or int(dst.max()) >= t.vb  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary id check)
                             or int(src.min()) < 0 or int(dst.min()) < 0):  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary id check)
                raise ValueError(
                    "tenant %r ids must be dense in [0, %d) — "
                    "out-of-range ids would scatter into another "
                    "slot's carried state" % (t.tid, t.vb))
        # the capacity gate and the enqueue are ONE atomic section
        # under the queue lock: a concurrent pump finalize may shrink
        # the queue (more room, never less), and feed's append of
        # (src, dst) must be indivisible against its prefix-drop
        with self._qlock:
            capacity = queue_windows() * self.eb
            room = capacity - t.queued
            take = len(src)
            if take > room:
                durable = not t.bp_stamped  # once per overflow episode
                t.bp_stamped = True
                if admission_policy() == "reject":
                    raise TenantBackpressure(
                        "tenant %r queue is full (%d queued of %d edge "
                        "capacity; GS_TENANT_QUEUE_WINDOWS); pump() the "
                        "cohort or retry later" % (t.tid, t.queued,
                                                   capacity),
                        t.tid, queued=t.queued, capacity=capacity,
                        _durable=durable)
                take = max(0, room)
                shed = len(src) - take
                t.dropped_edges += shed
                telemetry.event("tenant_rejected", durable=durable,
                                tenant=t.tid, kind="drop", shed=shed)
                metrics.counter_inc("gs_tenant_dropped_edges_total",
                                    shed, tenant=t.tid)
            # the batch is now CONSUMED (fully, or drop-policy
            # partially — either way the caller will not retry it
            # as-is): journal the sanitizer's rejects and advance the
            # source-offset domain. A backpressure-reject raised above
            # commits nothing, so the retried batch journals its
            # rejects exactly once.
            if report is not None:
                sanitize_mod.commit_report(
                    report, tenant=t.tid, origin="feed",
                    dlq=sanitize_mod.resolve_dlq())
                t.fed_offset += report.accepted + report.rejected
                t.last_report = report
            else:
                t.fed_offset += len(src)
            if ts_col is not None and len(ts_col):
                # the batch is consumed (fully, or drop-policy
                # partially — shed edges are gone either way): this
                # tenant's event clock advances to the batch's newest
                # validated stamp
                t.last_ts = int(ts_col[-1])  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary event-time check)
            if take:
                if self._wal is not None:
                    # durability boundary: the accepted edges hit the
                    # journal BEFORE the queue, so a kill anywhere past
                    # this point (including between journal append and
                    # enqueue — the wal_enqueue fault site below) is
                    # recoverable by replay; a rejected feed() journals
                    # nothing, keeping replay and the caller's view of
                    # what was accepted identical
                    self._wal.append(
                        t.tid, src[:take], dst[:take],
                        # admission stamp riding the ts column (int64
                        # ns, monotonic domain): recovery re-seeds the
                        # latency marks with the ORIGINAL admission
                        # time
                        np.full(take, latency.admit_ns(t_admit),
                                np.int64)
                        if lat else None)
                    faults.fire("wal_enqueue", t.tid)
                t.src = np.concatenate([t.src, src[:take]])
                t.dst = np.concatenate([t.dst, dst[:take]])
                if lat:
                    latency.on_admit(t.tid, take, t0=t_admit)
        metrics.gauge_set("gs_tenant_queue_edges", t.queued,
                          tenant=t.tid)
        return take

    # ------------------------------------------------------------------
    # GS_OOO_BOUND reorder buffer (event-time groundwork)
    # ------------------------------------------------------------------
    def _ooo_insert(self, t: _Tenant, src, dst, ts, bound: int):
        """Merge one batch into the tenant's ts-sorted hold and peel
        off the releasable prefix: everything at or before the
        watermark (newest stamp seen − bound). Caller holds _qlock.
        Raises ValueError (buffer untouched, nothing consumed) on a
        misaligned column or an edge older than the already-released
        frontier — with the buffer armed, "too late" means BEYOND the
        bound, not merely out of order."""
        src = np.asarray(src)  # gslint: disable=host-sync (host-input normalization: feed() takes numpy/lists, never device values)
        dst = np.asarray(dst)  # gslint: disable=host-sync (host-input normalization: feed() takes numpy/lists, never device values)
        col = np.asarray(ts, np.int64)  # gslint: disable=host-sync (host-input normalization: feed() takes numpy/lists, never device values)
        if len(src) != len(dst) or col.shape != (len(src),):
            raise ValueError(
                "tenant %r src/dst/ts length mismatch (%d/%d/%d)"
                % (t.tid, len(src), len(dst), col.size))
        if col.size and t.last_ts is not None \
                and int(col.min()) < t.last_ts:  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary event-time check)
            raise ValueError(
                "tenant %r event-time regression past the "
                "GS_OOO_BOUND=%d horizon: batch reaches back to %d "
                "but the watermark already released through %d"
                % (t.tid, bound, int(col.min()), t.last_ts))  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary event-time check)
        m_src = np.concatenate([t.ooo_src, src.astype(np.int64)])
        m_dst = np.concatenate([t.ooo_dst, dst.astype(np.int64)])
        m_ts = np.concatenate([t.ooo_ts, col])
        order = np.argsort(m_ts, kind="stable")
        m_src, m_dst, m_ts = m_src[order], m_dst[order], m_ts[order]
        if m_ts.size:
            wm = int(m_ts[-1]) - bound  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary event-time check)
            k = int(np.searchsorted(m_ts, wm, side="right"))  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary event-time check)
        else:
            k = 0
        t.ooo_src, t.ooo_dst, t.ooo_ts = (m_src[k:], m_dst[k:],
                                          m_ts[k:])
        self._note_watermark(t)
        return m_src[:k], m_dst[:k], m_ts[:k]

    def _ooo_unrelease(self, t: _Tenant, src, dst, ts) -> None:
        """Return a refused released prefix to the buffer front (its
        stamps precede every held one, so sort order is preserved).
        Caller holds _qlock."""
        t.ooo_src = np.concatenate([np.asarray(src, np.int64),  # gslint: disable=host-sync (the reorder hold is host numpy, never device values)
                                    t.ooo_src])
        t.ooo_dst = np.concatenate([np.asarray(dst, np.int64),  # gslint: disable=host-sync (the reorder hold is host numpy, never device values)
                                    t.ooo_dst])
        t.ooo_ts = np.concatenate([np.asarray(ts, np.int64),  # gslint: disable=host-sync (the reorder hold is host numpy, never device values)
                                   t.ooo_ts])
        self._note_watermark(t)

    def _note_watermark(self, t: _Tenant) -> None:
        """Report the tenant's TRUE event-time watermark lag to the
        latency plane (seconds between the newest stamp seen and the
        oldest edge still held), repointing the per-tenant age gauge
        while the reorder buffer is armed. Caller holds _qlock."""
        if t.ooo_ts.size:
            high = max(int(t.ooo_ts[-1]),  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary event-time check)
                       t.last_ts if t.last_ts is not None else 0)
            lag = max(0.0, (high - int(t.ooo_ts[0])) / 1e9)  # gslint: disable=host-sync (numpy-on-numpy: the admission-boundary event-time check)
        else:
            lag = 0.0
        latency.note_watermark(t.tid, lag, held=int(t.ooo_ts.size))

    def _ooo_flush(self, t: _Tenant) -> List[dict]:
        """Release the tenant's ENTIRE hold regardless of watermark —
        the close() boundary: a final window must not strand edges
        the bound never passed. Feeds in capacity-sized slices,
        pumping this tenant between slices when the queue is full;
        returns any summaries those interleaved pumps finalized."""
        out: List[dict] = []
        lat = latency.enabled()
        while t.ooo_ts.size:
            with self._qlock:
                room = max(0, queue_windows() * self.eb - t.queued)
                k = min(room, int(t.ooo_ts.size))
                src, dst, col = (t.ooo_src[:k], t.ooo_dst[:k],
                                 t.ooo_ts[:k])
                t.ooo_src = t.ooo_src[k:]
                t.ooo_dst = t.ooo_dst[k:]
                t.ooo_ts = t.ooo_ts[k:]
                self._note_watermark(t)
            if k:
                try:
                    self._feed_accepted(
                        t, src, dst, col, lat,
                        latency.clock() if lat else 0.0)
                except TenantBackpressure:
                    # a concurrent feeder filled the queue between the
                    # room check and the enqueue: put the slice back
                    # and drain below
                    with self._qlock:
                        self._ooo_unrelease(t, src, dst, col)
            if t.ooo_ts.size:
                # queue full: drain this tenant's full windows (the
                # queue capacity is ≥ one window, so progress is
                # guaranteed for a pumpable tenant) and keep flushing
                before = t.queued
                out.extend(self.pump(only=t.tid).get(t.tid, []))
                if k == 0 and t.queued >= before:
                    # nothing drains (a permanently quarantined
                    # tenant): stop — the hold stays buffered rather
                    # than spinning; close() still cuts the queue
                    break
        return out

    # ------------------------------------------------------------------
    # cohort programs / carries
    # ------------------------------------------------------------------
    def _fresh_carry(self, vb: int):
        """One tenant's zero-stream carry in the engine-shared layout
        (SummaryEngineBase._init_carry)."""
        key = (vb,)
        tpl = self._pad_carries.get(key)
        if tpl is None:
            tpl = self._pad_carries[key] = (
                np.zeros(vb + 1, np.int32),
                np.arange(vb + 1, dtype=np.int32),
                np.arange(2 * (vb + 1), dtype=np.int32))
        return tuple(jnp.asarray(a) for a in tpl)

    def _program(self, vb: int, kb: int, nb: int, wb: int):
        """The jitted cohort program at this slab shape (one per
        power-of-two (tenants, windows) bucket — ragged cohorts reuse
        O(log N x log W) programs, never one per population). Wrapped
        by the compile watch / cost observatory as `cohort_scan`. The
        window body is the vmapped XLA scan, or the tenant-axis
        Pallas megakernel when its own gate clears
        (scan_analytics.build_cohort_scan's nb path)."""
        key = (vb, kb, nb, wb)
        fn = self._programs.get(key)
        if fn is None:
            import jax

            run = scan_analytics.build_cohort_scan(self.eb, vb, kb,
                                                   nb=nb)
            fn = self._programs[key] = metrics.wrap_jit(
                "cohort_scan", jax.jit(run))
        return fn

    def _res_program(self, vb: int, kb: int, nb: int, wb: int):
        """The RESIDENT cohort program: the same cohort scan jitted
        with explicit donation of the stacked carry argument
        (resident_engine.donate_kw — in-place slab updates where the
        backend honors donation, bit-identical undonated elsewhere).
        Kept a separate program cache from _program: donation changes
        the jit signature, never the math."""
        key = (vb, kb, nb, wb)
        fn = self._res_programs.get(key)
        if fn is None:
            import jax

            run = scan_analytics.build_cohort_scan(self.eb, vb, kb,
                                                   nb=nb)
            fn = self._res_programs[key] = metrics.wrap_jit(
                "cohort_resident",
                jax.jit(run, **resident_engine.donate_kw()))
        return fn

    def _carry_of(self, t: _Tenant):
        """One tenant's live carry wherever it resides: its row of
        the resident cohort stack when the resident tier holds it,
        else its per-tenant carry, else the fresh zero-stream state.
        Pure read — never mutates tenant or stack state."""
        if t.res_row is not None:
            entry = self._res[(t.vb, t.kb)]
            return tuple(a[t.res_row] for a in entry["carry"])
        return (t.carry if t.carry is not None
                else self._fresh_carry(t.vb))

    def _evict_resident(self, key) -> None:
        """Materialize EVERY tenant out of one (vb, kb) resident stack
        and drop it. MUST run before anything replaces the stack at
        this key: a tenant whose res_row still points into a replaced
        stack would materialize a stranger's (or a pad row's fresh)
        carry and silently lose its own. Slicing the stacked leaves
        is the per-tenant gather the super-batch boundary contract
        names."""
        entry = self._res.pop(key, None)
        if entry is None:
            return
        for tid in entry["rows"]:
            other = self.tenants.get(tid) if tid else None
            if other is not None and other.res_row is not None:
                other.carry = tuple(a[other.res_row]
                                    for a in entry["carry"])
                other.res_row = None

    def _break_residency(self, t: _Tenant) -> None:
        """Materialize every tenant out of the resident stack this
        tenant shares, then drop the stack: membership is about to
        change (checkpoint restore, quarantine, demotion, probation
        absorb), so the next resident dispatch must restack from
        per-tenant carries."""
        if t.res_row is None:
            return
        self._evict_resident((t.vb, t.kb))
        t.res_row = None

    def _redo_kernel(self, vb: int, kb: int):
        """The escalated exact triangle recount of one K-overflowing
        window — the same 4x-K fallback every summary engine keeps."""
        key = (vb, kb)
        k = self._tri_redo.get(key)
        if k is None:
            k = self._tri_redo[key] = tri_ops.TriangleWindowKernel(
                edge_bucket=self.eb, vertex_bucket=vb,
                k_bucket=4 * kb)
        return k

    def _cohort_nb(self, vb: int) -> int:
        """Power-of-two bucket of the live cohort-tier population at
        this vertex bucket, capped at the admission cap — the slab's
        row dimension, and the arm-family key's N term."""
        n = sum(1 for t in self.tenants.values()
                if t.tier == "cohort" and not t.closed and t.vb == vb)
        cap = seg_ops.bucket_size(max_tenants())
        return min(seg_ops.bucket_size(max(1, n)), cap)

    def _tuner_space(self, nb: int) -> dict:
        """The `tenant_cohort` arm space at cohort bucket Nb:
        tenants-per-dispatch rungs under the live bucket, plus the
        windows-per-superbatch arm when the resident cohort tier is
        selected (its rungs divide the GS_RESIDENT_SPB bucket)."""
        space = {"tpd": sorted({max(1, nb // 4), max(1, nb // 2),
                                nb})}
        if resident_engine.resolve_resident_cohort():
            spb = seg_ops.bucket_size(
                resident_engine.resident_spb(self.eb))
            space["spb"] = sorted({max(1, spb // 4),
                                   max(1, spb // 2), spb})
        return space

    def _tuner(self, vb: int):
        """The cohort dispatch arms (ops/autotune.DispatchTuner,
        family `tenant_cohort`): tenants-per-dispatch (× windows-per-
        superbatch on the resident tier); pump rounds chunk ready
        tenants into tpd-sized dispatches and feed measured edges/s
        back. None when the tuner is disabled (GS_AUTOTUNE=0) or
        GS_TENANT_TPD pins.

        The arm-family key includes the COHORT BUCKET Nb
        (`tenant_cohort:eb=…:vb=…:N=…`): a tenant admitted during a
        vertex-bucket grow changes the slab's row dimension, and a
        grown cohort inheriting the old bucket's tenants-per-dispatch
        optimum (with its stale EMAs) would exploit a measurement
        taken on a different program shape — so a bucket change
        REKEYS the family (ops/autotune.DispatchTuner.rekey:
        incumbent and persisted-cache seed carry over only where the
        new space sanctions them, EMAs reset)."""
        from ..ops import autotune

        if pinned_tpd() > 0 or not autotune.enabled():
            return None
        key = (vb,)
        nb = self._cohort_nb(vb)
        tuner = self._tuners.get(key)
        if tuner is not None and self._tuner_nb.get(key) == nb:
            return tuner
        space = self._tuner_space(nb)
        init = {k: v[-1] for k, v in space.items()}
        name = "tenant_cohort:eb=%d:vb=%d:N=%d" % (self.eb, vb, nb)
        if tuner is None:
            tuner = self._tuners[key] = autotune.DispatchTuner(
                name, space, init)
        else:
            tuner.rekey(name, space=space, initial=init)
        self._tuner_nb[key] = nb
        return tuner

    def _resolve_tpd(self, vb: int, n_ready: int):
        """(tpd, tuner_arm): the dispatch-batch width this round. Pin
        wins; else the tuner's arm; else every ready tenant in one
        slab (the GS_AUTOTUNE=0 static form)."""
        pin = pinned_tpd()
        if pin > 0:
            return pin, None
        tuner = self._tuner(vb)
        if tuner is None:
            return n_ready, None
        arm = (tuner.best() if ingress_pipeline.forced_sync_active()
               else tuner.next_round())
        return arm["tpd"], arm

    # ------------------------------------------------------------------
    # the pump: rounds of vmapped cohort dispatches
    # ------------------------------------------------------------------
    def _window_ceiling(self) -> int:
        """Windows of ONE tenant folded per dispatch: the static wc
        on the scan tier; on the RESIDENT cohort tier the super-batch
        depth (the GS_RESIDENT_SPB bucket, narrowed by the tuner's
        windows-per-superbatch arm when one is live this round) — one
        donated dispatch folds a whole super-batch per tenant instead
        of wc windows. Chunking never changes summaries (window
        boundaries are count-based), so the ceiling is a pure
        throughput lever."""
        if not resident_engine.resolve_resident_cohort():
            return self.wc
        spb = seg_ops.bucket_size(
            resident_engine.resident_spb(self.eb))
        if self._round_spb:
            spb = min(spb, seg_ops.bucket_size(self._round_spb))
        return max(self.wc, spb)

    def _take_windows(self, t: _Tenant) -> int:
        """Full windows this tenant contributes to the next slab (plus
        the final partial one once closing)."""
        if t.tier != "cohort" or t.closed:
            return 0
        wc = self._window_ceiling()
        full = t.queued // self.eb
        if t.closing and t.queued % self.eb and full < wc:
            return min(full + 1, wc)
        return min(full, wc)

    def _prep_slab(self, batch: List[_Tenant], wins: List[int]):
        """Right-pad each tenant's next `wins` windows into the cohort
        slab [nb, wb, eb] (+ per-tenant failures for demotion). Runs
        on the ingress worker pool via the ingest ring when available;
        reads queues only — consumption happens at finalize."""
        st = latency.stamps()
        latency.stamp(st, "start")  # queue-wait ends: prep begins
        nb = seg_ops.bucket_size(len(batch))
        wb = seg_ops.bucket_size(max(wins))
        vb = batch[0].vb
        s = np.full((nb, wb, self.eb), vb, np.int32)
        d = np.full((nb, wb, self.eb), vb, np.int32)
        valid = np.zeros((nb, wb, self.eb), bool)
        real = []   # (tenant, row, windows, edges) actually packed
        failed = []  # (tenant, repr(error)) -> demotion at finalize
        for row, (t, w) in enumerate(zip(batch, wins)):
            try:
                faults.fire("tenant_prep", t.tid)
                # a consistent (src, dst) snapshot under the queue
                # lock: concurrent feeds only APPEND (atomically, both
                # arrays under _qlock), so the prefix this slab packs
                # is stable — the pump is the sole consumer
                with self._qlock:
                    n = min(w * self.eb, t.queued)
                    t_src, t_dst = t.src, t.dst
                flat_s = s[row].reshape(-1)
                flat_d = d[row].reshape(-1)
                flat_v = valid[row].reshape(-1)
                flat_s[:n] = t_src[:n]
                flat_d[:n] = t_dst[:n]
                flat_v[:n] = True
                real.append((t, row, w, n))
            except faults.InjectedFault as e:
                if e.fatal:
                    raise  # the simulated hard kill: never isolated
                failed.append((t, "%s: %s" % (type(e).__name__, e)))
            except Exception as e:  # gslint: disable=except-hygiene (captured per tenant: finalize demotes the sick tenant via record_demotion and the cohort keeps dispatching)
                failed.append((t, "%s: %s" % (type(e).__name__, e)))
        latency.stamp(st, "prep")
        return (nb, wb, s, d, valid, real, failed, st)

    def _dispatch_batch(self, vb: int, kb: int, slab, out: dict,
                        staged: list) -> int:
        """One vmapped cohort dispatch + finalize. Returns the number
        of edges covered (the tuner's measurement unit)."""
        nb, wb, s, d, valid, real, failed, st = slab
        for t, err in failed:
            self._demote(t, "slab prep failed: %s" % err)
        if not real:
            return 0
        res_on = resident_engine.resolve_resident_cohort()
        res_key = (vb, kb)
        # the resident stack's row signature for THIS dispatch: the
        # tenant at each slab row (None = pad row)
        sig = [None] * nb
        for t, row, _w, _n in real:
            sig[row] = t.tid
        sig = tuple(sig)
        entry = self._res.get(res_key) if res_on else None
        if entry is not None and entry["nb"] == nb \
                and entry["rows"] == sig \
                and all(t.res_row == row for t, row, _w, _n in real):
            # the steady-state resident hit: the cohort's carries are
            # already stacked ON DEVICE from the previous super-batch
            # — no per-tenant restack, no h2d of N carry slabs
            stacked = entry["carry"]
        else:
            # membership changed (admit/close/demote/restore) or the
            # tier just turned on: evict the WHOLE stale stack at this
            # key — not just this batch's tenants — so absent tenants
            # (closing peers, drained queues) materialize their rows
            # BEFORE the commit below replaces the stack under them;
            # then restack from wherever each carry lives (with the
            # tier off this also materializes rows stranded by a
            # flipped pin — a no-op when no stack exists)
            self._evict_resident(res_key)
            carries = [self._carry_of(t) for t, _r, _w, _n in real]
            by_row = {row: i
                      for i, (_t, row, _w, _n) in enumerate(real)}
            # pad rows (demoted-mid-prep or a non-power-of-two
            # cohort) carry a fresh zero-stream state — built only
            # when the slab actually has them
            pad = (self._fresh_carry(vb) if len(by_row) < nb
                   else None)
            stacked = tuple(
                jnp.stack([carries[by_row[r]][leaf] if r in by_row
                           else pad[leaf] for r in range(nb)])
                for leaf in range(3))
        run = (self._res_program(vb, kb, nb, wb) if res_on
               else self._program(vb, kb, nb, wb))
        edges = sum(n for _t, _row, _w, n in real)

        def _dispatch():
            # h2d INSIDE the guarded call (a wedged transfer must
            # surface as the typed StageTimeout, not hang the pump);
            # the boundary stamp closes the h2d stage right after
            sj, dj, vj = (jnp.asarray(s), jnp.asarray(d),
                          jnp.asarray(valid))
            latency.stamp(st, "h2d")
            return run(stacked, sj, dj, vj)

        with telemetry.span("cohort.dispatch", tenants=len(real),
                            windows=sum(w for _t, _r, w, _n in real),
                            edges=edges) as sp:
            faults.fire("cohort_dispatch",
                        tuple(t.tid for t, _r, _w, _n in real))
            new_carries, outs = resilience.call_guarded(
                "dispatch", ("cohort", self._round_no), _dispatch,
                retries=0)  # carry-mutating: deadline only, never re-run
        # the program-identity tags wrap_jit bound inside the dispatch
        # key the costmodel entry this span's bytes come from; pop
        # BEFORE the redo kernels below can rebind them
        tags = telemetry.pop_dispatch_tags()
        mats = tuple(np.array(x) for x in outs)  # gslint: disable=host-sync (sanctioned finalize boundary: the cohort's ONE batched d2h per dispatch)
        latency.stamp(st, "dispatch")  # device wait ends with the d2h
        mdeg, ncomp, odd, tri, ovf = mats
        # the bulkhead's output gate: BEFORE any tenant state mutates,
        # refuse implausible analytics per slab row (negative counts,
        # components past the bucket, non-finite values) — a poisoned
        # carry must never be folded back, and naming the rows lets
        # the dispatch loop quarantine exactly the poison tenants and
        # re-run the rest of the round
        poisoned = []
        for t, row, w, _n in real:
            bad = False
            redo = np.asarray(ovf[row, :w]) != 0  # gslint: disable=host-sync (numpy-on-numpy after the batched materialize)
            for arr, hi, skip in ((mdeg, None, None),
                                  (ncomp, t.vb + 1, None),
                                  (tri, None, redo)):
                v = np.asarray(arr[row, :w])  # gslint: disable=host-sync (numpy-on-numpy after the batched materialize)
                if v.dtype.kind == "f" and not np.isfinite(v).all():
                    bad = True
                ok = np.ones(w, bool) if skip is None else ~skip
                if (v[ok] < 0).any() \
                        or (hi is not None and (v[ok] > hi).any()):
                    bad = True
            if bad:
                poisoned.append(t.tid)
        if poisoned:
            raise PoisonOutput(
                "cohort dispatch finalized implausible analytics for "
                "tenant(s) %s" % ", ".join(poisoned), poisoned)
        if res_on:
            # commit the resident stack only now, PAST the poison
            # gate: a refused dispatch leaves the previous stack (and
            # every per-tenant carry) untouched for the bulkhead's
            # re-prep of the healthy remainder
            self._res[res_key] = {"nb": nb, "rows": sig,
                                  "carry": new_carries}
            self.resident_dispatches += 1
        # per-tenant cost attribution: split this dispatch's measured
        # wall seconds (and the program's modeled bytes) across the
        # REAL rows proportionally by valid-edge count — pad rows
        # attribute zero, and the shares reconcile exactly to the
        # span's total (pinned by test)
        metrics.attribute_dispatch(
            sp.elapsed, [(t.tid, n) for t, _r, _w, n in real],
            program=tags.get("program"), sig=tags.get("sig"))
        prov_tier = "cohort_resident" if res_on else "cohort"
        for t, row, w, n in real:
            summaries = []
            for j in range(w):
                lo = j * self.eb
                tri_w = int(tri[row, j])  # gslint: disable=host-sync (numpy-on-numpy after the batched materialize)
                if int(ovf[row, j]):  # gslint: disable=host-sync (numpy-on-numpy after the batched materialize)
                    tri_w = self._redo_kernel(t.vb, t.kb).count(
                        t.src[lo:min(lo + self.eb, n)],
                        t.dst[lo:min(lo + self.eb, n)])
                summaries.append({
                    "max_degree": int(mdeg[row, j]),  # gslint: disable=host-sync (numpy-on-numpy after the batched materialize)
                    "num_components": int(ncomp[row, j]),  # gslint: disable=host-sync (numpy-on-numpy after the batched materialize)
                    "odd_cycle": bool(odd[row, j]),
                    "triangles": int(tri_w),  # gslint: disable=host-sync (numpy-on-numpy after the batched materialize)
                })
            if res_on:
                # the carry stays IN the device-resident stack; the
                # tenant keeps only its row cursor (checkpoints and
                # demotions gather their slice via _carry_of)
                t.res_row = row
                t.carry = None
            else:
                t.carry = tuple(a[row] for a in new_carries)
            with self._qlock:
                t.src = t.src[n:]
                t.dst = t.dst[n:]
                t.bp_stamped = False  # queue drained: new episode
            if st is not None:
                # per-window ingest→deliver record: join each window
                # back to the admission mark of its completing edge;
                # the serving front-end defers emission to its sink
                # write (serve._emit stamps the `deliver` stage)
                for j in range(w):
                    latency.on_window(
                        t.tid,
                        edges=min((j + 1) * self.eb, n) - j * self.eb,
                        st=st, ordinal=t.windows_done + j,
                        defer=self.defer_delivery)
            if provenance.armed():
                # the recorded span is the tenant's own WAL cursor
                # (windows_done × eb — the checkpoint contract), so
                # replay_window can stream exactly these edges back
                # through the host twin and re-derive the digest
                for j in range(w):
                    lo = (t.windows_done + j) * self.eb
                    provenance.emit(
                        tenant=t.tid, window=t.windows_done + j,
                        wal_lo=lo,
                        wal_hi=lo + min((j + 1) * self.eb, n)
                        - j * self.eb,
                        tier=prov_tier, program="cohort_scan",
                        sig=tags.get("sig"),
                        summary=summaries[j])
            t.windows_done += w
            if n < w * self.eb:      # the final short window just cut
                t.closed_partial = True
            if t.closing and t.queued == 0:
                t.closed = True
            out.setdefault(t.tid, []).extend(summaries)
            metrics.mark_window(w, n, engine="cohort", tier="cohort",
                                tenant=t.tid)
            metrics.gauge_set("gs_tenant_queue_edges", t.queued,
                              tenant=t.tid)
            if st is not None:
                metrics.gauge_set("gs_tenant_queue_age_s",
                                  latency.queue_age(t.tid) or 0.0,
                                  tenant=t.tid)
            self._stage_ckpt(t, staged)
        return edges

    def _dispatch_guarded(self, vb: int, kb: int, batch, wins, slab,
                          out: dict, staged: list) -> int:
        """The cohort bulkhead around one dispatch batch. A dispatch
        that fails (typed StageError from the guard, a non-fatal
        injected fault) or finalizes implausible output is BISECTED to
        the poison tenant(s): the offending streams are quarantined
        (durable event, suspended feeds, solo probation — see
        _quarantine) and the remaining tenants re-dispatch THE SAME
        round from their untouched queues and carries, so one hostile
        stream can never take the cohort down.

        Quarantine requires DISCRIMINATING evidence — a failure that
        follows a strict subset of the tenants. If every tenant of
        the batch fails alone (a dead device, a wedged transfer: the
        failure follows the hardware, not the data), the quarantines
        are revoked and the typed error propagates exactly as it did
        before the bulkhead existed. Fatal injected faults (the chaos
        kill) and KeyboardInterrupt/SystemExit pass through — a kill
        must stay a kill. Exception-free dispatches run exactly the
        pre-bulkhead path (no re-prep, no overhead)."""
        errors = []  # (tenant_id, err) per singleton-failure quarantine
        edges = self._dispatch_bulkhead(vb, kb, batch, wins, slab,
                                        out, staged, errors)
        failed = {tid for tid, _e in errors}
        if errors and failed == {t.tid for t in batch}:
            for t in batch:
                if t.tid in failed:
                    self._unquarantine(
                        t, "systemic dispatch failure — every tenant "
                           "failed alone")
            raise errors[-1][1]
        return edges

    def _dispatch_bulkhead(self, vb: int, kb: int, batch, wins, slab,
                           out: dict, staged: list,
                           errors: list) -> int:
        try:
            return self._dispatch_batch(vb, kb, slab, out, staged)
        except PoisonOutput as e:
            # row-attributed evidence: quarantine exactly the named
            # tenants (no bisect, and never revoked as systemic —
            # the verdict names rows, not the whole dispatch)
            bad = set(e.tenants)
            for t in batch:
                if t.tid in bad:
                    self._quarantine(t, "implausible dispatch output")
            keep = [(t, w) for t, w in zip(batch, wins)
                    if t.tid not in bad]
        except faults.InjectedFault as e:
            if e.fatal:
                raise
            keep = self._bisect_split(batch, wins, e, errors)
            if keep is None:
                return 0
            # keep is (first_half, second_half): dispatch each
            return sum(
                self._dispatch_bulkhead(vb, kb, b, w,
                                        self._prep_slab(b, w), out,
                                        staged, errors)
                for b, w in keep if b)
        except resilience.StageError as e:
            keep = self._bisect_split(batch, wins, e, errors)
            if keep is None:
                return 0
            return sum(
                self._dispatch_bulkhead(vb, kb, b, w,
                                        self._prep_slab(b, w), out,
                                        staged, errors)
                for b, w in keep if b)
        # PoisonOutput path: re-run the named-healthy remainder once
        if not keep:
            return 0
        b = [t for t, _w in keep]
        w = [x for _t, x in keep]
        return self._dispatch_bulkhead(vb, kb, b, w,
                                       self._prep_slab(b, w), out,
                                       staged, errors)

    def _bisect_split(self, batch, wins, err, errors: list):
        """Halve a failing batch for fault attribution; a singleton
        failing batch IS the implicated tenant — quarantine it,
        record the evidence for the systemic-failure check, and stop
        (returns None)."""
        if len(batch) == 1:
            self._quarantine(batch[0], "poison dispatch: %s: %s"
                             % (type(err).__name__, err))
            errors.append((batch[0].tid, err))
            return None
        mid = len(batch) // 2
        telemetry.event("cohort_bisect", tenants=len(batch),
                        error=type(err).__name__)
        metrics.counter_inc("gs_cohort_bisects_total")
        return ((batch[:mid], wins[:mid]), (batch[mid:], wins[mid:]))

    def _unquarantine(self, t: _Tenant, reason: str) -> None:
        """Revoke a quarantine this round imposed without
        discriminating evidence (the systemic-failure path)."""
        if t.tier != "quarantined":
            return
        t.tier = "cohort"
        t.engine = None
        t.probation = 0
        t.quarantine_reason = None
        telemetry.event("quarantine_revoked", durable=True,
                        tenant=t.tid, reason=reason)
        metrics.gauge_set("gs_tenant_quarantined", 0, tenant=t.tid)

    def pump(self, max_rounds: Optional[int] = None,
             only: Optional[str] = None) -> Dict[str, list]:
        """Dispatch window cohorts while any tenant has a full window
        queued (plus the final partial window of closing tenants);
        demoted tenants run their own single-tenant engine alongside.
        Returns {tenant: [summary dict, ...]} for every window
        finalized by this call; due checkpoints are written at clean
        return (the delivery boundary — the engines' staged
        at-least-once contract). `only` restricts the pump to one
        tenant (close()'s drain — other tenants' windows must never
        be consumed by a call whose caller only reads one stream)."""
        out: Dict[str, list] = {}
        staged: list = []
        rounds = 0
        while max_rounds is None or rounds < max_rounds:
            self._pump_singles(out, staged, only=only)
            probed = self._pump_probation(out, staged, only=only)
            by_group: Dict[tuple, list] = {}
            for tid in self._tids():
                if only is not None and tid != only:
                    continue
                t = self.tenants[tid]
                if self._take_windows(t) > 0:
                    by_group.setdefault((t.vb, t.kb), []).append(t)
            if not by_group:
                if probed:
                    # probation made progress (a quarantined tenant
                    # finalized clean solo windows, possibly
                    # re-entering the cohort) — keep pumping; failing
                    # probes return 0, so this can never spin
                    rounds += 1
                    continue
                break
            rounds += 1
            self._round_no += 1
            for (vb, kb), ready in sorted(by_group.items()):
                tpd, arm = self._resolve_tpd(vb, len(ready))
                # the windows-per-superbatch arm (resident tier only)
                # narrows this round's per-tenant window ceiling
                self._round_spb = int((arm or {}).get("spb") or 0)
                batches = [ready[i:i + tpd]
                           for i in range(0, len(ready), tpd)]
                descs = [(b, [self._take_windows(t) for t in b])
                         for b in batches]
                with telemetry.span(
                        "cohort.round", vb=vb, tenants=len(ready),
                        edges=sum(min(w * self.eb, t.queued)
                                  for b, ws in descs
                                  for t, w in zip(b, ws))) as sp:
                    edges = self._run_batches(vb, kb, descs, out,
                                              staged)
                if arm is not None and edges:
                    tuner = self._tuner(vb)
                    if tuner is not None:
                        tuner.record(arm, edges, sp.elapsed)
        for (vb,), tuner in self._tuners.items():
            if not ingress_pipeline.forced_sync_active():
                tuner.save()
        for t, snap in staged:
            checkpoint.save(self._ckpt_path(t.tid), snap)
        return out

    def _run_batches(self, vb: int, kb: int, descs, out: dict,
                     staged: list) -> int:
        """Dispatch one round's batches with the ingest ring prepping
        batch k+1's slab on the worker pool while batch k computes
        (batches within a round cover DISJOINT tenants, so lookahead
        prep reads only queues no earlier batch consumes; across
        rounds the pump re-plans after finalize)."""
        edges = 0
        if len(descs) == 1:
            # one batch: a worker-pool round trip buys nothing — build
            # the slab inline (the serving-shape hot path)
            batch, wins = descs[0]
            return self._dispatch_guarded(vb, kb, batch, wins,
                                          self._prep_slab(batch, wins),
                                          out, staged)
        pending = {}
        try:
            for i, (batch, wins) in enumerate(descs):
                if i not in pending:
                    if not self._ring.submit(
                            lambda bw: self._prep_slab(*bw), i,
                            (batch, wins)):
                        pending[i] = None  # inline fallback
                    else:
                        pending[i] = "ring"
                # lookahead: top the ring up with the NEXT slab before
                # dispatching this one (classic double buffering)
                if i + 1 < len(descs) and i + 1 not in pending:
                    if self._ring.submit(
                            lambda bw: self._prep_slab(*bw), i + 1,
                            descs[i + 1]):
                        pending[i + 1] = "ring"
                if pending[i] == "ring":
                    fut, _item = self._ring.pop(i)
                    slab = fut.result()
                else:
                    slab = self._prep_slab(*descs[i])
                edges += self._dispatch_guarded(
                    vb, kb, descs[i][0], descs[i][1], slab, out,
                    staged)
        except BaseException:
            # a mid-round failure (stage timeout, fatal injected kill)
            # must not strand prepped slabs in the ring — the NEXT
            # pump re-plans from the queues, which finalize never
            # consumed for undispatched batches
            self._ring.drain()
            raise
        return edges

    def _pump_singles(self, out: dict, staged: list,
                      only: Optional[str] = None) -> None:
        """Demoted tenants: their queued full windows (and the final
        partial once closing) run through their OWN single-tenant
        engine — per-tenant dispatches, identical summaries. The
        engine marks the global health plane itself; the cohort adds
        the per-tenant row."""
        for tid in self._tids():
            if only is not None and tid != only:
                continue
            t = self.tenants[tid]
            if t.tier != "single" or t.closed:
                continue
            # the demoted engine's delivered rows keep the serving
            # contract: mirror the cohort's delivery deferral per
            # pump (serve restores defer_delivery=False at drain)
            t.engine._lat_defer = self.defer_delivery
            with self._qlock:
                n = (t.queued // self.eb) * self.eb
                if t.closing:
                    n = t.queued
                src, dst = t.src[:n], t.dst[:n]
            if n == 0:
                if t.closing:
                    t.closed = True
                continue
            with telemetry.span("tenant.single", tenant=t.tid,
                                edges=int(n)) as sp:
                summaries = t.engine.process(src, dst)
            # a demoted tenant owns its whole dispatch: 100% share
            metrics.attribute_dispatch(
                sp.elapsed, [(t.tid, int(n))],
                program=telemetry.pop_dispatch_tags().get("program"))
            with self._qlock:
                t.src = t.src[n:]
                t.dst = t.dst[n:]
                t.bp_stamped = False  # queue drained: new episode
            t.windows_done = t.engine.windows_done
            t.closed_partial = t.engine._closed_partial
            if t.closing and t.queued == 0:
                t.closed = True
            out.setdefault(t.tid, []).extend(summaries)
            metrics.mark_tenant(t.tid, len(summaries), int(n),
                                tier="single")
            self._stage_ckpt(t, staged)

    def _pump_probation(self, out: dict, staged: list,
                        only: Optional[str] = None) -> int:
        """Quarantined tenants' probation ladder: with
        GS_QUARANTINE_WINDOWS > 0, each pump gives every quarantined
        tenant with a full window queued ONE solo window through an
        isolated single-tenant engine (seeded from its last-good
        carry). A clean window advances probation (its exact summaries
        are delivered — isolation, not deletion); a failing or
        implausible one resets probation and discards the probe engine
        (the next probe re-seeds from the untouched last-good carry —
        the failed fold never sticks). After GS_QUARANTINE_WINDOWS
        consecutive clean windows the tenant re-enters the cohort
        tier. Returns windows finalized (0 on every-probe-failed, so
        pump()'s loop can never spin on a still-poisoned stream)."""
        qw = quarantine_windows()
        if qw <= 0:
            return 0  # permanent quarantine: truly suspended
        done = 0
        for tid in self._tids():
            if only is not None and tid != only:
                continue
            t = self.tenants[tid]
            if t.tier != "quarantined" or t.closed:
                continue
            with self._qlock:
                n = (self.eb if t.queued >= self.eb
                     else (t.queued if t.closing else 0))
            if n == 0:
                if t.closing:
                    t.closed = True
                continue
            if t.engine is None:
                eng = scan_analytics.StreamSummaryEngine(
                    edge_bucket=self.eb, vertex_bucket=t.vb,
                    k_bucket=t.kb)
                eng.load_state_dict(self.tenant_state_dict(t.tid))
                eng._lat_lane = t.tid
                eng._lat_admit = False
                t.engine = eng
            t.engine._lat_defer = self.defer_delivery
            with self._qlock:
                src, dst = t.src[:n], t.dst[:n]
            try:
                with telemetry.span("tenant.probation", tenant=t.tid,
                                    edges=int(n)) as sp:
                    summaries = t.engine.process(src, dst)
                metrics.attribute_dispatch(
                    sp.elapsed, [(t.tid, int(n))],
                    program=telemetry.pop_dispatch_tags()
                    .get("program"))
                if any(s["max_degree"] < 0 or s["num_components"] < 0
                       or s["num_components"] > t.vb + 1
                       or s["triangles"] < 0 for s in summaries):
                    raise PoisonOutput(
                        "probation window finalized implausible "
                        "analytics", [t.tid])
            except (KeyboardInterrupt, SystemExit):
                raise
            except faults.InjectedFault as e:
                if e.fatal:
                    raise
                self._probation_failed(t, e)
                continue
            except Exception as e:  # gslint: disable=except-hygiene (captured per probe: _probation_failed stamps the event and resets the ladder; the cohort keeps serving)
                self._probation_failed(t, e)
                continue
            # absorb the probe engine's state as the new last-good
            # carry (bit-exact: the engine layout IS the cohort's)
            est = t.engine.state_dict()
            self._break_residency(t)
            t.carry = tuple(jnp.asarray(a) for a in est["carry"])
            with self._qlock:
                t.src = t.src[n:]
                t.dst = t.dst[n:]
                t.bp_stamped = False
            t.windows_done = t.engine.windows_done
            t.closed_partial = t.engine._closed_partial
            if t.closing and t.queued == 0:
                t.closed = True
            t.probation += len(summaries)
            done += len(summaries)
            out.setdefault(t.tid, []).extend(summaries)
            metrics.mark_tenant(t.tid, len(summaries), int(n),
                                tier="quarantined")
            telemetry.event("quarantine_probe", tenant=t.tid,
                            clean=t.probation, required=qw)
            self._stage_ckpt(t, staged)
            if t.probation >= qw:
                t.tier = "cohort"
                t.engine = None
                t.quarantine_reason = None
                t.probation = 0
                telemetry.event("quarantine_released", durable=True,
                                tenant=t.tid,
                                windows_done=t.windows_done)
                metrics.counter_inc(
                    "gs_tenant_quarantine_releases_total")
                metrics.gauge_set("gs_tenant_quarantined", 0,
                                  tenant=t.tid)
        return done

    def _probation_failed(self, t: _Tenant, err) -> None:
        t.probation = 0
        t.engine = None  # re-seed from the last-good carry next probe
        telemetry.event("quarantine_probe_failed", durable=True,
                        tenant=t.tid,
                        error="%s: %s" % (type(err).__name__,
                                          str(err)[:200]))
        metrics.counter_inc("gs_tenant_probation_failures_total")

    def close(self, tenant_id) -> List[dict]:
        """Cut the tenant's final (possibly partial) window and retire
        it. Drains ONLY this tenant (pump(only=...)) — other tenants'
        queued windows stay queued for the next pump(), so a close()
        can never consume summaries its caller doesn't read."""
        t = self._tenant(tenant_id)
        if t.closed:
            return []
        # event-time hold flush: edges the GS_OOO_BOUND watermark
        # never passed release NOW (in ts order) — the final window
        # must not strand them
        early = self._ooo_flush(t) if t.ooo_ts.size else []
        t.closing = True
        if t.queued == 0 and t.tier == "cohort":
            t.closed = True
            return early
        out = self.pump(only=t.tid)
        return early + out.get(t.tid, [])

    # ------------------------------------------------------------------
    # quarantine (the bulkhead's suspended state)
    # ------------------------------------------------------------------
    def _quarantine(self, t: _Tenant, reason: str) -> None:
        """Suspend one poison stream: no cohort dispatches, feeds
        refused with typed TenantQuarantined, queued edges kept for
        the probation ladder (or the operator's DLQ triage). The
        durable `quarantine` event + demotion record are the
        post-mortem evidence; per-tenant /healthz + metrics rows ride
        the existing cardinality-bounded tenant labels."""
        if t.tier == "quarantined":
            return
        self._break_residency(t)  # its carry leaves the device stack
        from_tier = t.tier
        t.tier = "quarantined"
        t.engine = None
        t.probation = 0
        t.quarantine_reason = str(reason)[:200]
        telemetry.event("quarantine", durable=True, tenant=t.tid,
                        reason=t.quarantine_reason,
                        windows_done=t.windows_done)
        metrics.counter_inc("gs_tenant_quarantines_total")
        metrics.gauge_set("gs_tenant_quarantined", 1, tenant=t.tid)
        resilience.record_demotion(
            "tenant:%s" % t.tid, from_tier, "quarantined",
            t.windows_done, t.quarantine_reason, tenant=t.tid)

    def quarantine(self, tenant_id, reason: str = "operator") -> None:
        """Operator hook: suspend one tenant by hand (the same state a
        poisoned dispatch lands in)."""
        self._quarantine(self._tenant(tenant_id), reason)

    def quarantined(self) -> List[str]:
        """Currently quarantined tenant ids (the /healthz cell)."""
        return [tid for tid in self._tids()
                if self.tenants[tid].tier == "quarantined"]

    # ------------------------------------------------------------------
    # demotion (cohort → single-tenant engine)
    # ------------------------------------------------------------------
    def _demote(self, t: _Tenant, reason: str) -> None:
        if t.tier == "single":
            return
        self._break_residency(t)  # its carry leaves the device stack
        eng = scan_analytics.StreamSummaryEngine(
            edge_bucket=self.eb, vertex_bucket=t.vb, k_bucket=t.kb)
        eng.load_state_dict(self.tenant_state_dict(t.tid))
        # latency-plane lane continuity: the demoted engine records
        # its windows on THIS tenant's lane and must not re-stamp
        # admission (the cohort's feed() already did at the boundary)
        eng._lat_lane = t.tid
        eng._lat_admit = False
        t.engine = eng
        t.tier = "single"
        resilience.record_demotion(
            "tenant:%s" % t.tid, "cohort", "single",
            t.windows_done, reason, tenant=t.tid)

    def demote(self, tenant_id, reason: str = "operator") -> None:
        """Operator hook: pull one tenant off the cohort tier onto its
        own single-tenant engine (seeded from its live carry — exact).
        The cohort keeps dispatching everyone else."""
        self._demote(self._tenant(tenant_id), reason)

    # ------------------------------------------------------------------
    # checkpoints (per tenant; engine-interchangeable layout)
    # ------------------------------------------------------------------
    def tenant_state_dict(self, tenant_id) -> dict:
        """One tenant's resumable state in EXACTLY the summary
        engines' layout (ops/scan_analytics state_dict), so a cohort
        checkpoint restores into a single-tenant StreamSummaryEngine
        (the demotion ladder) and vice versa at equal buckets."""
        t = self._tenant(tenant_id)
        if t.tier == "single" or (t.tier == "quarantined"
                                  and t.engine is not None):
            state = t.engine.state_dict()
        else:
            # _carry_of gathers the tenant's slice out of the
            # resident stack when that tier holds it — the per-tenant
            # checkpoint gather at super-batch boundaries
            carry = self._carry_of(t)
            deg, labels, cover = (np.array(x) for x in carry)  # gslint: disable=host-sync (sanctioned checkpoint boundary: the tenant state_dict's one d2h)
            state = {
                "edge_bucket": self.eb,
                "vertex_bucket": t.vb,
                "windows_done": int(t.windows_done),
                "closed_partial": bool(t.closed_partial),
                # the journal offset at this finalized-window boundary
                # (cumulative edges folded into the carry): recover()
                # replays the WAL strictly past it — the
                # offset/checkpoint contract of DESIGN.md §18
                "wal_offset": int(t.windows_done) * self.eb,
                "carry": (deg, labels, cover),
            }
        if t.tier == "quarantined":
            # the bulkhead state rides the checkpoint (an engine
            # restoring this layout ignores the extra key): a killed
            # cohort must come back still-quarantined with its
            # probation progress, never silently re-admitting a
            # poison stream
            state["quarantine"] = {
                "probation": int(t.probation),
                "reason": t.quarantine_reason or "",
            }
        return state

    def load_tenant_state_dict(self, tenant_id, state: dict) -> None:
        t = self._tenant(tenant_id)
        if state["edge_bucket"] != self.eb \
                or state["vertex_bucket"] != t.vb:
            raise ValueError(
                "bucket mismatch: checkpoint was taken at eb=%d vb=%d, "
                "tenant %r runs eb=%d vb=%d" % (
                    state["edge_bucket"], state["vertex_bucket"],
                    t.tid, self.eb, t.vb))
        t.windows_done = int(state["windows_done"])  # gslint: disable=host-sync (checkpoint payloads are host numpy, never device values)
        t.closed_partial = bool(state["closed_partial"])
        woff = state.get("wal_offset")
        if woff is not None and int(woff) > t.windows_done * self.eb:
            # a journal offset AHEAD of the window cursor would make
            # recover() skip edges never folded — refuse loudly
            raise ValueError(
                "checkpoint wal_offset %d exceeds its own window "
                "coverage (%d windows x eb=%d)" % (
                    int(woff), t.windows_done, self.eb))
        # the checkpoint is authoritative: a tenant restored while
        # the resident stack holds its carry leaves the stack (and
        # the stack restacks without it next dispatch)
        self._break_residency(t)
        t.carry = tuple(jnp.asarray(a) for a in state["carry"])
        q = state.get("quarantine")
        if q is not None:
            t.tier = "quarantined"
            t.engine = None  # probes re-seed from the restored carry
            t.probation = int(q.get("probation", 0))  # gslint: disable=host-sync (checkpoint payloads are host scalars, never device values)
            t.quarantine_reason = q.get("reason") or "restored"
            metrics.gauge_set("gs_tenant_quarantined", 1,
                              tenant=t.tid)
        elif t.tier == "quarantined":
            # the checkpoint is authoritative: restoring a generation
            # taken before the quarantine rewinds the bulkhead too
            t.tier = "cohort"
            t.engine = None
            t.probation = 0
            t.quarantine_reason = None
            metrics.gauge_set("gs_tenant_quarantined", 0,
                              tenant=t.tid)
        if t.tier == "single":
            t.engine.load_state_dict(state)

    def state_dict(self) -> dict:
        """The whole cohort (cohort→cohort resume): per-tenant states
        in the shared layout under their ids."""
        return {
            "edge_bucket": self.eb,
            "tenants": {tid: self.tenant_state_dict(tid)
                        for tid in self._tids()},
        }

    def load_state_dict(self, state: dict) -> None:
        if state["edge_bucket"] != self.eb:
            raise ValueError(
                "bucket mismatch: cohort checkpoint was taken at "
                "eb=%d, this cohort runs eb=%d"
                % (state["edge_bucket"], self.eb))
        for tid, tstate in state["tenants"].items():
            if tid not in self.tenants:
                self.admit(tid,
                           vertex_bucket=tstate["vertex_bucket"])  # gslint: disable=ckpt-symmetry (read from the PER-TENANT sub-state, which tenant_state_dict always writes — the cohort's own top level carries only edge_bucket + tenants)
            self.load_tenant_state_dict(tid, tstate)

    def enable_auto_checkpoint(self, directory: str,
                               every_n_windows: int = 16,
                               every_seconds: float = 0.0) -> None:
        """Per-tenant auto-snapshots (`tenant_<id>.npz` under
        `directory`, atomic + last-2 rotation — utils/checkpoint) on a
        per-tenant CheckpointPolicy cadence, staged at dispatch
        boundaries and flushed at pump()'s clean return (the delivery
        boundary). A killed cohort resumes each tenant independently:
        resume_all() / try_resume(tenant)."""
        if every_n_windows <= 0 and every_seconds <= 0:
            raise ValueError("checkpoint policy has no trigger enabled")
        os.makedirs(directory, exist_ok=True)
        self._ckpt_dir = directory
        self._ckpt_every_n = max(0, every_n_windows)
        self._ckpt_every_s = max(0.0, every_seconds)
        for t in self.tenants.values():
            if t.ckpt_policy is None:
                t.ckpt_policy = checkpoint.CheckpointPolicy(
                    every_n_windows=self._ckpt_every_n,
                    every_seconds=self._ckpt_every_s)
                t.ckpt_policy.mark(t.windows_done)

    def _ckpt_path(self, tid: str) -> str:
        safe = "".join(c if c.isalnum() or c in "-_" else "_"
                       for c in tid)
        return os.path.join(self._ckpt_dir, "tenant_%s.npz" % safe)

    def _stage_ckpt(self, t: _Tenant, staged: list) -> None:
        if self._ckpt_dir is None or t.ckpt_policy is None:
            return
        if t.ckpt_policy.due(t.windows_done):
            t.ckpt_policy.mark(t.windows_done)
            staged.append((t, self.tenant_state_dict(t.tid)))

    # ------------------------------------------------------------------
    # write-ahead journal (utils/wal.py): durable live ingest
    # ------------------------------------------------------------------
    def enable_wal(self, directory: str) -> bool:
        """Journal every accepted feed() batch under `directory`
        before it enters the tenant's queue, so a kill at ANY point
        loses nothing the caller was told was accepted: recover()
        replays the un-checkpointed suffix bit-exactly. Returns False
        (a no-op) under the GS_WAL=0 kill switch."""
        if not wal_mod.enabled():
            return False
        self._wal_dir = directory
        self._wal = wal_mod.WriteAheadLog(directory)
        return True

    def seal_wal(self) -> None:
        """Durably close the journal (the graceful-drain marker). The
        caller drains the queues and flushes checkpoints first —
        core/serve.StreamServer.drain() owns that ordering."""
        if self._wal is not None:
            self._wal.seal()

    def recover(self) -> dict:
        """Crash recovery over an armed journal: discover journaled
        tenants (admitting any unknown), resume each from its newest
        checkpoint generation, then replay each tenant's journal
        suffix past its checkpointed `wal_offset` straight into the
        queues (bypassing admission capacity — these edges were
        already accepted once). The next pump() then produces windows
        bit-identical to a never-killed run."""
        if self._wal_dir is None:
            raise ValueError("enable_wal() first: recover() replays "
                             "the journal the crashed process wrote")
        info = wal_mod.scan(self._wal_dir)
        for tid in sorted(info["offsets"]):
            if tid not in self.tenants:
                self.admit(tid)
        resumed = self.resume_all()
        offsets = {tid: self.resume_offset(tid)
                   for tid in self.tenants}
        replayed: Dict[str, int] = {}
        for tid, _start, src, dst, ts in wal_mod.replay(
                self._wal_dir, offsets):
            t = self.tenants.get(tid)
            if t is None or t.closed:
                continue
            with self._qlock:
                t.src = np.concatenate([t.src, src])
                t.dst = np.concatenate([t.dst, dst])
            # re-seed the latency plane's admission marks with the
            # journaled ORIGINAL stamps: the replayed windows report
            # their honest, larger latency, never reset-to-zero
            latency.on_replay(tid, len(src), ts)
            replayed[tid] = replayed.get(tid, 0) + len(src)
        telemetry.event("wal_replayed", durable=True,
                        component="cohort", dir=self._wal_dir,
                        tenants=len(replayed),
                        edges=sum(replayed.values()),
                        sealed=info["sealed"])
        metrics.counter_inc("gs_wal_replayed_edges_total",
                            sum(replayed.values()))
        return {"resumed": resumed, "replayed_edges": replayed,
                "sealed": info["sealed"]}

    def checkpoint_all(self) -> int:
        """Force-flush a checkpoint for every live tenant NOW,
        regardless of cadence — the graceful-drain boundary
        (core/serve.StreamServer.drain: queues are already dry, so
        each snapshot covers the tenant's whole delivered stream).
        No-op without enable_auto_checkpoint. Returns tenants saved."""
        if self._ckpt_dir is None:
            return 0
        saved = 0
        for tid in self._tids():
            t = self.tenants[tid]
            checkpoint.save(self._ckpt_path(tid),
                            self.tenant_state_dict(tid))
            if t.ckpt_policy is not None:
                t.ckpt_policy.mark(t.windows_done)
            saved += 1
        # journal retention at the flush boundary (GS_WAL_RETAIN):
        # every tenant's floor moves in ONE truncate_covered call —
        # a per-tenant call would see the other tenants' records as
        # uncovered and never delete a shared segment
        self._wal_retention.flushed_many(
            self._wal, {tid: self.tenants[tid].windows_done * self.eb
                        for tid in self.tenants})
        return saved

    def try_resume(self, tenant_id) -> bool:
        """Restore one tenant from its newest intact checkpoint
        generation (rotation fallback — utils/checkpoint.load_latest);
        False when nothing usable exists. After a True return, feed
        the tenant from `resume_offset(tenant)` edges in."""
        import warnings

        t = self._tenant(tenant_id)
        if self._ckpt_dir is None:
            return False
        try:
            got = checkpoint.load_latest(self._ckpt_path(t.tid))
        except checkpoint.CheckpointCorrupt as e:
            warnings.warn(f"{e}; no intact generation — tenant "
                          f"{t.tid!r} starts fresh")
            return False
        if got is None:
            return False
        state, used = got
        self.load_tenant_state_dict(t.tid, state)
        if t.ckpt_policy is not None:
            t.ckpt_policy.mark(t.windows_done)
        telemetry.event("resume", durable=True, component="tenant",
                        tenant=t.tid, path=used,
                        windows_done=t.windows_done)
        return True

    def resume_all(self) -> Dict[str, bool]:
        """try_resume every admitted tenant; {tenant: resumed}."""
        return {tid: self.try_resume(tid)
                for tid in self._tids()}

    def resume_offset(self, tenant_id) -> int:
        """Edges already folded into the tenant's carried state (the
        windows_done cursor — windows are count-based eb-sized)."""
        return self._tenant(tenant_id).windows_done * self.eb

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def tenant_tier(self, tenant_id) -> str:
        return self._tenant(tenant_id).tier

    def queued_edges(self, tenant_id) -> int:
        return self._tenant(tenant_id).queued

    def windows_done(self, tenant_id) -> int:
        return self._tenant(tenant_id).windows_done


# ----------------------------------------------------------------------
# the GNN cohort (ops/gnn_window on the tenant axis)
# ----------------------------------------------------------------------
class GnnTenantCohort:
    """N tenants' windowed GNN rounds on ONE vmapped dispatch — the
    multi-tenant serving shape for the MXU workload (fresh per-window
    embeddings pushed to N tenants' subscriptions): each tenant owns a
    `[vb+1, F]` feature slab; pump rounds stack the ready tenants'
    full windows into `[N, W, eb]` slabs and fold them through
    ops/gnn_window.build_gnn_cohort_scan under one `gnn_cohort`
    program per power-of-two (tenants, windows) bucket. The cohort
    shares ONE snapped weight layer (the production recommendation
    shape — one model, many streams); per-tenant results are
    bit-identical to N separate GnnSummaryEngine runs by the lattice
    argument plus the cohort scan's live-window mask (tools/gnn_ab.py
    asserts it), and `tenant_state_dict()` is interchangeable with the
    GNN engines' load_state_dict at equal buckets and feature width —
    the cohort→single and cohort→host demotion ladders are layout
    conversions, not translations.

    Deliberately a focused scheduler next to TenantCohort: admission
    cap and typed rejects, per-tenant queues and window accounting,
    the bucketed program cache — without the analytics cohort's
    residency/quarantine/autotune machinery, which is specialized to
    the 3-slab analytics carry."""

    def __init__(self, edge_bucket: int, vertex_bucket: int,
                 feature_dim: int = None, activation: str = None):
        from ..ops import gnn_window as gnn_ops
        from ..ops import pallas_window

        self._gnn = gnn_ops
        self._pallas_window = pallas_window
        self.eb = seg_ops.bucket_size(edge_bucket)
        self.vb = seg_ops.bucket_size(vertex_bucket)
        self.F = int(feature_dim if feature_dim
                     else knobs.get_int("GS_GNN_F"))
        self.act = str(activation if activation
                       else (knobs.get_str("GS_GNN_ACT") or "relu"))
        self._w_units, self._b_units = gnn_ops.snap_weights(
            *gnn_ops.default_weights(self.F), self.F)
        self._wdev = None  # refreshed lazily after set_weights
        self._bdev = None
        self._tenants: Dict[str, dict] = {}
        self._order: List[str] = []
        self._programs: Dict[tuple, object] = {}
        self._lock = threading.RLock()

    # -- membership ----------------------------------------------------
    def admit(self, tenant_id, features=None,
              feature_units=None) -> None:
        tid = str(tenant_id)
        with self._lock:
            if tid in self._tenants:
                raise TenantRejected("tenant %r already admitted"
                                     % tid, tid)
            if len(self._tenants) >= max_tenants():
                raise TenantRejected(
                    "cohort full: GS_TENANT_MAX=%d tenants admitted"
                    % max_tenants(), tid)
            if feature_units is not None:
                slab = np.asarray(feature_units, np.float32)  # gslint: disable=host-sync (host-input normalization: admit payloads are numpy)
                if slab.shape != (self.vb + 1, self.F):
                    raise ValueError(
                        "unit slab must be [vb+1=%d, F=%d]; got %s"
                        % (self.vb + 1, self.F, slab.shape))
            elif features is not None:
                slab = self._gnn.snap_features(features, self.vb,
                                               self.F)
            else:
                slab = np.zeros((self.vb + 1, self.F), np.float32)
            self._tenants[tid] = {
                "carry": jnp.asarray(slab),
                "src": [], "dst": [], "queued": 0,
                "windows_done": 0,
            }
            self._order.append(tid)
        telemetry.event("tenant_admitted", tenant=tid,
                        workload="gnn")

    def _tenant(self, tenant_id) -> dict:
        t = self._tenants.get(str(tenant_id))
        if t is None:
            raise TenantError("unknown tenant %r" % tenant_id,
                              str(tenant_id))
        return t

    # -- weights -------------------------------------------------------
    def set_weights(self, W, b=None) -> None:
        """Adopt the cohort's shared dense layer, snapped onto the
        lattice (ops/gnn_window.snap_weights — the bit-exactness
        contract). Never recompiles: weights ride every dispatch as
        broadcast arguments."""
        if b is None:
            b = np.zeros(self.F, np.float32)
        with self._lock:
            self._w_units, self._b_units = self._gnn.snap_weights(
                W, b, self.F)
            self._wdev = None

    def weights(self):
        return self._w_units.copy(), self._b_units.copy()

    # -- ingest --------------------------------------------------------
    def feed(self, tenant_id, src, dst) -> int:
        src = np.asarray(src, np.int32)  # gslint: disable=host-sync (host-input normalization: feed payloads are numpy)
        dst = np.asarray(dst, np.int32)  # gslint: disable=host-sync (host-input normalization: feed payloads are numpy)
        with self._lock:
            t = self._tenant(tenant_id)
            t["src"].append(src)
            t["dst"].append(dst)
            t["queued"] += len(src)
            return t["queued"]

    def queued_edges(self, tenant_id) -> int:
        return self._tenant(tenant_id)["queued"]

    def windows_done(self, tenant_id) -> int:
        return self._tenant(tenant_id)["windows_done"]

    # -- the dispatch --------------------------------------------------
    def _program(self, nb: int, wb: int):
        """One jitted cohort program per power-of-two (tenants,
        windows) bucket — ragged cohorts reuse O(log N × log W)
        programs. Wrapped by the compile watch / cost observatory as
        `gnn_cohort`; the analytic slab model registers at the same
        label (armed only) so ledger spans join a stated cost."""
        key = (nb, wb)
        fn = self._programs.get(key)
        if fn is None:
            import jax

            run = self._gnn.build_gnn_cohort_scan(
                self.eb, self.vb, self.F, self.act)
            fn = self._programs[key] = metrics.wrap_jit(
                "gnn_cohort", jax.jit(run))
            self._pallas_window.register_gnn_cost_model(
                self.eb, self.vb, self.F, nb=nb)
        return fn

    def _take_windows(self, t: dict, drain: bool):
        """Cut the tenant's queue at the window boundary: every FULL
        window now, the sub-window remainder only when draining
        (close) — the same cut GnnSummaryEngine.process makes, so the
        per-tenant window sequence matches the single-engine run
        edge-for-edge."""
        if not t["queued"]:
            return None
        src = np.concatenate(t["src"]) if len(t["src"]) != 1 \
            else t["src"][0]
        dst = np.concatenate(t["dst"]) if len(t["dst"]) != 1 \
            else t["dst"][0]
        take = len(src) if drain else (len(src) // self.eb) * self.eb
        if not take:
            return None
        t["src"] = [src[take:]] if take < len(src) else []
        t["dst"] = [dst[take:]] if take < len(src) else []
        t["queued"] = len(src) - take
        return seg_ops.window_stack(src[:take], dst[:take], self.eb,
                                    sentinel=self.vb)

    def _dispatch(self, batch: List[str], taken: dict,
                  out: Dict[str, list]) -> None:
        import jax

        nb = seg_ops.bucket_size(len(batch))
        wb = seg_ops.bucket_size(max(t[0] for t in taken.values()))
        src = np.full((nb, wb, self.eb), self.vb, np.int32)
        dst = np.full((nb, wb, self.eb), self.vb, np.int32)
        valid = np.zeros((nb, wb, self.eb), bool)
        carries = []
        for i, tid in enumerate(batch):
            num_w, s, d, v = taken[tid]
            src[i, :num_w] = s
            dst[i, :num_w] = d
            valid[i, :num_w] = v
            carries.append(self._tenants[tid]["carry"])
        zero = jnp.zeros((self.vb + 1, self.F), jnp.float32)
        carries.extend([zero] * (nb - len(batch)))
        if self._wdev is None:
            self._wdev = jnp.asarray(self._w_units)
            self._bdev = jnp.asarray(self._b_units)
        # padded rows/windows are all-invalid and therefore inert
        # (the round's empty-window-holds rule); their summary rows
        # are dropped below
        with telemetry.span("cohort.dispatch", tenants=len(batch),
                            windows=sum(t[0] for t in taken.values())
                            ) as sp:
            hs, ys = self._program(nb, wb)(
                jnp.stack(carries), self._wdev, self._bdev,
                jnp.asarray(src), jnp.asarray(dst),
                jnp.asarray(valid))
            maxf, active, csum, nmsg = (np.array(y) for y in ys)  # gslint: disable=host-sync (sanctioned finalize boundary: the cohort's ONE batched d2h per pump round)
        tags = telemetry.pop_dispatch_tags()
        metrics.attribute_dispatch(
            sp.elapsed,
            [(tid, int(np.sum(taken[tid][3]))) for tid in batch],  # gslint: disable=host-sync (numpy-on-numpy: the host-built validity stack)
            program=tags.get("program"), sig=tags.get("sig"))
        for i, tid in enumerate(batch):
            t = self._tenants[tid]
            t["carry"] = hs[i]
            num_w = taken[tid][0]
            rows = out.setdefault(tid, [])
            for w in range(num_w):
                rows.append({
                    "max_feat": int(maxf[i, w]),  # gslint: disable=host-sync (numpy-on-numpy after the batched d2h)
                    "active_vertices": int(active[i, w]),  # gslint: disable=host-sync (numpy-on-numpy after the batched d2h)
                    "feat_checksum": int(csum[i, w]),  # gslint: disable=host-sync (numpy-on-numpy after the batched d2h)
                    "msg_edges": int(nmsg[i, w]),  # gslint: disable=host-sync (numpy-on-numpy after the batched d2h)
                })
            edges = int(np.sum(taken[tid][3]))  # gslint: disable=host-sync (numpy-on-numpy: the host-built validity stack)
            if provenance.armed():
                vrows = taken[tid][3]
                for w in range(num_w):
                    lo = (t["windows_done"] + w) * self.eb
                    provenance.emit(
                        tenant=tid,
                        window=t["windows_done"] + w,
                        wal_lo=lo,
                        wal_hi=lo + int(np.sum(vrows[w])),  # gslint: disable=host-sync (numpy-on-numpy: the host-built validity stack)
                        tier="gnn_cohort", program="gnn_round",
                        summary=rows[len(rows) - num_w + w])
            t["windows_done"] += num_w
            metrics.mark_window(num_w, edges, engine="GnnTenantCohort",
                                tier="gnn_cohort", tenant=tid)

    def pump(self) -> Dict[str, list]:
        """Fold every tenant's FULL queued windows in one vmapped
        dispatch; returns {tenant_id: [summary, ...]} for the windows
        folded this round. Sub-window remainders stay queued for the
        next feed or close — window cuts are the engine's."""
        with self._lock:
            taken = {}
            batch = []
            for tid in self._order:
                got = self._take_windows(self._tenants[tid],
                                         drain=False)
                if got is not None:
                    taken[tid] = got
                    batch.append(tid)
            out: Dict[str, list] = {}
            if batch:
                self._dispatch(batch, taken, out)
            return out

    def close(self, tenant_id) -> List[dict]:
        """Drain the tenant's remainder (its final padded window, if
        any), remove it from the cohort, return the last summaries.
        The carry is gone afterwards — checkpoint first
        (tenant_state_dict) to keep the slab."""
        tid = str(tenant_id)
        with self._lock:
            t = self._tenant(tid)
            out: Dict[str, list] = {}
            got = self._take_windows(t, drain=True)
            if got is not None:
                self._dispatch([tid], {tid: got}, out)
            del self._tenants[tid]
            self._order.remove(tid)
            return out.get(tid, [])

    # -- checkpoint / demotion ladder ----------------------------------
    def tenant_state_dict(self, tenant_id) -> dict:
        """One tenant's slice in the GNN ENGINE checkpoint layout —
        loadable by GnnSummaryEngine/GnnHostEngine.load_state_dict at
        equal buckets and feature width (the demotion ladder)."""
        with self._lock:
            t = self._tenant(tenant_id)
            h = np.array(t["carry"])  # gslint: disable=host-sync (sanctioned checkpoint boundary: tenant_state_dict's one d2h)
            return {
                "edge_bucket": self.eb,
                "vertex_bucket": self.vb,
                "windows_done": int(t["windows_done"]),  # gslint: disable=host-sync (cohort bookkeeping int, no device value in sight)
                "closed_partial": False,
                "wal_offset": int(t["windows_done"]) * self.eb,  # gslint: disable=host-sync (cohort bookkeeping int, no device value in sight)
                "carry": (h,),
                "gnn": {
                    "feat_dim": self.F,
                    "act": self.act,
                    "weights": self._w_units.copy(),
                    "bias": self._b_units.copy(),
                },
            }

    def load_tenant_state_dict(self, tenant_id, state: dict) -> None:
        """Adopt an engine checkpoint as a tenant's slab (the
        promotion direction of the same ladder)."""
        g = state.get("gnn") or {}
        if (int(state["edge_bucket"]) != self.eb  # gslint: disable=host-sync (checkpoint payloads are host numpy, never device values)
                or int(state["vertex_bucket"]) != self.vb  # gslint: disable=host-sync (checkpoint payloads are host numpy, never device values)
                or int(g.get("feat_dim", self.F)) != self.F):  # gslint: disable=host-sync (checkpoint payloads are host numpy, never device values)
            raise ValueError(
                "checkpoint shape (eb=%s, vb=%s, F=%s) does not match "
                "cohort (eb=%d, vb=%d, F=%d)"
                % (state.get("edge_bucket"), state.get("vertex_bucket"),
                   g.get("feat_dim"), self.eb, self.vb, self.F))
        with self._lock:
            t = self._tenant(tenant_id)
            (h,) = state["carry"]
            t["carry"] = jnp.asarray(np.asarray(h, np.float32))  # gslint: disable=host-sync (host-input normalization: checkpoint payloads are numpy)
            t["windows_done"] = int(state.get("windows_done", 0))  # gslint: disable=host-sync (checkpoint payloads are host numpy, never device values)

    def demote(self, tenant_id):
        """Pop the tenant out of the cohort onto its own
        GnnSummaryEngine, seeded from its live slab — per-tenant
        isolation without touching the cohort's other streams.

        Returns ``(engine, folded, (src, dst))``: FULL queued windows
        are folded through the engine during the hand-off and their
        summaries returned (never dropped); the sub-window remainder
        comes back UNFOLDED — prepend it to the continued stream so
        window cuts stay edge-for-edge with the no-demotion timeline
        (the engine's process() would CLOSE a partial trailing
        window, which only a stream's end may do)."""
        tid = str(tenant_id)
        with self._lock:
            t = self._tenant(tid)
            state = self.tenant_state_dict(tid)
            pend_s = (np.concatenate(t["src"]) if t["src"]
                      else np.empty(0, np.int32))
            pend_d = (np.concatenate(t["dst"]) if t["dst"]
                      else np.empty(0, np.int32))
            del self._tenants[tid]
            self._order.remove(tid)
        eng = self._gnn.GnnSummaryEngine(
            self.eb, self.vb, feature_dim=self.F,
            activation=self.act)
        eng.load_state_dict(state)
        resilience.record_demotion(
            "tenant:%s" % tid, "gnn_cohort", "gnn_scan",
            int(state["windows_done"]), "operator", tenant=tid)  # gslint: disable=host-sync (checkpoint payloads are host numpy, never device values)
        full = (len(pend_s) // self.eb) * self.eb
        folded = eng.process(pend_s[:full], pend_d[:full]) \
            if full else []
        return eng, folded, (pend_s[full:], pend_d[full:])

    def tenants(self) -> List[str]:
        return list(self._order)

    def state(self, tenant_id) -> np.ndarray:
        """[vb, F] feature snapshot in lattice units."""
        with self._lock:
            t = self._tenant(tenant_id)
            return np.asarray(t["carry"])[: self.vb].copy()  # gslint: disable=host-sync (sanctioned snapshot boundary: the cohort's state() d2h)
