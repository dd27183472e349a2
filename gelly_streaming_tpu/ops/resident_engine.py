"""Resident-state window megakernel: device-resident summaries +
double-buffered ingest, ONE dispatch per many windows.

Every ladder earlier rounds recorded (not current numbers) shows the
same shape: device compute is cheap and the per-window host↔device
round trip is the wall — the device path sat ~1M edges/s while the native CPU tier does
8.8-10.3M on the 524K/32768 rows. The IO-aware GNN papers (PAPERS.md)
say the fix is restructuring for the memory hierarchy, not faster
math. The three ingredients already landed — compact ingress
(ops/compact_ingress), delta egress (ops/delta_egress), and the fused
scans (ops/scan_analytics, core/driver._build_snapshot_scan) — and
this module is the refactor that joins them:

- **ResidentState** — the summary carry (degree slab, DisjointSet
  label slab, double-cover slab) as a named pytree pinned on device.
  The fused program takes it with explicit donation
  (`jax.jit(..., donate_argnums=(0,))` where the backend honors
  donation), so each super-batch UPDATES the slabs in place instead of
  re-allocating + copying them per dispatch. On backends that ignore
  donation (CPU) the same program runs undonated — bit-identical, just
  without the aliasing win.
- **IngestRing** — a small (default two-slot, `GS_RESIDENT_SLOTS`)
  device-side ingest ring built on the existing ingress-pipeline
  worker pool (ops/ingress_pipeline.submit_prep): while super-batch N
  computes, slot N+1's prep AND h2d run on a worker, so the host's
  only steady-state jobs are topping up edge slabs and draining
  compacted deltas. The ring depth feeds the health plane's
  `gs_inflight_chunks` backlog gauge.
- **ResidentSummaryEngine** — the resident tier of the fused summary
  engine: the same scan body and checkpoint layout as
  StreamSummaryEngine, with compact-ingress decode fused into the
  donated program and `GS_RESIDENT_SPB` windows folded per dispatch
  (the autotuner's windows-per-superbatch arm explores rungs under
  it). Checkpoints stay engine-interchangeable: the resident carry is
  gathered at super-batch boundaries only, so kill→resume lands on
  the scan tier or the numpy host twin bit-exactly.
- The **driver integration** lives in core/driver.py: `resident` is a
  snapshot tier ABOVE `scan` in the demotion ladder
  (resident → scan → native → host), selected by `resolve_resident()`
  below when GS_RESIDENT pins it.

Exactness: the resident program is the SAME scan body as the tiers
below it, so window-by-window results are bit-identical by
construction and asserted by tools/resident_ab.py, the chaos resident
leg (tools/chaos_run.py), and tests/operations/test_resident.py.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import compact_ingress
from . import scan_analytics
from . import segment as seg_ops
from . import triangles as tri_ops
from ..utils import knobs
from ..utils import metrics


# ----------------------------------------------------------------------
# knobs / selection
# ----------------------------------------------------------------------
def resident_spb(eb: int) -> int:
    """Windows per super-batch of the resident megakernel: the
    GS_RESIDENT_SPB bucket, compile-size-capped on TPU backends
    (ops/triangles.COMPILE_CAP). Off-chip the knob stands as asked."""
    spb = seg_ops.bucket_size(knobs.get_int("GS_RESIDENT_SPB"))
    if jax.default_backend() == "tpu":
        cap = max(1, tri_ops.COMPILE_CAP // max(eb, 1))
        spb = min(spb, seg_ops.bucket_size(cap))
    return spb


def ring_slots() -> int:
    """Ingest-ring depth (GS_RESIDENT_SLOTS, default 2): super-batches
    prepped+transferred ahead of the dispatch cursor. 2 is the
    double-buffered form the tentpole names; 1 degenerates to the
    scan tier's single-lookahead prefetch."""
    return knobs.get_int("GS_RESIDENT_SLOTS")


def donation_supported() -> bool:
    """True when this backend honors buffer donation. CPU ignores
    donate_argnums (with a per-compile warning), so the resident
    programs only request donation where it actually aliases —
    results are bit-identical either way."""
    try:
        return jax.default_backend() in ("tpu", "gpu", "cuda", "rocm")
    except Exception:  # gslint: disable=except-hygiene (availability probe: selects the no-donation form, never correctness)
        return False


def donate_kw() -> dict:
    """jit kwargs of the resident programs: explicit donation of the
    carry argument where the backend honors it (the ResidentState
    slabs then update in place), empty elsewhere — CPU would warn per
    compile and ignore it."""
    return {"donate_argnums": (0,)} if donation_supported() else {}


def resolve_resident() -> bool:
    """Should the driver's batched snapshot path run the RESIDENT tier
    instead of `scan`? Only when GS_RESIDENT pins it `on`."""
    return knobs.get_str("GS_RESIDENT") == "on"


def resolve_resident_cohort() -> bool:
    """Should TenantCohort keep its carries stacked on DEVICE between
    rounds (the resident cohort tier: one donated `[N, ...]` carry
    pytree updated by one super-batch program, restacked only when
    membership changes) instead of restacking per-tenant host-visible
    carries every dispatch? Only when GS_COHORT_RESIDENT pins it
    `on`."""
    return knobs.get_str("GS_COHORT_RESIDENT") == "on"


# ----------------------------------------------------------------------
# ResidentState
# ----------------------------------------------------------------------
class ResidentState(NamedTuple):
    """The device-resident summary carry, as a named pytree (NamedTuple
    registers with jax automatically): the same three slabs — and the
    same layouts — every fused scan carries (degrees [vb+1] with the
    sentinel slot vb, min-label DisjointSet slab [vb+1], double-cover
    slab [2(vb+1)]), so a resident checkpoint is interchangeable with
    the scan/sharded/host-twin engines at equal buckets. Kept a
    DISTINCT type (not a bare tuple) so donation sites and tests can
    name exactly what is pinned on device."""

    degrees: object   # [vb+1]  int32, sentinel slot vb
    labels: object    # [vb+1]  int32, min-label union-find slab
    cover: object     # [2(vb+1)] int32, double-cover slab

    @classmethod
    def fresh(cls, vb: int, xp=np) -> "ResidentState":
        """Zero-stream state in the shared layout (host numpy by
        default; pass jax.numpy to build on device)."""
        return cls(xp.zeros(vb + 1, xp.int32),
                   xp.arange(vb + 1, dtype=xp.int32),
                   xp.arange(2 * (vb + 1), dtype=xp.int32))

    def to_host(self) -> "ResidentState":
        """Gather the slabs to host numpy (the super-batch-boundary
        d2h checkpoints and demotions re-enter from)."""
        return ResidentState(*(np.asarray(a) for a in self))  # gslint: disable=host-sync (sanctioned gather boundary: the resident state's ONE d2h at super-batch/checkpoint edges)

    @classmethod
    def grow(cls, old: "ResidentState", old_vb: int,
             new_vb: int) -> "ResidentState":
        """Re-lay the carried slabs out over a wider vertex bucket
        (host-side; the caller re-uploads). Degrees copy (the sentinel
        slot always holds 0 — masked padding never folds); labels keep
        their values (real min-labels are < old_vb, new slots are
        identity); cover labels pointing at/past the (+)-sentinel
        old_vb shift with the sentinel to new_vb (the (−) half and
        both sentinels live above it), mirroring
        core/driver._grow_cover for the vb+1-offset resident layout."""
        if new_vb < old_vb:
            raise ValueError("vertex bucket cannot shrink: %d -> %d"
                             % (old_vb, new_vb))
        old = old.to_host()
        shift = new_vb - old_vb
        deg = np.zeros(new_vb + 1, np.int32)
        deg[:old_vb] = old.degrees[:old_vb]
        lab = np.arange(new_vb + 1, dtype=np.int32)
        lab[:old_vb] = old.labels[:old_vb]
        cov = np.arange(2 * (new_vb + 1), dtype=np.int32)
        shifted = np.where(old.cover >= old_vb, old.cover + shift,
                           old.cover).astype(np.int32)
        cov[:old_vb] = shifted[:old_vb]
        cov[new_vb + 1:new_vb + 1 + old_vb] = shifted[
            old_vb + 1:old_vb + 1 + old_vb]
        # sentinel slots stay identity: they only ever union with each
        # other (invalid edges map to the (sent+, sent−) pair), so
        # their labels never reach a real slot
        return cls(deg, lab, cov)


# ----------------------------------------------------------------------
# IngestRing
# ----------------------------------------------------------------------
class IngestRing:
    """The resident tier's bounded ingest ring over the shared
    ingress-pipeline worker pool: `submit(fn, key, item)` schedules
    one super-batch's prep+h2d (fn runs WHOLLY on a worker and returns
    the device payload), `pop(key)` hands the payload back in
    submission order. While super-batch N computes on device, slot N+1
    fills — the double-buffered h2d stage of the tentpole. Depth is
    `GS_RESIDENT_SLOTS` (2 = classic double buffering); with the
    pipeline disabled (forced_sync / GS_STREAM_PREFETCH=0 / zero
    workers) submit() declines and the caller builds inline — same
    payloads, the worker-pool determinism contract.

    The filled-slot count feeds the health plane's
    `gs_inflight_chunks` gauge (utils/metrics): the ring IS the
    resident tier's in-flight backlog."""

    def __init__(self, slots: Optional[int] = None):
        from collections import deque

        self.slots = max(1, slots if slots is not None
                         else ring_slots())
        self._q = deque()

    def __len__(self) -> int:
        return len(self._q)

    @property
    def full(self) -> bool:
        return len(self._q) >= self.slots

    def _gauge(self) -> None:
        metrics.gauge_set("gs_inflight_chunks", len(self._q))

    def submit(self, fn, key, item) -> bool:
        """Schedule fn(item) on the pool under `key`; False when the
        ring is full or pipelining is disabled (caller runs inline)."""
        from . import ingress_pipeline

        if self.full:
            return False
        fut = ingress_pipeline.submit_prep(fn, item)
        if fut is None:
            return False
        self._q.append((key, fut, item))
        self._gauge()
        return True

    def pop(self, key):
        """(future, item) of the ring head iff it is `key`, else None
        (out-of-order pops are a caller bug — the ring is FIFO by the
        scan carry's sequential-dispatch contract)."""
        if self._q and self._q[0][0] == key:
            _k, fut, item = self._q.popleft()
            self._gauge()
            return fut, item
        return None

    def drain(self) -> None:
        """Cancel everything still queued (error paths); workers
        already running simply complete into dropped futures."""
        while self._q:
            _k, fut, _item = self._q.popleft()
            fut.cancel()
        self._gauge()


# ----------------------------------------------------------------------
# Mailbox
# ----------------------------------------------------------------------
class Mailbox:
    """Small bounded thread-safe mailbox — the hand-off primitive of
    the async serving pump (core/serve.py). Two uses there: the
    ingest→pump wake channel (feed() posts, the pump thread blocks in
    `get`) and the per-connection `subscribe` delivery queues (the
    pump posts WindowResult rows, the connection thread drains; a
    full queue returns False from put() so the slow subscriber is
    SHED instead of wedging the pump — the same never-block-the-pump
    contract as GS_SERVE_IDLE_S).

    `close()` wakes every blocked `get` permanently (they return
    None); items already queued still drain first. All methods are
    safe from any thread."""

    def __init__(self, capacity: int = 256):
        import threading
        from collections import deque

        self.capacity = max(1, int(capacity))
        self._q = deque()
        self._cv = threading.Condition()
        self._closed = False
        self.dropped = 0  # put() refusals (the shed counter's feed)

    def __len__(self) -> int:
        with self._cv:
            return len(self._q)

    @property
    def closed(self) -> bool:
        with self._cv:
            return self._closed

    def put(self, item) -> bool:
        """Enqueue without ever blocking: False when the mailbox is
        full or closed (the caller owns the shed)."""
        with self._cv:
            if self._closed or len(self._q) >= self.capacity:
                self.dropped += 1
                return False
            self._q.append(item)
            self._cv.notify()
            return True

    def get(self, timeout: Optional[float] = None):
        """Dequeue one item, blocking up to `timeout` seconds (forever
        when None). Returns None on timeout or when the mailbox was
        closed and drained."""
        with self._cv:
            while not self._q:
                if self._closed:
                    return None
                if not self._cv.wait(timeout):
                    return None
            return self._q.popleft()

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()


# ----------------------------------------------------------------------
# ResidentSummaryEngine
# ----------------------------------------------------------------------
class ResidentSummaryEngine(scan_analytics.StreamSummaryEngine):
    """The resident tier of the fused summary engine: the
    StreamSummaryEngine scan body + chunk loop with (a) the carry held
    as a donated device-resident ResidentState across super-batches,
    (b) `GS_RESIDENT_SPB` windows per dispatch (the tuner's
    windows-per-superbatch arm explores rungs under it), (c)
    compact-ingress decode fused into the donated program whenever the
    vertex bucket fits uint16, and (d) the ingest ring bounded at
    GS_RESIDENT_SLOTS (INGEST_SLOTS → ops/ingress_pipeline
    .run_pipeline) so slot N+1's prep+h2d fills while super-batch N
    computes. Summaries, window cuts, and the checkpoint layout are
    bit-identical to every other summary engine — kill→resume lands on
    the scan tier or the numpy host twin exactly
    (tests/test_checkpoint_roundtrip.py)."""

    METRICS_TIER = "resident"
    TUNER_FAMILY = "resident"
    AUTOTUNE = True
    TUNABLE_INGRESS = False  # the wire format is fused at build

    def __init__(self, edge_bucket: int, vertex_bucket: int,
                 k_bucket: int = 0, ingress: str = None,
                 superbatch: int = None):
        self._superbatch = superbatch
        if ingress is None:
            # the fused decode is the point: compact whenever the
            # bucket fits uint16, standard only as the fallback
            vb = seg_ops.bucket_size(vertex_bucket)
            ingress = ("compact" if compact_ingress.supports(vb)
                       else "standard")
        super().__init__(edge_bucket, vertex_bucket,
                         k_bucket=k_bucket, ingress=ingress)
        # super-batch depth replaces the scan tier's 64-window chunk;
        # programs rebuilt donated (and re-capped) on vb growth
        self.MAX_WINDOWS = seg_ops.bucket_size(
            superbatch if superbatch else resident_spb(self.eb))
        self._rebuild_programs()

    @property
    def INGEST_SLOTS(self):
        # live read: tests and tools flip the knob mid-process
        return ring_slots()

    def _rebuild_programs(self) -> None:
        """(Re)wrap the scan body as the donated resident programs —
        one standard-wire, one compact-wire twin with the device-side
        decode fused in front of the scan."""
        body = self._body

        def run(carry, src_w, dst_w, valid_w):
            return jax.lax.scan(body, carry, (src_w, dst_w, valid_w))

        # wrap_jit also feeds the cost observatory (utils/costmodel):
        # the resident super-batch program's FLOPs/bytes land in the
        # cost registry per signature, and armed dispatches tag their
        # spans program="resident_fused"/sig for the attribution join
        # — "resident_pallas" when the selected body is the fused
        # window megakernel (ops/pallas_window), so the observatory
        # attributes the new program distinctly on this tier too
        self._pallas = bool(getattr(body, "pallas_window", False))
        self._run = metrics.wrap_jit(
            "resident_pallas" if self._pallas else "resident_fused",
            jax.jit(run, **donate_kw()))
        self._run_c = None
        if self.ingress == "compact":
            self._ensure_compact_fn()

    def _ensure_compact_fn(self):
        """Compact twin of the donated program: widen uint16 ids +
        rebuild the suffix mask ON DEVICE (the one shared decode,
        compact_ingress.widen_stack) fused into the same donated
        scan — or, when the Pallas megakernel is selected, fused one
        level deeper (the compact body decodes per tile INSIDE the
        kernel, ops/pallas_window), still under the same donation."""
        if self._run_c is None:
            eb_, vb_, body = self.eb, self.vb, self._body

            if getattr(body, "pallas_window", False):
                from . import pallas_window

                run_pc = pallas_window.maybe_compact_scan_fn(
                    eb_, vb_, self.kb, "resident_pallas_compact",
                    jit_kwargs=donate_kw())
                if run_pc is not None:
                    self._run_c = run_pc
                    return self._run_c

            def run_c(carry, s16, d16, nvalid):
                s_w, d_w, valid_w = compact_ingress.widen_stack(
                    s16, d16, nvalid, eb_, vb_)
                return jax.lax.scan(body, carry, (s_w, d_w, valid_w))

            self._run_c = metrics.wrap_jit(
                "resident_fused_compact",
                jax.jit(run_c, **donate_kw()))
        return self._run_c

    def resident_state(self) -> ResidentState:
        """The live carry as a named ResidentState (device arrays;
        `.to_host()` gathers)."""
        return ResidentState(*self._carry)

    def grow_vertex_bucket(self, vertex_bucket: int) -> None:
        """Adopt a wider vertex bucket MID-STREAM: the carried slabs
        re-lay out (ResidentState.grow), the donated programs rebuild
        at the new shapes, and the live tuner RE-KEYS instead of being
        discarded (ops/autotune.DispatchTuner.rekey) — the incumbent
        windows-per-superbatch survives as the prior and the persisted
        cache re-seeds the new key, so O(log V) bucket doublings never
        reset the learned dispatch configuration (the ISSUE-9
        arm-freezing fix, pinned by
        tests/operations/test_resident.py)."""
        new_vb = seg_ops.bucket_size(vertex_bucket)
        if new_vb <= self.vb:
            return
        grown = ResidentState.grow(self.resident_state(), self.vb,
                                   new_vb)
        old_eb, old_kb = self.eb, self.kb
        cursor = self.windows_done
        closed = self._closed_partial
        tuner = getattr(self, "_tuner", None)
        timers = self.stage_timers
        ck_path, ck_policy = self._ckpt_path, self._ckpt_policy
        # an explicit construction-time pin survives the rebuild (the
        # A/B tools must keep measuring the wire they pinned) — unless
        # the pinned compact wire turned lossy at the new bucket, in
        # which case the pin degrades to standard rather than raising
        pin = self.ingress if getattr(self, "_pinned_ingress",
                                      False) else None
        if pin == "compact" and not compact_ingress.supports(new_vb):
            pin = "standard"
        self.__init__(edge_bucket=old_eb, vertex_bucket=new_vb,
                      k_bucket=old_kb, ingress=pin,
                      superbatch=self._superbatch)
        self._carry = tuple(jnp.asarray(a) for a in grown)
        self.windows_done = cursor
        self._closed_partial = closed
        self.stage_timers = timers
        self._ckpt_path, self._ckpt_policy = ck_path, ck_policy
        if tuner is not None:
            # re-key-instead-of-discard (the driver's _ensure_buckets
            # discipline): learned state carries into the new identity.
            # The ingress arm re-pins to the REBUILT engine's wire
            # format — growing past the uint16 ceiling switches the
            # fused decode to standard, and a surviving compact arm
            # would be lossy at the new bucket.
            wbm = self.MAX_WINDOWS
            wbs = sorted({max(1, wbm // 4), max(1, wbm // 2), wbm})
            inc = dict(tuner.incumbent)
            if inc.get("wb") not in wbs:
                inc["wb"] = wbm
            inc["ingress"] = self.ingress
            tuner.rekey(
                "%s:eb=%d:vb=%d" % (self.TUNER_FAMILY, self.eb,
                                    self.vb),
                space={"wb": wbs, "ingress": [self.ingress]},
                initial=inc)
            self._tuner = tuner
