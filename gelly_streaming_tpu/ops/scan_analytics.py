"""Fused multi-analytics streaming scan: every analytic, every window,
ONE device dispatch per chunk.

The driver's per-window calls (core/driver.py) pay one host↔device
round trip per window per analytic — the dominant cost wherever a
dispatch is slow. This engine
generalizes `count_stream`'s batching to the full analytics suite: a
`lax.scan` carries (degree vector, CC labels, double-cover labels)
across a `[W, eb]` stack of windows and emits per-window summary
scalars, so an entire chunk of stream costs one h2d of COO, one fused
program, one d2h of `[W]` summaries.

Summaries per window (all cumulative over the stream so far, matching
the carried-state semantics of the reference's continuous aggregates):
  max_degree      — max running degree (SimpleEdgeStream.java:465-482)
  num_components  — count of touched roots (ConnectedComponents)
  odd_cycle       — any odd cycle seen (BipartitenessCheck)
  triangles       — exact count of THIS window (WindowTriangles)
  tri_overflow    — hub outran the K bucket (host recounts exactly)

Full per-vertex snapshots remain the driver's job; this engine is the
CHIP-side throughput path. On CPU backends it gains little —
measured, not argued (FUSED_BREAKDOWN.json, tools/
profile_fused_breakdown.py): the triangle stage compiled INTO this
scan is the XLA stream program, which a single core runs ~15x slower
than the numpy host twin, while the dispatch latency fusion saves is
~µs off-chip. One program
per chunk pays when dispatches are costly and the MXU/VPU runs the
intersect — the regime this engine was built for.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ingress_pipeline
from . import segment as seg_ops
from . import triangles as tri_ops
from . import unionfind
from ..utils import checkpoint
from ..utils import faults
from ..utils import latency
from ..utils import metrics
from ..utils import provenance
from ..utils import sanitize as sanitize_mod
from ..utils import telemetry
from ..utils import wal as wal_mod


def _build_scan(eb: int, vb: int, kb: int, pallas_ok: bool = True):
    """Scan body over fixed buckets. Cover layout: (+) side = v,
    (−) side = vb+1+v, so the shared sentinel slot vb (edge padding)
    maps to the two cover sentinels (vb, 2vb+1) and never touches real
    slots.

    When the fused Pallas window megakernel is selected
    (ops/pallas_window.resolve_pallas_window — GS_PALLAS_WINDOW=on)
    AND its build/trace
    probe succeeds, the returned body is the megakernel instead: one
    VMEM-tiled pallas_call per window computing ALL analytics from a
    single HBM read of the edge slab, same carry layout, same
    per-window outputs, bit-identical by construction. `pallas_ok`
    lets callers whose composition the kernel doesn't support opt
    out — build_cohort_scan's vmap form needs a pure-XLA body (its
    tenant-axis Pallas variant is its own kernel with the tenant axis
    as a grid dimension, ops/pallas_window.maybe_cohort_body, under
    its own pin)."""
    if pallas_ok:
        from . import pallas_window

        pbody = pallas_window.maybe_window_body(eb, vb, kb)
        if pbody is not None:
            return pbody
    sent = vb
    # pallas_ok propagates INTO the embedded triangle counter: a
    # pallas_ok=False caller (the vmapped cohort) must get a pure-XLA
    # body all the way down — a pallas_call smuggled in through
    # tri_body would be vmapped over the tenant axis anyway
    tri_body = tri_ops.build_window_counter(vb, kb,
                                            pallas_ok=pallas_ok)

    def body(carry, xs):
        deg, labels, cover = carry
        src, dst, valid = xs
        s = jnp.where(valid, src, sent)
        d = jnp.where(valid, dst, sent)
        ones = jnp.where(valid, 1, 0)

        deg = deg + (jax.ops.segment_sum(ones, s, vb + 1)
                     + jax.ops.segment_sum(ones, d, vb + 1))
        max_degree = jnp.max(deg[:vb])

        labels = unionfind.cc_fixpoint(labels, s, d)
        touched = deg[:vb] > 0
        num_components = jnp.sum(
            touched & (labels[:vb] == jnp.arange(vb)), dtype=jnp.int32)

        cover = unionfind.cc_fixpoint(
            cover, jnp.concatenate([s, s + (vb + 1)]),
            jnp.concatenate([d + (vb + 1), d]))
        odd = jnp.any(touched & (cover[:vb] == cover[vb + 1:2 * vb + 1]))

        tri_count, tri_overflow = tri_body(src, dst, valid)

        return (deg, labels, cover), (
            max_degree, num_components, odd, tri_count, tri_overflow)

    return body


def build_cohort_scan(eb: int, vb: int, kb: int, nb: int = None):
    """The multi-tenant cohort entry (core/tenancy.py): the SAME scan
    body as every fused summary engine, lifted over a leading tenant
    axis — carries are [N, ...] slabs, edge slabs are [N, W, eb], and
    one dispatch folds one window cohort across all N streams (the
    trick the sharded path already plays for panes, applied to
    tenants). Rows are independent by construction: a padded tenant
    row (all-invalid windows) folds as a no-op against its carry, so
    per-tenant results are bit-identical to N separate
    StreamSummaryEngine runs — the parity contract tools/tenancy_ab.py
    and tests/test_tenancy.py assert window by window.

    Two lowerings, same contract:

    - default: `jax.vmap` of the pure-XLA scan body over the tenant
      axis (pallas_ok=False all the way down — a pallas_call smuggled
      into the vmapped body would be batch-lowered, not
      tenant-gridded).
    - when `nb` is given AND the TENANT-AXIS Pallas megakernel
      clears its own gate+probe (ops/pallas_window.maybe_cohort_body
      — GS_COHORT_PALLAS=on), the window loop scans ONE pallas_call
      whose second grid dimension is the tenant axis: the whole
      cohort's carries VMEM-resident, one slab pass per window round.
      Refusal (gate off, VMEM budget, trace probe) degrades to the
      vmap form with a durable `selection.fallback` event — digests
      are bit-identical either way."""
    if nb is not None:
        from . import pallas_window

        cbody = pallas_window.maybe_cohort_body(eb, vb, kb, nb)
        if cbody is not None:
            def run_pallas(carries, src, dst, valid):
                # [N, W, eb] -> [W, N, eb]: the window axis is the
                # scan axis, the tenant axis rides into the kernel
                xs = tuple(jnp.moveaxis(a, 0, 1)
                           for a in (src, dst, valid))
                carries, ys = jax.lax.scan(cbody, carries, xs)
                # per-window outputs come back [W, N] — restore the
                # vmap form's [N, W] leading tenant axis
                return carries, tuple(jnp.moveaxis(y, 0, 1)
                                      for y in ys)

            run_pallas.pallas_window = True
            return run_pallas

    body = _build_scan(eb, vb, kb, pallas_ok=False)

    def one_tenant(carry, src_w, dst_w, valid_w):
        return jax.lax.scan(body, carry, (src_w, dst_w, valid_w))

    def run(carries, src, dst, valid):
        return jax.vmap(one_tenant)(carries, src, dst, valid)

    return run


class SummaryEngineBase:
    """Shared scaffolding of the single-chip and sharded fused scan
    engines: carried-state reset/snapshot, the chunk loop, the
    partial-window-must-be-final guard, and summary assembly.
    Subclasses provide `_dispatch_async` (enqueue one [W, eb] chunk
    against the device-resident carry, returning raw un-materialized
    outputs), `_materialize` (d2h those outputs into the writable
    summary tuple (mdeg, ncomp, odd, tri, b_ovf, k_ovf)), and `_redo`
    (exact triangle recount of one overflowing window)."""

    MAX_WINDOWS = 64
    # tier label of this engine's mark_window health-plane marks —
    # subclasses on another tier (sharded mesh, numpy host twin)
    # override it so /healthz never claims the single-chip scan tier
    # for a demoted or mesh-resident stream
    METRICS_TIER = "fused_scan"
    # stream-chunk wire format; StreamSummaryEngine takes a compact
    # pin, the sharded engine keeps the standard format (its chunks
    # are mesh-sharded)
    ingress = "standard"
    # online dispatch autotuning (ops/autotune.py): only the
    # single-chip engine opts in — the sharded engine's jit programs
    # have no AOT warm cache, so an arm change there would compile
    # mid-measurement
    AUTOTUNE = False
    TUNABLE_INGRESS = False
    # cache-identity prefix of the dispatch tuner (ops/autotune): the
    # resident engine re-keys its own family so its learned
    # windows-per-superbatch never cross-seeds the scan tier's
    TUNER_FAMILY = "fused_scan"
    # max prepped+transferred chunks in flight ahead of dispatch; None
    # = the global GS_PIPELINE_INFLIGHT. The resident engine narrows
    # it to its GS_RESIDENT_SLOTS ingest ring.
    INGEST_SLOTS = None

    def reset(self) -> None:
        self._closed_partial = False
        self.windows_done = 0  # resume cursor (checkpoint/resume)
        if not hasattr(self, "stage_timers"):
            # per-stage pipeline counters (ops/ingress_pipeline);
            # survive reset() so a timed run's snapshot is cumulative
            # until explicitly .reset()
            self.stage_timers = ingress_pipeline.StageTimers()
        if not hasattr(self, "_ckpt_path"):
            # auto-checkpoint config survives reset() like the timers
            self._ckpt_path = None
            self._ckpt_policy = None
        if not hasattr(self, "_lat_lane"):
            # latency-plane lane of this engine's windows; a cohort
            # demotion re-points it at the tenant (core/tenancy),
            # clears _lat_admit (the cohort's feed() already stamped
            # admission at the serving boundary) and mirrors the
            # cohort's delivery deferral per pump
            self._lat_lane = None
            self._lat_admit = True
            self._lat_defer = False
        # per-chunk stage-boundary stamps keyed by chunk start
        # (filled by the dispatch closure, drained by
        # _finalize_summaries). Cleared on EVERY reset — a stamp
        # stranded by a mid-call failure must never join a later
        # run's window at the same chunk offset.
        self._lat_stamps = {}
        # cumulative fed edges incl. sanitizer rejects — the DLQ's
        # source-offset domain for this engine's admission boundary
        self._fed_edges = 0
        if not hasattr(self, "_wal"):
            # write-ahead journal config survives reset() too
            self._wal = None
            self._wal_dir = None
            self._wal_tenant = "engine"
            # GS_WAL_RETAIN bookkeeping (utils/wal.RetentionCursor):
            # remembers the last two flushed checkpoint offsets so
            # truncation never outruns a rotation-fallback recovery
            self._wal_retention = wal_mod.RetentionCursor()
        elif self._ckpt_policy is not None:
            # re-anchor the cadence with the rewound cursor: a stale
            # high-water mark would suppress every due() until the new
            # stream re-passed it (same fix as the driver's reset)
            self._ckpt_policy.mark(0)
        self._carry = self._init_carry()

    def _init_carry(self):
        """Fresh carried state in the shared layout (degrees [vb+1],
        cc labels [vb+1], double cover [2(vb+1)]; sentinel slot vb).
        Device engines carry jnp arrays; the numpy host twin
        (parallel/host_twin.HostSummaryEngine) overrides this and
        `_to_carry` to stay off the device entirely — the layout (and
        therefore checkpoint interchangeability) is identical."""
        return (
            jnp.zeros(self.vb + 1, jnp.int32),
            jnp.arange(self.vb + 1, dtype=jnp.int32),
            jnp.arange(2 * (self.vb + 1), dtype=jnp.int32),
        )

    def _to_carry(self, a):
        """Lift one restored checkpoint leaf into this engine's carry
        representation (device array by default; numpy on the host
        twin)."""
        return jnp.asarray(a)

    def state(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(degrees[vb], cc_labels[vb], odd[vb]) snapshots."""
        deg, labels, cover = (np.asarray(x) for x in self._carry)  # gslint: disable=host-sync (sanctioned snapshot boundary: the engine's state() d2h)
        odd = cover[: self.vb] == cover[self.vb + 1: 2 * self.vb + 1]
        return deg[: self.vb], labels[: self.vb], odd

    # ------------------------------------------------------------------
    # checkpoint / resume (utils/checkpoint.py)
    # ------------------------------------------------------------------
    def state_dict(self) -> dict:
        """Full resumable state: the carried vectors (d2h'd to host
        arrays) plus the windows_done cursor. The layout is the
        carry's own, shared by the single-chip and sharded engines, so
        checkpoints are engine-interchangeable at equal buckets. When
        the online tuner is live, its learned state rides along so a
        resumed stream keeps its configuration."""
        carry = tuple(np.array(x) for x in self._carry)  # gslint: disable=host-sync (sanctioned checkpoint boundary: state_dict's one d2h)
        state = {
            "edge_bucket": self.eb,
            "vertex_bucket": self.vb,
            "windows_done": int(self.windows_done),
            "closed_partial": bool(self._closed_partial),
            # journal offset at this finalized-window boundary (edges
            # folded into the carry): resume_and_replay() re-feeds the
            # WAL strictly past it (DESIGN.md §18)
            "wal_offset": int(self.windows_done) * self.eb,
            "carry": carry,
        }
        if getattr(self, "_tuner", None) is not None:
            state["autotune"] = self._tuner.state_dict()
        return state

    def load_state_dict(self, state: dict) -> None:
        if state["edge_bucket"] != self.eb \
                or state["vertex_bucket"] != self.vb:
            raise ValueError(
                "bucket mismatch: checkpoint was taken at eb=%d vb=%d, "
                "engine runs eb=%d vb=%d — count-based windows are cut "
                "by eb, so resuming across buckets would shift every "
                "window boundary" % (state["edge_bucket"],
                                     state["vertex_bucket"],
                                     self.eb, self.vb))
        self.windows_done = int(state["windows_done"])  # gslint: disable=host-sync (checkpoint payloads are host numpy, never device values)
        self._closed_partial = bool(state["closed_partial"])
        woff = state.get("wal_offset")
        if woff is not None and int(woff) > self.windows_done * self.eb:
            raise ValueError(
                "checkpoint wal_offset %d exceeds its own window "
                "coverage (%d windows x eb=%d)" % (
                    int(woff), self.windows_done, self.eb))
        self._carry = tuple(self._to_carry(a) for a in state["carry"])
        # .get: checkpoints from before the autotune key (and engines
        # with the tuner off) restore without it
        if state.get("autotune") is not None and self.AUTOTUNE:
            from . import autotune

            if autotune.enabled():
                self._ensure_tuner().load_state_dict(state["autotune"])

    def enable_auto_checkpoint(self, path: str,
                               every_n_windows: int = 16,
                               every_seconds: float = 0.0,
                               policy=None) -> None:
        """Auto-snapshot on a CheckpointPolicy cadence, evaluated at
        chunk DISPATCH boundaries (where the device carry exactly
        covers the finalized-or-dispatched prefix) and flushed to disk
        only once every covered window's summary has been handed to
        the caller — the driver's staged at-least-once contract."""
        if policy is None:
            policy = checkpoint.CheckpointPolicy(
                every_n_windows=max(0, every_n_windows),
                every_seconds=every_seconds)
        if not policy.enabled():
            raise ValueError("checkpoint policy has no trigger enabled")
        self._ckpt_path = path
        self._ckpt_policy = policy

    def try_resume(self, path: str) -> bool:
        """Restore from the newest intact checkpoint generation
        (rotation fallback on corruption — utils/checkpoint.
        load_latest); False when nothing usable exists. After a True
        return, feed the stream from `resume_offset()` edges in."""
        import warnings

        try:
            got = checkpoint.load_latest(path)
        except checkpoint.CheckpointCorrupt as e:
            warnings.warn(f"{e}; no intact generation — starting fresh")
            return False
        if got is None:
            return False
        state, used = got
        if used != path:
            warnings.warn(
                f"checkpoint {path!r} is corrupt; resumed from the "
                f"rotated previous generation {used!r}")
        self.load_state_dict(state)
        # durable stamp: the resume point pairs with the pre-kill
        # spans under the process's one trace ID, so a crash/resume
        # reads as a single timeline in the run ledger
        telemetry.event("resume", durable=True, component="engine",
                        path=used, windows_done=self.windows_done)
        return True

    def enable_wal(self, directory: str,
                   tenant: str = "engine") -> bool:
        """Journal every process() call's edges under `directory`
        BEFORE they fold (utils/wal.py), making this live-fed engine
        a replayable source: after a kill, `resume_and_replay()`
        restores the newest checkpoint and re-feeds the journal
        suffix, reproducing the lost windows bit-exactly. Returns
        False (a no-op) under the GS_WAL=0 kill switch."""
        if not wal_mod.enabled():
            return False
        self._wal_dir = directory
        self._wal_tenant = str(tenant)
        self._wal = wal_mod.WriteAheadLog(directory)
        return True

    def seal_wal(self) -> None:
        """Durably close the journal (the clean-drain marker)."""
        if self._wal is not None:
            self._wal.seal()

    def resume_and_replay(self, ckpt_path: str) -> list:
        """Kill recovery for a journal-armed engine: try_resume the
        newest checkpoint, then replay the journal suffix past the
        checkpointed `wal_offset` through process(). Returns the
        replayed windows' summaries — everything the crashed process
        computed (or had accepted) but never delivered, bit-identical
        to the fault-free run's same windows."""
        self.try_resume(ckpt_path)
        if self._wal_dir is None:
            return []
        off = self.resume_offset()
        parts_s, parts_d = [], []
        for tid, _start, src, dst, ts in wal_mod.replay(
                self._wal_dir, {self._wal_tenant: off}):
            if tid != self._wal_tenant:
                continue
            parts_s.append(src)
            parts_d.append(dst)
            # re-seed the latency plane's admission marks with the
            # journaled ORIGINAL stamps (latency.window records of the
            # replayed windows report honest, larger latency)
            latency.on_replay(self._lat_lane or self._wal_tenant,
                              len(src), ts)
        edges = sum(len(s) for s in parts_s)
        telemetry.event("wal_replayed", durable=True,
                        component="engine", dir=self._wal_dir,
                        edges=edges)
        metrics.counter_inc("gs_wal_replayed_edges_total", edges)
        if not edges:
            return []
        # suspend journaling for the replay feed: these edges are
        # already in the journal — re-appending would double them on
        # the NEXT recovery. Admission is likewise suspended: the
        # marks above already carry the ORIGINAL stamps.
        live, self._wal = self._wal, None
        admit_prev, self._lat_admit = self._lat_admit, False
        try:
            return self.process(np.concatenate(parts_s),
                                np.concatenate(parts_d))
        finally:
            self._wal = live
            self._lat_admit = admit_prev

    def resume_offset(self) -> int:
        """Edges already folded into the carried state: a resumed
        caller feeds `src[offset:], dst[offset:]` and gets exactly the
        uninterrupted run's remaining summaries (the windows_done
        cursor — windows are count-based eb-sized)."""
        return self.windows_done * self.eb

    def _h2d(self, args):
        """Transfer one chunk's prepped host stacks to device arrays
        (the pipeline's timed h2d stage; the sharded engine overrides
        with its mesh-sharded device_put)."""
        return tuple(jnp.asarray(a) for a in args)

    def _dispatch_async(self, s, d, valid):
        """Enqueue one chunk (updating the device-resident carry) and
        return the raw per-window outputs WITHOUT materializing them —
        process()'s depth-2 pipeline defers the d2h to _materialize so
        it overlaps the next chunk's execution."""
        raise NotImplementedError

    def _dispatch_async_compact(self, s16, d16, nvalid):
        """Compact-wire-format twin of _dispatch_async (uint16 stacks +
        per-window valid counts; widening fused into the scan
        program). Only engines whose `ingress` resolves compact need
        it."""
        raise NotImplementedError

    def _materialize(self, raw):
        """d2h one chunk's raw outputs into writable numpy arrays
        (mdeg, ncomp, odd, tri, b_ovf, k_ovf)."""
        raise NotImplementedError

    def _redo(self, src, dst, b_ovf: int, k_ovf: int) -> int:
        raise NotImplementedError

    def warm_fallback(self) -> None:
        """Compile the overflow-recount path's base program so a skewed
        stream's first hub window doesn't compile mid-measurement."""
        self._redo(np.array([0]), np.array([1]), 1, 1)  # gslint: disable=host-sync (host constants, not a device sync)

    def process(self, src: np.ndarray, dst: np.ndarray) -> list:
        """Fold the stream's `edge_bucket`-sized windows; returns one
        summary dict per window.

        A call whose length is not a multiple of `edge_bucket` CLOSES
        its partial trailing window (count-based tumbling semantics),
        so it must be the stream's final call — feed mid-stream chunks
        in edge_bucket multiples (enforced below)."""
        lat = latency.enabled()
        t_admit = latency.clock() if lat else 0.0
        metrics.on_stream_start(type(self).__name__)
        # "admit" fault site + armed sanitizer: the engine's admission
        # boundary mirrors the cohort's feed() — garbage ids peel off
        # to the dead-letter journal BEFORE the journal/fold see them;
        # GS_SANITIZE=off (default) skips straight to the legacy path
        got = faults.fire("admit", (self._wal_tenant, src, dst))
        if got is not None:
            _t, src, dst = got
        if sanitize_mod.enabled():
            try:
                rep = sanitize_mod.sanitize(
                    src, dst, self.vb, tenant=self._wal_tenant,
                    origin="engine", offset=self._fed_edges,
                    dlq=sanitize_mod.resolve_dlq())
            except sanitize_mod.BatchRejected as e:
                self._fed_edges += e.size
                raise
            self._fed_edges += rep.accepted + rep.rejected
            src, dst = rep.src, rep.dst
        else:
            self._fed_edges += len(np.atleast_1d(np.asarray(src)))  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
        src = np.asarray(src, np.int32)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
        dst = np.asarray(dst, np.int32)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
        n = len(src)
        if n == 0:
            return []
        if self._closed_partial:
            raise ValueError(
                "a previous process() call closed a partial window "
                "(length not a multiple of edge_bucket); reset() before "
                "feeding more of the stream")
        if self._wal is not None:
            # journal-before-fold: the edges are durable before any
            # dispatch touches the carry, so a kill mid-call replays
            # them from resume_offset() (the wal_enqueue fault site
            # pins the append→fold gap in tests). Armed, the batch's
            # admission stamp rides the ts column so replayed windows
            # keep their original admission time.
            self._wal.append(
                self._wal_tenant, src, dst,
                np.full(n, latency.admit_ns(t_admit), np.int64)
                if lat else None)
            faults.fire("wal_enqueue", self._wal_tenant)
        if lat and self._lat_admit:
            latency.on_admit(self._lat_lane or self._wal_tenant, n,
                             t0=t_admit)
        if self._lat_stamps:
            # stamps stranded by a failed earlier call (dispatch ran,
            # finalize never did) must not join THIS call's windows
            # at the same chunk offsets
            self._lat_stamps.clear()
        self._closed_partial = n % self.eb != 0
        num_w = -(-n // self.eb)
        out = []
        base = self.windows_done
        staged = []  # checkpoint snapshots due mid-call (see below)

        from . import autotune

        if self.AUTOTUNE and autotune.enabled() \
                and num_w > self.MAX_WINDOWS:
            # long streams: the online tuner picks each round's
            # (windows-per-dispatch, ingress) arm — identical
            # summaries, measured dispatch knobs; GS_AUTOTUNE=0 (or a
            # short call) runs the static path below bit-identically
            self._process_tuned(src, dst, num_w, base, staged, out)
        else:
            self._process_static(src, dst, num_w, base, staged, out)
        if self._ckpt_path is not None:
            if self._ckpt_policy.due(self.windows_done):
                self._ckpt_policy.mark(self.windows_done)
                staged.append(self.state_dict())
            # clean completion: deliver, then persist. Only the last
            # two snapshots can survive save's rotation anyway, so the
            # rest would be pure wasted compression + I/O.
            for snap in staged[-2:]:
                checkpoint.save(self._ckpt_path, snap)
                # journal retention at the flush boundary
                # (GS_WAL_RETAIN): the floor is the snapshot's replay
                # cursor — resume_offset() restarts at windows_done
                # windows, so every record past that must survive
                self._wal_retention.flushed(
                    self._wal, self._wal_tenant,
                    int(snap["windows_done"]) * self.eb)  # gslint: disable=host-sync (checkpoint payloads are host scalars, never device values)
        return out

    # -- shared pipeline pieces (static path + autotuned rounds) -------

    def _stage_ckpt_at(self, base: int, at: int, staged: list) -> None:
        """Stage a due checkpoint at a chunk-DISPATCH boundary: the
        device carry there covers exactly the `base + at` windows
        dispatched so far — the one point where a bit-exact
        window-boundary snapshot costs a single d2h sync. Snapshots
        are written only on clean process() return (the call is the
        delivery unit: a crash mid-call hands the caller nothing, so
        a flushed checkpoint covering this call's windows would make
        resume skip summaries never delivered — at-most-once)."""
        if (self._ckpt_path is not None and at
                and self._ckpt_policy.due(base + at)):
            self._ckpt_policy.mark(base + at)
            snap = self.state_dict()
            snap["windows_done"] = base + at
            snap["closed_partial"] = False  # never mid-call
            staged.append(snap)

    def _finalize_summaries(self, item, src, dst, out: list) -> None:
        """Materialize one chunk's raw outputs into summary dicts
        (exact overflow redo included) — the finalize stage both the
        static and the tuned pipeline share."""
        f_at, f_real, raw = item
        mdeg, ncomp, odd, tri, b_ovf, k_ovf = (
            x[:f_real] for x in self._materialize(raw))
        for w in np.nonzero(b_ovf + k_ovf)[0]:  # exact redo
            lo = (f_at + int(w)) * self.eb
            tri[w] = self._redo(src[lo:lo + self.eb],
                                dst[lo:lo + self.eb],
                                int(b_ovf[w]), int(k_ovf[w]))  # gslint: disable=host-sync (numpy-on-numpy: _materialize already d2h'd these slabs)
        for w in range(f_real):
            out.append({
                "max_degree": int(mdeg[w]),  # gslint: disable=host-sync (numpy-on-numpy after _materialize)
                "num_components": int(ncomp[w]),  # gslint: disable=host-sync (numpy-on-numpy after _materialize)
                "odd_cycle": bool(odd[w]),
                "triangles": int(tri[w]),  # gslint: disable=host-sync (numpy-on-numpy after _materialize)
            })
        if latency.enabled():
            # per-window ingest→deliver record (deliver = finalize on
            # the engine path: summaries are handed to the caller at
            # the very next return) — joined to the chunk's boundary
            # stamps collected by the pipeline closures
            st = self._lat_stamps.pop(f_at, None)
            lane = self._lat_lane or self._wal_tenant
            for w in range(f_real):
                lo_w = (f_at + w) * self.eb
                latency.on_window(
                    lane,
                    edges=min(lo_w + self.eb, len(src)) - lo_w,
                    st=st, ordinal=self.windows_done + w,
                    defer=self._lat_defer)
        if provenance.armed():
            # one ledger record per finalized window, emitted at the
            # SAME cursor arithmetic as the checkpoint's wal_offset
            # contract (windows_done × eb) — replay across the
            # recorded span re-derives exactly this summary
            tenant = self._lat_lane or self._wal_tenant
            for w in range(f_real):
                lo = (self.windows_done + w) * self.eb
                lo_c = (f_at + w) * self.eb
                n_w = min(lo_c + self.eb, len(src)) - lo_c
                provenance.emit(
                    tenant=tenant, window=self.windows_done + w,
                    wal_lo=lo, wal_hi=lo + n_w,
                    tier=self.METRICS_TIER, program="fused_scan",
                    summary=out[len(out) - f_real + w])
        self.windows_done += f_real
        # window-finalize mark (utils/metrics): throughput counters +
        # the staleness clock the health watchdog reads
        lo_e = f_at * self.eb
        metrics.mark_window(
            f_real, min((f_at + f_real) * self.eb, len(src)) - lo_e,
            engine=type(self).__name__, tier=self.METRICS_TIER)

    def _run_window_rounds(self, src, dst, at0: int, hi_w: int,
                           wb: int, compact: bool, data, base: int,
                           staged: list, out: list) -> None:
        """Windows [at0, hi_w) through the shared three-stage ingress
        pipeline (ops/ingress_pipeline) at an explicit chunk size and
        wire format: chunk prep runs on the worker pool, dispatches
        stay in chunk order on this thread (the scan carry is
        sequential), and each chunk's d2h + extraction materializes
        one chunk behind its dispatch — host work hides behind device
        execution (same discipline as the driver's _run_batched and
        the triangle _run_stack_loop). `data` is the prebuilt
        whole-stream stack in the chunk's wire format."""
        def prep(at):
            st = latency.stamps()
            latency.stamp(st, "start")  # queue-wait ends here
            hi = min(at + wb, hi_w)
            # ragged tails pad the window axis to a power-of-two bucket
            # (all-invalid rows fold as no-ops against the carry), so
            # varying stream lengths reuse O(log MAX_WINDOWS) programs
            if data is None:
                # tuned rounds: chunk stacks build from the raw COO on
                # the (pooled) prep stage — exploring the other wire
                # format must not hold a second whole-stream stack
                lo = at * self.eb
                hi_e = min(hi * self.eb, len(src))
                if compact:
                    from . import compact_ingress

                    m, s16, d16, nv = compact_ingress.window_stack(
                        src[lo:hi_e], dst[lo:hi_e], self.eb)
                    sc, dc, nvc, real = compact_ingress.pad_chunk(
                        s16, d16, nv, 0, m, wb, self.eb)
                    latency.stamp(st, "prep")
                    return at, real, (sc, dc, nvc), st
                m, s, d, valid = seg_ops.window_stack(
                    src[lo:hi_e], dst[lo:hi_e], self.eb,
                    sentinel=self.vb)
                sc, dc, vc, real = seg_ops.pad_window_chunk(
                    s, d, valid, 0, m, wb, self.eb, self.vb)
                latency.stamp(st, "prep")
                return at, real, (sc, dc, vc), st
            if compact:
                from . import compact_ingress

                s16, d16, nv = data
                sc, dc, nvc, real = compact_ingress.pad_chunk(
                    s16, d16, nv, at, hi, wb, self.eb)
                latency.stamp(st, "prep")
                return at, real, (sc, dc, nvc), st
            s, d, valid = data
            sc, dc, vc, real = seg_ops.pad_window_chunk(
                s, d, valid, at, hi, wb, self.eb, self.vb)
            latency.stamp(st, "prep")
            return at, real, (sc, dc, vc), st

        def h2d(payload):
            at, real, args, st = payload
            dev = self._h2d(args)
            latency.stamp(st, "h2d")
            return at, real, dev, st

        def dispatch(dev_payload):
            at, real, dev, st = dev_payload
            self._stage_ckpt_at(base, at, staged)
            raw = (self._dispatch_async_compact(*dev) if compact
                   else self._dispatch_async(*dev))
            latency.stamp(st, "dispatch")
            if st is not None:
                # the finalize stage runs one chunk behind dispatch:
                # park the boundary stamps for _finalize_summaries
                self._lat_stamps[at] = st
            return at, real, raw

        def finalize(item):
            self._finalize_summaries(item, src, dst, out)

        ingress_pipeline.run_pipeline(
            range(at0, hi_w, wb), prep, h2d, dispatch, finalize,
            timers=self.stage_timers, inflight=self.INGEST_SLOTS)

    def _build_stack(self, src, dst, fmt: str):
        """Whole-stream window stack in wire format `fmt` (compact
        validates ids on the MAIN thread first — a wrapped id would
        corrupt ANOTHER vertex's carried state, and callers must see
        the same ValueError every tier raises)."""
        if fmt == "compact":
            from . import compact_ingress

            compact_ingress.validate_ids(src, dst, self.vb + 1,
                                         "fused summary scan")
            return compact_ingress.window_stack(src, dst, self.eb)[1:]
        return seg_ops.window_stack(src, dst, self.eb,
                                    sentinel=self.vb)[1:]

    def _process_static(self, src, dst, num_w: int, base: int,
                        staged: list, out: list) -> None:
        """The legacy single-configuration path: one pipeline over the
        whole call at the statically resolved (MAX_WINDOWS, ingress)."""
        compact = self.ingress == "compact"
        data = self._build_stack(src, dst,
                                 "compact" if compact else "standard")
        self._run_window_rounds(src, dst, 0, num_w, self.MAX_WINDOWS,
                                compact, data, base, staged, out)

    # -- online autotuning (ops/autotune.py) ---------------------------

    def _ensure_tuner(self):
        from . import autotune
        from . import compact_ingress

        if getattr(self, "_tuner", None) is None:
            wbm = self.MAX_WINDOWS
            wbs = sorted({max(1, wbm // 4), max(1, wbm // 2), wbm})
            ing = [self.ingress]
            if self.TUNABLE_INGRESS \
                    and not getattr(self, "_pinned_ingress", False):
                ing = ["standard"]
                if compact_ingress.supports(self.vb):
                    ing.append("compact")
            init = {"wb": wbm,
                    "ingress": (self.ingress if self.ingress in ing
                                else "standard")}
            self._tuner = autotune.DispatchTuner(
                "%s:eb=%d:vb=%d" % (self.TUNER_FAMILY, self.eb,
                                    self.vb),
                {"wb": wbs, "ingress": ing}, init)
        return self._tuner

    def _warm_arm(self, arm: dict) -> None:
        """Run one ALL-PADDING chunk at the arm's shape before its
        first timed round: padded rows fold as no-ops against the
        carry (values bit-identical), so this is a pure compile+warm
        dispatch — steady-state rounds never compile mid-measurement."""
        warmed = getattr(self, "_warmed_arms", None)
        if warmed is None:
            warmed = self._warmed_arms = set()
        key = (arm["wb"], arm["ingress"])
        if key in warmed:
            return
        wb = arm["wb"]
        if arm["ingress"] == "compact":
            z16 = np.zeros((wb, self.eb), np.uint16)
            raw = self._dispatch_async_compact(
                *self._h2d((z16, z16, np.zeros(wb, np.int32))))
        else:
            zi = np.full((wb, self.eb), self.vb, np.int32)
            raw = self._dispatch_async(
                *self._h2d((zi, zi, np.zeros((wb, self.eb), bool))))
        self._materialize(raw)  # block until the compile completes
        warmed.add(key)

    def _process_tuned(self, src, dst, num_w: int, base: int,
                       staged: list, out: list) -> None:
        """The autotuned twin of _process_static: measurement rounds
        of `autotune.round_chunks()` chunks each, arm-per-round, the
        measured edges/s fed back to the tuner. Summaries are
        identical at every arm; under forced_sync the tuner freezes
        (see ingress_pipeline.forced_sync_active)."""
        from . import autotune

        tuner = self._ensure_tuner()
        freeze = ingress_pipeline.forced_sync_active()
        validated = False
        round_len = autotune.round_chunks()
        at0 = 0
        while at0 < num_w:
            arm = tuner.best() if freeze else tuner.next_round()
            self._warm_arm(arm)
            wb, fmt = arm["wb"], arm["ingress"]
            if fmt == "compact" and not validated:
                # the shared main-thread wrap-safety check, once per
                # call (prep builds compact stacks on the pool)
                from . import compact_ingress

                compact_ingress.validate_ids(src, dst, self.vb + 1,
                                             "fused summary scan")
                validated = True
            take = min(num_w - at0, round_len * wb)
            # telemetry span doubles as the round stopwatch (same
            # perf_counter measurement disarmed)
            with telemetry.span("fused_scan.round", window=base + at0,
                                wb=wb, ingress=fmt,
                                edges=take * self.eb) as sp:
                self._run_window_rounds(src, dst, at0, at0 + take, wb,
                                        fmt == "compact", None,
                                        base, staged, out)
            # full rounds (or a whole call smaller than one) only: a
            # long call's ragged tail would drag the arm's EMA with
            # tail economics
            if not freeze and take == min(round_len * wb, num_w):
                tuner.record(arm, take * self.eb, sp.elapsed)
            at0 += take
        if not freeze:
            tuner.save()


class StreamSummaryEngine(SummaryEngineBase):
    """Single-chip carried-state analytics over chunks of windows, one
    dispatch per MAX_WINDOWS windows. Exact: triangle windows whose
    hubs overflow K are recounted by the escalating per-window
    kernel."""

    AUTOTUNE = True
    TUNABLE_INGRESS = True

    def __init__(self, edge_bucket: int, vertex_bucket: int,
                 k_bucket: int = 0, ingress: str = None):
        self.eb = seg_ops.bucket_size(edge_bucket)
        self.vb = seg_ops.bucket_size(vertex_bucket)
        self.kb = seg_ops.bucket_size(
            k_bucket if k_bucket else tri_ops._tuned_kb(self.eb))
        # compile-size cap on TPU backends (tri_ops.COMPILE_CAP)
        self.MAX_WINDOWS = min(type(self).MAX_WINDOWS,
                               tri_ops.capped_chunk(self.eb))
        # stream-chunk wire format: standard unless pinned, with the
        # same vb gate on a compact pin as TriangleWindowKernel
        if ingress == "compact":
            from . import compact_ingress

            if not compact_ingress.supports(self.vb):
                raise ValueError(
                    "compact ingress is lossy for vertex_bucket %d "
                    "(ids must fit uint16)" % self.vb)
        self.ingress = ingress or "standard"
        # an explicit pin freezes the wire format for the tuner too
        # (the A/B tools must measure exactly what they pinned)
        self._pinned_ingress = ingress is not None
        body = _build_scan(self.eb, self.vb, self.kb)

        @jax.jit
        def run(carry, src_w, dst_w, valid_w):
            return jax.lax.scan(body, carry, (src_w, dst_w, valid_w))

        # compile watch (utils/metrics): distinct abstract signatures
        # count against the O(log V) recompile envelope. The cost
        # observatory (utils/costmodel) rides the same wrapper: armed,
        # each signature's cost_analysis is captured and dispatches
        # tag their ledger spans program="fused_scan"/sig — or
        # program="pallas_window" when the megakernel body was
        # selected, so the observatory attributes the new program
        # separately from the scan-of-gathers it replaces.
        self._pallas = bool(getattr(body, "pallas_window", False))
        self._run = metrics.wrap_jit(
            "pallas_window" if self._pallas else "fused_scan", run)
        self._body = body
        self._run_c = None  # compact twin, built on first use
        if self.ingress == "compact":
            self._ensure_compact_fn()
        self._tri_fallback = tri_ops.TriangleWindowKernel(
            edge_bucket=self.eb, vertex_bucket=self.vb,
            k_bucket=4 * self.kb)
        self.reset()

    def _ensure_compact_fn(self):
        """The compact twin of _run: the shared device-side decode
        (compact_ingress.widen_stack — widen uint16 ids + rebuild the
        suffix mask from per-window counts) fused into the same scan
        program, applied to the whole [W, eb] stack before the scan
        consumes it. Built lazily so a standard-resolved engine whose
        TUNER explores compact pays for it only when explored.

        When the Pallas megakernel is selected, the decode fuses one
        level deeper: the compact body consumes the RAW uint16 stacks
        and widens per tile INSIDE the kernel (the tentpole's
        compact-ingress-decode stage) — no [W, eb] int32
        intermediates ever materialize."""
        if self._run_c is None:
            eb_, vb_, body = self.eb, self.vb, self._body

            if getattr(body, "pallas_window", False):
                from . import pallas_window

                run_pc = pallas_window.maybe_compact_scan_fn(
                    eb_, vb_, self.kb, "pallas_window_compact")
                if run_pc is not None:
                    self._run_c = run_pc
                    return self._run_c

            from . import compact_ingress as _ci

            @jax.jit
            def run_c(carry, s16, d16, nvalid):
                s_w, d_w, valid_w = _ci.widen_stack(
                    s16, d16, nvalid, eb_, vb_)
                return jax.lax.scan(body, carry, (s_w, d_w, valid_w))

            self._run_c = metrics.wrap_jit("fused_scan_compact", run_c)
        return self._run_c

    def _dispatch_async(self, s, d, valid):
        self._carry, outs = self._run(
            self._carry, jnp.asarray(s), jnp.asarray(d),
            jnp.asarray(valid))
        return outs

    def _dispatch_async_compact(self, s16, d16, nvalid):
        self._carry, outs = self._ensure_compact_fn()(
            self._carry, jnp.asarray(s16), jnp.asarray(d16),
            jnp.asarray(nvalid))
        return outs

    def _materialize(self, raw):
        mdeg, ncomp, odd, tri, ovf = (np.array(x) for x in raw)  # gslint: disable=host-sync (sanctioned finalize boundary: the engine's ONE batched d2h per chunk)
        # single-chip scan has one overflow signal: report it as k_ovf
        return mdeg, ncomp, odd, tri, np.zeros_like(ovf), ovf

    def _redo(self, src, dst, b_ovf: int, k_ovf: int) -> int:
        return self._tri_fallback.count(src, dst)


class SlidingSummaryEngine:
    """Sliding windows on the fused scan via pane composition
    (`slide=`): an inner StreamSummaryEngine at edge_bucket=slide
    folds each edge into its pane ONCE. The cumulative analytics
    (max_degree, num_components, odd_cycle) read the carried state at
    every pane boundary — bit-identical at any pane size, so the pane
    path IS the sliding path for them. The per-window analytic
    (triangles) recomputes per emission off the composed pane edge
    slab: a ring of the last panes_per_window − 1 pane (src, dst)
    slabs plus the fresh pane runs through TriangleWindowKernel at
    the FULL window bucket, keeping its exact-redo K escalation.

    One summary dict per emission — every `slide` edges, the window
    covering the trailing `edge_bucket` edges (growing at the head of
    the stream, ragged on a final partial pane). slide == edge_bucket
    degenerates to exactly one pane per window: tumbling.

    The ring rides state_dict()/load_state_dict(), so a kill →
    resume mid-pane-ring recomposes the SAME windows the uninterrupted
    run emits (tests/test_sliding_windows.py)."""

    def __init__(self, edge_bucket: int, vertex_bucket: int,
                 slide: int, k_bucket: int = 0):
        eb = seg_ops.bucket_size(edge_bucket)
        slide = int(slide)
        if slide <= 0 or slide > eb or eb % slide \
                or slide & (slide - 1):
            raise ValueError(
                "slide must be a power of two dividing the window "
                "size (%d), got %d" % (eb, slide))
        self.eb = eb
        self.vb = seg_ops.bucket_size(vertex_bucket)
        self.slide = slide
        self.panes_per_window = eb // slide
        self.inner = StreamSummaryEngine(
            edge_bucket=slide, vertex_bucket=self.vb,
            k_bucket=k_bucket)
        # per-emission triangle recount at the FULL window bucket —
        # the composed slab holds up to eb edges
        self._tri = tri_ops.TriangleWindowKernel(
            edge_bucket=eb, vertex_bucket=self.vb,
            k_bucket=k_bucket)
        self._ring = []  # last ≤ wp−1 pane (src, dst) pairs

    # pass-throughs the serving/driver integration reads
    @property
    def windows_done(self) -> int:
        """Emissions done (the inner scan's pane cursor)."""
        return self.inner.windows_done

    def reset(self) -> None:
        self.inner.reset()
        self._ring = []

    def resume_offset(self) -> int:
        return self.inner.windows_done * self.slide

    def process(self, src, dst) -> list:
        """Fold the stream's slide-sized panes; one summary per pane
        (= per emission). Mid-stream calls must be multiples of
        `slide` (the inner engine enforces it); a ragged call closes
        the stream with a final partial emission."""
        src = np.asarray(src, np.int32)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
        dst = np.asarray(dst, np.int32)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
        if sanitize_mod.enabled():
            # sanitize HERE so the pane slabs below slice the same
            # clean arrays the inner engine folds (its own sanitize
            # pass of the already-clean batch is a no-op)
            rep = sanitize_mod.sanitize(
                src, dst, self.vb, tenant=self.inner._wal_tenant,
                origin="engine", offset=self.inner._fed_edges,
                dlq=sanitize_mod.resolve_dlq())
            src, dst = (np.asarray(rep.src, np.int32),  # gslint: disable=host-sync (sanitizer output is host numpy)
                        np.asarray(rep.dst, np.int32))  # gslint: disable=host-sync (sanitizer output is host numpy)
        summaries = self.inner.process(src, dst)
        wp, s = self.panes_per_window, self.slide
        out = []
        for i, pane_sum in enumerate(summaries):
            lo, hi = i * s, min((i + 1) * s, len(src))
            pane = (src[lo:hi], dst[lo:hi])
            slab = self._ring + [pane]
            with telemetry.span("sliding.emit",
                                panes=len(slab),
                                edges=sum(len(p[0]) for p in slab)):
                tri = self._tri.count(
                    np.concatenate([p[0] for p in slab]),
                    np.concatenate([p[1] for p in slab]))
            row = dict(pane_sum)
            row["triangles"] = int(tri)
            out.append(row)
            self._ring = (self._ring + [pane])[-(wp - 1):] \
                if wp > 1 else []
        return out

    # ------------------------------------------------------------------
    # checkpoint / resume — the pane ring rides along (R6-symmetric)
    # ------------------------------------------------------------------
    def state_dict(self) -> dict:
        return {
            "slide": self.slide,
            "edge_bucket": self.eb,
            "vertex_bucket": self.vb,
            "ring_src": [np.asarray(s) for s, _d in self._ring],  # gslint: disable=host-sync (the pane ring holds host int32 slabs, never device values)
            "ring_dst": [np.asarray(d) for _s, d in self._ring],  # gslint: disable=host-sync (the pane ring holds host int32 slabs, never device values)
            "inner": self.inner.state_dict(),
        }

    def load_state_dict(self, state: dict) -> None:
        ck_slide = int(state["slide"])  # gslint: disable=host-sync (checkpoint scalars are host values)
        ck_eb = int(state["edge_bucket"])  # gslint: disable=host-sync (checkpoint scalars are host values)
        ck_vb = int(state["vertex_bucket"])  # gslint: disable=host-sync (checkpoint scalars are host values)
        if (ck_slide, ck_eb, ck_vb) != (self.slide, self.eb, self.vb):
            raise ValueError(
                "sliding checkpoint was taken at slide=%d eb=%d "
                "vb=%d; engine runs slide=%d eb=%d vb=%d" % (
                    ck_slide, ck_eb, ck_vb,
                    self.slide, self.eb, self.vb))
        self._ring = [(np.asarray(s, np.int32), np.asarray(d, np.int32))  # gslint: disable=host-sync (checkpoint arrays are host numpy)
                      for s, d in zip(state["ring_src"],
                                      state["ring_dst"])]
        self.inner.load_state_dict(state["inner"])
