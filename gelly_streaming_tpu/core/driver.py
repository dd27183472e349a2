"""Columnar streaming analytics driver — the production ingest→device
path.

The record-level DataStream runtime (core/runtime.py) reproduces the
reference's per-record operator semantics for API parity; this driver
is the TPU-first way to run the same analytics at stream rate
(SURVEY.md §7 design stance): no per-edge Python objects anywhere.

    file → native parse (native/ingest.cpp)
         → tumbling event-time window assignment (Flink TimeWindow
           floor semantics, SimpleEdgeStream.java:90-94)
         → incremental vertex interning (C++ hash map)
         → per-window fixed-shape device kernels with carried state:
             degrees   — running degree vector
                         (continuous semantics of SimpleEdgeStream.java:465-482,
                          emitted per window batch)
             cc        — carried min-label components
                         (library/ConnectedComponents.java via ops/unionfind)
             bipartite — carried double-cover 2-coloring
                         (library/BipartitenessCheck.java)
             triangles — exact per-window count
                         (example/WindowTriangles.java via ops/triangles)

Single chip by default; pass a `jax.sharding.Mesh` to run every kernel
sharded over it (parallel/sharded.py — P1 edges, P2/P6 collective
merges). Vertex and edge buckets grow by doubling, so an unbounded
stream triggers only O(log V) recompiles.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import List, Optional, Sequence

import numpy as np

from .. import native
from ..ops import delta_egress
from ..ops import host_snapshot
from ..ops import ingress_pipeline
from ..ops import segment as seg_ops
from ..ops import triangles as tri_ops
from ..ops import unionfind
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
from ..utils.interning import make_interner, parallel_intern_arrays
from ..utils.tracing import StepTimer


def _snapshot_view(a: np.ndarray, row_size: int = 0) -> np.ndarray:
    """Read-only array with snapshot semantics. When the slice covers
    most of its backing row (steady state: nv ≈ vb — exactly when the
    per-window copy is what costs, ~20% of the 10M-edge driver leg's
    host time), return a frozen VIEW: the backing stack is fresh per
    chunk and never written after extraction (the device scan's
    outputs are already immutable; the native tier's are np.empty
    slabs the kernel filled before extraction). When the slice is
    small relative to `row_size` (early stream), return an owned copy
    instead — a tiny window's field must not pin its chunk's whole
    [W, vb] stack in memory for consumers that retain results. Every
    returned array is read-only, so the 'fields are snapshots, never
    live state' contract is uniform across tiers and paths."""
    if row_size and 4 * a.size < row_size:
        a = a.copy()
    else:
        a = a[:]
    a.flags.writeable = False
    return a


def _frozen_delta(idx: np.ndarray, vals: np.ndarray) -> tuple:
    """Freeze a (changed ids, new values) delta pair — the delta
    streams share the WindowResult read-only snapshot contract (both
    arrays are fresh fancy-indexed copies, never aliases of carried
    state, so freezing costs nothing)."""
    idx.flags.writeable = False
    vals.flags.writeable = False
    return (idx, vals)


def snapshot_fold_body(vb: int, analytics: tuple, deltas: bool = False,
                       egress: str = "full", cap: int = 0):
    """The per-window body of the batched snapshot scan, shared by the
    single-chip scan (_build_snapshot_scan) and the mesh scan
    (parallel/sharded.make_sharded_snapshot_scan): body(carry, xs)
    folds one window (src, dst, valid) into the carried (degrees, cc
    labels, double-cover labels) and emits that window's snapshots:
    degrees and CC labels whole, the cover as the `[vb]` bool odd flag
    (`odd`, what WindowResult hands out; the labels ride the carry).
    Cover layout: (+) = v, (−) = vb + v. Padding lanes go to each
    table's LAST slot, read from the carry's shapes: the single chip
    carries [vb+1] / [2vb+1] (sentinels vb, 2vb), the mesh engine
    [vb+2] / [2vb+2] (sentinels vb+1, 2vb+1), so neither layout is
    converted on the hot path.

    Each window folds into the CC and cover labels through their roots
    (ops/unionfind.cc_fold_rooted): every carry that enters the scan is
    flat and min-rooted, so a fixpoint round costs the window's edges,
    not the table, and relabels the table through the roots that moved;
    with degrees in the analytics, the carried degrees before the
    window's scatter-add tell which of those roots have members. Both
    folds also emit their round counts, `cc_rounds` and
    `cover_rounds`, and their moved roots with members,
    `cc_relabel_roots` and `cover_relabel_roots` ([W] int32 each), in
    every egress and donate variant: the read-back's fold counters
    (finalize)."""
    import jax.numpy as jnp

    from ..ops import delta_egress
    from ..ops import unionfind as uf

    want_deg = "degrees" in analytics
    want_cc = "cc" in analytics
    want_bip = "bipartite" in analytics
    delta_out = egress == "delta"

    def body(carry, xs):
        deg, labels, cover = carry
        src, dst, valid = xs
        sent = labels.shape[0] - 1
        s = jnp.where(valid, src, sent)
        d = jnp.where(valid, dst, sent)
        outs = {}
        # the folds' witness of a root with no other member: an
        # endpoint of degree 0 before this window had no edge
        seen = (deg[s] > 0, deg[d] > 0) if want_deg else None
        if want_deg:
            new_deg = deg.at[s].add(1).at[d].add(1)  # last slot: pads
            chg = (new_deg[:vb] != deg[:vb]) \
                if (deltas or delta_out) else None
            if delta_out:
                (outs["deg_cnt"], outs["deg_idx"],
                 outs["deg_val"]) = delta_egress.compact_changed(
                    chg, new_deg[:vb], cap, 0)
            else:
                if deltas:
                    outs["deg_chg"] = chg
                outs["deg"] = new_deg
            deg = new_deg
        if want_cc:
            (new_labels, outs["cc_rounds"],
             outs["cc_relabel_roots"]) = uf.cc_fold_rooted(
                labels, s, d, seen)
            chg = (new_labels[:vb] != labels[:vb]) \
                if (deltas or delta_out) else None
            if delta_out:
                (outs["labels_cnt"], outs["labels_idx"],
                 outs["labels_val"]) = delta_egress.compact_changed(
                    chg, new_labels[:vb], cap, 0)
            else:
                if deltas:
                    outs["labels_chg"] = chg
                outs["labels"] = new_labels
            labels = new_labels
        if want_bip:
            sent2 = cover.shape[0] - 1
            s2 = jnp.concatenate([
                jnp.where(valid, s, sent2),
                jnp.where(valid, s + vb, sent2)])
            d2 = jnp.concatenate([
                jnp.where(valid, d + vb, sent2),
                jnp.where(valid, d, sent2)])
            # both halves of the cover's edges join the window's own
            # endpoints, so each half takes their witness
            seen2 = None if seen is None else tuple(
                jnp.concatenate([w, w]) for w in seen)
            (new_cover, outs["cover_rounds"],
             outs["cover_relabel_roots"]) = uf.cc_fold_rooted(
                cover, s2, d2, seen2)
            # the consumer-visible value is the odd flag, so the row
            # read back, the mask and the delta wire all carry IT, not
            # the raw labels (the chunk's final cover is the carry)
            new_odd = new_cover[:vb] == new_cover[vb:2 * vb]
            if deltas or delta_out:
                chg = new_odd != (cover[:vb] == cover[vb:2 * vb])
            if delta_out:
                (outs["cover_cnt"], outs["cover_idx"],
                 outs["cover_val"]) = delta_egress.compact_changed(
                    chg, new_odd, cap, 0)
            else:
                if deltas:
                    outs["cover_chg"] = chg
                outs["odd"] = new_odd
            cover = new_cover
        return (deg, labels, cover), outs

    return body


def _build_snapshot_scan(vb: int, analytics: tuple,
                         deltas: bool = False, egress: str = "full",
                         cap: int = 0, donate: bool = False):
    """One jitted lax.scan of snapshot_fold_body over a [W, eb] window
    stack, carrying (degrees, cc labels, double-cover labels) and
    emitting PER-WINDOW snapshots — the driver's batched single-chip
    fast path (sharded meshes use
    parallel.sharded.make_sharded_snapshot_scan, the same body): one
    dispatch + one d2h per run_arrays call instead of one per analytic
    per window (per-dispatch latency dominates per-window
    economics). Cover layout matches the driver's
    host state: (+) = v, (−) = vb + v, sentinel slot 2vb.

    With `deltas`, each analytic also emits a per-window changed-slot
    bool mask over [:vb] (new state vs the scan carry — computed
    on-device, so a consumer of the reference's improving streams
    (SimpleEdgeStream.java:473-481) can reconstruct per-update records
    from snapshot + mask without diffing full vectors on host).

    With egress="delta" (ops/delta_egress), the per-window output is
    the COMPACT changed-slot wire instead of full vectors: per analytic
    an int32 count plus [cap]-sized (indices, new values) rows —
    2-3 orders of magnitude fewer d2h bytes on settled streams; the
    driver reconstructs full snapshots from its host mirrors, and a
    count exceeding `cap` routes the chunk to the bit-exact host fold.
    The full masks are then NOT emitted (the wire subsumes them).

    The chunk's final cover labels, which the host mirror resyncs
    from, are the returned carry's: the driver reads them back once a
    chunk as `cover_final`. With `donate` (the RESIDENT tier,
    ops/resident_engine), the carry argument is donated where the
    backend honors donation — the ResidentState slabs update in place
    across super-batches instead of being re-allocated per dispatch —
    so the program emits that cover as an explicit FRESH output
    (`cover_final`): the next super-batch donates the carry buffers,
    so the drain must never alias them."""
    import jax
    import jax.numpy as jnp

    body = snapshot_fold_body(vb, analytics, deltas=deltas, egress=egress,
                              cap=cap)
    want_bip = "bipartite" in analytics

    if donate:
        from ..ops import resident_engine

        def run_fn(carry, s_w, d_w, valid_w):
            new_carry, outs = jax.lax.scan(body, carry,
                                           (s_w, d_w, valid_w))
            if want_bip:
                # explicit copy primitive — a folded-away no-op like
                # `+ 0` would leave this the same HLO value as the
                # carry's cover, whose donated buffer super-batch N+1
                # overwrites before chunk N's finalize reads it
                outs["cover_final"] = jnp.copy(new_carry[2])
            return new_carry, outs

        return jax.jit(run_fn, **resident_engine.donate_kw())

    @jax.jit
    def run(carry, s_w, d_w, valid_w):
        return jax.lax.scan(body, carry, (s_w, d_w, valid_w))

    return run


def _real_rows(outs: dict, windows: int) -> dict:
    """A chunk's scan outputs cut on the device to its `windows` real
    windows: the W-bucket's rows past them are sentinel windows that
    no consumer reads, so they never cross the d2h. `cover_final`, one
    row a chunk, passes whole. One jitted program per (out tree, real
    rows), warmed with its W-bucket (_warm_scan_arm)."""
    rows = {k: v for k, v in outs.items() if k != "cover_final"}
    cut = _head_rows_fn()(rows, windows)
    if "cover_final" in outs:
        cut["cover_final"] = outs["cover_final"]
    return cut


@functools.lru_cache(maxsize=None)
def _head_rows_fn():
    import jax

    return jax.jit(lambda rows, n: {k: v[:n] for k, v in rows.items()},
                   static_argnums=1)


def _readback_counters(outs: dict, windows: int,
                       sentinel_rows: int) -> None:
    """The read-back's counters, from a chunk's materialized scan
    outputs (no further sync), all of whose per-window rows are the
    chunk's `windows` real windows: bytes brought back, the
    W-bucket's `sentinel_rows` left on the device (_real_rows), the
    rounds the CC and double-cover folds took, the moved roots with
    members they relabelled (`relabel_roots`), and how many of the
    folds had more than the relabel's list holds and gathered instead
    (`relabel_gathers`)."""
    telemetry.counter("driver.readback_bytes",
                      sum(v.nbytes for v in outs.values()),
                      windows=windows)
    telemetry.counter("driver.readback_sentinel_rows", sentinel_rows,
                      windows=windows)
    for key in ("cc_rounds", "cover_rounds"):
        if key in outs:
            telemetry.counter("driver." + key, int(outs[key].sum()),
                              windows=windows)
    moved = [outs[key] for key in ("cc_relabel_roots",
                                   "cover_relabel_roots")
             if key in outs]
    if moved:
        telemetry.counter("driver.relabel_roots",
                          int(sum(m.sum() for m in moved)),
                          windows=windows)
        telemetry.counter("driver.relabel_gathers",
                          int(sum((m > unionfind.RELABEL_K_MAX).sum()
                                  for m in moved)),
                          windows=windows)


def resolve_snapshot_tier() -> str:
    """Batched snapshot-analytics tier: the device scan, or the
    RESIDENT megakernel (ops/resident_engine) when GS_RESIDENT pins
    it. The native and host tiers below them are the demotion
    ladder's rungs and the `snapshot_tier=` pin's."""
    from ..ops import resident_engine

    return "resident" if resident_engine.resolve_resident() else "scan"


@dataclasses.dataclass
class WindowResult:
    """Per-window analytics snapshot. Vertex-indexed arrays are in dense
    slot order; `vertex_ids[slot]` maps back to external ids.

    EVERY array field is a READ-ONLY snapshot — vertex_ids, degrees,
    cc_labels, bipartite_odd, and the arrays inside the delta_*
    tuples, uniformly across tiers (device scan / native / sharded)
    and dispatch paths (batched / per-window). They are often
    zero-copy views of the chunk's output stacks (_snapshot_view) and
    never alias live carried state, so consecutive windows' snapshots
    are independently stable; consumers that need a mutable array
    call `.copy()`. The contract is documented in README.md
    ("WindowResult snapshots are read-only")."""

    window_start: int
    num_edges: int
    vertex_ids: np.ndarray                      # external id per slot
    degrees: Optional[np.ndarray] = None        # running, per slot
    cc_labels: Optional[np.ndarray] = None      # carried min-label slots
    bipartite_odd: Optional[np.ndarray] = None  # carried odd-cycle flag
    triangles: Optional[int] = None             # exact, this window only
    # emit_deltas=True: per-window changed-slot streams — (slot ids,
    # new values) for every slot whose value differs from the previous
    # window's snapshot (the per-update improving-stream analog of
    # SimpleEdgeStream.java:473-481; start states are all-zero degrees,
    # identity labels, all-False odd). Slots are in the same dense slot
    # space as the snapshot arrays.
    delta_degrees: Optional[tuple] = None       # (int32 ids, int64 vals)
    delta_cc: Optional[tuple] = None            # (int32 ids, int32 vals)
    delta_bipartite: Optional[tuple] = None     # (int32 ids, bool vals)
    # latency plane (utils/latency.py, GS_LATENCY=1): this window's
    # ingest→deliver record — {"e2e_s", "stages": {...}, "replayed"} —
    # joined back to the admission stamp of its completing edge.
    # Always None disarmed (the digest-parity contract).
    latency: Optional[dict] = None


class StreamingAnalyticsDriver:
    ANALYTICS = ("degrees", "cc", "bipartite", "triangles")

    def __init__(self, window_ms: int,
                 analytics: Sequence[str] = ANALYTICS,
                 vertex_bucket: int = 1 << 12,
                 edge_bucket: int = 1 << 12,
                 mesh=None, tracing: bool = False,
                 emit_deltas: bool = False,
                 snapshot_tier: str = None,
                 egress: str = None,
                 tenant: str = None,
                 slide: int = None):
        unknown = set(analytics) - set(self.ANALYTICS)
        if unknown:
            raise ValueError(f"unknown analytics: {sorted(unknown)}")
        if snapshot_tier not in (None, "resident", "scan", "native",
                                 "host"):
            raise ValueError(f"unknown snapshot_tier: {snapshot_tier!r}")
        if snapshot_tier == "resident" and mesh is not None:
            raise ValueError(
                "the resident tier is single-chip: a mesh session's "
                "base tier is the sharded engine (its demotion ladder "
                "re-enters on scan, never resident)")
        if snapshot_tier == "native" and not native.snapshot_available():
            raise ValueError("native snapshot tier pinned but "
                             "libgsnative lacks gs_snapshot_windows")
        if egress not in (None, "full", "delta"):
            raise ValueError(f"unknown egress: {egress!r}")
        # d2h egress of the batched snapshot scan: explicit pin (tests,
        # tools/egress_ab.py) or the GS_EGRESS knob
        # (ops/delta_egress.resolve_egress); sharded meshes always full
        self._egress_pin = egress
        # multi-tenant label (core/tenancy.py serving story): when this
        # driver serves ONE stream of a multi-tenant deployment, its
        # health-plane marks and demotion records carry the tenant so
        # /healthz per-tenant liveness and the degradations evidence
        # name the stream, not just "driver"
        self.tenant = None if tenant is None else str(tenant)
        self.window_ms = window_ms
        self.analytics = tuple(analytics)
        # batched snapshot analytics tier: explicit pin (tests, the
        # profiler's A/B) or resolve_snapshot_tier()
        self._snapshot_tier = snapshot_tier
        self.emit_deltas = bool(emit_deltas)
        self.mesh = mesh
        self.timer = StepTimer() if tracing else None
        self.interner = make_interner(np.array([0]))
        self._ext_ids = np.zeros(0, np.int64)  # slot → external id cache
        self.vb = seg_ops.bucket_size(vertex_bucket)
        self.eb = seg_ops.bucket_size(edge_bucket)
        # sliding windows via pane composition (DESIGN.md §22): the
        # count-based path cuts PANE-sized emissions (`slide` edges)
        # and recomputes the per-window triangle count off the composed
        # slab of the last panes_per_window panes — each edge is folded
        # into the cumulative analytics exactly once. slide=None (and
        # GS_SLIDE=0) keeps the tumbling legacy path bit-identical.
        if slide is None:
            slide = knobs.get_int("GS_SLIDE") or 0
        slide = int(slide) or None
        if slide is not None:
            if mesh is not None:
                raise ValueError(
                    "sliding windows are single-chip: the sharded "
                    "engine cuts whole-window slabs across the mesh "
                    "(compose panes upstream or drop slide=)")
            if (seg_ops.bucket_size(slide) != slide
                    or self.eb % slide != 0):
                raise ValueError(
                    "slide must be a power of two dividing the window "
                    "size (%d), got %d" % (self.eb, slide))
        self.slide = slide
        self._wp = (self.eb // slide) if slide else 1
        self._pane_ring: list = []  # last ≤ wp−1 interned (s, d) panes
        self._degrees = np.zeros(0, np.int64)
        self._deg_state = None    # device-carried degrees (single-chip)
        self._cc = np.zeros(0, np.int32)
        self._bip = np.zeros(0, np.int32)
        self._tri_kernel = None
        self._engine = None       # sharded: ShardedWindowEngine
        self._sh_tri = None       # sharded: ShardedTriangleWindowKernel
        self._tri_pending = None  # batched-dispatch collector (transient)
        self.windows_done = 0     # survives checkpoints: resume cursor
        self.edges_done = 0       # count-based window_start offset
        self._closed_partial = False  # count-based misuse guard
        self._ckpt_path = None
        self._ckpt_policy = None  # utils.checkpoint.CheckpointPolicy
        self._pending_ckpt = []  # staged (windows_done, state) — see
        self._emitted = None     # _stage_ckpt; not-None inside stream_file
        # write-ahead edge journal (utils/wal.py): armed by
        # enable_wal(), appended to in run_arrays BEFORE windowing —
        # the durable source the live (non-file) feed path never had.
        # _in_stream suppresses journaling under stream_file: a
        # file-backed source is already replayable, and journaling it
        # would double-replay on recovery.
        self._wal = None
        self._wal_dir = None
        # GS_WAL_RETAIN bookkeeping: journal truncation at checkpoint
        # FLUSH boundaries, floored at the older kept generation
        self._wal_retention = wal_mod.RetentionCursor()
        self._in_stream = 0
        # cumulative fed edges incl. sanitizer rejects: the DLQ's
        # source-offset domain for this driver's admission boundary
        self._fed_edges = 0
        # tier demotion (utils/resilience): a persistent device failure
        # in the batched snapshot path demotes scan→native→host
        # mid-stream instead of killing the job; None = not demoted
        self._demoted_tier = None
        self._demoted_at = 0      # windows_done when last demoted
        self._demotions = []      # event dicts (also in the registry)
        # online dispatch tuner of the batched snapshot scan
        # (ops/autotune; built lazily, None with GS_AUTOTUNE=0)
        self._scan_tuner = None
        # resident tier (ops/resident_engine): its own tuner family
        # (windows-per-superbatch arms) and a per-call flag the scan
        # program cache keys on (_scan_key) — the resident programs
        # are a DISTINCT donated family
        self._resident_tuner = None
        self._resident_now = False

    def reset(self) -> None:
        """Clear all carried stream state (interner, analytics vectors,
        cursors) while keeping every compiled kernel, so warmup windows
        can be discarded without polluting a measured run."""
        self.interner = make_interner(np.array([0]))
        self._ext_ids = np.zeros(0, np.int64)
        self._degrees = np.zeros(0, np.int64)
        self._deg_state = None
        self._cc = np.zeros(0, np.int32)
        self._bip = np.zeros(0, np.int32)
        self.windows_done = 0
        self.edges_done = 0
        self._closed_partial = False
        self._pane_ring = []
        self._pending_ckpt = []
        if self._ckpt_policy is not None:
            # re-anchor the cadence: the cursor just rewound to 0, so
            # a stale high-water mark would suppress every due() until
            # the stream re-passed it
            self._ckpt_policy.mark(0)
        if self._engine is not None:
            self._engine.reset()

    # ------------------------------------------------------------------
    # bucket growth (O(log V) recompiles over an unbounded stream)
    # ------------------------------------------------------------------
    def _ensure_buckets(self, num_vertices: int, window_edges: int) -> None:
        vb_grew = eb_grew = False
        while num_vertices > self.vb:
            self.vb *= 2
            vb_grew = True
        while window_edges > self.eb:
            self.eb *= 2
            eb_grew = True
        first = self._tri_kernel is None and self._engine is None
        if not (vb_grew or eb_grew or first):
            return
        if (vb_grew or eb_grew) and self._scan_tuner is not None:
            # bucket growth changes the per-chunk economics the scan
            # tuner measured (and its cache identity): re-key it — the
            # incumbent survives as the prior, stale rates reset, and
            # the persisted cache re-seeds the new key when this shape
            # was tuned in an earlier run
            cap = self._scan_chunk()
            self._scan_tuner.rekey(
                self._scan_tuner_key(),
                space={"wb": sorted({max(1, cap // 4),
                                     max(1, cap // 2), cap})},
                initial={"wb": cap})
        if (vb_grew or eb_grew) and self._resident_tuner is not None:
            # same re-key-instead-of-discard contract for the resident
            # tier's windows-per-superbatch tuner: the incumbent
            # survives as the prior under the new bucket identity and
            # the persisted cache re-seeds it — without this, bucket
            # growth silently FROZE the resident arm at a dead key
            # (the ISSUE-9 arm-freezing fix, pinned by
            # tests/operations/test_resident.py)
            cap = self._resident_chunk()
            self._resident_tuner.rekey(
                self._resident_tuner_key(),
                space={"wb": sorted({max(1, cap // 4),
                                     max(1, cap // 2), cap})},
                initial={"wb": cap})
        if self.mesh is not None:
            from ..parallel.sharded import (ShardedTriangleWindowKernel,
                                            ShardedWindowEngine)

            if vb_grew or first:  # eb growth alone keeps the engine
                old = self._engine
                self._engine = ShardedWindowEngine(
                    self.mesh, num_vertices_bucket=self.vb)
                if old is not None:  # carry state into the wider bucket
                    st = old.state_dict()
                    new = self._engine.state_dict()
                    # np.array: the fresh state leaf is a read-only
                    # device view; only degree_state is patched in place
                    new["degree_state"] = np.array(new["degree_state"])
                    n_deg = len(st["degree_state"]) - 2
                    new["degree_state"][:n_deg] = st["degree_state"][:-2]
                    lab = np.arange(self.vb + 2, dtype=np.int32)
                    lab[:n_deg] = st["labels"][:-2]
                    new["labels"] = lab
                    if "bip_labels" in st:
                        # same cover re-layout as the single-chip path,
                        # plus the engine's two trailing sentinel slots
                        new["bip_labels"] = np.concatenate([
                            self._grow_cover(
                                np.asarray(st["bip_labels"][:-2]),
                                self.vb),
                            np.arange(2 * self.vb, 2 * self.vb + 2,
                                      dtype=np.int32)])
                    self._engine.load_state_dict(new)
            if "triangles" in self.analytics:
                self._sh_tri = ShardedTriangleWindowKernel(
                    self.mesh, edge_bucket=self.eb,
                    vertex_bucket=self.vb)
                if self._mesh_live():
                    # every TRIANGLE stream-chunk program compiles at
                    # (re)build time, never mid-stream; the final-flush
                    # analytics programs still first-compile at the
                    # flush — the one violation window scale_run's
                    # assert tolerates. A DEMOTED mesh skips the warm:
                    # compiling against a dead mesh is exactly the
                    # failure we demoted away from (re-promotion warms
                    # on first use instead).
                    self._sh_tri.warm_chunks()
        elif "triangles" in self.analytics:
            self._tri_kernel = tri_ops.TriangleWindowKernel(
                edge_bucket=self.eb, vertex_bucket=self.vb)
            self._tri_kernel.warm_chunks()
        if self.mesh is None:
            # keyed: triangle-less configs keep `first` True forever
            # (no kernel object exists to flip it), and re-warming per
            # window would put 1-3 empty dispatches on the hot path
            key = (self.vb, self.eb, self.analytics)
            if getattr(self, "_warmed_tail", None) != key:
                self._warm_tail_programs()
                self._warmed_tail = key

    def _warm_tail_programs(self) -> None:
        """Compile the per-window analytics programs at the steady
        (eb, vb) shapes by running each once on an EMPTY padded batch.

        Steady-state windows ride the batched snapshot scan; only a
        stream's final partial window falls onto the per-window path —
        which, unwarmed, first-compiled degree_update + cc_fixpoint
        (+ the double-cover form) at the stream TAIL, violating the
        zero-steady-state-compile discipline tools/endurance_run.py
        asserts. The empty batches hit exactly the runtime shapes (the
        edge_bucket clamp pads 0 edges up to eb) and touch no state."""
        import jax.numpy as jnp

        empty = np.zeros(0, np.int32)
        if "degrees" in self.analytics:
            sp = seg_ops.pad_to(empty, self.eb, fill=self.vb)
            seg_ops.degree_update(
                jnp.zeros(self.vb + 1, jnp.int32),
                jnp.asarray(sp), jnp.asarray(sp))
        if "cc" in self.analytics:
            unionfind.connected_components_with_labels(
                empty, empty, empty, 0, vertex_bucket=self.vb,
                edge_bucket=self.eb)
        if "bipartite" in self.analytics:
            unionfind.connected_components_with_labels(
                empty, empty, empty, 0, vertex_bucket=2 * self.vb,
                edge_bucket=2 * self.eb)

    # ------------------------------------------------------------------
    def run_file(self, path: str) -> List[WindowResult]:
        src, dst, ts = native.parse_edge_file(path)
        return self.run_arrays(src, dst, ts)

    def stream_file(self, path: str, chunk_bytes: int = 1 << 26,
                    resume: bool = False):
        """Generator over WindowResults for an arbitrarily large file,
        in bounded memory: the file is parsed in `chunk_bytes` pieces
        (default 64MB ≈ tens of windows per piece, so the batched
        fast path gets full dispatch batches; prefetched ahead in a
        producer thread)
        (io/sources.iter_edge_chunks) and the still-open final window
        of each piece is held back until the next piece closes it —
        tumbling windows never split at chunk boundaries.

        resume=True (after try_resume) skips the `edges_done` edges the
        restored checkpoint already folded into carried state, so
        re-feeding the same file never double-counts.

        Auto-checkpoints taken during the stream are staged and only
        flushed once every window they cover has been YIELDED to the
        consumer (_stage_ckpt) — a crash mid-stream therefore re-emits
        windows on resume (at-least-once) instead of silently dropping
        computed-but-never-delivered ones."""
        from ..io.sources import iter_edge_chunks

        if self._wal is not None:
            # wal_offset is DEFINED as edges_done, and stream_file
            # edges are deliberately never journaled (the file is its
            # own journal) — mixing the two sources would advance the
            # cursor past journaled live edges and make recovery skip
            # them. One driver, one source model.
            raise ValueError(
                "stream_file() on a journal-armed driver would skew "
                "the wal_offset/edges_done contract: use run_arrays "
                "(journaled live feed) OR file streaming, not both "
                "on one driver")
        to_skip = self.edges_done if resume else 0
        pend = (np.zeros(0, np.int64),) * 3
        timestamped = None
        self._emitted = self.windows_done
        self._in_stream += 1  # file source: already replayable, no WAL
        try:
            for src, dst, ts in iter_edge_chunks(path, chunk_bytes):
                if to_skip:
                    drop = min(to_skip, len(src))
                    src, dst, ts = src[drop:], dst[drop:], ts[drop:]
                    to_skip -= drop
                    if not len(src):
                        continue
                chunk_timestamped = bool(len(ts)) and int(ts.max()) >= 0
                if timestamped is None:
                    timestamped = chunk_timestamped
                elif timestamped != chunk_timestamped:
                    raise ValueError(
                        "mixed timestamped and untimestamped chunks")
                src = np.concatenate([pend[0], src])
                dst = np.concatenate([pend[1], dst])
                ts = np.concatenate([pend[2], ts])
                if timestamped:
                    if int(ts.min()) < 0:
                        raise ValueError(
                            "mixed timestamped and untimestamped rows")
                    starts = native.assign_windows(ts, self.window_ms)
                    open_from = int(np.searchsorted(starts, starts[-1]))
                else:
                    open_from = len(src) - (len(src) % self.eb)
                done = slice(0, open_from)
                if open_from:
                    yield from self._emit(self.run_arrays(
                        src[done], dst[done],
                        _starts=starts[done] if timestamped else None))
                pend = (src[open_from:], dst[open_from:], ts[open_from:])
            if len(pend[0]):
                yield from self._emit(self.run_arrays(
                    pend[0], pend[1],
                    pend[2] if timestamped else None))
        finally:
            # abandonment or completion: still-staged checkpoints
            # cover windows the consumer never received — drop them
            # (the last FLUSHED checkpoint stays ≤ what was delivered)
            self._pending_ckpt = []
            self._emitted = None
            self._in_stream -= 1

    def run_arrays(self, src: np.ndarray, dst: np.ndarray,
                   ts: Optional[np.ndarray] = None,
                   _starts: Optional[np.ndarray] = None
                   ) -> List[WindowResult]:
        """Process a (possibly partial) stream. With no timestamps,
        windows are count-based `edge_bucket`-sized chunks (the
        ingestion-time analog at a fixed batch rate). `_starts` lets
        stream_file pass its already-computed window assignment."""
        metrics.on_stream_start("driver", tenant=self.tenant)
        # latency plane: every batch is stamped at THIS admission
        # boundary (the driver's live-feed entry). The WAL ts column
        # stays reserved for EVENT time on this path (replay feeds it
        # back through event-time windowing), so driver replays
        # re-stamp at the replay moment rather than overload it.
        lat_t0 = latency.clock() if latency.enabled() else None
        # "admit" fault site + armed sanitizer (utils/sanitize): the
        # driver's admission boundary. Runs BEFORE the journal below,
        # so a journaled batch is always clean and replay can never
        # re-raise a rejection; the keep-mask filters the aligned
        # ts/_starts columns so event-time windowing stays consistent.
        # GS_SANITIZE=off (default) skips straight to the legacy path.
        got = faults.fire("admit", (self.tenant or "driver", src, dst))
        if got is not None:
            _t, src, dst = got
        if sanitize_mod.enabled():
            try:
                rep = sanitize_mod.sanitize(
                    # vb=None: the driver's ids are EXTERNAL int64
                    # keys the interner densifies (the bucket grows),
                    # so only representability/policy checks apply
                    src, dst, None,
                    tenant=self.tenant or "driver", origin="driver",
                    offset=self._fed_edges,
                    dlq=sanitize_mod.resolve_dlq())
            except sanitize_mod.BatchRejected as e:
                self._fed_edges += e.size
                raise
            self._fed_edges += rep.accepted + rep.rejected
            src, dst = rep.src, rep.dst
            if rep.rejected:
                if ts is not None and len(np.atleast_1d(ts)):
                    ts = np.asarray(ts)[rep.keep]
                if _starts is not None:
                    _starts = np.asarray(_starts)[rep.keep]
        else:
            self._fed_edges += len(np.atleast_1d(np.asarray(src)))
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)

        def _journal(ts_arr=None):
            # durability boundary of the LIVE feed path: journal
            # (with timestamps when event-time) AFTER validation —
            # a rejected batch must leave no journal record, or the
            # journal offsets skew against edges_done and replay
            # re-raises the rejection — and BEFORE any window is cut,
            # so a kill past this point is recoverable by
            # resume_and_replay(). stream_file never journals: its
            # file is its own journal (and enable_wal refuses the
            # mixed-source mode outright).
            if self._wal is not None and len(src) \
                    and not self._in_stream:
                self._wal.append(self.tenant or "driver", src, dst,
                                 ts_arr)
                faults.fire("wal_enqueue", self.tenant or "driver")
        if _starts is not None or (
                ts is not None and len(ts) and int(np.max(ts)) >= 0):
            if self._wp > 1:
                raise ValueError(
                    "sliding windows (slide=) are count-based: "
                    "event-time streams window by window_ms (panes "
                    "over event time need an upstream assigner)")
            if _starts is not None:
                starts = _starts
            else:
                ts = np.asarray(ts, np.int64)
                if int(np.min(ts)) < 0:
                    raise ValueError(
                        "mixed timestamped and untimestamped rows: every "
                        "edge needs a timestamp for event-time windows "
                        "(rows without a third column parse as ts=-1)")
                starts = native.assign_windows(ts, self.window_ms)
            if np.any(np.diff(starts) < 0):
                raise ValueError(
                    "timestamps must be ascending (the reference's "
                    "AscendingTimestampExtractor contract, "
                    "SimpleEdgeStream.java:90-94)")
            bounds = np.flatnonzero(np.diff(starts)) + 1
            slices = np.split(np.arange(len(src)), bounds)
            windows = [(int(starts[idx[0]]), src[idx], dst[idx])
                       for idx in slices if len(idx)]
            _journal(np.asarray(ts, np.int64)
                     if ts is not None and len(np.atleast_1d(ts))
                     else None)
            if lat_t0 is not None:
                latency.on_admit(self.tenant or "driver", len(src),
                                 t0=lat_t0)
            return self._dispatch_windows(windows)
        # count-based: window_start = absolute stream offset; the
        # edges_done cursor advances per window (inside _window, so
        # checkpoints carry it), making chunked calls accumulate
        if self._closed_partial:
            # same guard as scan_analytics.SummaryEngineBase.process:
            # a previous call already closed a short window, so feeding
            # more edges would silently shift every subsequent window
            # boundary relative to a single whole-stream call
            raise ValueError(
                "a previous count-based run closed a partial window "
                "(length not a multiple of edge_bucket); chunked "
                "count-based feeding must use edge_bucket multiples")
        _journal()
        if lat_t0 is not None:
            latency.on_admit(self.tenant or "driver", len(src),
                             t0=lat_t0)
        windows = []
        at = self.edges_done
        cut = self._cut_size()
        for i in range(0, len(src), cut):
            idx = slice(i, min(i + cut, len(src)))
            windows.append((at, src[idx], dst[idx]))
            at += idx.stop - idx.start
        return self._dispatch_windows(windows, count_based=True)

    def _cut_size(self) -> int:
        """Emission granularity of the count-based path: the pane size
        under sliding windows (each `slide`-edge pane fires one
        emission whose triangle slab composes the ring — _window), the
        whole edge bucket otherwise."""
        return self.slide if self._wp > 1 else self.eb

    def _dispatch_windows(self, windows,
                          count_based: bool = False
                          ) -> List[WindowResult]:
        """Route a call's windows: the batched snapshot-scan fast path
        on multi-window calls (single-chip jit or shard_map over the
        mesh), the per-window path (with
        batched triangle dispatch) otherwise."""
        # sliding: pane emissions stay on the per-window path — the
        # batched snapshot scan pads whole-eb slabs and its triangle
        # stack counts the raw window, not the composed pane ring (the
        # per-window path still batches triangle dispatches through
        # _batched_triangles, so it is one count_windows flush per call)
        batched_ok = len(windows) > 1 and self._wp == 1
        if batched_ok and self.mesh is not None:
            from ..parallel.mesh import shard_count

            # shard_map splits the edge axis: the stack's eb must
            # divide evenly (power-of-two buckets on power-of-two
            # meshes always do)
            batched_ok = self.eb % shard_count(self.mesh) == 0
        cut = self._cut_size()
        with self._batched_triangles():
            if batched_ok:
                return self._run_batched(
                    windows,
                    closes_partial=(count_based
                                    and len(windows[-1][1]) < cut))
            out = []
            for wstart, s, d in windows:
                if count_based and len(s) < cut:
                    # set ONLY when the short final window is actually
                    # being emitted, so a checkpoint taken by an
                    # earlier window of this call never persists a
                    # closed_partial the restored state hasn't seen
                    self._closed_partial = True
                out.append(self._window(wstart, s, d))
            return out

    # ------------------------------------------------------------------
    # batched fast path (single-chip or sharded): all of a call's
    # windows in one
    # snapshot-scan dispatch (+ one count_windows dispatch)
    # ------------------------------------------------------------------
    _SCAN_CHUNK = 64  # max windows per dispatch; W pads to buckets

    def _scan_chunk(self) -> int:
        """Windows per snapshot-scan dispatch: _SCAN_CHUNK, compile-
        size-capped on TPU backends (ops/triangles.COMPILE_CAP)."""
        return min(self._SCAN_CHUNK, tri_ops.capped_chunk(self.eb))

    def _scan_tuner_key(self) -> str:
        return ("snapshot_scan:eb=%d:vb=%d:%s"
                % (self.eb, self.vb, "+".join(self.analytics)))

    # ------------------------------------------------------------------
    # resident tier (ops/resident_engine): windows-per-superbatch cap,
    # its own tuner family, and the chunk cap the scan-cache helpers
    # read — the donated program family dispatches super-batches of
    # GS_RESIDENT_SPB windows instead of _SCAN_CHUNK chunks
    # ------------------------------------------------------------------
    def _resident_chunk(self) -> int:
        from ..ops import resident_engine

        return resident_engine.resident_spb(self.eb)

    def _chunk_cap(self) -> int:
        """Windows-per-dispatch cap of the ACTIVE program family:
        the resident super-batch while the resident tier runs, the
        compile-capped scan chunk otherwise."""
        return (self._resident_chunk() if self._resident_now
                else self._scan_chunk())

    def _resident_tuner_key(self) -> str:
        return ("resident_scan:eb=%d:vb=%d:%s"
                % (self.eb, self.vb, "+".join(self.analytics)))

    def _ensure_resident_tuner(self):
        """The resident tier's windows-per-superbatch tuner
        (ops/autotune): power-of-two rungs under the resident cap,
        keyed as its own family so scan-tier rates never cross-seed
        it. None when GS_AUTOTUNE=0 — the static super-batch stepping
        then runs bit-identically."""
        from ..ops import autotune

        if not autotune.enabled():
            return None
        if getattr(self, "_resident_tuner", None) is None:
            cap = self._resident_chunk()
            wbs = sorted({max(1, cap // 4), max(1, cap // 2), cap})
            self._resident_tuner = autotune.DispatchTuner(
                self._resident_tuner_key(), {"wb": wbs}, {"wb": cap})
        return self._resident_tuner

    def _ensure_scan_tuner(self):
        """The driver's online windows-per-dispatch tuner for the
        batched snapshot scan (ops/autotune): arms are power-of-two
        rungs under the compile-capped _scan_chunk(). None when
        GS_AUTOTUNE=0 — the static stepping then runs bit-identically."""
        from ..ops import autotune

        if not autotune.enabled():
            return None
        if getattr(self, "_scan_tuner", None) is None:
            cap = self._scan_chunk()
            wbs = sorted({max(1, cap // 4), max(1, cap // 2), cap})
            self._scan_tuner = autotune.DispatchTuner(
                self._scan_tuner_key(), {"wb": wbs}, {"wb": cap})
        return self._scan_tuner

    def _warm_scan_arm(self, take: int) -> None:
        """Compile (and execute once, on an all-padding stack against a
        throwaway carry) the W-bucket program a chunk of `take` windows
        needs BEFORE its first measured chunk, with the cut of its
        outputs to `take` real rows (_real_rows) when the bucket is
        larger, so exploration never compiles — and the warm run never
        touches carried state. Keyed like the scan cache; re-warms
        after bucket growth invalidates it."""
        import jax
        import jax.numpy as jnp

        wb = seg_ops.bucket_size(take)
        warmed = getattr(self, "_warmed_scan_arms", None)
        key3 = self._scan_key()
        if warmed is None or warmed[0] != key3:
            warmed = self._warmed_scan_arms = (key3, set())
        if (wb, take) in warmed[1]:
            return
        # prime the program cache for THIS bucket (bypassing _scan_wb's
        # bigger-bucket reuse — the arm must compile its own size)
        if getattr(self, "_scan_cache_key", None) != key3:
            self._scan_cache = {}
            self._scan_cache_key = key3
        fn = self._scan_fn_at(wb)
        vb = self.vb
        carry = (jnp.zeros(vb + 1, jnp.int32),
                 jnp.arange(vb + 1, dtype=jnp.int32),
                 jnp.arange(2 * vb + 1, dtype=jnp.int32))
        s_w = jnp.full((wb, self.eb), vb, jnp.int32)
        valid = jnp.zeros((wb, self.eb), jnp.bool_)
        _carry, outs = fn(carry, s_w, s_w, valid)
        if take < wb:
            outs = _real_rows(outs, take)
        jax.block_until_ready(outs)  # the compiles must finish here
        warmed[1].add((wb, take))

    def _scan_wb(self, num_w: int) -> int:
        """The W-bucket the snapshot scan will run `num_w` windows at
        — the bucket selection WITHOUT building a program, so the
        ingress pipeline's prep worker can size a chunk's stacks off
        the main thread. A W-bucket with no compiled program reuses
        the smallest already-compiled LARGER bucket instead (sentinel
        window rows are no-ops, outputs are read per real row), so a
        long stream's ragged final chunk never compiles at the tail
        (tools/endurance_run.py's steady-state assert); right-sized
        programs still compile for callers whose FIRST batch is small
        (the per-window dispatch mode)."""
        wb = seg_ops.bucket_size(min(num_w, self._chunk_cap()))
        key3 = self._scan_key()
        if getattr(self, "_scan_cache_key", None) != key3:
            self._scan_cache = {}
            self._scan_cache_key = key3
        if wb not in self._scan_cache:
            bigger = [b for b in self._scan_cache if b > wb]
            if bigger:
                wb = min(bigger)
        return wb

    def _scan_key(self):
        """Identity of the compiled snapshot-scan program family —
        bucket growth, analytics, the egress format (a delta program
        emits a different out tree), mesh liveness (a demotion off
        the sharded tier switches the family to the single-chip
        programs; re-promotion switches back) AND the resident flag
        (the resident tier's donated programs are a distinct family —
        a demotion to scan must never dispatch a donating program)
        invalidate the cache."""
        return (self.vb, self.eb, self.analytics, self._scan_egress(),
                self._mesh_live(), self._resident_now)

    def _scan_egress(self) -> str:
        """The batched scan's d2h egress format: the constructor pin,
        else the GS_EGRESS knob (ops/delta_egress.resolve_egress).
        Sharded meshes always run full-vector egress — their snapshots
        ride replicated shard_map outputs, symmetric to compact
        ingress staying off the mesh path."""
        if self.mesh is not None:
            return "full"
        return self._egress_pin or delta_egress.resolve_egress()

    def _scan_fn_at(self, wb: int):
        """Jitted snapshot scan for exactly W-bucket `wb` (selection
        already applied by _scan_wb), cached per
        (vb, eb, analytics, egress, W-bucket) — O(log) programs
        total."""
        if wb not in self._scan_cache:
            name = "snapshot_scan"
            if self._mesh_live():
                from ..parallel.sharded import make_sharded_snapshot_scan

                fn = make_sharded_snapshot_scan(
                    self.mesh, self.vb, self.analytics,
                    deltas=self.emit_deltas)
            else:
                fn = _build_snapshot_scan(
                    self.vb, self.analytics, deltas=self.emit_deltas,
                    egress=self._scan_egress(),
                    cap=delta_egress.egress_cap(self.eb, self.vb),
                    donate=self._resident_now)
                if self._resident_now:
                    name = "resident_scan"
            # compile watch (utils/metrics): every distinct abstract
            # signature this program family sees counts against the
            # O(log V) recompile envelope
            self._scan_cache[wb] = metrics.wrap_jit(name, fn)
        return self._scan_cache[wb]

    def _run_batched(self, windows,
                     closes_partial: bool = False) -> List[WindowResult]:
        """Process [(wstart, src, dst), ...] with ONE snapshot-scan
        dispatch per _SCAN_CHUNK windows and one batched triangle
        dispatch, instead of per-window per-analytic round trips.
        Semantics identical to the per-window path (same fixpoint
        kernels and carried state; single-chip carries the host
        mirrors, sharded carries the ShardedWindowEngine's state).

        Consistency unit = one chunk: cursors, host mirrors, and the
        auto-checkpoint all advance together at each chunk boundary, so
        an exception mid-call leaves the driver exactly at the last
        completed chunk (resumable), never with cursors ahead of
        mirrors."""
        import jax.numpy as jnp

        # intern everything first: buckets grow ONCE for the call. The
        # per-element hash-map work rides the ingress prep pool
        # (utils/interning.parallel_intern_arrays: first-occurrence
        # uniques + dense scatter run parallel, slot ASSIGNMENT stays
        # sequential over the tiny unique lists — slots are identical
        # to the sequential loop at every pool size); sizes[] gives
        # each window's post-intern vertex cursor for snapshot slicing
        total_edges = sum(len(s) for _w, s, _d in windows)
        with self._step("intern", 2 * total_edges):
            flat = []
            for _wstart, src, dst in windows:
                flat.append(src)
                flat.append(dst)
            dense, sizes = parallel_intern_arrays(self.interner, flat)
        interned = [
            (windows[i][0], dense[2 * i], dense[2 * i + 1],
             sizes[2 * i + 1])
            for i in range(len(windows))]
        nv_final = len(self.interner)
        max_len = max(len(s) for _w, s, _d, _n in interned)
        self._ensure_buckets(nv_final, max_len)

        # tier-demotion loop: chunks are the consistency unit (mirrors,
        # cursors, checkpoints move together at each boundary), so a
        # persistent device failure at chunk k leaves the driver
        # resumable at the last finalized chunk — demote the snapshot
        # tier (scan→native→host), rebuild the carried state from the
        # mirrors, and continue with the NOT-yet-finalized windows.
        results: List[WindowResult] = []
        while True:
            tier = self._effective_tier()
            try:
                self._scan_interned(interned[len(results):], results,
                                    closes_partial, tier)
                return results
            except resilience.StageError as e:
                if not self._maybe_demote(tier, e):
                    raise

    def _base_tier(self) -> str:
        """The tier this driver runs on when nothing is demoted: the
        mesh when one was given, else the pinned/resolved single-chip
        snapshot tier."""
        if self.mesh is not None:
            return "sharded"
        return self._snapshot_tier or resolve_snapshot_tier()

    def _mesh_live(self) -> bool:
        """True while the sharded engines are the active tier: a mesh
        was configured AND no demotion has pushed the stream onto the
        single-chip ladder (re-promotion restores it)."""
        return self.mesh is not None and self._demoted_tier is None

    def _mesh_shape(self):
        """Device counts per mesh axis (degradations provenance);
        None on a single-chip driver."""
        if self.mesh is None:
            return None
        return [int(x) for x in self.mesh.devices.shape]

    def _effective_tier(self) -> str:
        """The snapshot tier the next chunk runs on: a live demotion
        wins over the pin/resolution; after GS_TIER_RETRY_WINDOWS
        windows of probation on the demoted tier, one re-promotion
        probe runs the higher tier again (a repeat failure re-demotes
        and restarts probation). Re-promoting a mesh session back to
        the sharded tier pushes the host mirrors — which carried the
        stream through probation — back into the engine state."""
        if self._demoted_tier is not None:
            n = resilience.tier_retry_windows()
            if n and self.windows_done - self._demoted_at >= n:
                prev = self._demoted_tier
                if self.mesh is not None:
                    # stage the slabs BEFORE declaring the probe: a
                    # mesh still dead at probe time fails the h2d
                    # here — record the failed probe, restart
                    # probation, and stay on the demoted tier (the
                    # documented repeat-failure-re-demotes contract)
                    # instead of crashing the stream
                    try:
                        self._sync_engine_from_mirrors()
                    except Exception as e:
                        if not isinstance(e, (RuntimeError, OSError,
                                              MemoryError)):
                            # same filter as _maybe_demote: a semantic
                            # error is a programming bug, never a dead
                            # mesh — surface it, don't mask it as a
                            # failed probe forever
                            raise
                        event = resilience.record_demotion(
                            "snapshot", prev, prev, self.windows_done,
                            "re-promotion probe failed (%s: %s); "
                            "probation restarted"
                            % (type(e).__name__, e),
                            mesh_shape=self._mesh_shape(),
                            tenant=self.tenant)
                        self._demotions.append(event)
                        self._demoted_at = self.windows_done
                        return prev
                event = resilience.record_demotion(
                    "snapshot", prev, self._base_tier(),
                    self.windows_done,
                    "re-promotion probe after %d probation windows"
                    % (self.windows_done - self._demoted_at),
                    mesh_shape=self._mesh_shape(),
                    tenant=self.tenant)
                self._demotions.append(event)
                if self.timer:
                    self.timer.event("tier_repromotion", event)
                self._demoted_tier = None
            else:
                return self._demoted_tier
        return self._base_tier()

    def _maybe_demote(self, tier: str, err: BaseException) -> bool:
        """Decide whether `err` on `tier` demotes to the next ladder
        rung. Only failure shapes a tier change can plausibly cure
        demote — stage timeouts and wrapped runtime/OS-level failures
        (a hung transfer, a dead device or shard, an injected fault);
        semantic errors (ValueError/TypeError/...) re-raise so a
        programming bug is never silently 'fixed' by falling off the
        fast tier.

        The full ladder is sharded → resident → scan → native → host
        (resident — the donated megakernel above scan — demotes TO
        scan but is never itself a demotion target):
        a mesh session that loses a shard degrades to one device (the
        engine's gathered replicated state becomes the host mirrors —
        the same chunk-boundary sources the single-chip rungs re-enter
        from) instead of wedging; GS_MESH_DEMOTE=0 pins the mesh rung
        specifically, GS_TIER_DEMOTE=0 pins them all."""
        if not resilience.tier_demotion_enabled():
            return False
        if tier == "sharded" and not resilience.mesh_demotion_enabled():
            return False
        cause = err.__cause__
        if not isinstance(err, resilience.StageTimeout):
            if not isinstance(cause, (RuntimeError, OSError,
                                      MemoryError)):
                return False
        order = ("sharded", "resident", "scan", "native", "host")
        shard_id = getattr(cause, "shard", None)
        for nxt in order[order.index(tier) + 1:]:
            if nxt == "resident":
                # never a demotion TARGET: resident is the tier ABOVE
                # scan — a device failure on any rung lands on the
                # proven scan rung, not on a bigger donated program
                # against the same ailing device
                continue
            if nxt == "native" and not native.snapshot_available():
                continue
            event = resilience.record_demotion(
                "snapshot", tier, nxt, self.windows_done,
                "%s: %s" % (type(err).__name__, err),
                mesh_shape=self._mesh_shape(), shard_id=shard_id,
                tenant=self.tenant)
            self._demotions.append(event)
            if self.timer:
                self.timer.event("tier_demotion", event)
            if tier == "sharded":
                # leaving the mesh: the engine's gathered state
                # becomes the host mirrors every lower rung re-enters
                # from (and the twin a resumed host session loads)
                self._absorb_engine_state()
            self._demoted_tier = nxt
            self._demoted_at = self.windows_done
            return True
        return False

    # ------------------------------------------------------------------
    # mesh ↔ mirror state conversion (the demotion/re-promotion and
    # cross-mode resume hand-off): the engine's slabs are replicated
    # (parallel/sharded state_dict docstring), so the gathered copy IS
    # the single-chip layout up to slicing — degrees/labels [:nv],
    # cover [:2vb] (both layouts place (−) at vb + v)
    # ------------------------------------------------------------------
    def _absorb_engine_state(self, engine_state: dict = None) -> None:
        """Engine slabs → host mirrors (every sharded finalized
        boundary, mesh demotion, sharded checkpoint resumed off-mesh).
        Called with no state at DEMOTION time, the gather from the
        failing mesh is best-effort only: the batched and per-window
        sharded paths both refresh the mirrors at their boundaries, so
        when the d2h itself died with the mesh the mirrors already
        hold the last finalized state and the demotion proceeds
        without touching the mesh at all."""
        st = engine_state
        if st is None:
            if self._engine is None:
                return
            try:
                st = self._engine.state_dict()
            except Exception as e:
                telemetry.event(
                    "mesh_gather_failed", durable=True,
                    window=self.windows_done,
                    error="%s: %s" % (type(e).__name__, e))
                return  # mirrors are the truth (see above)
        nv = len(self.interner)
        deg = np.asarray(st["degree_state"])
        self._degrees = deg[:nv].astype(np.int64)
        self._deg_state = None
        self._cc = np.asarray(st["labels"])[:nv].astype(np.int32)
        if "bip_labels" in st:
            # engine cover is [2vb+2] ((−) at vb + v, two trailing
            # sentinels); the mirror keeps the [2vb] meaningful run
            cov = np.asarray(st["bip_labels"])
            self._bip = cov[:len(cov) - 2].astype(np.int32)

    def _engine_state_from_mirrors(self) -> dict:
        """Engine-layout state assembled PURELY from the host mirrors
        — no mesh access, so a demoted session can checkpoint (and a
        re-promotion can stage its slabs) without touching the dead
        mesh. Sentinel slots reset to their identities — they absorb
        padding and feed no output, so results are unchanged."""
        vb = self.vb
        st = {"vb": vb, "mesh_shape": self._mesh_shape()}
        deg = np.zeros(vb + 2, np.int32)
        deg[:len(self._degrees)] = self._degrees
        st["degree_state"] = deg
        lab = np.arange(vb + 2, dtype=np.int32)
        lab[:len(self._cc)] = self._cc
        st["labels"] = lab
        if len(self._bip):
            if len(self._bip) != 2 * vb:
                self._bip = self._grow_cover(self._bip, vb)
            cov = np.arange(2 * vb + 2, dtype=np.int32)
            cov[:2 * vb] = self._bip
            st["bip_labels"] = cov
        return st

    def _sync_engine_from_mirrors(self) -> None:
        """Host mirrors → engine slabs (mesh re-promotion /
        single-chip checkpoint resumed onto a mesh)."""
        if self._engine is None:
            return
        self._engine.load_state_dict(self._engine_state_from_mirrors())

    def demotion_log(self) -> List[dict]:
        """Demotion/re-promotion events of this driver's lifetime (the
        process-global registry in utils/resilience feeds PERF.json's
        `degradations` section; this is the per-driver view)."""
        return list(self._demotions)

    def _scan_interned(self, interned, results, closes_partial: bool,
                       tier: str) -> None:
        """Run the batched snapshot analytics over `interned`
        [(wstart, s, d, nv)] on `tier`, appending per-window results.
        Carried state is (re)built from the chunk-boundary sources —
        host mirrors / engine state — at entry, which is what makes
        the call re-enterable after a mid-stream tier demotion."""
        import jax.numpy as jnp

        if not interned:
            return
        vb = self.vb
        run_scan = any(a in self.analytics
                       for a in ("degrees", "cc", "bipartite"))
        # tier-driven, NOT engine-driven: a mesh session demoted off
        # the sharded rung runs the single-chip programs against the
        # host mirrors (populated by _absorb_engine_state) even though
        # its engine object still exists for the re-promotion path
        sharded = tier == "sharded"
        # resident tier (ops/resident_engine): the device-scan branch
        # with the donated super-batch program family, prep+h2d on the
        # ingest ring, and one dispatch per GS_RESIDENT_SPB windows.
        # The flag keys the program cache (_scan_key), so a demotion
        # to scan mid-call switches families cleanly at re-entry.
        resident = tier == "resident"
        self._resident_now = resident
        # native/host tiers of the snapshot stage: carried union-find
        # + degree fold (C++ or numpy — bit-exact twins) producing the
        # SAME per-window `outs`
        # stacks as the scan — including, under emit_deltas, the
        # changed-slot masks (host-diffed against the chunk-start
        # snapshot below, same semantics as the scan's device masks).
        native_state = None
        if run_scan and not sharded and tier in ("native", "host"):
            # COPIES (never aliases of the mirrors): the C++/numpy
            # kernels fold unions in place mid-chunk, and mirrors must
            # only move at chunk boundaries (the consistency unit —
            # an exception mid-chunk leaves them resumable)
            native_state = self._chunk_start_state()
        carry = None
        if run_scan and sharded:
            # carried state straight from the engine (its layouts:
            # deg/labels [vb+2], cover [2vb+2])
            st = self._engine.state_dict()
            cov0 = (st["bip_labels"] if "bip_labels" in st
                    else np.arange(2 * vb + 2, dtype=np.int32))
            carry = (jnp.asarray(st["degree_state"]),
                     jnp.asarray(st["labels"]), jnp.asarray(cov0))
        elif run_scan and native_state is None:
            # carried state from the host mirrors (same sources the
            # per-window path uses)
            deg0 = np.zeros(vb + 1, np.int32)
            deg0[:len(self._degrees)] = self._degrees
            lab0 = np.arange(vb + 1, dtype=np.int32)
            lab0[:len(self._cc)] = self._cc
            if "bipartite" in self.analytics \
                    and len(self._bip) != 2 * vb:
                self._bip = self._grow_cover(self._bip, vb)
            cov0 = np.arange(2 * vb + 1, dtype=np.int32)
            cov0[:len(self._bip)] = self._bip
            carry = (jnp.asarray(deg0), jnp.asarray(lab0),
                     jnp.asarray(cov0))

        num_w = len(interned)
        scan_chunk = self._chunk_cap()
        # Depth-2 pipeline over the DEVICE scan branch: the scan carry
        # is a device array, so chunk i+1's dispatch needs only the
        # un-materialized carry — chunk i's d2h + extraction + chunk-
        # boundary bookkeeping run while the device executes chunk
        # i+1. The consistency unit is unchanged: a chunk's mirrors/
        # cursors/checkpoint still move only in its finalize, in chunk
        # order; an exception mid-call still leaves the driver at the
        # last FINALIZED chunk (resumable). The host/native tier stays
        # synchronous — one core, nothing to overlap with.
        disp_t = [None]  # dispatch-boundary stamp of the pending chunk

        def _boundary(at, chunk):
            # chunk boundary: cursors, the partial flag, and the
            # checkpoint move together (mirrors moved just before)
            edges = sum(len(s) for _w, s, _d, _n in chunk)
            if latency.enabled():
                # per-window ingest→deliver records (the driver's
                # coarse decomposition: its dispatch boundary folds
                # prep+h2d+device wait; synchronous tiers have no
                # dispatch stamp and report admission+finalize only).
                # Results were appended by this chunk's finalize, so
                # each record attaches to its WindowResult.
                st = ({"dispatch": disp_t[0]}
                      if disp_t[0] is not None else None)
                disp_t[0] = None
                lane = self.tenant or "driver"
                for i, (_w, s, _d, _n) in enumerate(chunk):
                    rec = latency.on_window(
                        lane, edges=len(s), st=st,
                        ordinal=self.windows_done + i)
                    if rec is not None \
                            and len(results) >= len(chunk):
                        res = results[len(results) - len(chunk) + i]
                        res.latency = {
                            "e2e_s": rec["e2e_s"],
                            "stages": dict(rec["stages"]),
                            "replayed": rec["replayed"],
                        }
            if provenance.armed() and len(results) >= len(chunk):
                # this chunk's finalize just appended its results;
                # wal_lo/hi follow the edges_done cursor (== the
                # checkpoint's wal_offset contract)
                lane = self.tenant or "driver"
                lo = self.edges_done
                for i, (_w, s, _d, _n) in enumerate(chunk):
                    res = results[len(results) - len(chunk) + i]
                    provenance.emit(
                        tenant=lane, window=self.windows_done + i,
                        wal_lo=lo, wal_hi=lo + len(s),
                        tier=tier, program="driver",
                        digest=provenance.result_digest(res))
                    lo += len(s)
            self.windows_done += len(chunk)
            self.edges_done += edges
            metrics.mark_window(len(chunk), edges, engine="driver",
                                tier=tier,
                                mesh_shape=self._mesh_shape(),
                                tenant=self.tenant)
            if closes_partial and at + len(chunk) >= num_w:
                # the short final window lives in this chunk: the flag
                # joins this boundary's state (and its checkpoint),
                # never an earlier one's
                self._closed_partial = True
            if self._ckpt_due():
                self._stage_ckpt()

        def _finalize_chunk(at, chunk, outs):
            if any(key in outs for key in
                   ("deg_cnt", "labels_cnt", "cover_cnt")):
                # delta-compacted egress (ops/delta_egress)
                if self._delta_overflowed(outs):
                    # a label cascade outran the changed-slot cap:
                    # the chunk refolds on the bit-exact host twin
                    # and takes the full-vector extraction below
                    outs = self._refold_chunk_outs(chunk)
                else:
                    self._emit_delta_chunk(chunk, outs, results)
                    _boundary(at, chunk)
                    return
            nv_chunk = chunk[-1][3]
            last = len(chunk) - 1
            for i, (wstart, s, d, nv) in enumerate(chunk):
                res = WindowResult(
                    window_start=wstart, num_edges=len(s),
                    vertex_ids=self._vertex_ids(nv))
                if "deg" in outs:
                    snap = outs["deg"][i][:nv].astype(np.int64)
                    self._check_degree_width(snap)
                    res.degrees = _snapshot_view(snap)
                    if "deg_chg" in outs:
                        idx = np.nonzero(
                            outs["deg_chg"][i][:nv])[0].astype(np.int32)
                        res.delta_degrees = _frozen_delta(idx, snap[idx])
                if "labels" in outs:
                    res.cc_labels = _snapshot_view(
                        outs["labels"][i][:nv], self.vb)
                    if "labels_chg" in outs:
                        idx = np.nonzero(
                            outs["labels_chg"][i][:nv])[0].astype(
                                np.int32)
                        res.delta_cc = _frozen_delta(
                            idx, res.cc_labels[idx])
                if "odd" in outs:
                    res.bipartite_odd = _snapshot_view(
                        outs["odd"][i][:nv], self.vb)
                    if "cover_chg" in outs:
                        idx = np.nonzero(
                            outs["cover_chg"][i][:nv])[0].astype(
                                np.int32)
                        res.delta_bipartite = _frozen_delta(
                            idx, np.asarray(res.bipartite_odd)[idx])
                if "triangles" in self.analytics:
                    # _batched_triangles (always active around this
                    # path when triangles are on) flushes these in one
                    # count_windows dispatch on clean exit
                    self._tri_pending.append(
                        (res, np.asarray(s, np.int32),
                         np.asarray(d, np.int32)))
                results.append(res)

            # ---- chunk boundary: mirrors, cursors, checkpoint move
            # together. Mirror values come from the chunk's LAST
            # window row (== the carry, no extra d2h) and the cover's
            # from the carry itself, read back once a chunk.
            if sharded and run_scan:
                # engine.state_dict() is a full d2h sync — fetch it
                # only for keys the scan did NOT produce (all enabled
                # analytics come from `outs`, so usually never)
                st = {"vb": vb}
                cur = None
                for key, out_key in (("degree_state", "deg"),
                                     ("labels", "labels")):
                    if out_key in outs:
                        st[key] = outs[out_key][last]
                    else:
                        cur = cur or self._engine.state_dict()
                        st[key] = cur[key]
                if "cover_final" in outs:
                    st["bip_labels"] = outs["cover_final"]
                else:
                    cur = cur or self._engine.state_dict()
                    if "bip_labels" in cur:
                        st["bip_labels"] = cur["bip_labels"]
                self._engine.load_state_dict(st)
                # host mirrors track every finalized boundary in
                # sharded mode too: the demotion hand-off must never
                # depend on a d2h gather from the very mesh that just
                # failed, and the rows here are already materialized
                # host arrays — the copies are O(vb)
                self._absorb_engine_state(st)
            else:
                if "deg" in outs:
                    self._degrees = outs["deg"][last][:nv_chunk].astype(
                        np.int64)
                    self._deg_state = None  # per-window path: rebuild
                if "labels" in outs:
                    self._cc = outs["labels"][last][:nv_chunk].copy()
                if "cover_final" in outs:
                    self._bip = outs["cover_final"][:2 * vb].copy()
            _boundary(at, chunk)

        # (at, chunk, device outs, W-bucket sentinel rows, superbatch
        # stopwatch)
        pending = None

        def finalize_pending():
            nonlocal pending
            if pending is None:
                return
            f_at, f_chunk, f_outs, f_sentinel, f_sw = pending
            pending = None
            with self._step("snapshot_wait",
                            sum(len(s) for _w, s, _d, _n in f_chunk)):
                # the materialize leg of the snapshot path: a hung d2h
                # surfaces as a typed
                # StageTimeout (deadline only — the d2h is a pure read,
                # but a retry would re-block on the same dead transfer)
                def _mat(f_outs=f_outs):
                    faults.fire("finalize")
                    return {k: np.asarray(v) for k, v in f_outs.items()}

                f_outs = resilience.call_guarded(
                    "finalize", f_at, _mat, retries=0)
            _readback_counters(f_outs, len(f_chunk), f_sentinel)
            # the dispatch boundary of this chunk's waterfall closes
            # with the materialize (device execute + d2h, observed)
            disp_t[0] = (latency.clock() if latency.enabled()
                         else None)
            with self._step("snapshot_extract",
                            sum(len(s) for _w, s, _d, _n in f_chunk)):
                _finalize_chunk(f_at, f_chunk, f_outs)
            if f_sw is not None:
                # the resident super-batch span closes at its DRAIN —
                # dispatch through materialize + extraction (the
                # owner-rule mark_window already fired in _boundary)
                f_sw.stop(windows=len(f_chunk))

        # prep stage of the device-scan branch: the [wb, eb] stack
        # build for chunk i+1 runs on the ingress prep pool while
        # chunk i executes on device (the scan carry forces dispatches
        # sequential, so only prep — and, on the resident tier, h2d —
        # pipelines). The scan tier keeps its single lookahead; the
        # RESIDENT tier runs a GS_RESIDENT_SLOTS ingest ring whose
        # worker task is prep + h2d of a whole super-batch, so slot
        # N+1 transfers while super-batch N computes. The W-bucket is
        # chosen on the MAIN thread at submit time (item = (chunk,
        # wb)): _scan_wb reads/mutates the jit cache, which a pool
        # worker must never touch concurrently with _scan_fn_at's
        # insertions.
        def _build_stack(item):
            chunk, wb = item
            s_w, d_w, valid = seg_ops.stack_window_rows(
                [(s, d) for _w, s, d, _n in chunk], wb, self.eb, vb)
            return wb, s_w, d_w, valid

        def _build_dev(item):
            # resident ring task: prep + h2d on the worker
            # (jnp.asarray is thread-safe — the run_pipeline h2d
            # contract), so the main thread's only steady-state job is
            # dispatching and draining
            wb, s_w, d_w, valid = _build_stack(item)
            return (wb, jnp.asarray(s_w), jnp.asarray(d_w),
                    jnp.asarray(valid))

        build_job = _build_dev if resident else _build_stack

        def _build_inline(item):
            # a stack the ring built is spanned by the pool's own prep
            chunk, _wb = item
            with telemetry.span("ingress.prep", window=chunk[0][0]):
                return build_job(item)

        from ..ops import resident_engine

        ring = resident_engine.IngestRing(
            slots=resident_engine.ring_slots() if resident else 1)
        fold = (native.snapshot_windows if tier == "native"
                else host_snapshot.snapshot_windows)

        # online wb tuning of the DEVICE scan branch (ops/autotune):
        # each chunk is one measurement round; the arm (its window
        # count) is decided — and its W-bucket program warmed — at the
        # chunk's PREP-submit point, so exploration never compiles
        # mid-measurement. GS_AUTOTUNE=0 (or the native/host/sharded
        # branches) keeps the static scan_chunk stepping
        # bit-identically. The resident tier tunes its own family —
        # windows-per-superbatch rungs under the resident cap.
        tuner = ((self._ensure_resident_tuner() if resident
                  else self._ensure_scan_tuner())
                 if run_scan and not sharded and native_state is None
                 else None)
        decided = {}  # chunk start -> (take, arm)

        def _decide(pos):
            if pos not in decided:
                if tuner is None:
                    decided[pos] = (scan_chunk, None)
                else:
                    arm = (tuner.best()
                           if ingress_pipeline.forced_sync_active()
                           else tuner.next_round())
                    take = min(arm["wb"], num_w - pos)
                    # warm the bucket the round will ACTUALLY dispatch
                    # (not the arm's full rung): pre-compiling an
                    # oversized bucket would hand _scan_wb's
                    # bigger-bucket reuse to every small call, making
                    # 2-window pieces pay a full-rung scan of
                    # sentinel rows
                    self._warm_scan_arm(take)
                    decided[pos] = (take, arm)
            return decided[pos]

        meas = None  # (arm, edges, stopwatch) of the last dispatch

        def _meas_flush():
            nonlocal meas
            if meas is not None and tuner is not None \
                    and not ingress_pipeline.forced_sync_active():
                arm, edges, sw = meas
                if arm is not None:
                    # the telemetry stopwatch closes the dispatch-to-
                    # dispatch round (recording the span when armed)
                    tuner.record(arm, edges, sw.stop(edges=edges))
            meas = None

        next_submit = 0  # first window position not yet on the ring

        def _chunk_loop():
          nonlocal carry, native_state, pending, meas, next_submit
          at = 0
          while at < num_w:
            take, cur_arm = _decide(at)
            chunk = interned[at:at + take]
            outs = {}
            if run_scan and native_state is not None:
                flat_s = np.concatenate(
                    [s for _w, s, _d, _n in chunk])
                flat_d = np.concatenate(
                    [d for _w, _s, d, _n in chunk])
                offs = np.zeros(len(chunk) + 1, np.int64)
                offs[1:] = np.cumsum(
                    [len(s) for _w, s, _d, _n in chunk])
                prevs = (tuple(a.copy() if a is not None else None
                               for a in native_state)
                         if self.emit_deltas else None)
                with self._step("snapshot_scan", len(flat_s)):
                    # guarded, NEVER retried: the fold mutates the
                    # chunk-local carried copies in place. A failure
                    # demotes (native→host) and _scan_interned
                    # re-enters with fresh copies off the mirrors.
                    def _fold(flat_s=flat_s, flat_d=flat_d, offs=offs):
                        faults.fire("dispatch")
                        return fold(flat_s, flat_d, offs, self.vb,
                                    *native_state)

                    outs = resilience.call_guarded(
                        "dispatch", at, _fold, retries=0)
                if prevs is not None:
                    self._host_mask_outs(outs, prevs)
            elif run_scan:
                got = ring.pop(at)
                if got is not None:
                    fut, item = got
                    timeout = resilience.stage_timeout_s()
                    try:
                        wb, s_w, d_w, valid = fut.result(
                            timeout=2 * timeout if timeout > 0
                            else None)
                    except BaseException as e:
                        # interrupts and the simulated hard kill pass
                        # through; any other failure (a hung worker's
                        # _FutureTimeout, a transient PrepError) gets
                        # the guard's retry budget — prep (and the
                        # resident ring's h2d) is pure, so the inline
                        # rebuild is always safe
                        if (not isinstance(e, Exception)
                                or ingress_pipeline._is_fatal(e)
                                or not resilience.guard_active()):
                            raise  # inert knobs keep legacy fail-fast
                        wb, s_w, d_w, valid = resilience.call_guarded(
                            "prep", at,
                            lambda: _build_inline(item))
                else:
                    wb, s_w, d_w, valid = _build_inline(
                        (chunk, self._scan_wb(len(chunk))))
                if next_submit <= at:
                    next_submit = at + take
                fn = self._scan_fn_at(wb)
                # close the previous chunk's measurement BEFORE the
                # next arm's decide: an exploration arm's warm-up
                # (compile + throwaway dispatch inside _decide →
                # _warm_scan_arm) must never bleed into the
                # incumbent's recorded interval
                _meas_flush()
                # top the ingest ring up only after this chunk's
                # program is in the cache, so the ragged final chunk's
                # bigger-bucket reuse sees it (no tail compile) and
                # the worker itself never touches the cache. The scan
                # tier's ring is one slot (the legacy single
                # lookahead); the resident ring keeps GS_RESIDENT_SLOTS
                # super-batches prepped+transferred ahead.
                while next_submit < num_w and not ring.full:
                    s_take, _ = _decide(next_submit)
                    s_chunk = interned[next_submit:
                                       next_submit + s_take]
                    s_item = (s_chunk, self._scan_wb(len(s_chunk)))
                    if not ring.submit(build_job, next_submit, s_item):
                        break  # pipelining disabled: build inline
                    next_submit += s_take
                # one measurement round per chunk (the dispatch-to-
                # dispatch interval is the pipelined steady state's
                # per-chunk wall time). Recorded only when the chunk
                # is full-rung OR the whole call fits under the rung
                # (small stream_file pieces: ragged IS their real
                # economics); a long call's final ragged tail is
                # skipped — its amortization would drag the arm's EMA
                if tuner is not None and cur_arm is not None \
                        and len(chunk) == min(cur_arm["wb"], num_w):
                    meas = (cur_arm,
                            sum(len(s) for _w, s, _d, _n in chunk),
                            telemetry.stopwatch("driver.scan_round",
                                                window=at,
                                                wb=cur_arm["wb"]))
                sw = (telemetry.stopwatch(
                          "resident.superbatch", window=at, wb=take,
                          edges=sum(len(s)
                                    for _w, s, _d, _n in chunk))
                      if resident else None)
                telemetry.pop_dispatch_tags()  # drop stale warm-up tags
                with self._step("snapshot_scan",
                                sum(len(s) for _w, s, _d, _n in chunk)) \
                        as scan_sp:
                    # async dispatch: returns device arrays without
                    # blocking; the d2h lands in this chunk's finalize
                    # (snapshot_wait), AFTER the next chunk is queued.
                    # Guarded WITH retries on the scan tier: the
                    # jitted scan is pure (carry in, new carry out —
                    # rebound only on success), so re-dispatching a
                    # failed chunk is safe. The RESIDENT tier is
                    # deadline-only (retries=0): its program DONATES
                    # the carry buffers, so a failed attempt may
                    # already have consumed them — the failure demotes
                    # to scan and _run_batched re-enters from the
                    # mirrors instead. Exhausted budgets surface as
                    # typed StageFailed/StageTimeout either way.
                    # the wrapped scan program binds its program/sig
                    # tags (utils/costmodel) in the TLS of whichever
                    # thread runs the dispatch — the watchdog helper
                    # when GS_STAGE_TIMEOUT_S is armed — so they are
                    # captured inside _disp and carried back through
                    # the closure onto the step span
                    disp_tags = {}

                    def _disp(s_w=s_w, d_w=d_w, valid=valid,
                              carry_in=carry):
                        faults.fire("dispatch")
                        if sharded:
                            # mesh fault hooks + optional wire check
                            # INSIDE the guarded fn, so a transient
                            # corrupt wire / stalled dispatch is
                            # retried with a fresh firing
                            from ..parallel import sharded as _sh
                            from ..parallel.mesh import shard_count

                            nsh = shard_count(self.mesh)
                            s_w, d_w = _sh.guard_wire(
                                (s_w, d_w), nsh, self.vb + 1)
                            _sh.fire_shard_dispatch(nsh)
                        out = fn(carry_in, jnp.asarray(s_w),
                                 jnp.asarray(d_w), jnp.asarray(valid))
                        disp_tags.update(telemetry.pop_dispatch_tags())
                        return out

                    carry, outs = resilience.call_guarded(
                        "dispatch", at, _disp,
                        retries=(0 if resident
                                 else resilience.stage_retries()))
                    if scan_sp is not None:
                        scan_sp.attrs.update(disp_tags)
                    # the read-back carries what WindowResult hands
                    # out: each window's odd flag, never its cover
                    # labels, so the cover-label mirror resyncs from
                    # the chunk's final cover, which IS the carry — one
                    # [2vb+1] d2h per chunk. (The donated resident
                    # program already emits a fresh cover_final —
                    # aliasing the donated carry here would read a
                    # consumed buffer.)
                    if "bipartite" in self.analytics \
                            and "cover_final" not in outs:
                        outs["cover_final"] = carry[2]
                    # real rows only: cut on the device here, right
                    # behind the scan, so the cut never waits on the
                    # next chunk's dispatch
                    if len(chunk) < wb:
                        outs = _real_rows(outs, len(chunk))
                finalize_pending()
                pending = (at, chunk, outs, wb - len(chunk), sw)
                at += take
                continue
            # only the device-scan branch (which `continue`s above)
            # ever sets `pending`, and branch selection is fixed for
            # the whole call — the sync tiers never have one in flight
            assert pending is None
            _finalize_chunk(at, chunk, outs)
            at += take

        try:
            _chunk_loop()
        except Exception:
            # drain the in-flight chunk before surfacing: its outputs
            # were already dispatched, so materialize + finalize them
            # best-effort — mirrors/cursors then sit at the last chunk
            # the device actually completed, which is what makes the
            # demotion re-entry (and an operator resume) exact
            ring.drain()
            try:
                finalize_pending()
            except Exception as drain_err:
                pending = None
                try:
                    telemetry.event(
                        "drain_failed", durable=True,
                        component="driver",
                        error="%s: %s" % (type(drain_err).__name__,
                                          drain_err))
                except Exception:  # gslint: disable=except-hygiene (a failing ledger write must not replace the typed StageError the demotion ladder keys on)
                    pass
            raise
        finalize_pending()
        _meas_flush()
        if tuner is not None:
            tuner.save()

    def _host_mask_outs(self, outs: dict, prevs: tuple) -> None:
        """Changed-slot masks vs the previous window's snapshot
        (row -1 = the chunk-start carried state in `prevs`) for
        full-vector `outs` from the host/native fold — the scan tier's
        mask semantics: raw values for degrees/labels, the
        consumer-visible ODD flag for the cover."""
        pd, pl, pc = prevs
        if "deg" in outs:
            outs["deg_chg"] = outs["deg"] != np.concatenate(
                [pd[None], outs["deg"][:-1]])
        if "labels" in outs:
            outs["labels_chg"] = (
                outs["labels"] != np.concatenate(
                    [pl[None], outs["labels"][:-1]]))
        if "odd" in outs:
            odd = outs["odd"]
            podd = (pc[:self.vb] == pc[self.vb:])[None]
            outs["cover_chg"] = odd != np.concatenate(
                [podd, odd[:-1]])

    # ------------------------------------------------------------------
    # delta-compacted d2h egress (ops/delta_egress): decode + fallback
    # ------------------------------------------------------------------
    @staticmethod
    def _delta_overflowed(outs: dict) -> bool:
        """True when any window's changed count exceeded the wire's
        [cap]-sized index row (its idx/val rows are then truncated —
        the chunk must refold on the host twin)."""
        for key in ("deg", "labels", "cover"):
            if key + "_cnt" in outs and int(
                    np.max(outs[key + "_cnt"])
                    ) > outs[key + "_idx"].shape[1]:
                return True
        return False

    def _chunk_start_state(self):
        """Fresh int32 copies of the carried mirrors in the host-fold
        layouts — the (deg, cc, cov) a chunk's host/native fold (or a
        delta-egress refold) may mutate freely without moving the real
        mirrors before the chunk boundary."""
        vb = self.vb
        deg32 = lab = cov = None
        if "degrees" in self.analytics:
            deg32 = np.zeros(vb, np.int32)
            deg32[:len(self._degrees)] = self._degrees
        if "cc" in self.analytics:
            lab = np.arange(vb, dtype=np.int32)
            lab[:len(self._cc)] = self._cc
        if "bipartite" in self.analytics:
            if len(self._bip) != 2 * vb:
                self._bip = self._grow_cover(self._bip, vb)
            cov = self._bip.astype(np.int32)
        return deg32, lab, cov

    def _refold_chunk_outs(self, chunk) -> dict:
        """Delta-egress overflow fallback: recompute one chunk's FULL
        snapshot rows with the bit-exact numpy twin
        (ops/host_snapshot) from the chunk-start mirrors. Rare by
        construction (a label cascade wider than the cap); exactness
        therefore never depends on the cap choice."""
        deg32, lab, cov = self._chunk_start_state()
        prevs = (tuple(a.copy() if a is not None else None
                       for a in (deg32, lab, cov))
                 if self.emit_deltas else None)
        flat_s = np.concatenate([s for _w, s, _d, _n in chunk])
        flat_d = np.concatenate([d for _w, _s, d, _n in chunk])
        offs = np.zeros(len(chunk) + 1, np.int64)
        offs[1:] = np.cumsum([len(s) for _w, s, _d, _n in chunk])
        outs = host_snapshot.snapshot_windows(
            flat_s, flat_d, offs, self.vb, deg32, lab, cov)
        if prevs is not None:
            self._host_mask_outs(outs, prevs)
        return outs

    def _emit_delta_chunk(self, chunk, outs: dict,
                          results: List[WindowResult]) -> None:
        """Decode one chunk's delta-egress wire: apply each window's
        (idx, vals) pairs to working copies of the carried mirrors —
        the working copy after window w IS window w's snapshot — and
        advance the mirrors to the chunk end. Bit-identical to the
        full-vector extraction: a changed-mask applied to the previous
        snapshot is exactly the next snapshot, and slots the window
        never touched are unchanged by definition."""
        vb = self.vb
        want_deg = "deg_cnt" in outs
        want_cc = "labels_cnt" in outs
        want_bip = "cover_cnt" in outs
        if want_deg:
            deg_work = np.zeros(vb, np.int64)
            deg_work[:len(self._degrees)] = self._degrees
        if want_cc:
            lab_work = np.arange(vb, dtype=np.int32)
            lab_work[:len(self._cc)] = self._cc
        if want_bip:
            if len(self._bip) != 2 * vb:
                self._bip = self._grow_cover(self._bip, vb)
            odd_work = self._bip[:vb] == self._bip[vb:2 * vb]
        for i, (wstart, s, d, nv) in enumerate(chunk):
            res = WindowResult(
                window_start=wstart, num_edges=len(s),
                vertex_ids=self._vertex_ids(nv))
            if want_deg:
                k = int(outs["deg_cnt"][i])
                idx = outs["deg_idx"][i][:k].copy()
                vals = outs["deg_val"][i][:k].astype(np.int64)
                self._check_degree_width(vals)
                delta_egress.apply_delta(deg_work, k, idx, vals)
                res.degrees = _snapshot_view(deg_work[:nv].copy())
                if self.emit_deltas:
                    res.delta_degrees = _frozen_delta(idx, vals)
            if want_cc:
                k = int(outs["labels_cnt"][i])
                idx = outs["labels_idx"][i][:k].copy()
                vals = outs["labels_val"][i][:k].copy()
                delta_egress.apply_delta(lab_work, k, idx, vals)
                res.cc_labels = _snapshot_view(lab_work[:nv].copy())
                if self.emit_deltas:
                    res.delta_cc = _frozen_delta(idx, vals)
            if want_bip:
                k = int(outs["cover_cnt"][i])
                idx = outs["cover_idx"][i][:k].copy()
                vals = outs["cover_val"][i][:k].copy()
                delta_egress.apply_delta(odd_work, k, idx, vals)
                res.bipartite_odd = _snapshot_view(odd_work[:nv].copy())
                if self.emit_deltas:
                    res.delta_bipartite = _frozen_delta(idx, vals)
            if "triangles" in self.analytics:
                self._tri_pending.append(
                    (res, np.asarray(s, np.int32),
                     np.asarray(d, np.int32)))
            results.append(res)
        # chunk boundary: mirrors advance to the chunk's final state —
        # degree/label mirrors ARE the fully-applied working copies;
        # the cover mirror resyncs from the carry the dispatch attached
        nv_chunk = chunk[-1][3]
        if want_deg:
            self._degrees = deg_work[:nv_chunk].copy()
            self._deg_state = None  # per-window path: rebuild
        if want_cc:
            self._cc = lab_work[:nv_chunk].copy()
        if want_bip:
            self._bip = np.asarray(outs["cover_final"])[:2 * vb].copy()

    def _stage_ckpt(self) -> None:
        """Stage a due auto-checkpoint instead of saving it inline.

        The batched path processes (and used to checkpoint) windows the
        stream consumer has not been handed yet; a crash after such a
        save silently DROPPED the un-yielded windows' results on resume
        (the skip cursor jumps past them — at-most-once delivery, found
        by tools/endurance_run.py phase B). Staged checkpoints are
        flushed only once every window they cover has been yielded
        (stream_file's _emit), so a crash can only ever re-emit
        already-computed windows from a deterministic re-feed —
        at-least-once, the reference's Flink checkpoint contract. One
        snapshot is queued per crossed boundary (a batch can cross
        several); staging itself happens at scan-chunk boundaries, so
        the flushed checkpoint can lag the consumer by up to one
        checkpoint interval PLUS one scan chunk. Outside a streaming
        generator (direct
        run_arrays callers get the whole result list in the same
        action) the stage flushes immediately — the old behavior."""
        self._ckpt_policy.mark(self.windows_done)
        snap = (self.windows_done, self.state_dict())
        if self._emitted is None:
            with self._step("checkpoint", 0):
                checkpoint.save(self._ckpt_path, snap[1])
            self._wal_retention.flushed(
                self._wal, self.tenant or "driver",
                int(snap[1]["wal_offset"]))
        else:
            self._pending_ckpt.append(snap)

    def _emit(self, results):
        """Yield a batch's WindowResults one by one, flushing each
        staged checkpoint the moment its coverage has been fully
        emitted (never before)."""
        for res in results:
            yield res
            self._emitted += 1
            flushed = None
            while (self._pending_ckpt
                    and self._pending_ckpt[0][0] <= self._emitted):
                flushed = self._pending_ckpt.pop(0)
            if flushed is not None:
                with self._step("checkpoint", 0):
                    checkpoint.save(self._ckpt_path, flushed[1])
                # journal-armed drivers refuse stream_file (so this
                # flush site normally has no journal), but the
                # retention contract holds wherever a flush lands
                self._wal_retention.flushed(
                    self._wal, self.tenant or "driver",
                    int(flushed[1]["wal_offset"]))

    @contextlib.contextmanager
    def _batched_triangles(self):
        """Collect the enclosed windows' triangle work and flush it as
        one batched count_windows dispatch. Flushes only on clean exit:
        an exception mid-call leaves the incomplete windows' `triangles`
        None rather than counts for windows the caller never saw."""
        if "triangles" not in self.analytics \
                or self._tri_pending is not None:
            yield
            return
        self._tri_pending = []
        try:
            yield
            pending = self._tri_pending
            if pending:
                edges = sum(len(s) for _r, s, _d in pending)
                windows = [(s, d) for _r, s, d in pending]
                with self._step("triangles", edges):
                    counts = self._flush_triangle_windows(windows)
                for (res, _s, _d), c in zip(pending, counts):
                    res.triangles = c
        finally:
            self._tri_pending = None

    def _flush_triangle_windows(self, windows) -> list:
        """Count the flush's windows down the SAME demotion ladder as
        the snapshot stage: the sharded kernel while the mesh lives,
        the single-chip device kernel after a mesh demotion, the
        pure-numpy twin once the device itself is gone — each rung's
        typed stage failure demotes and the next rung recounts only
        the windows the failed rung had not finalized (the sharded
        kernel drains its finalized counts)."""
        done: list = []
        while True:
            kern = self._tri_kern()
            try:
                return done + kern.count_windows(windows[len(done):])
            except resilience.StageError as e:
                tier = ("sharded"
                        if kern is self._sh_tri and self._mesh_live()
                        else self._demoted_tier or self._base_tier())
                if not self._maybe_demote(tier, e):
                    raise
                done += list(getattr(kern, "drained_counts", None)
                             or [])

    def _tri_kern(self):
        """The triangle kernel of the CURRENT tier: the sharded kernel
        while the mesh is live; the single-chip device kernel after a
        mesh demotion to the scan tier (built + warmed lazily, rebuilt
        if buckets grew since); the pure-numpy host twin
        (parallel/host_twin.HostTriangleWindowKernel) once the session
        has demoted past the device entirely (native/host rungs) —
        the availability floor must not compile against the dead
        backend it demoted away from."""
        if self._mesh_live() and self._sh_tri is not None:
            return self._sh_tri
        if self._demoted_tier in ("native", "host"):
            k = getattr(self, "_host_tri", None)
            if k is None or (k.eb, k.vb) != (self.eb, self.vb):
                from ..parallel.host_twin import HostTriangleWindowKernel

                k = self._host_tri = HostTriangleWindowKernel(
                    edge_bucket=self.eb, vertex_bucket=self.vb)
            return k
        if (self._tri_kernel is None or self._tri_kernel.eb != self.eb
                or self._tri_kernel.vb != self.vb):
            self._tri_kernel = tri_ops.TriangleWindowKernel(
                edge_bucket=self.eb, vertex_bucket=self.vb)
            self._tri_kernel.warm_chunks()
        return self._tri_kernel

    def _step(self, name: str, num_records: int):
        """Driver step timing: through the StepTimer when tracing is
        on (it forwards to the flight recorder), straight to a
        telemetry span otherwise — the recorder sees the driver's
        dispatch/materialize decomposition whether or not per-op
        tracing was requested (a no-op stopwatch when disarmed)."""
        if self.timer:
            return self.timer.step(name, num_records)
        return telemetry.span("step." + name, records=num_records)

    def _vertex_ids(self, nv: int) -> np.ndarray:
        """Slot → external-id table; slots are assigned once, so the
        cache only extends by the slots added since the last window
        (O(new) per window, not O(V))."""
        have = len(self._ext_ids)
        if nv > have:
            fresh = np.asarray(self.interner.ids_of(
                np.arange(have, nv, dtype=np.int32)))
            self._ext_ids = np.concatenate([self._ext_ids, fresh])
        # read-only view: the cache only ever grows by REALLOCATION
        # (np.concatenate), so an earlier window's view keeps pointing
        # at its own immutable snapshot of the table
        return _snapshot_view(self._ext_ids[:nv])

    def _prev_snapshots(self) -> dict:
        """Previous-window snapshot values for host-side delta diffing
        on the per-window path (the batched path gets its masks from
        the device scan instead). Single-chip reads the host mirrors;
        sharded syncs the engine state (one extra d2h — the per-window
        path already pays several per window)."""
        if self._mesh_live() and self._engine is not None:
            st = self._engine.state_dict()
            vb = st["vb"]
            prev = {"deg": np.asarray(st["degree_state"])[:vb].astype(
                np.int64),
                "cc": np.asarray(st["labels"])[:vb]}
            if "bip_labels" in st:
                cov = np.asarray(st["bip_labels"])
                prev["odd"] = cov[:vb] == cov[vb:2 * vb]
            return prev
        prev = {"deg": self._degrees, "cc": self._cc.copy()}
        if len(self._bip):
            _, _, odd = unionfind.decode_double_cover(
                self._bip, len(self._bip) // 2)
            prev["odd"] = odd
        return prev

    @staticmethod
    def _host_delta(new: np.ndarray, prev: np.ndarray, init):
        """(changed ids, new values) of `new` vs `prev` padded to its
        length with the analytic's start value (0 degrees / identity
        labels / False odd)."""
        full = np.empty(len(new), new.dtype)
        if init == "identity":
            full[:] = np.arange(len(new))
        else:
            full[:] = init
        n = min(len(prev), len(new))
        full[:n] = prev[:n]
        idx = np.nonzero(new != full)[0].astype(np.int32)
        return _frozen_delta(idx, new[idx])

    def _attach_host_deltas(self, res: WindowResult,
                            prev: dict) -> None:
        if res.degrees is not None:
            res.delta_degrees = self._host_delta(
                res.degrees, prev.get("deg", ()), 0)
        if res.cc_labels is not None:
            res.delta_cc = self._host_delta(
                res.cc_labels, prev.get("cc", ()), "identity")
        if res.bipartite_odd is not None:
            res.delta_bipartite = self._host_delta(
                np.asarray(res.bipartite_odd),
                np.asarray(prev.get("odd", ()), bool), False)

    def _window(self, wstart: int, src: np.ndarray,
                dst: np.ndarray) -> WindowResult:
        prev = self._prev_snapshots() if self.emit_deltas else None
        with self._step("intern", 2 * len(src)):
            s = self.interner.intern_array(src)
            d = self.interner.intern_array(dst)
        nv = len(self.interner)
        self._ensure_buckets(nv, len(src))
        res = WindowResult(
            window_start=wstart, num_edges=len(src),
            vertex_ids=self._vertex_ids(nv),
        )
        for name in self.analytics:
            if name == "triangles" and self._tri_pending is not None:
                # deferred to the batched flush, which logs the real
                # 'triangles' step — timing the append here would
                # double-count the records at a near-infinite rate
                self._run_one(name, s, d, nv, res)
            else:
                with self._step(name, len(src)):
                    self._run_one_laddered(name, s, d, nv, res)
        if prev is not None:
            self._attach_host_deltas(res, prev)
        if self._wp > 1:
            # rotate the pane ring AFTER the analytics saw the prior
            # panes: the next emission's triangle slab is the last
            # wp−1 panes plus its own
            self._pane_ring.append((np.asarray(s, np.int32).copy(),
                                    np.asarray(d, np.int32).copy()))
            del self._pane_ring[:-(self._wp - 1)]
        if latency.enabled():
            rec = latency.on_window(self.tenant or "driver",
                                    edges=len(src),
                                    ordinal=self.windows_done)
            if rec is not None:
                res.latency = {"e2e_s": rec["e2e_s"],
                               "stages": dict(rec["stages"]),
                               "replayed": rec["replayed"]}
        if provenance.armed():
            provenance.emit(
                tenant=self.tenant or "driver",
                window=self.windows_done,
                wal_lo=self.edges_done,
                wal_hi=self.edges_done + len(src),
                tier=self._demoted_tier or self._base_tier(),
                program="driver",
                digest=provenance.result_digest(res))
        self.windows_done += 1
        self.edges_done += len(src)
        metrics.mark_window(
            1, len(src), engine="driver",
            tier=self._demoted_tier or self._base_tier(),
            mesh_shape=self._mesh_shape(),
            tenant=self.tenant)
        if self._ckpt_due():
            self._stage_ckpt()
        return res

    @staticmethod
    def _check_degree_width(snap: np.ndarray) -> None:
        """Device degree state is int32 (TPUs run 32-bit; x64 is a
        global jax switch). A window adds < 2^32 endpoint counts, so a
        vertex crossing 2^31 shows up negative at the very next
        snapshot — fail loudly there instead of persisting a wrapped
        count into checkpoints."""
        if len(snap) and int(snap.min()) < 0:
            raise OverflowError(
                "a vertex's running degree crossed 2^31 (int32 device "
                "state); shard the stream or reset windows before any "
                "single vertex accumulates that many incident edges")

    @staticmethod
    def _grow_cover(old: np.ndarray, vb: int) -> np.ndarray:
        """Re-lay a double-cover labeling out over a wider vertex
        bucket: (−) slots move from old_vb+v to vb+v, labels pointing
        into the (−) half shift with them, new slots are identity."""
        old_vb = len(old) // 2
        cover = np.arange(2 * vb, dtype=np.int32)
        if old_vb:
            shifted = np.where(old >= old_vb, old + (vb - old_vb),
                               old).astype(np.int32)
            cover[:old_vb] = shifted[:old_vb]
            cover[vb:vb + old_vb] = shifted[old_vb:]
        return cover

    def _run_one_laddered(self, name: str, s: np.ndarray,
                          d: np.ndarray, nv: int,
                          res: WindowResult) -> None:
        """One per-window analytic under the SAME demotion ladder as
        the batched path: a typed stage failure on the mesh demotes
        and re-runs THIS analytic on the single-chip tier (the mirrors
        — refreshed per window — already hold every earlier analytic's
        state, and the failed dispatch itself is pure-rebind, so the
        retry never double-applies). Event-time mesh sessions
        therefore degrade instead of wedging, same as run_arrays."""
        try:
            self._run_one(name, s, d, nv, res)
        except resilience.StageError as e:
            if not (self._mesh_live()
                    and self._maybe_demote("sharded", e)):
                raise
            self._run_one(name, s, d, nv, res)

    def _run_one(self, name: str, s: np.ndarray, d: np.ndarray,
                 nv: int, res: WindowResult) -> None:
        # tier-aware: a mesh session demoted off the sharded rung runs
        # the single-chip per-window kernels against the host mirrors
        sharded = self._mesh_live() and self._engine is not None
        if name == "degrees":
            if sharded:
                snap = np.array(self._engine.degrees(s, d)[:nv])
                self._check_degree_width(snap)
                # mirror tracks every window boundary (host-side copy
                # of an already-gathered array): the demotion hand-off
                # never needs a gather from a failing mesh
                self._degrees = snap.astype(np.int64)
                res.degrees = _snapshot_view(snap)
            else:
                import jax.numpy as jnp

                # carried device state (length vb+1; slot vb is the
                # padding sentinel), lazily (re)built from the host
                # mirror after construction, reset, resume, or growth.
                # int32 on device — the same width the sharded engine
                # carries; snapshots widen back to the int64 contract.
                if (self._deg_state is None
                        or len(self._deg_state) != self.vb + 1):
                    st = np.zeros(self.vb + 1, np.int32)
                    st[:len(self._degrees)] = self._degrees
                    self._deg_state = jnp.asarray(st)
                # clamp small batches UP to the steady edge bucket: the
                # stream's final partial window reuses the compiled
                # steady-state program instead of a fresh tiny-bucket
                # ladder at the tail (tools/endurance_run.py)
                nb = seg_ops.bucket_size(max(len(s), self.eb))
                sp = seg_ops.pad_to(np.asarray(s, np.int32), nb,
                                    fill=self.vb)
                dp = seg_ops.pad_to(np.asarray(d, np.int32), nb,
                                    fill=self.vb)
                self._deg_state = seg_ops.degree_update(
                    self._deg_state, jnp.asarray(sp), jnp.asarray(dp))
                # slice on the HOST: jnp's [:nv] would trace a fresh
                # dynamic_slice program for every distinct vertex count
                # (one recompile per window on a growing stream)
                snap = np.asarray(self._deg_state)[:nv].astype(np.int64)
                self._check_degree_width(snap)
                self._degrees = snap  # host mirror: checkpoint source
                res.degrees = _snapshot_view(snap.copy())
        elif name == "cc":
            if sharded:
                lab = np.array(self._engine.cc_labels(s, d)[:nv])
                self._cc = lab.copy()  # mirror: see degrees above
                res.cc_labels = _snapshot_view(lab)
            else:
                if len(self._cc) < nv:
                    self._cc = np.concatenate([
                        self._cc,
                        np.arange(len(self._cc), nv, dtype=np.int32)])
                self._cc = unionfind.connected_components_with_labels(
                    s, d, self._cc, nv, vertex_bucket=self.vb,
                    edge_bucket=self.eb)
                res.cc_labels = _snapshot_view(self._cc.copy())
        elif name == "bipartite":
            if sharded:
                _, _, odd = self._engine.bipartite(s, d)
                # mirror: see degrees above (one extra d2h of the
                # cover labels — the per-window path already pays
                # several per window)
                self._bip = np.asarray(
                    self._engine._bip_labels)[:2 * self.vb].astype(
                        np.int32)
                res.bipartite_odd = _snapshot_view(np.array(odd[:nv]))
            else:
                # cover layout is VERTEX-BUCKET based ((+) = v,
                # (−) = vb + v), so the kernel shape depends only on
                # buckets: re-layout happens on the O(log V) bucket
                # doublings, not on every window's new vertex count
                # (which recompiled cc_fixpoint per window in round 2)
                if len(self._bip) != 2 * self.vb:
                    self._bip = self._grow_cover(self._bip, self.vb)
                s2, d2 = unionfind.double_cover_edges(s, d, self.vb)
                self._bip = unionfind.connected_components_with_labels(
                    s2, d2, self._bip, 2 * self.vb,
                    edge_bucket=2 * self.eb)
                _, _, odd = unionfind.decode_double_cover(self._bip,
                                                          self.vb)
                res.bipartite_odd = _snapshot_view(odd[:nv])
        elif name == "triangles":
            # sliding: the per-window count runs on the COMPOSED slab
            # (ring panes + this pane, ≤ eb edges) — the emission's
            # window is the last panes_per_window panes, and triangles
            # is the only non-cumulative analytic so only it recomputes
            s, d = self._tri_window_edges(s, d)
            if self._tri_pending is not None:
                # batched mode (run_arrays): defer — all of the call's
                # windows go to the device in ONE count_windows stack
                # dispatch instead of one dispatch per window (the
                # per-dispatch latency dominates)
                self._tri_pending.append(
                    (res, np.asarray(s, np.int32), np.asarray(d, np.int32)))
            else:
                res.triangles = self._tri_kern().count(s, d)

    def _tri_window_edges(self, s: np.ndarray, d: np.ndarray):
        """The triangle window slab of the CURRENT emission under
        sliding windows: the ring's ≤ panes_per_window−1 prior interned
        panes concatenated with this pane (≤ eb edges total, so the
        full-window kernels fit unchanged). Interned slot ids are
        stable across windows, so ring panes stay valid as the
        vocabulary grows. Tumbling (wp == 1) passes through."""
        if self._wp == 1 or not self._pane_ring:
            return s, d
        ss = [ps for ps, _pd in self._pane_ring]
        dd = [pd for _ps, pd in self._pane_ring]
        ss.append(np.asarray(s, np.int32))
        dd.append(np.asarray(d, np.int32))
        return np.concatenate(ss), np.concatenate(dd)

    # ------------------------------------------------------------------
    # checkpoint / resume + failure recovery (utils/checkpoint.py)
    # ------------------------------------------------------------------
    def enable_auto_checkpoint(self, path: str,
                               every_n_windows: int = 16,
                               every_seconds: float = 0.0,
                               policy=None) -> None:
        """Snapshot all carried state to `path` (atomic replace +
        last-2 rotation, utils/checkpoint.save) on a
        `CheckpointPolicy` cadence: every N processed windows and/or
        every T seconds, whichever comes first — the failure-recovery
        hook the reference's combine-fn javadoc alludes to but never
        implements (library/ConnectedComponents.java:117-118). Pass
        `policy` (a utils.checkpoint.CheckpointPolicy) to inject a
        deterministic clock.

        Granularity: the per-window path checks the cadence on every
        window; the batched fast path at its chunk boundaries (every
        _SCAN_CHUNK=64 windows) — a crash loses at most
        max(N, 64) windows of work (or one time interval plus a
        chunk)."""
        if policy is None:
            if every_n_windows < 1 and every_seconds <= 0:
                raise ValueError(
                    "need every_n_windows >= 1 and/or every_seconds > 0")
            policy = checkpoint.CheckpointPolicy(
                every_n_windows=max(0, every_n_windows),
                every_seconds=every_seconds)
        if not policy.enabled():
            raise ValueError("checkpoint policy has no trigger enabled")
        self._ckpt_path = path
        self._ckpt_policy = policy

    def _ckpt_due(self) -> bool:
        return (self._ckpt_path is not None
                and self._ckpt_policy.due(self.windows_done))

    def try_resume(self, path: str) -> bool:
        """Restore from `path` if a readable checkpoint exists; returns
        whether state was restored. After resume, `windows_done` is the
        cursor of fully-processed windows — feed the stream from there.

        An UNREADABLE generation (truncated/corrupt — possible only
        through external damage, since save() writes atomically via
        tmp+rename) falls back to the rotated previous checkpoint
        (`checkpoint.load_latest`); only when every generation is
        damaged does resume behave like a missing checkpoint: warn and
        return False, so the caller reprocesses from the start, which
        is always correct. SEMANTIC mismatches (cross-mode, window
        size) still raise from load_state_dict — those need an
        operator decision, not a silent full reprocess — and so do
        OPERATIONAL failures (PermissionError / EIO / out-of-memory):
        the file may be intact, and silently reprocessing a
        multi-million-edge stream would mask a fixable problem."""
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
        # durable stamp: pairs with the pre-kill spans under the
        # process's one trace ID, so a crash/resume reads as a single
        # timeline in the run ledger (asserted by tools/chaos_run.py)
        telemetry.event("resume", durable=True, component="driver",
                        path=used, windows_done=self.windows_done)
        return True

    def enable_wal(self, directory: str) -> bool:
        """Journal every LIVE run_arrays() feed under `directory`
        (utils/wal.py) before any window is cut — the durable,
        replayable source the file path already is and the live path
        never was. After a kill, `resume_and_replay(ckpt)` restores
        the newest checkpoint and re-feeds the journal suffix past
        its `wal_offset`, reproducing the lost windows bit-exactly.
        A journal-armed driver REFUSES stream_file() (the file is its
        own journal; mixing the sources would skew the offset
        contract). Returns False (a no-op) under GS_WAL=0."""
        if not wal_mod.enabled():
            return False
        self._wal_dir = directory
        self._wal = wal_mod.WriteAheadLog(directory)
        return True

    def seal_wal(self) -> None:
        """Durably close the journal (the clean-drain marker)."""
        if self._wal is not None:
            self._wal.seal()

    def resume_and_replay(self, ckpt_path: str) -> List[WindowResult]:
        """Kill recovery for a journal-armed driver: try_resume the
        newest checkpoint generation, then replay the journal suffix
        past the checkpointed `wal_offset` through run_arrays().
        Returns the replayed WindowResults — every window the crashed
        process computed (or had accepted) but never delivered."""
        self.try_resume(ckpt_path)
        if self._wal_dir is None:
            return []
        tenant = self.tenant or "driver"
        off = self.edges_done
        parts = []
        for tid, _start, src, dst, ts in wal_mod.replay(
                self._wal_dir, {tenant: off}):
            if tid == tenant:
                parts.append((src, dst, ts))
        edges = sum(len(p[0]) for p in parts)
        telemetry.event("wal_replayed", durable=True,
                        component="driver", dir=self._wal_dir,
                        edges=edges)
        metrics.counter_inc("gs_wal_replayed_edges_total", edges)
        if not edges:
            return []
        src = np.concatenate([p[0] for p in parts])
        dst = np.concatenate([p[1] for p in parts])
        ts = (np.concatenate([p[2] for p in parts])
              if all(p[2] is not None for p in parts) else None)
        # suspend journaling for the replay feed: these edges are
        # already in the journal
        live, self._wal = self._wal, None
        try:
            return self.run_arrays(src, dst, ts)
        finally:
            self._wal = live

    def state_dict(self) -> dict:
        state = {
            "window_ms": self.window_ms,
            "analytics": list(self.analytics),
            "sharded": self.mesh is not None,
            "mesh_shape": self._mesh_shape(),  # gslint: disable=ckpt-symmetry (provenance: load converts cross-mesh, never needs the source shape back)
            "windows_done": self.windows_done,
            "edges_done": self.edges_done,
            # journal offset at this finalized-window boundary (the
            # edges_done cursor IS the cumulative live-feed edge
            # count): resume_and_replay() re-feeds the WAL strictly
            # past it (DESIGN.md §18)
            "wal_offset": self.edges_done,
            "edge_bucket": self.eb,
            "vertex_bucket": self.vb,
            "closed_partial": self._closed_partial,
            "vertex_ids": np.array(self._vertex_ids(len(self.interner))),
            "degrees": self._degrees.copy(),
            "cc": self._cc.copy(),
            "bip": self._bip.copy(),
        }
        if self._wp > 1:
            # sliding: the pane ring rides the checkpoint so a resumed
            # stream's next emissions compose the SAME triangle slabs
            # the killed run would have (interned ids are stable:
            # load re-interns vertex_ids in insertion order)
            state["slide"] = self.slide
            state["pane_ring_src"] = [s.copy()
                                      for s, _d in self._pane_ring]
            state["pane_ring_dst"] = [d.copy()
                                      for _s, d in self._pane_ring]
        if self._engine is not None:
            # demoted mesh session: the host mirrors carried the
            # stream since the demotion — the checkpoint assembles
            # the engine slabs from them on the HOST, never touching
            # (or persisting stale state from) the dead mesh
            state["engine"] = (self._engine.state_dict()
                               if self._mesh_live()
                               else self._engine_state_from_mirrors())
        if getattr(self, "_scan_tuner", None) is not None:
            # the learned dispatch configuration rides the checkpoint
            # so a resumed stream keeps its optimum (ops/autotune)
            state["autotune"] = self._scan_tuner.state_dict()
        if getattr(self, "_resident_tuner", None) is not None:
            # the resident tier's windows-per-superbatch tuner rides
            # beside it under its own key (distinct arm space)
            state["autotune_resident"] = \
                self._resident_tuner.state_dict()
        return state

    def load_state_dict(self, state: dict) -> None:
        if state["window_ms"] != self.window_ms:
            raise ValueError("window size mismatch")
        if tuple(state["analytics"]) != self.analytics:
            raise ValueError(
                f"analytics mismatch: checkpoint has "
                f"{state['analytics']}, driver runs {list(self.analytics)}")
        self.interner = make_interner(np.array([0]))
        self._ext_ids = np.zeros(0, np.int64)
        self.windows_done = int(state.get("windows_done", 0))
        self.edges_done = int(state.get("edges_done", 0))
        woff = state.get("wal_offset")
        if woff is not None and int(woff) != self.edges_done:
            # the journal offset is DEFINED as the folded-edge cursor;
            # divergence means a hand-edited checkpoint that would
            # replay a hole or a double-fold — refuse loudly
            raise ValueError(
                "checkpoint wal_offset %d disagrees with its own "
                "edges_done cursor %d" % (int(woff), self.edges_done))
        # persist the misuse guard: a checkpoint taken after a partial
        # count-based window must refuse further unaligned feeding just
        # like the live driver would
        self._closed_partial = bool(state.get("closed_partial", False))
        ckpt_slide = state.get("slide")
        if (int(ckpt_slide) if ckpt_slide else None) != self.slide:
            # pane-boundary math is governed by slide exactly as window
            # cuts are by eb: a mismatched resume would silently shift
            # every subsequent emission's slab — refuse loudly
            raise ValueError(
                "slide mismatch: checkpoint has %r, driver runs %r"
                % (ckpt_slide, self.slide))
        self._pane_ring = [
            (np.asarray(s, np.int32), np.asarray(d, np.int32))
            for s, d in zip(state.get("pane_ring_src", []),
                            state.get("pane_ring_dst", []))]
        if "edge_bucket" in state:
            # count-based windowing is governed by eb exactly as event
            # time is by window_ms: restore it so resumed streams cut
            # the same windows the checkpointed run would have
            self.eb = int(state["edge_bucket"])
        if "vertex_bucket" in state:
            # adopt the checkpointed capacity up front; without this a
            # sharded resume built with a different vertex_bucket dies
            # deep in ShardedWindowEngine.load_state_dict with a
            # 'vertex bucket mismatch' that never names the parameter.
            # Single-chip keeps a LARGER pre-sized constructor bucket
            # (carried host mirrors re-lay out lazily), so resuming a
            # small checkpoint doesn't re-introduce the bucket-doubling
            # recompiles the caller pre-sized to avoid.
            ckpt_vb = int(state["vertex_bucket"])
            self.vb = (ckpt_vb if self.mesh is not None
                       else max(self.vb, ckpt_vb))
            # force rebuild of everything compiled at the old capacity
            self._engine = None
            self._tri_kernel = None
            self._sh_tri = None
        self.interner.intern_array(np.asarray(state["vertex_ids"],
                                              np.int64))
        self._degrees = np.array(state["degrees"])
        self._deg_state = None  # rebuilt from the mirror on next window
        self._cc = np.array(state["cc"])
        self._bip = np.array(state["bip"])
        self._ensure_buckets(len(state["vertex_ids"]), 1)
        # cross-MODE resume: the engine's carried slabs are gathered
        # replicated state (shard-count independent — parallel/sharded
        # state_dict), so a mesh checkpoint converts to the single-chip
        # mirrors and vice versa. A 4-shard checkpoint therefore
        # resumes on any mesh width, on 1 device, or on the host tier.
        ckpt_sharded = bool(state.get("sharded", False))
        if self._engine is not None:
            if "engine" in state:
                self._engine.load_state_dict(state["engine"])
            elif not ckpt_sharded:
                # single-chip checkpoint onto a mesh: mirrors → slabs
                self._sync_engine_from_mirrors()
        elif ckpt_sharded and "engine" in state:
            # mesh checkpoint onto a single-chip driver: slabs →
            # mirrors (the checkpointed mirrors are empty in sharded
            # mode — the engine state is the truth)
            self._absorb_engine_state(state["engine"])
        # .get: checkpoints predating the autotune key restore cleanly;
        # with GS_AUTOTUNE=0 the state is carried nowhere (inert)
        if state.get("autotune") is not None and self.mesh is None:
            tuner = self._ensure_scan_tuner()
            if tuner is not None:
                tuner.load_state_dict(state["autotune"])
        if state.get("autotune_resident") is not None \
                and self.mesh is None:
            tuner = self._ensure_resident_tuner()
            if tuner is not None:
                tuner.load_state_dict(state["autotune_resident"])

    def trace_report(self) -> List[dict]:
        return self.timer.report() if self.timer else []
