"""Columnar windowed neighborhood reduce — `reduceOnEdges` /
`foldNeighbors` at stream rate (BASELINE.json config #2).

The record-level runtime executes the generic neighborhood UDFs by
handing per-edge Python `Edge` lists to a kernel per window
(core/runtime.py) — exact reference semantics
(GraphWindowStream.java:101-121), but interpreter-bound. This engine is
the production columnar form: interned COO windows (src, dst, value
arrays) flow straight into the flattened (window, vertex) segment
kernels — the SAME cell trick as the sliding pane path
(ops/neighborhood.py `_make_pane_reduce` with panes = tumbling
windows), so one fixed-shape device dispatch reduces an entire
windows_per_dispatch stack of windows with zero per-edge Python.

Monoid names ('sum'|'min'|'max') run the parallel segment kernels;
a user fn DECLARED associative runs the flagged associative scan
(seg_ops.segmented_reduce_associative). Direction follows the
reference's EdgeDirection: OUT groups by src, IN by dst, ALL by both
(each edge contributes its value to both endpoints' neighborhoods —
SimpleEdgeStream.java slice(ALL) duplicates exactly this way).

Multi-chip: `parallel.sharded.make_sharded_pane_reduce(mesh, vb, pb,
panes_per_window=1, name)` IS this engine's sharded form (a tumbling
window is a sliding window with one pane); ShardedWindowEngine
.sliding_reduce exposes it.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from . import segment as seg_ops
from ..utils import telemetry

_DIRECTIONS = ("out", "in", "all")


def _device_cell_fill(name: str, dtype):
    """The DEVICE segment kernels' empty-segment identity — what an
    untouched cell of the full-egress [wb, vbp] stack holds: the XLA
    reduce init values (segment sum → 0; segment min → +inf /
    iinfo.max; segment max → -inf / iinfo.min). The delta-egress
    decode refills reconstructed rows with it so both egress formats
    are bit-identical cell-for-cell, not just on touched cells (cells
    with count 0 are contractually compared by count, but the bit
    contract keeps the A/B's sha256 assertion meaningful)."""
    dtype = np.dtype(dtype)
    if name == "sum":
        return dtype.type(0)
    if np.issubdtype(dtype, np.floating):
        return np.inf if name == "min" else -np.inf
    info = np.iinfo(dtype)
    return info.max if name == "min" else info.min


def _host_identity(name: str, dtype):
    """Monoid identity for the HOST (numpy) tiers and the reference
    oracle — one definition so the tier, the oracle, and any future
    parity fix cannot drift apart (the device tier's jnp form lives in
    neighborhood._pane_identity, which uses finfo extremes instead of
    ±inf for floats; cells with count 0 are compared by count, never
    by value, so the two conventions never meet in an assertion)."""
    dtype = np.dtype(dtype)
    if name == "sum":
        return 0
    if np.issubdtype(dtype, np.integer):
        return (np.iinfo(dtype).max if name == "min"
                else np.iinfo(dtype).min)
    return np.inf if name == "min" else -np.inf


class WindowedEdgeReduce:
    """Per-window per-vertex reduce over tumbling `edge_bucket`-sized
    windows of a COO value stream.

    `process_stream(src, dst, val)` -> list of (values, counts), one
    pair per window; values[v] is the reduce of the window's edges
    incident to dense vertex v in the given direction, counts[v] the
    number of contributing edges (0 = vertex absent — min/max cells
    hold the fill, mask by counts like the pane path).

    One jitted program per windows-per-dispatch bucket over fixed
    [wb, eb] shapes — steady-state streaming recompiles nothing
    (the same dispatch economics as TriangleWindowKernel).
    """

    MAX_STREAM_WINDOWS = 64

    def __init__(self, vertex_bucket: int, edge_bucket: int,
                 name: str = "sum", direction: str = "out",
                 fn=None, ingress: str = None, egress: str = None,
                 slide: int = None):
        if direction not in _DIRECTIONS:
            raise ValueError(f"direction must be one of {_DIRECTIONS}")
        if egress not in (None, "full", "delta"):
            raise ValueError(f"unknown egress: {egress!r}")
        if fn is not None:
            name = None
        assert name in (None, "sum", "min", "max"), name
        # sliding windows by pane composition: panes are monoid
        # summaries, so each edge folds into its slide-sized pane ONCE
        # and every emission composes the last panes_per_window pane
        # (cells, counts) pairs — O(1) panes per edge instead of the
        # naive twin's O(panes_per_window) refolds of the overlap
        if slide is not None and int(slide) != 0:
            slide = int(slide)
            if name is None:
                raise ValueError(
                    "slide= needs a monoid name (sum/min/max): pane "
                    "composition relies on the named identity fills")
            eb_n = seg_ops.bucket_size(edge_bucket)
            if (slide <= 0 or slide > eb_n or eb_n % slide
                    or slide & (slide - 1)):
                raise ValueError(
                    "slide must be a power of two dividing the "
                    "window size (%d), got %d" % (eb_n, slide))
            self.slide = slide
        else:
            self.slide = None
        self.vb = seg_ops.bucket_size(vertex_bucket)
        self.eb = seg_ops.bucket_size(edge_bucket)
        # compile-size cap on TPU backends (ops/triangles.COMPILE_CAP)
        from . import triangles as _tri

        self.MAX_STREAM_WINDOWS = min(
            type(self).MAX_STREAM_WINDOWS, _tri.capped_chunk(self.eb))
        self.name = name
        self.fn = fn
        self.direction = direction
        # stream-chunk wire format of the monoid DEVICE tier: uint16
        # ids + per-window valid counts with the (window, vertex) cell
        # ids computed on device (2×u16 + vals vs host-built int64
        # flat ids — fewer h2d bytes AND the id packing moves off the
        # single host core). Standard unless pinned; a compact pin
        # needs compact_ingress.supports(vb).
        if ingress == "compact":
            from . import compact_ingress

            if not compact_ingress.supports(self.vb):
                raise ValueError(
                    "compact ingress is lossy for vertex_bucket %d "
                    "(ids must fit uint16)" % self.vb)
        self.ingress = ingress or "standard"
        # d2h egress of the monoid DEVICE tier: full [wb, vbp]
        # cells+counts stacks, or the touched-cell delta wire
        # (ops/delta_egress — a window touches at most one cell per
        # contribution, so the [cap]-sized wire is exact, no overflow
        # path needed). Same pin as the driver's snapshot egress.
        from . import delta_egress as _de

        self.egress = egress if egress else _de.resolve_egress()
        from . import ingress_pipeline as _ip

        self.stage_timers = _ip.StageTimers()
        self._fns = {}
        # sliding mode: the inner pane engine (this engine at
        # edge_bucket=slide) and the tumbling refold twin, built lazily
        self.panes_per_window = (self.eb // self.slide
                                 if self.slide else 1)
        self._pane_engine = None
        self._full_engine = None

    # ---- sliding windows (pane composition) ---------------------------

    def _monoid_op(self):
        return {"sum": np.add, "min": np.minimum,
                "max": np.maximum}[self.name]

    def _pane_eng(self) -> "WindowedEdgeReduce":
        if self._pane_engine is None:
            self._pane_engine = WindowedEdgeReduce(
                self.vb, self.slide, name=self.name,
                direction=self.direction)
        return self._pane_engine

    def _full_eng(self) -> "WindowedEdgeReduce":
        if self._full_engine is None:
            self._full_engine = WindowedEdgeReduce(
                self.vb, self.eb, name=self.name,
                direction=self.direction)
        return self._full_engine

    def _compose_panes(self, panes: List[Tuple[np.ndarray,
                                               np.ndarray]]
                       ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """One emission per pane: emission i composes panes
        [max(0, i-wp+1), i] — cells under the monoid ufunc (identity
        fills are true identities, so untouched cells stay untouched),
        counts by sum. Head-of-stream emissions compose fewer panes
        (growing windows), the ragged tail pane is just a smaller
        pane: both fall out of the same composition."""
        wp = self.panes_per_window
        op = self._monoid_op()
        out: List[Tuple[np.ndarray, np.ndarray]] = []
        for i in range(len(panes)):
            cells, counts = panes[i]
            cells, counts = cells.copy(), counts.copy()
            for c2, n2 in panes[max(0, i - wp + 1):i]:
                op(cells, c2, out=cells)
                counts += n2
            out.append((cells, counts))
        return out

    def process_stream_naive(self, src, dst, val
                             ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """The refold twin of the sliding path (parity oracle + A/B
        baseline, tools/pump_ab.py): every emission re-reduces its
        FULL window slice through the tumbling engine — each edge is
        folded up to panes_per_window times. Bit-identical emissions
        to the pane path for integer monoids."""
        if self.slide is None:
            return self.process_stream(src, dst, val)
        src = np.asarray(src)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
        dst = np.asarray(dst)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
        val = np.asarray(val)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
        n = len(src)
        out: List[Tuple[np.ndarray, np.ndarray]] = []
        eng, s = self._full_eng(), self.slide
        for i in range(-(-n // s)):
            lo = max(0, (i + 1) * s - self.eb)
            hi = min((i + 1) * s, n)
            out.extend(eng.process_stream(src[lo:hi], dst[lo:hi],
                                          val[lo:hi]))
        return out

    # ---- jitted stack program (monoid tier) ---------------------------

    def _delta_cap(self) -> int:
        """Exact per-window touched-cell bound of the delta egress
        wire: one cell per contribution (two for direction 'all'),
        never more than the row width."""
        per = self.eb * (2 if self.direction == "all" else 1)
        return min(per, self.vb + 1)

    def _delta_tail(self, cap: int):
        """The vmapped per-window encode appended to a stack program
        when egress is delta (ops/delta_egress.compact_touched)."""
        import jax

        from . import delta_egress

        return jax.vmap(
            lambda c, n: delta_egress.compact_touched(c, n, cap))

    def _stack_fn(self, wb: int, delta: bool = False):
        key = (wb, delta)
        fn = self._fns.get(key)
        if fn is None:
            import jax
            import jax.numpy as jnp

            vbp = self.vb + 1
            n_cells = wb * vbp
            name = self.name
            tail = self._delta_tail(self._delta_cap()) if delta else None

            @jax.jit
            def run(ids, vals):
                cells = seg_ops.segment_reduce(
                    vals, ids, n_cells + 1, name)[:-1].reshape(wb, vbp)
                counts = jax.ops.segment_sum(
                    jnp.where(ids < n_cells, 1, 0), ids,
                    n_cells + 1)[:-1].reshape(wb, vbp)
                return tail(cells, counts) if tail else (cells, counts)

            self._fns[key] = fn = run
        return fn

    def _stack_fn_compact(self, wb: int, delta: bool = False):
        """Compact twin of _stack_fn: consumes [wb, eb] uint16 id
        stacks + [wb] valid counts + [wb, eb] values, rebuilds the
        suffix mask and the flattened (window, vertex) cell ids ON
        DEVICE (the widening fused into the same program), then runs
        the identical segment kernels — same cells/counts."""
        key = ("compact", wb, delta)
        fn = self._fns.get(key)
        if fn is None:
            import jax
            import jax.numpy as jnp

            vbp = self.vb + 1
            n_cells = wb * vbp
            eb = self.eb
            name = self.name
            direction = self.direction

            from . import compact_ingress

            tail = self._delta_tail(self._delta_cap()) if delta else None

            @jax.jit
            def run(s16, d16, nvalid, vals):
                # shared compact decode (sentinel 0: the trash-cell
                # `where` below masks padded slots by `valid`)
                s32, d32, valid = compact_ingress.widen_stack(
                    s16, d16, nvalid, eb, 0)
                base = (jnp.arange(wb, dtype=jnp.int32) * vbp)[:, None]

                def ids_of(v32):
                    return jnp.where(valid, base + v32,
                                     n_cells).reshape(-1)

                if direction == "out":
                    ids, v = ids_of(s32), vals.reshape(-1)
                elif direction == "in":
                    ids, v = ids_of(d32), vals.reshape(-1)
                else:
                    ids = jnp.concatenate([ids_of(s32), ids_of(d32)])
                    v = jnp.concatenate([vals.reshape(-1)] * 2)
                cells = seg_ops.segment_reduce(
                    v, ids, n_cells + 1, name)[:-1].reshape(wb, vbp)
                counts = jax.ops.segment_sum(
                    jnp.where(ids < n_cells, 1, 0), ids,
                    n_cells + 1)[:-1].reshape(wb, vbp)
                return tail(cells, counts) if tail else (cells, counts)

            self._fns[key] = fn = run
        return fn

    def _cell_ids(self, src, dst, win, valid, vbp, n_cells):
        """Flattened (window, vertex) cell id per contribution; ALL
        direction doubles the stream (one contribution per endpoint)."""
        if self.direction == "out":
            vtx = [src]
        elif self.direction == "in":
            vtx = [dst]
        else:
            vtx = [src, dst]
        ids, rep = [], len(vtx)
        for v in vtx:
            ids.append(np.where(valid, win * vbp + v, n_cells))
        return np.concatenate(ids), rep

    def process_stream(self, src: np.ndarray, dst: np.ndarray,
                       val: np.ndarray) -> List[Tuple[np.ndarray,
                                                      np.ndarray]]:
        src = np.asarray(src)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
        dst = np.asarray(dst)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
        val = np.asarray(val)  # gslint: disable=host-sync (host-input normalization: callers pass numpy/lists, never device values)
        assert len(src) == len(dst) == len(val)
        n = len(src)
        if n == 0:
            return []
        if self.slide is not None:
            # sliding: fold each edge into its pane once (the inner
            # engine at edge_bucket=slide), compose panes per emission
            # on the host — one (cells, counts) pair per slide-sized
            # emission
            with telemetry.span("reduce.sliding", monoid=self.name,
                                edges=n, slide=self.slide,
                                panes_per_window=self.panes_per_window):
                panes = self._pane_eng().process_stream(src, dst, val)
                return self._compose_panes(panes)
        # device rounds run through the shared ingress pipeline, whose
        # chunk/stage spans nest under this engine-level span
        with telemetry.span("reduce.stream", tier="device",
                            monoid=self.name or "fn", edges=n):
            return self._device_process_stream(
                src.astype(np.int64, copy=False),
                dst.astype(np.int64, copy=False), val)

    def _native_process_stream(self, src, dst, val):
        """The C++ fused form, outside process_stream (the profiler and
        the parity tests call it): one pass produces both cells and
        counts (ingest.cpp gs_windowed_reduce), chunked only to bound
        the dense [num_w, vbp] scratch. Same (cells, counts) per window
        as the device path; cells cast back to the value dtype."""
        from .. import native as native_mod

        if not native_mod.windowed_reduce_available():
            return None
        eb, vbp = self.eb, self.vb + 1
        n = len(src)
        num_w = -(-n // eb)
        ident = int(_host_identity(self.name, val.dtype))
        out: List[Tuple[np.ndarray, np.ndarray]] = []
        # chunk by a ~64MB dense-scratch budget (two [chunk_w, vbp]
        # int64 slabs): chunk size only amortizes ctypes call overhead,
        # so a big vertex bucket just takes more, smaller calls instead
        # of multi-GB allocations
        chunk_w = max(1, min(1024, (64 << 20) // (vbp * 16)))
        for at in range(0, num_w, chunk_w):
            lo, hi = at * eb, min((at + chunk_w) * eb, n)
            cells, counts = native_mod.windowed_reduce(
                src[lo:hi], dst[lo:hi], val[lo:hi], eb, vbp,
                self.name, self.direction, ident)
            cells = cells.astype(val.dtype, copy=False)
            out.extend((cells[w], counts[w])
                       for w in range(cells.shape[0]))
        return out

    def _device_process_stream(self, src, dst, val):
        """The device path of process_stream. Monoid chunks route through
        the shared three-stage ingress pipeline
        (ops/ingress_pipeline): cell-id/stack prep on the worker
        pool, h2d + dispatch in chunk order, each chunk's d2h one
        chunk behind — with the compact wire format (uint16 stacks +
        valid counts, widening fused on device) when the kernel's
        resolved ingress is compact. The associative-user-fn tier
        keeps its host argsort inline (its reduce runs through the
        host-sorted flagged scan, not the stack program)."""
        n = len(src)
        out: List[Tuple[np.ndarray, np.ndarray]] = []
        eb, vbp = self.eb, self.vb + 1
        num_w = -(-n // eb)
        chunks = []
        at = 0
        while at < num_w:
            wb = min(self.MAX_STREAM_WINDOWS, num_w - at)
            wb = seg_ops.bucket_size(wb)   # O(log) programs over tails
            chunks.append((at, wb))
            at += wb
        def standard_chunk(at, wb):
            """Flat (cell ids, values) of windows [at, at+wb) in the
            standard wire format — shared by the associative-fn inline
            loop and the pipeline's prep stage."""
            lo, hi = at * eb, min((at + wb) * eb, n)
            s = seg_ops.pad_to(src[lo:hi], wb * eb)
            d = seg_ops.pad_to(dst[lo:hi], wb * eb)
            v = seg_ops.pad_to(val[lo:hi], wb * eb)
            valid = seg_ops.pad_to(np.ones(hi - lo, bool), wb * eb,
                                   fill=False)
            win = np.arange(wb * eb) // eb
            ids, rep = self._cell_ids(s, d, win, valid, vbp, wb * vbp)
            return ids, np.concatenate([v] * rep)

        if self.name is None:
            for at, wb in chunks:
                n_cells = wb * vbp
                ids, vals = standard_chunk(at, wb)
                order = np.argsort(ids, kind="stable")
                res, _has = seg_ops.segmented_reduce_associative(
                    self.fn, ids[order], vals[order], n_cells)
                cells = np.asarray(res).reshape(wb, vbp)  # gslint: disable=host-sync (sanctioned finalize boundary: the associative tier's one materialize per chunk)
                counts = np.bincount(
                    ids[ids < n_cells],
                    minlength=n_cells).reshape(wb, vbp)
                for w in range(min(wb, num_w - at)):
                    out.append((cells[w], counts[w]))
            return out

        from . import ingress_pipeline

        compact = self.ingress == "compact"
        if compact and n:
            from . import compact_ingress

            # the shared main-thread wrap-safety check: bad ids raise
            # the same ValueError every other tier raises (a pooled
            # prep failure would wrap it in PrepError/RuntimeError)
            compact_ingress.validate_ids(src, dst, vbp,
                                         "windowed reduce")

        def prep(item):
            at, wb = item
            lo, hi = at * eb, min((at + wb) * eb, n)
            if compact:
                from . import compact_ingress

                _w, s16, d16, nv = compact_ingress.window_stack(
                    src[lo:hi], dst[lo:hi], eb)
                s16 = seg_ops.pad_to(s16, wb)
                d16 = seg_ops.pad_to(d16, wb)
                nv = seg_ops.pad_to(nv, wb)
                v = seg_ops.pad_to(val[lo:hi],
                                   wb * eb).reshape(wb, eb)
                return at, wb, (s16, d16, nv, v)
            return (at, wb) + (standard_chunk(at, wb),)

        def h2d(payload):
            import jax.numpy as jnp

            at, wb, args = payload
            return at, wb, tuple(jnp.asarray(a) for a in args)

        delta = self.egress == "delta"

        def dispatch(dev_payload):
            at, wb, dev = dev_payload
            fn = (self._stack_fn_compact(wb, delta) if compact
                  else self._stack_fn(wb, delta))
            return (at, wb) + tuple(fn(*dev))

        def finalize(raw):
            if delta:
                # touched-cell wire (ops/delta_egress): d2h one
                # (cnt, idx, cells, counts) [wb, cap] quad instead of
                # two full [wb, vbp] stacks; untouched cells refill
                # with the device kernels' own empty-segment identity,
                # so rows are bit-identical to the full tier's
                at, wb, cnt, idx, cv, cn = raw
                cnt, idx, cv, cn = (np.asarray(x)  # gslint: disable=host-sync (sanctioned finalize boundary: the delta wire's ONE batched d2h per chunk)
                                    for x in (cnt, idx, cv, cn))
                fill = _device_cell_fill(self.name, cv.dtype)
                for w in range(min(wb, num_w - at)):
                    k = int(cnt[w])  # gslint: disable=host-sync (numpy-on-numpy: the materialize above already d2h'd the wire)
                    cells = np.full(vbp, fill, cv.dtype)
                    counts = np.zeros(vbp, cn.dtype)
                    cells[idx[w, :k]] = cv[w, :k]
                    counts[idx[w, :k]] = cn[w, :k]
                    out.append((cells, counts))
                return
            at, wb, cells, counts = raw
            cells, counts = np.asarray(cells), np.asarray(counts)  # gslint: disable=host-sync (sanctioned finalize boundary: the stack program's ONE batched d2h per chunk)
            for w in range(min(wb, num_w - at)):
                out.append((cells[w], counts[w]))

        ingress_pipeline.run_pipeline(chunks, prep, h2d, dispatch,
                                      finalize,
                                      timers=self.stage_timers)
        return out

    def cohort_step(self, rows: List[tuple]) -> List[Tuple[np.ndarray,
                                                           np.ndarray]]:
        """Multi-tenant cohort entry (core/tenancy.py): fold N
        tenants' next windows (each ≤ eb edges) in ONE device
        dispatch — the windowed-reduce leg of the cohort slab. The
        stack program already batches over a leading window axis and
        tumbling windows carry no cross-window state, so a tenant
        cohort is literally MORE WINDOWS IN THE STACK: row r of the
        [nb, vbp] result is tenant r's window, bit-identical to that
        tenant's own single-window device dispatch (the cell ids are
        built by the same standard_chunk recipe, in the same order,
        so even float accumulation folds identically).

        `rows` is a list of (src, dst, val) triples; returns one
        (values, counts) pair per row. Monoid kernels only (a user-fn
        reduce runs its host-sorted flagged scan per tenant); egress
        is the full [nb, vbp] stack — one cohort dispatch's d2h is
        already amortized N ways."""
        if not rows:
            return []
        if self.name is None:
            raise ValueError("cohort_step serves the monoid stack "
                             "kernels; user-fn reduces run per tenant")
        import jax.numpy as jnp

        eb, vbp = self.eb, self.vb + 1
        nb = seg_ops.bucket_size(len(rows))
        n_cells = nb * vbp
        n_rows = len(rows)
        s = np.zeros(nb * eb, np.int64)
        d = np.zeros(nb * eb, np.int64)
        # the shared value buffer takes the PROMOTED dtype across all
        # rows (mixed cohorts fold in np.result_type, never silently
        # truncating a wider row to the first row's dtype); rows that
        # share a dtype — the normal cohort — keep it exactly
        v = np.zeros(nb * eb, np.result_type(
            *(np.asarray(val).dtype for _s, _d, val in rows)))  # gslint: disable=host-sync (host-input dtype probe: cohort rows are numpy/lists, never device values)
        valid = np.zeros(nb * eb, bool)
        for row, (src, dst, val) in enumerate(rows):
            src = np.asarray(src, np.int64)  # gslint: disable=host-sync (host-input normalization: cohort rows are numpy/lists, never device values)
            dst = np.asarray(dst, np.int64)  # gslint: disable=host-sync (host-input normalization: cohort rows are numpy/lists, never device values)
            val = np.asarray(val)  # gslint: disable=host-sync (host-input normalization: cohort rows are numpy/lists, never device values)
            if not len(src) == len(dst) == len(val):
                raise ValueError("row %d: src/dst/val length mismatch"
                                 % row)
            if len(src) > eb:
                raise ValueError(
                    "row %d: %d edges exceed the %d-edge window bucket"
                    % (row, len(src), eb))
            lo = row * eb
            s[lo:lo + len(src)] = src
            d[lo:lo + len(dst)] = dst
            v[lo:lo + len(val)] = val
            valid[lo:lo + len(src)] = True
        win = np.arange(nb * eb) // eb
        ids, rep = self._cell_ids(s, d, win, valid, vbp, n_cells)
        vals = np.concatenate([v] * rep)
        fn = self._stack_fn(nb)
        cells, counts = fn(jnp.asarray(ids), jnp.asarray(vals))
        cells = np.asarray(cells)  # gslint: disable=host-sync (sanctioned finalize boundary: the cohort step's ONE batched d2h)
        counts = np.asarray(counts)  # gslint: disable=host-sync (sanctioned finalize boundary: the cohort step's ONE batched d2h)
        return [(cells[r], counts[r]) for r in range(n_rows)]

    # ---- host (numpy) tier -------------------------------------------

    def _host_process_stream(self, src, dst, val):
        """Vectorized host form of the monoid reduce, outside
        process_stream (the profiler and the parity tests call it): one
        flattened (window, vertex)-cell bincount per chunk for 'sum'
        (falling back to exact ufunc.at when float64 accumulation
        could round an integer sum), ufunc.at for 'min'/'max'. Same
        cells/counts as the device tier."""
        out: List[Tuple[np.ndarray, np.ndarray]] = []
        eb, vbp = self.eb, self.vb + 1
        n = len(src)
        num_w = -(-n // eb)
        ident = _host_identity(self.name, val.dtype)
        # The bincount fast path accumulates in float64, then casts
        # back. For integer values that is used only when the worst-
        # case cell sum (max|val| × contributions per cell — direction
        # 'all' gives every cell up to 2·eb of them) is exact in
        # float64 AND fits val.dtype: a sum that would overflow the
        # dtype must take the ufunc.at path, whose numpy integer
        # arithmetic wraps modularly exactly like the device
        # segment_sum (out-of-range float→int casts are undefined).
        per_cell = eb * (2 if self.direction == "all" else 1)
        if np.issubdtype(val.dtype, np.integer):
            limit = min(1 << 53, int(np.iinfo(val.dtype).max))
            exact_bincount = (self.name == "sum" and n > 0
                              and int(np.abs(val).max()) * per_cell
                              <= limit)
        else:
            exact_bincount = self.name == "sum"
        if exact_bincount:
            # per-window bincounts: no flattened (window, vertex) cell
            # ids to materialize and no chunk-wide minlength slab —
            # ~3x the flattened form's rate on one core (the cell-id
            # multiply-add and the giant bincount were the cost, not
            # the per-window Python loop)
            for lo in range(0, n, eb):
                s, d, v = src[lo:lo + eb], dst[lo:lo + eb], \
                    val[lo:lo + eb]
                if self.direction == "out":
                    ids, vals = s, v
                elif self.direction == "in":
                    ids, vals = d, v
                else:
                    ids = np.concatenate([s, d])
                    vals = np.concatenate([v, v])
                counts = np.bincount(ids, minlength=vbp)
                if len(counts) > vbp:
                    # the flattened path's reshape raised for ids ≥
                    # vbp; this path must fail as loudly, not emit a
                    # ragged window
                    raise ValueError(
                        "vertex id %d outside [0, %d) in windowed "
                        "reduce input" % (int(ids.max()), vbp))
                cells = np.bincount(
                    ids, weights=vals, minlength=vbp).astype(val.dtype)
                out.append((cells, counts))
            return out
        for at in range(0, num_w, self.MAX_STREAM_WINDOWS):
            hi_w = min(at + self.MAX_STREAM_WINDOWS, num_w)
            lo, hi = at * eb, min(hi_w * eb, n)
            s, d, v = src[lo:hi], dst[lo:hi], val[lo:hi]
            win = np.arange(hi - lo) // eb
            if self.direction == "out":
                vtx = [s]
            elif self.direction == "in":
                vtx = [d]
            else:
                vtx = [s, d]
            ids = np.concatenate([win * vbp + x for x in vtx])
            vals = np.concatenate([v] * len(vtx))
            wb = hi_w - at
            n_cells = wb * vbp
            counts = np.bincount(ids, minlength=n_cells).reshape(
                wb, vbp)
            op = {"sum": np.add, "min": np.minimum,
                  "max": np.maximum}[self.name]
            flat = np.full(n_cells, ident, val.dtype)
            op.at(flat, ids, vals)
            cells = flat.reshape(wb, vbp)
            for w in range(wb):
                out.append((cells[w], counts[w]))
        return out


def numpy_reference(src, dst, val, eb: int, direction: str = "out",
                    name: str = "sum"
                    ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Faithful per-window host port of the reference's windowed
    neighborhood reduce (GraphWindowStream.java:101-121): a per-edge
    fold into a per-vertex slot — the comparison baseline the measured
    leg reports against, and the parity oracle for fuzz tests. Cells
    with count 0 hold the monoid identity (cross-check counts, not
    values, for absence)."""
    op = {"sum": np.add, "min": np.minimum, "max": np.maximum}[name]
    ident = _host_identity(name, np.asarray(val).dtype)  # gslint: disable=host-sync (host oracle: reference inputs are numpy, never device values)
    src = np.asarray(src, np.int64)  # gslint: disable=host-sync (host oracle: reference inputs are numpy, never device values)
    dst = np.asarray(dst, np.int64)  # gslint: disable=host-sync (host oracle: reference inputs are numpy, never device values)
    val = np.asarray(val)  # gslint: disable=host-sync (host oracle: reference inputs are numpy, never device values)
    nv = int(max(src.max(), dst.max())) + 1 if len(src) else 1  # gslint: disable=host-sync (host oracle: numpy-on-numpy bound, no device value in sight)
    out = []
    for lo in range(0, len(src), eb):
        s, d, v = src[lo:lo + eb], dst[lo:lo + eb], val[lo:lo + eb]
        if direction == "out":
            pairs = [(s, v)]
        elif direction == "in":
            pairs = [(d, v)]
        else:
            pairs = [(s, v), (d, v)]
        acc = np.full(nv, ident, val.dtype)
        cnt = np.zeros(nv, np.int64)
        for vtx, vv in pairs:
            op.at(acc, vtx, vv)
            np.add.at(cnt, vtx, 1)
        out.append((acc, cnt))
    return out


def sliding_numpy_reference(src, dst, val, eb: int, slide: int,
                            direction: str = "out", name: str = "sum"
                            ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Independent sliding oracle: one emission per completed (or
    final ragged) pane, each a FULL per-edge refold of its window
    slice [max(0, (i+1)·slide − eb), (i+1)·slide) through
    numpy_reference — no pane machinery shared with the engine under
    test. Arrays are sized by the slice's max vertex id (compare
    cells under the counts mask, like numpy_reference)."""
    src = np.asarray(src)  # gslint: disable=host-sync (pure-host oracle: numpy on numpy)
    dst = np.asarray(dst)  # gslint: disable=host-sync (pure-host oracle: numpy on numpy)
    val = np.asarray(val)  # gslint: disable=host-sync (pure-host oracle: numpy on numpy)
    n = len(src)
    out: List[Tuple[np.ndarray, np.ndarray]] = []
    for i in range(-(-n // slide)):
        lo = max(0, (i + 1) * slide - eb)
        hi = min((i + 1) * slide, n)
        out.extend(numpy_reference(src[lo:hi], dst[lo:hi],
                                   val[lo:hi], eb, direction, name))
    return out
