"""Per-window delta streams (driver emit_deltas=True): the on-device
changed-slot masks must let a consumer reconstruct every snapshot by
cumulatively applying (ids, values) deltas from the analytic's start
state — the per-update improving-stream contract of the reference's
continuous aggregates (SimpleEdgeStream.java:473-481), delivered as
one compact record set per window instead of per input edge
(core/driver.py:12-16 documents that granularity divergence)."""

import numpy as np
import pytest

from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver

ANALYTICS = ("degrees", "cc", "bipartite")
# the single-chip snapshot tiers: None is the unpinned default (the
# scan); native and host are the demotion ladder's rungs
TIERS = pytest.mark.parametrize("tier", [None, "native", "host"])


def fuzz_stream(num_edges, num_vertices, seed):
    rng = np.random.default_rng(seed)
    # power-ish skew so CC merges + bipartite flips actually happen
    src = rng.zipf(1.7, num_edges) % num_vertices
    dst = (src + 1 + rng.zipf(1.7, num_edges) % (num_vertices - 1)) \
        % num_vertices
    return src.astype(np.int64), dst.astype(np.int64)


class Reconstructor:
    """Applies delta records; never looks at the snapshots."""

    def __init__(self):
        self.deg = np.zeros(0, np.int64)
        self.cc = np.zeros(0, np.int32)
        self.odd = np.zeros(0, bool)

    def _grow(self, n):
        if len(self.deg) < n:
            old = len(self.deg)
            self.deg = np.concatenate(
                [self.deg, np.zeros(n - old, np.int64)])
            self.cc = np.concatenate(
                [self.cc, np.arange(old, n, dtype=np.int32)])
            self.odd = np.concatenate(
                [self.odd, np.zeros(n - old, bool)])

    def apply(self, res):
        n = len(res.vertex_ids)
        self._grow(n)
        for field, arr in (("delta_degrees", self.deg),
                           ("delta_cc", self.cc),
                           ("delta_bipartite", self.odd)):
            ids, vals = getattr(res, field)
            arr[ids] = vals

    def check(self, res):
        n = len(res.vertex_ids)
        np.testing.assert_array_equal(self.deg[:n], res.degrees)
        np.testing.assert_array_equal(self.cc[:n], res.cc_labels)
        np.testing.assert_array_equal(self.odd[:n], res.bipartite_odd)


def roundtrip(driver, src, dst, chunks=1):
    recon = Reconstructor()
    windows = 0
    per = len(src) // chunks
    for c in range(chunks):
        lo, hi = c * per, (c + 1) * per if c < chunks - 1 else len(src)
        for res in driver.run_arrays(src[lo:hi], dst[lo:hi]):
            assert res.delta_degrees is not None
            recon.apply(res)
            recon.check(res)
            windows += 1
    return windows


@TIERS
def test_batched_single_chip_fuzz(tier):
    src, dst = fuzz_stream(6000, 700, seed=11)
    drv = StreamingAnalyticsDriver(
        window_ms=0, analytics=ANALYTICS, vertex_bucket=256,
        edge_bucket=512, emit_deltas=True, snapshot_tier=tier)
    assert roundtrip(drv, src, dst) >= 11


@TIERS
def test_deltas_are_sparse(tier):
    """The point of the masks: windows that touch few vertices emit few
    records, not vb-length vectors."""
    src, dst = fuzz_stream(4096, 2000, seed=3)
    drv = StreamingAnalyticsDriver(
        window_ms=0, analytics=ANALYTICS, vertex_bucket=4096,
        edge_bucket=1024, emit_deltas=True, snapshot_tier=tier)
    results = drv.run_arrays(src, dst)
    for res in results[1:]:
        ids, _ = res.delta_degrees
        # ≤ 2 endpoints per edge can change degree
        assert len(ids) <= 2 * res.num_edges
        assert len(ids) < len(res.vertex_ids)  # strictly sparse here


@TIERS
def test_per_window_path_matches_batched(tier):
    """Single-window calls route through _window (host-diff deltas);
    feeding the same stream window-by-window must reconstruct
    identically to the batched device-mask path."""
    src, dst = fuzz_stream(2048, 300, seed=5)
    eb = 512
    drv_b = StreamingAnalyticsDriver(
        window_ms=0, analytics=ANALYTICS, vertex_bucket=256,
        edge_bucket=eb, emit_deltas=True, snapshot_tier=tier)
    batched = drv_b.run_arrays(src, dst)
    drv_w = StreamingAnalyticsDriver(
        window_ms=0, analytics=ANALYTICS, vertex_bucket=256,
        edge_bucket=eb, emit_deltas=True, snapshot_tier=tier)
    recon = Reconstructor()
    for i, lo in enumerate(range(0, len(src), eb)):
        (res,) = drv_w.run_arrays(src[lo:lo + eb], dst[lo:lo + eb])
        recon.apply(res)
        recon.check(res)
        b = batched[i]
        for field in ("delta_degrees", "delta_cc", "delta_bipartite"):
            ids_w, vals_w = getattr(res, field)
            ids_b, vals_b = getattr(b, field)
            np.testing.assert_array_equal(ids_w, ids_b)
            np.testing.assert_array_equal(vals_w, vals_b)


@TIERS
def test_event_time_windows_with_growth(tier):
    """Event-time windows of ragged sizes + vertex-bucket growth mid
    stream (the scan rebuilds at the wider bucket) keep the delta
    contract."""
    rng = np.random.default_rng(17)
    n = 3000
    src = rng.integers(0, 900, n)
    dst = rng.integers(0, 900, n)
    ts = np.sort(rng.integers(0, 4000, n))
    drv = StreamingAnalyticsDriver(
        window_ms=250, analytics=ANALYTICS, vertex_bucket=64,
        edge_bucket=64, emit_deltas=True, snapshot_tier=tier)
    recon = Reconstructor()
    for res in drv.run_arrays(src, dst, ts):
        recon.apply(res)
        recon.check(res)


def test_sharded_mesh_deltas():
    from gelly_streaming_tpu.parallel.mesh import make_mesh

    src, dst = fuzz_stream(4096, 500, seed=23)
    drv = StreamingAnalyticsDriver(
        window_ms=0, analytics=ANALYTICS, vertex_bucket=512,
        edge_bucket=512, mesh=make_mesh(), emit_deltas=True)
    assert roundtrip(drv, src, dst, chunks=2) == 8


@TIERS
def test_off_by_default(tier):
    src, dst = fuzz_stream(1024, 200, seed=2)
    drv = StreamingAnalyticsDriver(
        window_ms=0, analytics=ANALYTICS, vertex_bucket=256,
        edge_bucket=512, snapshot_tier=tier)
    for res in drv.run_arrays(src, dst):
        assert res.delta_degrees is None
        assert res.delta_cc is None
        assert res.delta_bipartite is None
