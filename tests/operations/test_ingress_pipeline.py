"""The three-stage host-ingress pipeline (ops/ingress_pipeline):
pipeline-vs-sync parity for EVERY kernel routed through it, worker-pool
determinism (same results at pool sizes 1/2/4), per-stage timers, prep
error propagation with the worker traceback preserved, and the
parallel interning scheme's exact slot parity — the parametrized
extension of test_iter_edge_chunks_prefetch_matches_sync to the whole
ingress layer."""


import numpy as np
import pytest

from gelly_streaming_tpu.ops import ingress_pipeline as ip


@pytest.fixture
def pool_env(monkeypatch):
    """Set the pool width for a test and always restore + rebuild."""

    def set_workers(n):
        monkeypatch.setenv("GS_PIPELINE_WORKERS", str(n))
        ip.reset_pool()

    yield set_workers
    monkeypatch.delenv("GS_PIPELINE_WORKERS", raising=False)
    ip.reset_pool()


def _stream(n, v, seed=11):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, n).astype(np.int32)
    dst = rng.integers(0, v, n).astype(np.int32)
    keep = src != dst
    return src[keep], dst[keep]


# ----------------------------------------------------------------------
# run_pipeline unit contract
# ----------------------------------------------------------------------

def test_run_pipeline_orders_and_lags_finalize():
    """Finalize sees chunks in order and lags dispatch by exactly one;
    per-stage timers count every chunk once."""
    events = []
    timers = ip.StageTimers()

    ip.run_pipeline(
        range(5),
        prep=lambda i: ("prep", i),
        h2d=lambda p: ("dev", p[1]),
        dispatch=lambda d: (events.append(("dispatch", d[1]))
                            or ("raw", d[1])),
        finalize=lambda r: events.append(("finalize", r[1])),
        timers=timers)

    assert [e for e in events if e[0] == "finalize"] == [
        ("finalize", i) for i in range(5)]
    d_at = [i for i, e in enumerate(events) if e[0] == "dispatch"]
    f_at = [i for i, e in enumerate(events) if e[0] == "finalize"]
    # chunk i finalizes AFTER chunk i+1 dispatches (depth-2), except
    # the last, which flushes at the end
    for i in range(4):
        assert f_at[i] > d_at[i + 1]
    assert timers.chunks == 5
    snap = timers.snapshot()
    assert set(snap) == {"chunks", "prep_ms_per_chunk",
                         "h2d_ms_per_chunk", "compute_ms_per_chunk"}


def test_run_pipeline_prep_error_carries_worker_traceback():
    """A prep failure surfaces as PrepError (a RuntimeError) whose
    message contains the WORKER'S formatted traceback — the frames
    where prep actually died, not just the consumer-side re-raise —
    with the original exception chained as __cause__."""

    def bad_prep(i):
        if i == 2:
            raise ValueError("prep exploded here")
        return i

    with pytest.raises(RuntimeError) as ei:
        ip.run_pipeline(range(4), bad_prep, lambda p: p, lambda d: d,
                        lambda r: None)
    assert isinstance(ei.value, ip.PrepError)
    msg = str(ei.value)
    assert "prep exploded here" in msg
    assert "bad_prep" in msg          # the worker-side frame
    assert "Traceback" in msg
    assert isinstance(ei.value.__cause__, ValueError)


def test_run_pipeline_sync_and_parallel_identical(pool_env):
    """Same finalize stream at every pool size and in forced_sync."""

    def run():
        out = []
        ip.run_pipeline(range(7),
                        prep=lambda i: i * 10,
                        h2d=lambda p: p + 1,
                        dispatch=lambda d: d * 2,
                        finalize=out.append)
        return out

    with ip.forced_sync():
        want = run()
    for w in (1, 2, 4):
        pool_env(w)
        assert run() == want


def test_run_pipeline_inflight_cap_and_interrupts(pool_env,
                                                  monkeypatch):
    """GS_PIPELINE_INFLIGHT bounds look-ahead without changing
    results, and a KeyboardInterrupt in prep aborts UNWRAPPED (never
    converted into a PrepError a broad fallback would eat)."""
    pool_env(4)
    monkeypatch.setenv("GS_PIPELINE_INFLIGHT", "1")
    out = []
    ip.run_pipeline(range(6), lambda i: i, lambda p: p,
                    lambda d: d, out.append)
    assert out == list(range(6))
    monkeypatch.delenv("GS_PIPELINE_INFLIGHT")

    def interrupt(i):
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        with ip.forced_sync():
            ip.run_pipeline(range(2), interrupt, lambda p: p,
                            lambda d: d, lambda r: None)


def test_map_ordered_preserves_order_and_errors(pool_env):
    pool_env(4)
    assert ip.map_ordered(lambda x: x * x, range(20)) == [
        x * x for x in range(20)]
    with pytest.raises(ip.PrepError, match="boom"):
        ip.map_ordered(
            lambda x: (_ for _ in ()).throw(RuntimeError("boom")),
            range(3))


# ----------------------------------------------------------------------
# pipeline-vs-sync parity for every routed kernel (the parametrized
# extension of test_iter_edge_chunks_prefetch_matches_sync)
# ----------------------------------------------------------------------

def _triangle_counts(ingress, src, dst):
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    kern = TriangleWindowKernel(edge_bucket=256, vertex_bucket=256,
                                ingress=ingress)
    kern.MAX_STREAM_WINDOWS = 3   # several chunks + a ragged tail
    return kern._count_stream_device(src, dst)


def _reduce_cells(ingress, src, dst):
    from gelly_streaming_tpu.ops.windowed_reduce import WindowedEdgeReduce

    val = (1 + (src.astype(np.int64) + 3 * dst) % 97).astype(np.int32)
    eng = WindowedEdgeReduce(vertex_bucket=256, edge_bucket=256,
                             name="sum", direction="all",
                             ingress=ingress)
    eng.MAX_STREAM_WINDOWS = 3
    out = eng._device_process_stream(src.astype(np.int64),
                                     dst.astype(np.int64), val)
    return [(c.tolist(), k.tolist()) for c, k in out]


def _fused_summaries(ingress, src, dst):
    from gelly_streaming_tpu.ops.scan_analytics import StreamSummaryEngine

    eng = StreamSummaryEngine(edge_bucket=256, vertex_bucket=256,
                              ingress=ingress)
    eng.MAX_WINDOWS = 3
    return eng.process(src, dst)


def _driver_results(_ingress, src, dst):
    from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver

    drv = StreamingAnalyticsDriver(window_ms=0, edge_bucket=256,
                                   vertex_bucket=256)
    drv._SCAN_CHUNK = 3
    out = []
    for res in drv.run_arrays(src.astype(np.int64),
                              dst.astype(np.int64)):
        out.append((res.window_start, res.num_edges,
                    res.vertex_ids.tolist(), res.degrees.tolist(),
                    res.cc_labels.tolist(),
                    np.asarray(res.bipartite_odd).tolist(),
                    res.triangles))
    return out


ENGINES = [
    ("triangles-standard", _triangle_counts, "standard"),
    ("triangles-compact", _triangle_counts, "compact"),
    ("reduce-standard", _reduce_cells, "standard"),
    ("reduce-compact", _reduce_cells, "compact"),
    ("fused-standard", _fused_summaries, "standard"),
    ("fused-compact", _fused_summaries, "compact"),
    ("driver", _driver_results, None),
]


@pytest.mark.parametrize("name,fn,ingress",
                         ENGINES, ids=[e[0] for e in ENGINES])
def test_pipeline_matches_sync_every_engine(name, fn, ingress,
                                            pool_env):
    """Every kernel routed through the ingress pipeline produces
    byte-identical results with the pipeline on (several pool sizes)
    and forced synchronous — the worker-pool determinism contract."""
    src, dst = _stream(10 * 256 + 96, 256, seed=23)
    with ip.forced_sync():
        want = fn(ingress, src, dst)
    assert want  # the stream produces real windows
    for workers in (1, 2, 4):
        pool_env(workers)
        assert fn(ingress, src, dst) == want, \
            "%s diverged at %d workers" % (name, workers)


def test_host_and_native_tiers_parallel_parity(pool_env):
    """The host twin counts identical windows through the pool and
    sequentially, and the native C++ counter agrees with it."""
    from gelly_streaming_tpu.ops import host_triangles

    from gelly_streaming_tpu import native

    src, dst = _stream(9 * 128 + 50, 200, seed=5)
    with ip.forced_sync():
        want = host_triangles.count_stream(src, dst, 128)
    for workers in (1, 2, 4):
        pool_env(workers)
        assert host_triangles.count_stream(src, dst, 128) == want
    if native.triangles_available():
        assert [int(c) for c in
                native.triangle_count_stream(src, dst, 128)] == want


def test_stage_timers_populated_by_stream_run():
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    kern = TriangleWindowKernel(edge_bucket=128, vertex_bucket=128)
    kern.MAX_STREAM_WINDOWS = 2
    src, dst = _stream(8 * 128, 128, seed=9)
    kern._count_stream_device(src, dst)
    snap = kern.stage_timers.snapshot()
    assert snap["chunks"] >= 4
    assert snap["compute_ms_per_chunk"] > 0


def test_parallel_intern_accepts_unorderable_hashables(pool_env):
    """Arbitrary-hashable (unorderable) id streams — the Python
    interner's contract — must still intern with the pool enabled:
    the parallel uniques scheme needs orderable elements, so object
    arrays take the sequential loop instead of crashing in
    np.unique's sort."""
    from gelly_streaming_tpu.utils.interning import (
        IncrementalInterner, parallel_intern_arrays)

    pool_env(4)
    mixed = [np.array([(1, 2), 7, "x", 7, (1, 2)], dtype=object),
             np.array(["x", (3,), 7], dtype=object)]
    seq = IncrementalInterner()
    want = [seq.intern_array(a).tolist() for a in mixed]
    par = IncrementalInterner()
    dense, sizes = parallel_intern_arrays(par, mixed)
    assert [d.tolist() for d in dense] == want
    assert sizes[-1] == len(seq)


def test_compact_fused_engine_rejects_wrapping_ids():
    """Ids the uint16 cast would wrap must raise loudly through the
    fused engine's compact path (same contract as the windowed-reduce
    compact prep), never silently corrupt another vertex's carried
    state."""
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)

    eng = StreamSummaryEngine(edge_bucket=64, vertex_bucket=65536,
                              ingress="compact")
    with pytest.raises(ValueError, match="outside \\[0"):
        eng.process(np.array([70000], np.int64),
                    np.array([1], np.int64))


def test_parallel_intern_matches_sequential(pool_env):
    """parallel_intern_arrays assigns EXACTLY the slots the sequential
    loop would, at every pool size (first-occurrence order preserved
    through the uniques scheme)."""
    from gelly_streaming_tpu.utils.interning import (
        IncrementalInterner, parallel_intern_arrays)

    rng = np.random.default_rng(3)
    arrays = [rng.integers(0, 500, rng.integers(0, 400))
              for _ in range(9)]
    seq = IncrementalInterner()
    want = []
    sizes_want = []
    for a in arrays:
        want.append(seq.intern_array(a).tolist())
        sizes_want.append(len(seq))
    for workers in (1, 2, 4):
        pool_env(workers)
        par = IncrementalInterner()
        dense, sizes = parallel_intern_arrays(par, arrays)
        assert [d.tolist() for d in dense] == want
        assert sizes == sizes_want
        assert par.ids_of(np.arange(len(par))) == seq.ids_of(
            np.arange(len(seq)))
