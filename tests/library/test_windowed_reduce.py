"""Columnar windowed-reduce engine (ops/windowed_reduce.py) — the
stream-rate form of reduceOnEdges/foldNeighbors (BASELINE.json config
#2; reference hot loop GraphWindowStream.java:101-121).

Parity is pinned three ways: against the record-level runtime on the
reference's golden TestSlice graph (same numbers the reference's own
TestSlice.java:81-121 asserts), against a faithful numpy per-window
fold on a 1M-edge fuzz stream, and across the monoid/associative-fn
tiers.
"""

import numpy as np
import pytest

from gelly_streaming_tpu import (EdgeDirection, EdgesReduce,
                                 SimpleEdgeStream, Time)
from gelly_streaming_tpu.ops import segment as seg_ops
from gelly_streaming_tpu.ops.windowed_reduce import (WindowedEdgeReduce,
                                                     numpy_reference)

from ..conftest import long_long_edges, run_and_sort

FOLD_EXPECTED = {  # reference TestSlice.java:81-121
    "out": {1: 25, 2: 23, 3: 69, 4: 45, 5: 51},
    "in": {1: 51, 2: 12, 3: 36, 4: 34, 5: 80},
    "all": {1: 76, 2: 35, 3: 105, 4: 79, 5: 131},
}


@pytest.mark.parametrize("direction", ["out", "in", "all"])
def test_columnar_matches_golden_slice(direction):
    """The columnar engine reproduces the reference's TestSlice sums
    exactly (single window covering the whole 7-edge graph)."""
    edges = long_long_edges()
    src = np.array([e.source for e in edges])
    dst = np.array([e.target for e in edges])
    val = np.array([e.value for e in edges])
    uniq, (s_d, d_d) = seg_ops.intern(src, dst)
    eng = WindowedEdgeReduce(vertex_bucket=len(uniq), edge_bucket=8,
                             name="sum", direction=direction)
    (cells, counts), = eng.process_stream(s_d, d_d, val)
    got = {int(uniq[slot]): int(cells[slot])
           for slot in np.nonzero(counts)[0]}
    assert got == FOLD_EXPECTED[direction]


@pytest.mark.parametrize("direction,enum_dir", [
    ("out", EdgeDirection.OUT), ("in", EdgeDirection.IN),
    ("all", EdgeDirection.ALL)])
def test_columnar_matches_record_level_path(env, direction, enum_dir):
    """Same windows through the record-level runtime
    (slice().reduce_on_edges with a host UDF — exact reference
    semantics) and the columnar engine: identical per-vertex sums."""
    edges = long_long_edges()
    out = SimpleEdgeStream(env.from_collection(edges), env).slice(
        Time.seconds(1), enum_dir).reduce_on_edges(
        EdgesReduce(lambda a, b: a + b))
    record_level = run_and_sort(env, out)

    src = np.array([e.source for e in edges])
    dst = np.array([e.target for e in edges])
    val = np.array([e.value for e in edges])
    uniq, (s_d, d_d) = seg_ops.intern(src, dst)
    eng = WindowedEdgeReduce(vertex_bucket=len(uniq), edge_bucket=8,
                             name="sum", direction=direction)
    (cells, counts), = eng.process_stream(s_d, d_d, val)
    columnar = sorted("%d,%d" % (uniq[slot], cells[slot])
                      for slot in np.nonzero(counts)[0])
    assert columnar == record_level


@pytest.mark.parametrize("direction", ["out", "in", "all"])
@pytest.mark.parametrize("name", ["sum", "min", "max"])
def test_columnar_fuzz_vs_numpy_fold(direction, name):
    """Multi-window fuzz (ragged tail, duplicate edges, skew) against
    the faithful per-window numpy fold."""
    rng = np.random.default_rng(41)
    n, nv, eb = 10_000, 700, 1024
    src = (rng.zipf(1.4, n) % nv).astype(np.int64)
    dst = rng.integers(0, nv, n)
    val = rng.integers(1, 1000, n).astype(np.int32)
    eng = WindowedEdgeReduce(vertex_bucket=nv, edge_bucket=eb,
                             name=name, direction=direction)
    got = eng.process_stream(src, dst, val)
    want = numpy_reference(src, dst, val, eb, direction, name)
    assert len(got) == len(want) == -(-n // eb)
    for (gc, gn), (wc, wn) in zip(got, want):
        np.testing.assert_array_equal(gn[:nv], wn)
        occ = wn > 0
        np.testing.assert_array_equal(gc[:nv][occ], wc[occ])


@pytest.mark.slow
def test_columnar_million_edge_fuzz():
    """VERDICT r3 item 3's fuzz bar: 1M edges through the engine at the
    bench window size, exact parity with the numpy fold."""
    rng = np.random.default_rng(43)
    n, nv, eb = 1 << 20, 1 << 14, 8192
    src = (rng.zipf(1.3, n) % nv).astype(np.int64)
    dst = rng.integers(0, nv, n)
    val = rng.integers(1, 100, n).astype(np.int64)
    eng = WindowedEdgeReduce(vertex_bucket=nv, edge_bucket=eb,
                             name="sum", direction="out")
    got = eng.process_stream(src, dst, val)
    want = numpy_reference(src, dst, val, eb, "out", "sum")
    assert len(got) == len(want) == n // eb
    for (gc, gn), (wc, wn) in zip(got, want):
        np.testing.assert_array_equal(gn[:nv], wn)
        np.testing.assert_array_equal(gc[:nv], wc)


needs_native_reduce = pytest.mark.skipif(
    not __import__("gelly_streaming_tpu.native",
                   fromlist=["x"]).windowed_reduce_available(),
    reason="libgsnative.so lacks gs_windowed_reduce")


@needs_native_reduce
@pytest.mark.parametrize("direction", ["out", "in", "all"])
@pytest.mark.parametrize("name", ["sum", "min", "max"])
def test_native_reduce_tier_matches_numpy(direction, name):
    """The C++ fused tier (native/ingest.cpp gs_windowed_reduce):
    identical (cells, counts) to the numpy tier on ragged, skewed,
    duplicate-heavy streams — both the i32 fast path and the i64
    form."""
    rng = np.random.default_rng(47)
    n, nv, eb = 9_500, 700, 1024
    src = (rng.zipf(1.4, n) % nv).astype(np.int64)
    dst = rng.integers(0, nv, n)
    val = rng.integers(-50, 1000, n).astype(np.int32)
    eng = WindowedEdgeReduce(vertex_bucket=nv, edge_bucket=eb,
                             name=name, direction=direction)
    want = eng._host_process_stream(src, dst, val)
    for cast in (np.int32, np.int64):   # i32 fast path + i64 form
        got = eng._native_process_stream(src.astype(cast),
                                         dst.astype(cast), val)
        assert got is not None and len(got) == len(want)
        for (gc, gn), (wc, wn) in zip(got, want):
            np.testing.assert_array_equal(gn, wn)
            occ = wn > 0
            np.testing.assert_array_equal(
                gc[occ] if name != "sum" else gc,
                wc[occ] if name != "sum" else wc)


@needs_native_reduce
def test_native_reduce_rejects_out_of_range_ids():
    """The C++ kernel must fail as loudly as the other tiers on bad
    ids (bincount raises) — never write outside its slabs."""
    from gelly_streaming_tpu import native

    for bad in (np.array([900], np.int32), np.array([-1], np.int32)):
        with pytest.raises(ValueError, match="outside"):
            native.windowed_reduce(bad, np.array([1], bad.dtype),
                                   np.array([7], bad.dtype), 4, 10,
                                   "sum", "out", 0)


@needs_native_reduce
def test_native_i32_output_gate_covers_counts_slab():
    """The int32-output fast form is gated on the COUNTS slab too: a
    cell can receive up to 2·eb contributions regardless of the
    reduce op, so min/max and the all-zero-sum case (where the old
    value-only bound 0 × per_cell passed vacuously) must fall back to
    int64 slabs whenever 2*eb exceeds INT32_MAX. Normal window sizes
    keep the int32 fast path."""
    from gelly_streaming_tpu import native
    from gelly_streaming_tpu.ops.windowed_reduce import _host_identity

    src = np.array([0, 1, 2], np.int32)
    dst = np.array([1, 2, 0], np.int32)
    ones = np.ones(3, np.int32)
    huge_eb = (1 << 30) + 1           # 2*eb > INT32_MAX, n stays tiny
    for name, val in (("min", ones), ("max", ones),
                      ("sum", np.zeros(3, np.int32))):
        cells, counts = native.windowed_reduce(
            src, dst, val, huge_eb, 8, name, "all",
            int(_host_identity(name, val.dtype)))
        assert counts.dtype == np.int64, (name, counts.dtype)
        assert cells.dtype == np.int64, (name, cells.dtype)
    if native.windowed_reduce_available() and hasattr(
            native._load(), "gs_windowed_reduce_i32o"):
        cells, counts = native.windowed_reduce(
            src, dst, ones, 8, 8, "min", "all", int(2 ** 31 - 1))
        assert counts.dtype == np.int32   # the fast path still fires


def test_host_sum_fast_path_rejects_out_of_range_ids():
    """The per-window bincount fast path must raise (like the
    flattened path's reshape did), not emit a ragged window."""
    eng = WindowedEdgeReduce(vertex_bucket=64, edge_bucket=32,
                             name="sum", direction="out")
    src = np.array([1, 2, 200], np.int64)   # 200 >= vbp=65
    dst = np.array([3, 4, 5], np.int64)
    val = np.ones(3, np.int32)
    with pytest.raises(ValueError, match="outside"):
        eng._host_process_stream(src, dst, val)


def test_associative_fn_tier_matches_monoid():
    """fn=jnp.minimum through the flagged associative scan equals
    name='min' through the segment kernels — and a non-monoid
    associative fn (gcd) equals a direct per-cell fold."""
    import math

    import jax.numpy as jnp

    rng = np.random.default_rng(47)
    n, nv, eb = 600, 40, 128
    src = rng.integers(0, nv, n)
    dst = rng.integers(0, nv, n)
    val = rng.integers(1, 10_000, n).astype(np.int32)

    m = WindowedEdgeReduce(nv, eb, name="min").process_stream(
        src, dst, val)
    f = WindowedEdgeReduce(nv, eb, fn=jnp.minimum).process_stream(
        src, dst, val)
    for (mc, mn), (fc, fnn) in zip(m, f):
        np.testing.assert_array_equal(mn, fnn)
        occ = mn > 0
        np.testing.assert_array_equal(mc[occ], fc[occ])

    g = WindowedEdgeReduce(nv, eb, fn=jnp.gcd).process_stream(
        src, dst, val)
    for w, (gc, gn) in enumerate(g):
        s, v = src[w * eb:(w + 1) * eb], val[w * eb:(w + 1) * eb]
        for vtx in range(nv):
            mask = s == vtx
            assert gn[vtx] == mask.sum()
            if mask.any():
                acc = None
                for x in v[mask].tolist():
                    acc = x if acc is None else math.gcd(acc, x)
                assert gc[vtx] == acc


def test_window_chunking_boundaries():
    """Streams longer than one dispatch chunk (MAX_STREAM_WINDOWS)
    split without losing or shifting windows."""
    rng = np.random.default_rng(53)
    nv, eb = 64, 32
    n = eb * 70 + 11   # > one 64-window chunk, ragged tail
    src = rng.integers(0, nv, n)
    dst = rng.integers(0, nv, n)
    val = rng.integers(1, 50, n).astype(np.int32)
    eng = WindowedEdgeReduce(nv, eb, name="sum")
    got = eng.process_stream(src, dst, val)
    want = numpy_reference(src, dst, val, eb)
    assert len(got) == len(want) == 71
    for (gc, gn), (wc, wn) in zip(got, want):
        np.testing.assert_array_equal(gc[:nv], wc)
        np.testing.assert_array_equal(gn[:nv], wn)
