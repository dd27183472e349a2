"""Window triangle count parity tests.

Golden data and result from the reference
(ExamplesTestData.java:20-33: 19-edge timestamped graph, 400ms windows →
"(2,1199) (2,399) (3,799)"; asserted by WindowTrianglesITCase.java:42-44),
checked against BOTH the API-parity candidate pipeline and the fused
device kernel, plus randomized cross-checks of the two device kernels
against a brute-force count.
"""

import itertools

import numpy as np
import pytest

from gelly_streaming_tpu import StreamEnvironment, Time
from gelly_streaming_tpu.core.types import text_line
from gelly_streaming_tpu.models.triangles import WindowTriangleCount
from gelly_streaming_tpu.models.workloads import (timestamped_graph,
                                                  window_triangles_pipeline)
from gelly_streaming_tpu.ops import triangles as tri_ops

TRIANGLES_DATA = "\n".join([
    # reference: ExamplesTestData.java:22-29
    "1 2 100", "1 3 150", "3 2 200", "2 4 250", "3 4 300", "3 5 350",
    "4 5 400", "4 6 450", "6 5 500", "5 7 550", "6 7 600", "8 6 650",
    "7 8 700", "7 9 750", "8 9 800", "10 8 850", "9 10 900", "9 11 950",
    "10 11 1000",
])

EXPECTED = sorted(["(2,1199)", "(2,399)", "(3,799)"])


@pytest.fixture
def data_file(tmp_path):
    p = tmp_path / "edges.txt"
    p.write_text(TRIANGLES_DATA + "\n")
    return str(p)


def _run(pipeline_fn, data_file):
    env = StreamEnvironment()
    graph = timestamped_graph(env, data_file)
    sink = pipeline_fn(graph).collect()
    env.execute()
    return sorted(text_line(v) for v in env.results_of(sink))


def test_window_triangles_api_pipeline(data_file):
    assert _run(
        lambda g: window_triangles_pipeline(g, Time.milliseconds_of(400)),
        data_file,
    ) == EXPECTED


def test_window_triangles_fused_device(data_file):
    assert _run(
        lambda g: WindowTriangleCount(Time.milliseconds_of(400)).run(g),
        data_file,
    ) == EXPECTED


def _brute_force(src, dst, n):
    adj = [set() for _ in range(n)]
    for u, v in zip(src, dst):
        if u != v:
            adj[u].add(v)
            adj[v].add(u)
    count = 0
    for a, b, c in itertools.combinations(range(n), 3):
        if b in adj[a] and c in adj[a] and c in adj[b]:
            count += 1
    return count


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("kernel", ["dense", "sparse", "pallas"])
def test_kernels_vs_brute_force(seed, kernel):
    rng = np.random.default_rng(seed)
    n = 30
    e = 120
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    expected = _brute_force(src, dst, n)
    if kernel == "pallas":
        from gelly_streaming_tpu.ops.pallas_triangles import \
            triangle_count_dense_pallas as fn
    else:
        fn = (tri_ops.triangle_count_dense if kernel == "dense"
              else tri_ops.triangle_count_sparse)
    assert fn(src, dst, n) == expected


def test_cpu_backend_selects_binary_search_intersect():
    """On CPU backends the measured winner is the binary search (~5x,
    PERF.md `intersect`); the resolvers must pick it — and it must
    agree with the broadcast compare on the sorted-row contract the
    single-chip builder guarantees (build_window_counter sorts via
    dedupe_and_positions)."""
    import jax
    import jax.numpy as jnp

    assert jax.default_backend() == "cpu"  # conftest pins the backend
    assert (tri_ops.resolve_xla_intersect()
            is tri_ops.intersect_local_bsearch)
    rng = np.random.default_rng(5)
    vb, k, ep = 128, 64, 512
    # rows exactly as the builder lays them out: unique ascending
    # neighbors packed at the FRONT, sentinel suffix (mid-row sentinels
    # would break the searchsorted contract — and never occur)
    nbr = np.full((vb + 1, k), vb, np.int32)
    for v in range(vb):
        row = np.unique(rng.integers(0, vb, size=k // 2))
        nbr[v, :len(row)] = row.astype(np.int32)
    ea = rng.integers(0, vb, ep).astype(np.int32)
    eb_ = rng.integers(0, vb, ep).astype(np.int32)
    emask = rng.random(ep) < 0.9
    args = tuple(jnp.asarray(x) for x in (nbr, ea, eb_, emask))
    assert int(tri_ops.intersect_local_bsearch(*args)) == int(
        tri_ops.intersect_local(*args))


@pytest.mark.parametrize("seed", range(3))
def test_pallas_intersect_matches_xla_compare(seed):
    """The Pallas rows-intersect prototype (ops/pallas_intersect.py)
    agrees with intersect_local on random sorted dedup'd rows,
    including ragged (non-TILE_E-multiple) edge counts and padding."""
    import jax.numpy as jnp

    from gelly_streaming_tpu.ops.pallas_intersect import \
        intersect_local_pallas

    rng = np.random.default_rng(seed)
    # shapes chosen to exercise EVERY kernel dimension: ep=600 → ten
    # TILE_E=64 grid tiles (ragged final tile of 24 via padding),
    # k=160 → two CHUNK_K=128 compare chunks (ragged final chunk of 32)
    vb, k, ep = 64, 160, 600
    fill = rng.integers(0, vb, size=(vb + 1, k)).astype(np.int32)
    fill.sort(axis=1)
    # dedupe within rows; duplicates become the sentinel
    dup = np.concatenate(
        [np.zeros((vb + 1, 1), bool), fill[:, 1:] == fill[:, :-1]], axis=1)
    nbr = np.where(dup, vb, fill).astype(np.int32)
    ea = rng.integers(0, vb, ep).astype(np.int32)
    eb_ = rng.integers(0, vb, ep).astype(np.int32)
    emask = rng.random(ep) < 0.9
    args = tuple(jnp.asarray(x) for x in (nbr, ea, eb_, emask))
    assert int(intersect_local_pallas(*args)) == int(
        tri_ops.intersect_local(*args))


def test_pallas_intersect_multi_slab(monkeypatch):
    """Edge buckets beyond MAX_TILES*TILE_E are processed in several
    pallas_calls (the [g] partial vector lives in scarce SMEM, so g is
    capped per call). Shrinking MAX_TILES exercises the slab loop —
    slab-boundary slicing, whole-slab padding, cross-slab accumulation
    — with the same small fixture."""
    import jax.numpy as jnp

    from gelly_streaming_tpu.ops import pallas_intersect

    monkeypatch.setattr(pallas_intersect, "MAX_TILES", 2)  # 128-edge slabs
    rng = np.random.default_rng(11)
    vb, k, ep = 64, 128, 300   # pads to 384 = 3 slabs, ragged last slab
    fill = rng.integers(0, vb, size=(vb + 1, k)).astype(np.int32)
    fill.sort(axis=1)
    dup = np.concatenate(
        [np.zeros((vb + 1, 1), bool), fill[:, 1:] == fill[:, :-1]], axis=1)
    nbr = np.where(dup, vb, fill).astype(np.int32)
    ea = rng.integers(0, vb, ep).astype(np.int32)
    eb_ = rng.integers(0, vb, ep).astype(np.int32)
    emask = rng.random(ep) < 0.9
    args = tuple(jnp.asarray(x) for x in (nbr, ea, eb_, emask))
    assert int(pallas_intersect.intersect_local_pallas(*args)) == int(
        tri_ops.intersect_local(*args))


def test_streaming_window_kernel_matches_sparse():
    """Fixed-shape streaming engine (one compile for all windows) agrees
    with the dynamic host path across windows of varying size/shape."""
    k = tri_ops.TriangleWindowKernel(edge_bucket=4096, vertex_bucket=512)
    for seed in range(4):
        rng = np.random.default_rng(seed)
        e = int(rng.integers(10, 4000))
        src = rng.integers(0, 500, e)
        dst = rng.integers(0, 500, e)
        assert k.count(src, dst) == tri_ops.triangle_count_sparse(
            src, dst, 512)
    assert k.count(np.array([], np.int64), np.array([], np.int64)) == 0
    # oversized window is rejected, not silently truncated
    with pytest.raises(ValueError):
        k.count(np.zeros(5000, np.int64), np.ones(5000, np.int64))


def test_streaming_window_kernel_overflow_fallback():
    """A hub whose oriented out-degree exceeds k_bucket must trigger the
    exact fallback, not a wrong count."""
    k = tri_ops.TriangleWindowKernel(edge_bucket=256, vertex_bucket=128,
                                     k_bucket=8)
    # star + clique: vertex 0 connects to everyone; 40-clique on 1..40
    src, dst = [], []
    for v in range(1, 100):
        src.append(0)
        dst.append(v)
    for u in range(1, 41):
        for v in range(u + 1, 41):
            src.append(u)
            dst.append(v)
    src, dst = np.array(src[:256]), np.array(dst[:256])
    assert k.count(src, dst) == _brute_force(src, dst, 128)


def test_count_stream_matches_per_window_counts():
    """Batched lax.map streaming path = per-window counts, including a
    ragged tail window and the empty stream."""
    k = tri_ops.TriangleWindowKernel(edge_bucket=512, vertex_bucket=256)
    rng = np.random.default_rng(11)
    e = 512 * 3 + 137  # three full windows + ragged tail
    src = rng.integers(0, 200, e)
    dst = rng.integers(0, 200, e)
    expected = [k.count(src[s:s + 512], dst[s:s + 512])
                for s in range(0, e, 512)]
    assert k.count_stream(src, dst) == expected
    assert k.count_stream(np.array([], np.int64), np.array([], np.int64)) == []


def test_count_stream_overflow_windows_recounted_exactly():
    """Windows whose hubs outrun K are redone exactly; clean windows in
    the same chunk keep their batched counts."""
    k = tri_ops.TriangleWindowKernel(edge_bucket=256, vertex_bucket=128,
                                     k_bucket=8)
    rng = np.random.default_rng(3)
    # window 0: random sparse (fits K); window 1: 40-clique (overflows)
    s0 = rng.integers(0, 100, 256)
    d0 = rng.integers(0, 100, 256)
    s1, d1 = [], []
    for u in range(1, 41):
        for v in range(u + 1, 41):
            s1.append(u)
            d1.append(v)
    s1, d1 = np.array(s1[:256]), np.array(d1[:256])
    src = np.concatenate([s0, s1])
    dst = np.concatenate([d0, d1])
    assert k.count_stream(src, dst) == [
        _brute_force(s0, d0, 128), _brute_force(s1, d1, 128)]


def test_escalation_ladder_widens_to_kmax():
    k = tri_ops.TriangleWindowKernel(edge_bucket=4096, vertex_bucket=512,
                                     k_bucket=8)
    ladder = k._escalation_ladder()
    assert ladder[0] == 8 and ladder[-1] >= k.kb_max
    assert all(b > a for a, b in zip(ladder, ladder[1:]))


def test_kernels_empty_and_tiny():
    assert tri_ops.triangle_count_sparse(np.array([]), np.array([]), 0) == 0
    assert tri_ops.triangle_count_dense(np.array([0]), np.array([1]), 2) == 0
    tri = tri_ops.triangle_count(np.array([0, 1, 2]), np.array([1, 2, 0]), 3)
    assert tri == 1


def test_numpy_baseline_port_matches_python_port():
    """bench.py's PRIMARY CPU baseline (numpy-vectorized faithful port)
    must compute exactly what the interpreted reference port computes —
    the vectorization may change the cost model, never the counts."""
    import bench

    rng = np.random.default_rng(11)
    for _ in range(8):
        e = int(rng.integers(1, 3000))
        v = int(rng.integers(4, 400))
        src = rng.integers(0, v, e)
        dst = (src + 1 + rng.integers(0, v - 1, e)) % v
        assert (bench.cpu_reference_window_counts_numpy(src, dst, 512)
                == bench.cpu_reference_window_counts(src, dst, 512))


def test_warm_chunks_precompiles_every_stream_bucket():
    """After warm_chunks, count_stream on any ragged stream length must
    trigger ZERO new XLA compiles — the steady-state discipline the
    scale run asserts for the driver (a tuned chunk size must never
    move first-use compiles into the stream tail)."""
    import logging

    import jax

    kern = tri_ops.TriangleWindowKernel(edge_bucket=64, vertex_bucket=64)
    kern.warm_chunks()

    events = []

    class Counter(logging.Handler):
        def emit(self, record):
            if "compiling" in record.getMessage().lower():
                events.append(record.getMessage())

    counter = Counter()
    jax.config.update("jax_log_compiles", True)
    logging.getLogger("jax").addHandler(counter)
    for name in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
        logging.getLogger(name).setLevel(logging.DEBUG)
    try:
        rng = np.random.default_rng(5)
        for num_w in (1, 3, 7, kern.MAX_STREAM_WINDOWS + 5):
            e = num_w * kern.eb - 3
            kern.count_stream(rng.integers(0, 60, e),
                              rng.integers(0, 60, e))
    finally:
        jax.config.update("jax_log_compiles", False)
        logging.getLogger("jax").removeHandler(counter)
    assert not events, events


# ----------------------------------------------------------------------
# host (numpy) streaming tier: ops/host_triangles.py
# ----------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(5))
def test_host_window_count_vs_brute_force(seed):
    from gelly_streaming_tpu.ops import host_triangles

    rng = np.random.default_rng(100 + seed)
    n, e = 30, 120
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)   # includes self-loops + duplicates
    assert host_triangles.window_count(src, dst) == _brute_force(
        src, dst, n)


def test_host_count_stream_matches_device_kernel():
    """Same window boundaries, same exact counts as
    TriangleWindowKernel._count_stream_device on a skewed stream with
    duplicates — the parity contract of the host twin."""
    from gelly_streaming_tpu.ops import host_triangles
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    rng = np.random.default_rng(7)
    eb, vb, num_w = 512, 256, 5
    # zipf-ish skew so hubs stress the orientation + wedge enumeration
    src = (rng.zipf(1.3, num_w * eb) % vb).astype(np.int32)
    dst = (rng.zipf(1.3, num_w * eb) % vb).astype(np.int32)
    kern = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb)
    dev = kern._count_stream_device(src, dst)
    host = host_triangles.count_stream(src, dst, eb)
    assert host == dev
    assert sum(host) > 0
    # count_windows form on ragged windows
    wins = [(src[:300], dst[:300]), (src[300:900], dst[300:900])]
    assert (host_triangles.count_windows(wins)
            == [host_triangles.window_count(*w) for w in wins])


def test_host_window_count_wedge_chunking():
    """The wedge-slice cap only bounds memory, never changes counts."""
    from gelly_streaming_tpu.ops import host_triangles

    rng = np.random.default_rng(13)
    n, e = 200, 3000
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    want = host_triangles.window_count(src, dst)
    orig = host_triangles._WEDGE_CHUNK
    try:
        host_triangles._WEDGE_CHUNK = 64   # force many slices
        assert host_triangles.window_count(src, dst) == want
    finally:
        host_triangles._WEDGE_CHUNK = orig


# ----------------------------------------------------------------------
# native (C++) streaming tier: native/ingest.cpp gs_triangle_count_stream
# ----------------------------------------------------------------------

needs_native = pytest.mark.skipif(
    not __import__("gelly_streaming_tpu.native",
                   fromlist=["x"]).triangles_available(),
    reason="libgsnative.so not built in this environment")


@needs_native
@pytest.mark.parametrize("seed", range(5))
def test_native_window_count_vs_brute_force(seed):
    from gelly_streaming_tpu import native

    rng = np.random.default_rng(300 + seed)
    n, e = 30, 120
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)   # includes self-loops + duplicates
    (got,) = native.triangle_count_stream(src, dst, e)
    assert got == _brute_force(src, dst, n)


@needs_native
def test_native_count_stream_matches_both_tiers():
    """Same window boundaries, same exact counts as the numpy tier and
    the device kernel — on a skewed stream (direct-index branch) and on
    a sparse id space (compression branch)."""
    from gelly_streaming_tpu import native
    from gelly_streaming_tpu.ops import host_triangles
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    rng = np.random.default_rng(7)
    eb, vb, num_w = 512, 256, 5
    src = (rng.zipf(1.3, num_w * eb) % vb).astype(np.int32)
    dst = (rng.zipf(1.3, num_w * eb) % vb).astype(np.int32)
    kern = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb)
    dev = kern._count_stream_device(src, dst)
    assert list(native.triangle_count_stream(src, dst, eb)) == dev
    # sparse ids (> 16x edge count): the sort-unique compression branch
    big = np.int64(1) << 40
    s2 = src.astype(np.int64) * big // 256
    d2 = dst.astype(np.int64) * big // 256
    assert (list(native.triangle_count_stream(s2, d2, eb))
            == host_triangles.count_stream(src, dst, eb))


def test_stream_prefetch_parity_and_error_propagation(monkeypatch):
    """The producer-thread prefetch path (default) and the
    single-threaded form (GS_STREAM_PREFETCH=0) return identical
    counts in window order; a prep failure mid-stream surfaces as the
    original exception, not a hang or a truncated result."""
    # ingress pinned standard: the hand-built bad_chunk below fabricates
    # STANDARD-format stacks (this test pins the pipeline loop's
    # contract, not the wire-format selection)
    kern = tri_ops.TriangleWindowKernel(edge_bucket=256,
                                       vertex_bucket=128,
                                       ingress="standard")
    kern.MAX_STREAM_WINDOWS = 4   # many chunks: 16 windows -> 4 chunks
    rng = np.random.default_rng(11)
    src = rng.integers(0, 128, 16 * 256).astype(np.int32)
    dst = rng.integers(0, 128, 16 * 256).astype(np.int32)
    got = kern._count_stream_device(src, dst)
    monkeypatch.setenv("GS_STREAM_PREFETCH", "0")
    assert kern._count_stream_device(src, dst) == got
    monkeypatch.undo()

    boom = RuntimeError("prep exploded")

    def bad_chunk(at, hi):
        if at >= 8:
            raise boom
        from gelly_streaming_tpu.ops import segment as seg
        num_w, s, d, valid = seg.window_stack(src, dst, kern.eb,
                                              sentinel=kern.vb)
        sc, dc, vc, n = seg.pad_window_chunk(
            s, d, valid, at, hi, kern.MAX_STREAM_WINDOWS, kern.eb,
            kern.vb)
        return (sc, dc, vc), n

    with pytest.raises(RuntimeError, match="prep exploded"):
        kern._run_stack_loop(16, bad_chunk, lambda w: 0)
