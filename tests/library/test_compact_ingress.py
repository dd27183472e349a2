"""Compact-ingress parity: the 4-bytes/slot uint16+counts wire format
(ops/compact_ingress.py) must reconstruct EXACTLY the arrays the
standard 9-bytes/slot format ships, and the compact stream program
must produce identical window counts — including ragged tails, empty
windows, hub-overflow recounts, and the id boundary at 65535."""

import numpy as np
import pytest

from gelly_streaming_tpu.ops import compact_ingress
from gelly_streaming_tpu.ops import segment as seg_ops
from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel


def _stream(n, v, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, n).astype(np.int32)
    dst = rng.integers(0, v, n).astype(np.int32)
    keep = src != dst
    return src[keep], dst[keep]


def _reconstruct(s16, d16, nvalid, eb, vb):
    """Host-side mirror of the device widen/mask rebuild."""
    pos = np.arange(eb)[None, :]
    valid = pos < nvalid[:, None]
    s = np.where(valid, s16.astype(np.int64), vb).astype(np.int32)
    d = np.where(valid, d16.astype(np.int64), vb).astype(np.int32)
    return s, d, valid


@pytest.mark.parametrize("n,eb", [(100, 64), (257, 64), (64, 64),
                                  (1, 64), (4096, 512)])
def test_window_stack_parity(n, eb):
    vb = 256
    src, dst = _stream(n, vb, seed=n)
    num_w_std, s_std, d_std, v_std = seg_ops.window_stack(
        src, dst, eb, sentinel=vb)
    num_w, s16, d16, nvalid = compact_ingress.window_stack(src, dst, eb)
    assert num_w == num_w_std
    s, d, valid = _reconstruct(s16, d16, nvalid, eb, vb)
    np.testing.assert_array_equal(s, s_std)
    np.testing.assert_array_equal(d, d_std)
    np.testing.assert_array_equal(valid, v_std)


def test_stack_window_list_parity():
    vb = 512
    rng = np.random.default_rng(3)
    windows = []
    for k in (0, 1, 17, 64):
        ws = rng.integers(0, vb, k).astype(np.int32)
        wd = rng.integers(0, vb, k).astype(np.int32)
        windows.append((ws, wd))
    s_std, d_std, v_std = seg_ops.stack_window_list(windows, 64,
                                                    sentinel=vb)
    s16, d16, nvalid = compact_ingress.stack_window_list(windows, 64)
    s, d, valid = _reconstruct(s16, d16, nvalid, 64, vb)
    np.testing.assert_array_equal(s, s_std)
    np.testing.assert_array_equal(d, d_std)
    np.testing.assert_array_equal(valid, v_std)


def test_stack_window_list_oversize_raises():
    with pytest.raises(ValueError):
        compact_ingress.stack_window_list(
            [(np.zeros(65, np.int32), np.zeros(65, np.int32))], 64)


def test_pad_chunk_parity():
    vb, eb, n = 128, 32, 517
    src, dst = _stream(n, vb, seed=9)
    _, s_std, d_std, v_std = seg_ops.window_stack(src, dst, eb,
                                                  sentinel=vb)
    num_w, s16, d16, nvalid = compact_ingress.window_stack(src, dst, eb)
    for at, hi, max_w in [(0, 8, 8), (8, num_w, 8), (0, num_w, 32),
                          (0, 3, 8)]:
        hi = min(hi, num_w)
        sc_s, dc_s, vc_s, n_s = seg_ops.pad_window_chunk(
            s_std, d_std, v_std, at, hi, max_w, eb, vb)
        sc, dc, nv, n_c = compact_ingress.pad_chunk(
            s16, d16, nvalid, at, hi, max_w, eb)
        assert n_c == n_s
        s, d, valid = _reconstruct(sc, dc, nv, eb, vb)
        np.testing.assert_array_equal(s, sc_s)
        np.testing.assert_array_equal(d, dc_s)
        np.testing.assert_array_equal(valid, vc_s)


def test_supports_boundary():
    assert compact_ingress.supports(65536)
    assert compact_ingress.supports(4)
    assert not compact_ingress.supports(65537)
    assert not compact_ingress.supports(1 << 20)


def test_compact_pin_rejects_wide_vertex_bucket():
    """An explicit compact pin with ids wider than uint16 must be an
    ERROR, not a silent id-wrapping miscount."""
    with pytest.raises(ValueError):
        TriangleWindowKernel(edge_bucket=256, vertex_bucket=1 << 17,
                             ingress="compact")


def test_compact_stream_counts_match_device_path():
    """End-to-end: the compact program's counts == the standard device
    path's counts == the escalating per-window kernel, on a stream
    sized to produce ragged tails and nonzero triangles."""
    vb, eb, n = 128, 256, 2400  # 10 windows with a 96-edge ragged tail
    src, dst = _stream(n, vb, seed=21)
    kernel = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb,
                                  ingress="standard")
    std = kernel._count_stream_device(src, dst)

    # the kernel's integrated compact path, on both dispatch surfaces
    k_cmp = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb,
                                 ingress="compact")
    assert k_cmp._count_stream_device(src, dst) == std
    # multi-chunk form: 10 windows through 3-window chunks exercises
    # the prefetch producer thread + ragged-tail padding on the
    # COMPACT wire format (the single-chunk default skips the thread)
    k_cmp.MAX_STREAM_WINDOWS = 3
    assert k_cmp._count_stream_device(src, dst) == std
    k_cmp.MAX_STREAM_WINDOWS = _tuned = TriangleWindowKernel(
        edge_bucket=eb, vertex_bucket=vb).MAX_STREAM_WINDOWS
    windows = [(src[s:s + eb], dst[s:s + eb])
               for s in range(0, len(src), eb)]
    assert k_cmp.count_windows(windows) == std
    # cross-check against the per-window escalating path
    per_window = [
        kernel.count(src[s:s + kernel.eb], dst[s:s + kernel.eb])
        for s in range(0, len(src), kernel.eb)
    ]
    assert std == per_window


def test_compact_stream_id_65535():
    """The top uint16 id must survive the round trip (padded slots use
    0 + mask, NOT a u16 sentinel, so 65535 stays a real id)."""
    import jax
    import jax.numpy as jnp

    vb = 65536
    eb = 64
    # a triangle among the three highest representable ids
    src = np.array([65535, 65534, 65533], np.int32)
    dst = np.array([65534, 65533, 65535], np.int32)
    kernel = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb)
    run = jax.jit(compact_ingress.build_stream_fn(
        kernel._fns[kernel.kb], kernel.vb, kernel.eb))
    num_w, s16, d16, nvalid = compact_ingress.window_stack(src, dst, eb)
    c, o = run(jnp.asarray(s16), jnp.asarray(d16), jnp.asarray(nvalid))
    assert int(np.array(o)[0]) == 0
    assert int(np.array(c)[0]) == 1


def test_compact_parity_at_vb_65536_boundary():
    """vb=65536 is the LAST supported bucket (ids ≤ 65535 fit uint16):
    end-to-end counts through the compact kernel must match the
    standard path there, with real ids at the top of the range."""
    vb, eb = 65536, 64
    rng = np.random.default_rng(44)
    # ids clustered at the top of the uint16 range + a known triangle
    src = np.concatenate([
        rng.integers(65000, vb, 200),
        np.array([65535, 65534, 65533])]).astype(np.int32)
    dst = np.concatenate([
        rng.integers(65000, vb, 200),
        np.array([65534, 65533, 65535])]).astype(np.int32)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    std = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb,
                               ingress="standard")
    cmp_ = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb,
                                ingress="compact")
    want = std._count_stream_device(src, dst)
    assert cmp_._count_stream_device(src, dst) == want
    assert sum(want) > 0


def test_vb_gate_falls_back_to_standard_everywhere():
    """Every engine runs standard ingress unless pinned; a compact pin
    is honoured where ids fit uint16 (supports(vb)) and is an ERROR
    past the gate (vb > 65536) instead of wrapping ids."""
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)
    from gelly_streaming_tpu.ops.windowed_reduce import (
        WindowedEdgeReduce)

    small = dict(edge_bucket=64, vertex_bucket=256)
    big = dict(edge_bucket=64, vertex_bucket=1 << 17)
    for kw in (small, big):
        assert TriangleWindowKernel(**kw).ingress == "standard"
        assert StreamSummaryEngine(**kw).ingress == "standard"
        assert WindowedEdgeReduce(**kw).ingress == "standard"
    assert TriangleWindowKernel(ingress="compact",
                                **small).ingress == "compact"
    assert StreamSummaryEngine(ingress="compact",
                               **small).ingress == "compact"
    assert WindowedEdgeReduce(ingress="compact",
                              **small).ingress == "compact"
    with pytest.raises(ValueError):
        StreamSummaryEngine(ingress="compact", **big)
    with pytest.raises(ValueError):
        WindowedEdgeReduce(ingress="compact", **big)


def test_compact_reduce_rejects_out_of_range_ids():
    """Ids the uint16 cast would wrap must fail as loudly through the
    compact reduce prep as the host tier does."""
    from gelly_streaming_tpu.ops.windowed_reduce import (
        WindowedEdgeReduce)

    eng = WindowedEdgeReduce(vertex_bucket=256, edge_bucket=64,
                             name="sum", direction="out",
                             ingress="compact")
    ok = np.array([1], np.int64)
    for bad in (np.array([70000], np.int64),
                np.array([-3], np.int64)):  # both wrap through uint16
        # plain ValueError, same as every other tier (validated on the
        # main thread, never wrapped by the pipeline's PrepError)
        with pytest.raises(ValueError, match="outside \\[0"):
            eng._device_process_stream(bad, ok, np.ones(1, np.int32))


def test_compact_overflow_recount_exact():
    """A hub whose oriented degree overflows the pinned K must be
    recounted exactly through the compact dispatch path (the shared
    _run_stack_loop recount branch)."""
    vb, eb = 256, 128
    # star around vertex 0 + closing edges -> many triangles at the hub
    hub_deg = 60
    src = np.concatenate([np.zeros(hub_deg, np.int64),
                          np.arange(1, hub_deg, dtype=np.int64)])
    dst = np.concatenate([np.arange(1, hub_deg + 1, dtype=np.int64),
                          np.arange(2, hub_deg + 1, dtype=np.int64)])
    src = src.astype(np.int32)[:eb]
    dst = dst.astype(np.int32)[:eb]
    k_std = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb,
                                 k_bucket=4, ingress="standard")
    k_cmp = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb,
                                 k_bucket=4, ingress="compact")
    want = [k_std.count(src, dst)]  # escalating exact path
    assert k_std._count_stream_device(src, dst) == want
    assert k_cmp._count_stream_device(src, dst) == want
    assert want[0] > 0
