"""Delta-compacted d2h egress (ops/delta_egress.py): bit-identical to
full-vector egress window-by-window across tiers, through the
cap-overflow host refold, a mid-stream tier demotion, and a
checkpoint kill→resume; plus the resolve_egress adoption gate."""


import numpy as np
import pytest

from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver
from gelly_streaming_tpu.ops import delta_egress
from gelly_streaming_tpu.ops.windowed_reduce import WindowedEdgeReduce
from gelly_streaming_tpu.utils import faults, resilience


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    monkeypatch.setenv("GS_AUTOTUNE", "0")  # egress in isolation
    monkeypatch.delenv("GS_EGRESS", raising=False)
    monkeypatch.delenv("GS_EGRESS_CAP", raising=False)


def _stream(n=6144, v=700, seed=5):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, v, size=n).astype(np.int64),
            rng.integers(0, v, size=n).astype(np.int64))


def _snap_key(results):
    return [(r.window_start, r.num_edges,
             None if r.triangles is None else int(r.triangles),
             None if r.degrees is None else r.degrees.tolist(),
             None if r.cc_labels is None else r.cc_labels.tolist(),
             None if r.bipartite_odd is None
             else r.bipartite_odd.tolist(),
             None if r.delta_degrees is None
             else [a.tolist() for a in r.delta_degrees],
             None if r.delta_cc is None
             else [a.tolist() for a in r.delta_cc],
             None if r.delta_bipartite is None
             else [a.tolist() for a in r.delta_bipartite])
            for r in results]


def _driver(**kw):
    kw.setdefault("analytics", ("degrees", "cc", "bipartite"))
    kw.setdefault("emit_deltas", True)
    return StreamingAnalyticsDriver(window_ms=0, edge_bucket=512,
                                    vertex_bucket=1024, **kw)


# ----------------------------------------------------------------------
# driver snapshot egress
# ----------------------------------------------------------------------
@pytest.mark.parametrize("emit_deltas", [False, True])
def test_delta_equals_full_window_by_window(emit_deltas):
    src, dst = _stream()
    want = _snap_key(_driver(
        snapshot_tier="scan", egress="full",
        emit_deltas=emit_deltas).run_arrays(src, dst))
    got = _snap_key(_driver(
        snapshot_tier="scan", egress="delta",
        emit_deltas=emit_deltas).run_arrays(src, dst))
    assert got == want


def test_cap_overflow_refolds_on_host_bit_exactly(monkeypatch):
    """A changed-set wider than the wire cap routes the chunk to the
    bit-exact host fold — results identical at ANY cap."""
    src, dst = _stream(seed=6)
    want = _snap_key(_driver(snapshot_tier="scan",
                             egress="full").run_arrays(src, dst))
    monkeypatch.setenv("GS_EGRESS_CAP", "8")  # absurdly tight: every
    got = _driver(snapshot_tier="scan",      # chunk overflows
                  egress="delta").run_arrays(src, dst)
    assert _snap_key(got) == want


def test_delta_matches_across_tiers():
    """The host tier (and native, when the library exports the
    symbol) produces the same windows as the delta-egress scan."""
    src, dst = _stream(seed=7)
    want = _snap_key(_driver(snapshot_tier="scan",
                             egress="delta").run_arrays(src, dst))
    host = _snap_key(_driver(snapshot_tier="host").run_arrays(src, dst))
    assert host == want
    from gelly_streaming_tpu import native

    if native.snapshot_available():
        nat = _snap_key(_driver(
            snapshot_tier="native").run_arrays(src, dst))
        assert nat == want


def test_delta_survives_mid_stream_demotion():
    """A persistent device failure demotes scan→native/host MID-STREAM
    while delta egress is live: the mirrors the delta decode maintains
    must hand the next tier exact carried state."""
    resilience.reset_demotions()
    src, dst = _stream(seed=8)
    want = _snap_key(_driver(snapshot_tier="scan",
                             egress="full").run_arrays(src, dst))
    drv = _driver(snapshot_tier="scan", egress="delta")
    # three calls: the first decodes deltas cleanly; the second's
    # dispatch fails persistently (demotes scan→native/host off the
    # delta-maintained mirrors); the third runs on the demoted tier
    cut1, cut2 = 4 * 512, 8 * 512
    got = drv.run_arrays(src[:cut1], dst[:cut1])
    with faults.inject(faults.FaultSpec(site="dispatch", on_call=1)):
        got += drv.run_arrays(src[cut1:cut2], dst[cut1:cut2])
    got += drv.run_arrays(src[cut2:], dst[cut2:])
    assert _snap_key(got) == want
    assert drv.demotion_log(), "the fault never demoted — the test " \
        "exercised nothing"


def test_delta_checkpoint_kill_resume(tmp_path):
    src, dst = _stream(seed=9)
    want = _snap_key(_driver(snapshot_tier="scan",
                             egress="full").run_arrays(src, dst))
    path = str(tmp_path / "edges.txt")
    with open(path, "w") as f:
        for s, d in zip(src.tolist(), dst.tolist()):
            f.write("%d %d\n" % (s, d))
    ckpt = str(tmp_path / "ck.npz")
    drv = _driver(snapshot_tier="scan", egress="delta")
    drv.enable_auto_checkpoint(ckpt, every_n_windows=3)
    got = {}
    try:
        with faults.inject(faults.FaultSpec(site="dispatch",
                                            on_call=3, fatal=True)):
            for r in drv.stream_file(path, chunk_bytes=1 << 14):
                got[r.window_start] = r
    except faults.InjectedFault:
        pass
    drv2 = _driver(snapshot_tier="scan", egress="delta")
    assert drv2.try_resume(ckpt)
    for r in drv2.stream_file(path, chunk_bytes=1 << 14,
                              resume=True):
        got[r.window_start] = r  # at-least-once: keep last
    final = [got[k] for k in sorted(got)]
    assert _snap_key(final) == want


def test_degree_overflow_still_detected_under_delta():
    """The int32 width guard must fire from the delta wire's changed
    values exactly like the full snapshot's min() check."""
    drv = StreamingAnalyticsDriver(
        window_ms=0, edge_bucket=8, vertex_bucket=16,
        analytics=("degrees",), snapshot_tier="scan", egress="delta")
    # seed the mirror just under the cliff, then two more windows
    drv._degrees = np.array([2**31 - 2], np.int64)
    drv.interner.intern_array(np.array([7]))
    src = np.zeros(16, np.int64) + 7
    with pytest.raises(OverflowError):
        drv.run_arrays(src, src)


# ----------------------------------------------------------------------
# windowed reduce egress
# ----------------------------------------------------------------------
@pytest.mark.parametrize("name", ["sum", "min", "max"])
@pytest.mark.parametrize("direction", ["out", "all"])
def test_reduce_delta_equals_full(name, direction):
    src, dst = _stream(4096, 2000, seed=11)
    val = (1 + (src + 3 * dst) % 97).astype(np.int32)

    def rows(egress, ingress=None):
        eng = WindowedEdgeReduce(
            vertex_bucket=2048, edge_bucket=256, name=name,
            direction=direction, egress=egress, ingress=ingress)
        return eng._device_process_stream(src, dst, val)

    full = rows("full")
    delta = rows("delta")
    assert len(full) == len(delta)
    for (c0, n0), (c1, n1) in zip(full, delta):
        np.testing.assert_array_equal(np.asarray(c0), c1)
        np.testing.assert_array_equal(np.asarray(n0), n1)
    # the delta egress composes with compact ingress (both wires live)
    compact = rows("delta", ingress="compact")
    for (c0, n0), (c1, n1) in zip(full, compact):
        np.testing.assert_array_equal(np.asarray(c0), c1)
        np.testing.assert_array_equal(np.asarray(n0), n1)


# ----------------------------------------------------------------------
# the egress pin
# ----------------------------------------------------------------------
def test_resolve_egress_defaults_full_and_honors_pin(monkeypatch):
    assert delta_egress.resolve_egress() == "full"
    monkeypatch.setenv("GS_EGRESS", "delta")
    assert delta_egress.resolve_egress() == "delta"
    monkeypatch.setenv("GS_EGRESS", "full")
    assert delta_egress.resolve_egress() == "full"


def test_egress_cap_bounds(monkeypatch):
    assert delta_egress.egress_cap(256, 4096) == 512
    assert delta_egress.egress_cap(4096, 1024) == 1024
    monkeypatch.setenv("GS_EGRESS_CAP", "64")
    assert delta_egress.egress_cap(256, 4096) == 64
    monkeypatch.setenv("GS_EGRESS_CAP", "999999")
    assert delta_egress.egress_cap(256, 4096) == 4096  # clamped to vb
