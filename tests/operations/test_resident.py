"""Resident-state window megakernel (ops/resident_engine.py): exact
parity with the scan tier at engine and driver level, the ingest ring,
the GS_RESIDENT selection gate, the demotion ladder rung, the
re-key-instead-of-discard tuner contract on vertex-bucket growth (the
ISSUE-9 arm-freezing fix), and the observability ownership rules
(resident.superbatch spans at the drain, mark_window counted once,
gs_inflight_chunks covering the ring)."""

import numpy as np
import pytest

from gelly_streaming_tpu.core import driver as driver_mod
from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver
from gelly_streaming_tpu.ops import resident_engine
from gelly_streaming_tpu.ops.resident_engine import (IngestRing,
                                                     ResidentState,
                                                     ResidentSummaryEngine)
from gelly_streaming_tpu.ops.scan_analytics import StreamSummaryEngine
from gelly_streaming_tpu.utils import faults, metrics, resilience

pytestmark = pytest.mark.faults


def _stream(n=4096, v=384, seed=9):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, v, size=n).astype(np.int64),
            rng.integers(0, v, size=n).astype(np.int64))


def _key(results):
    return [(r.window_start, r.num_edges, r.vertex_ids.tolist(),
             None if r.degrees is None else r.degrees.tolist(),
             None if r.cc_labels is None else r.cc_labels.tolist(),
             None if r.bipartite_odd is None
             else r.bipartite_odd.tolist(),
             r.triangles)
            for r in results]


def _driver(tier, **kw):
    return StreamingAnalyticsDriver(
        window_ms=0, edge_bucket=512, vertex_bucket=1024,
        snapshot_tier=tier, **kw)


# ----------------------------------------------------------------------
# parity
# ----------------------------------------------------------------------
def test_engine_parity_with_scan_tier():
    src, dst = _stream(n=2048, v=200)
    s32, d32 = src.astype(np.int32), dst.astype(np.int32)
    scan = StreamSummaryEngine(edge_bucket=256,
                               vertex_bucket=256).process(s32, d32)
    res = ResidentSummaryEngine(edge_bucket=256,
                                vertex_bucket=256).process(s32, d32)
    assert res == scan


def test_engine_parity_standard_wire():
    """The standard-wire fallback (vb too wide for uint16 would force
    it; here we pin it) matches the compact-fused default exactly."""
    src, dst = _stream(n=2048, v=200)
    s32, d32 = src.astype(np.int32), dst.astype(np.int32)
    compact = ResidentSummaryEngine(edge_bucket=256, vertex_bucket=256)
    standard = ResidentSummaryEngine(edge_bucket=256,
                                     vertex_bucket=256,
                                     ingress="standard")
    assert compact.ingress == "compact"
    assert standard.ingress == "standard"
    assert compact.process(s32, d32) == standard.process(s32, d32)


def test_driver_parity_and_chunked_calls():
    src, dst = _stream()
    full = _key(_driver("scan").run_arrays(src, dst))
    assert _key(_driver("resident").run_arrays(src, dst)) == full
    drv = _driver("resident")
    head = _key(drv.run_arrays(src[:2048], dst[:2048]))
    tail = _key(drv.run_arrays(src[2048:], dst[2048:]))
    assert head + tail == full


def test_driver_parity_delta_egress_and_deltas():
    src, dst = _stream(seed=11)
    kw = dict(emit_deltas=True)
    a = _driver("scan", egress="full", **kw)
    b = _driver("resident", egress="delta", **kw)
    ra, rb = a.run_arrays(src, dst), b.run_arrays(src, dst)
    assert _key(ra) == _key(rb)
    for x, y in zip(ra, rb):
        for f in ("delta_degrees", "delta_cc", "delta_bipartite"):
            dx, dy = getattr(x, f), getattr(y, f)
            assert np.array_equal(dx[0], dy[0])
            assert np.array_equal(dx[1], dy[1])


# ----------------------------------------------------------------------
# selection pin + ladder
# ----------------------------------------------------------------------
def test_resolve_resident_pins(monkeypatch):
    monkeypatch.setenv("GS_RESIDENT", "on")
    assert resident_engine.resolve_resident() is True
    monkeypatch.setenv("GS_RESIDENT", "off")
    assert resident_engine.resolve_resident() is False
    monkeypatch.delenv("GS_RESIDENT")
    assert resident_engine.resolve_resident() is False


def test_resident_tier_resolution_flows_to_driver(monkeypatch):
    monkeypatch.setenv("GS_RESIDENT", "on")
    assert driver_mod.resolve_snapshot_tier() == "resident"


def test_resident_demotes_to_scan_with_parity():
    """A runtime failure on the resident rung demotes resident → scan
    mid-call (never INTO resident from above), and results stay exact.
    """
    src, dst = _stream()
    full = _key(_driver("scan").run_arrays(src, dst))
    drv = _driver("resident")
    with faults.inject(faults.FaultSpec(site="dispatch", on_call=1)):
        out = _key(drv.run_arrays(src, dst))
    assert out == full
    transitions = [(e["from"], e["to"]) for e in drv.demotion_log()]
    assert ("resident", "scan") in transitions
    assert not any(to == "resident" for _f, to in transitions)


def test_resident_checkpoint_carries_its_tuner(tmp_path,
                                               monkeypatch):
    """The resident tuner's state rides the driver checkpoint under
    its own key, beside (not inside) the scan tuner's."""
    monkeypatch.setenv("GS_TUNE_CACHE", "0")
    drv = _driver("resident")
    tuner = drv._ensure_resident_tuner()
    if tuner is None:
        pytest.skip("autotune disabled in this environment")
    tuner.record(tuner.best(), 1000, 0.01)
    state = drv.state_dict()
    assert state["autotune_resident"] == tuner.state_dict()
    drv2 = _driver("resident")
    drv2.load_state_dict(state)
    assert drv2._resident_tuner.state_dict() == tuner.state_dict()


# ----------------------------------------------------------------------
# the arm-freezing fix: vb growth re-keys instead of discarding
# ----------------------------------------------------------------------
def test_engine_growth_rekeys_tuner_and_keeps_parity(monkeypatch):
    """ResidentSummaryEngine.grow_vertex_bucket migrates the carried
    ResidentState to the wider bucket (parity pinned) AND re-keys the
    live tuner — round counter and learned state survive into the new
    key instead of freezing at the dead one."""
    monkeypatch.setenv("GS_TUNE_CACHE", "0")
    src, dst = _stream(n=2048, v=200)
    s32, d32 = src.astype(np.int32), dst.astype(np.int32)
    full = ResidentSummaryEngine(edge_bucket=256,
                                 vertex_bucket=512).process(s32, d32)

    eng = ResidentSummaryEngine(edge_bucket=256, vertex_bucket=256)
    head = eng.process(s32[:1024], d32[:1024])
    tuner = eng._ensure_tuner()
    tuner.record(tuner.best(), 1000, 0.01)
    rounds_before = tuner.state_dict()["round"]
    old_key = tuner.key
    assert rounds_before >= 1

    eng.grow_vertex_bucket(512)
    assert eng.vb == 512
    # same tuner OBJECT, new identity, learned state carried
    assert eng._tuner is tuner
    assert tuner.key != old_key
    assert "vb=512" in tuner.key
    assert tuner.state_dict()["round"] == rounds_before
    assert eng.process(s32[1024:], d32[1024:]) == full[4:]
    assert head == full[:4]


def test_driver_growth_rekeys_resident_tuner(monkeypatch):
    """The driver's bucket growth re-keys the resident tuner with the
    same re-key-instead-of-discard contract as the scan tuner."""
    monkeypatch.setenv("GS_TUNE_CACHE", "0")
    drv = _driver("resident")
    tuner = drv._ensure_resident_tuner()
    if tuner is None:
        pytest.skip("autotune disabled in this environment")
    tuner.record(tuner.best(), 1000, 0.01)
    rounds = tuner.state_dict()["round"]
    old_key = tuner.key
    src, dst = _stream(n=4096, v=2000, seed=3)  # forces vb growth
    drv.run_arrays(src, dst)
    assert drv.vb > 1024
    assert drv._resident_tuner is tuner
    assert tuner.key != old_key
    assert str(drv.vb) in tuner.key
    assert tuner.state_dict()["round"] >= rounds


def test_engine_growth_past_uint16_repins_ingress(monkeypatch):
    """Growing past the uint16 ceiling switches the fused decode to
    the standard wire — the re-keyed tuner must re-pin its ingress
    arm with it (a surviving compact arm would be lossy)."""
    monkeypatch.setenv("GS_TUNE_CACHE", "0")
    eng = ResidentSummaryEngine(edge_bucket=256, vertex_bucket=65536)
    assert eng.ingress == "compact"
    tuner = eng._ensure_tuner()
    tuner.record(tuner.best(), 1000, 0.01)
    eng.grow_vertex_bucket(2 * 65536)
    assert eng.ingress == "standard"
    assert tuner.space["ingress"] == ["standard"]
    assert tuner.incumbent["ingress"] == "standard"


def test_engine_growth_preserves_ingress_pin(monkeypatch):
    """An explicit construction-time ingress pin survives bucket
    growth — the rebuild must keep measuring the wire the caller
    pinned (and keep the tuner frozen to it), not re-resolve. A pinned
    compact wire that turns lossy at the new bucket degrades to
    standard instead of raising."""
    monkeypatch.setenv("GS_TUNE_CACHE", "0")
    eng = ResidentSummaryEngine(edge_bucket=64, vertex_bucket=256,
                                ingress="standard")
    assert eng._pinned_ingress
    eng.grow_vertex_bucket(512)
    # an unpinned rebuild would re-resolve to compact (512 fits uint16)
    assert eng.ingress == "standard"
    assert eng._pinned_ingress
    # pinned compact grown past uint16: degrade, don't raise
    eng2 = ResidentSummaryEngine(edge_bucket=64, vertex_bucket=1024,
                                 ingress="compact")
    eng2.grow_vertex_bucket(2 * 65536)
    assert eng2.ingress == "standard"
    assert eng2._pinned_ingress


def test_pipeline_inflight_narrows_not_replaces(monkeypatch):
    """The ring's `inflight` narrows the look-ahead BELOW the global
    GS_PIPELINE_INFLIGHT bound; it can never raise it above the
    operator's ceiling."""
    from gelly_streaming_tpu.ops import ingress_pipeline as ip

    import threading

    monkeypatch.setenv("GS_PIPELINE_INFLIGHT", "2")
    lock = threading.Lock()
    state = {"started": 0, "dispatched": 0, "peak": 0}

    def prep(it):
        with lock:
            state["started"] += 1
            state["peak"] = max(
                state["peak"],
                state["started"] - state["dispatched"])
        return it

    def dispatch(d):
        with lock:
            state["dispatched"] += 1
        return d

    seen = []
    items = list(range(8))
    ip.run_pipeline(items, prep=prep, h2d=lambda p: p,
                    dispatch=dispatch,
                    finalize=lambda r: seen.append(r),
                    inflight=6)
    assert seen == items  # order preserved under the narrowed bound
    # lookahead must be min(6, GS_PIPELINE_INFLIGHT=2), not 6: one
    # extra slot covers the pop→dispatch→refill race window
    assert state["peak"] <= 3


def test_resident_state_grow_layout():
    st = ResidentState.fresh(4)
    st.degrees[:4] = [3, 1, 0, 2]
    st.labels[:4] = [0, 0, 2, 2]
    # cover: (+) side joined across to (−) side for vertex 1: label
    # points into the (−) half (>= vb) and must shift with it
    st.cover[1] = 4 + 1 + 0  # old (−)0 slot
    grown = ResidentState.grow(st, 4, 8)
    assert grown.degrees[:4].tolist() == [3, 1, 0, 2]
    assert grown.degrees[4:].tolist() == [0] * 5
    assert grown.labels[:4].tolist() == [0, 0, 2, 2]
    assert grown.labels[4:].tolist() == [4, 5, 6, 7, 8]
    assert grown.cover[1] == 8 + 1 + 0  # shifted with the (−) half
    assert grown.cover[8] == 8  # sentinel identity


# ----------------------------------------------------------------------
# ingest ring
# ----------------------------------------------------------------------
def test_ingest_ring_bounds_and_order():
    ring = IngestRing(slots=2)
    done = []
    for i in range(3):
        ok = ring.submit(lambda item: done.append(item) or item, i, i)
        if not ok and len(ring) == 0:
            pytest.skip("ingress pipelining disabled here")
        if i < 2:
            assert ok
        else:
            assert not ok  # full at 2 slots
    assert len(ring) == 2 and ring.full
    assert ring.pop(1) is None  # FIFO: head is 0
    fut, item = ring.pop(0)
    assert fut.result() == 0 and item == 0
    ring.drain()
    assert len(ring) == 0


def test_ring_slots_knob(monkeypatch):
    monkeypatch.setenv("GS_RESIDENT_SLOTS", "5")
    assert resident_engine.ring_slots() == 5
    assert IngestRing().slots == 5
    monkeypatch.setenv("GS_RESIDENT_SLOTS", "0")  # clamped at lo=1
    assert resident_engine.ring_slots() == 1


def test_superbatch_knob(monkeypatch):
    monkeypatch.setenv("GS_RESIDENT_SPB", "100")
    # bucketed to a power of two
    assert resident_engine.resident_spb(4096) == 128
    eng = ResidentSummaryEngine(edge_bucket=256, vertex_bucket=256)
    assert eng.MAX_WINDOWS == 128


# ----------------------------------------------------------------------
# observability ownership
# ----------------------------------------------------------------------
def test_superbatch_spans_and_single_marks(monkeypatch):
    """One resident.superbatch span per super-batch drain, windows
    marked exactly once (the owner rule), and the ring feeding the
    gs_inflight_chunks gauge."""
    from gelly_streaming_tpu.utils import telemetry

    monkeypatch.setenv("GS_TELEMETRY", "1")
    monkeypatch.setenv("GS_METRICS", "1")
    # several super-batches, so the ingest ring actually cycles (a
    # single-superbatch call never submits to the ring at all; spb
    # buckets have a floor of 8 — seg_ops.bucket_size)
    monkeypatch.setenv("GS_RESIDENT_SPB", "8")
    telemetry.reset()
    metrics.reset()
    try:
        src, dst = _stream(n=8192)
        out = _driver("resident").run_arrays(src, dst)
        spans = [r for r in telemetry.records()
                 if r.get("t") == "span"
                 and r.get("name") == "resident.superbatch"]
        assert spans, "no resident.superbatch span recorded"
        assert sum((s.get("a") or {}).get("windows", 0)
                   for s in spans) == len(out)
        snap = metrics.health_snapshot()
        assert snap["windows_finalized"] == len(out)
        gauges = {name: v for (name, _l), v in metrics.gauges().items()}
        assert "gs_inflight_chunks" in gauges
    finally:
        telemetry.reset()
        metrics.reset()


def test_resident_metrics_tier_label(monkeypatch):
    monkeypatch.setenv("GS_METRICS", "1")
    metrics.reset()
    try:
        src, dst = _stream()
        _driver("resident").run_arrays(src, dst)
        tiers = {dict(labels).get("tier")
                 for (name, labels), _v in metrics.counters().items()
                 if name == "gs_windows_finalized_total"}
        assert "resident" in tiers
    finally:
        metrics.reset()


def test_mesh_refuses_resident_pin():
    with pytest.raises(ValueError, match="single-chip"):
        StreamingAnalyticsDriver(window_ms=0, mesh=object(),
                                 snapshot_tier="resident")


def test_donation_config_matches_backend():
    import jax

    kw = resident_engine.donate_kw()
    if jax.default_backend() in ("tpu", "gpu", "cuda", "rocm"):
        assert kw == {"donate_argnums": (0,)}
    else:
        assert kw == {}
