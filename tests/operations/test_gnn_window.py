"""Windowed GNN message passing (ops/gnn_window.py): device ≡ numpy
twin BIT-exactness across (eb, vb, F, act) grids with ragged tails,
the empty-window-holds rule that makes dispatch padding inert, the
lattice snapping helpers, kill→resume through checkpoint + WAL
(gnn→gnn and the gnn→host demotion hand-off), the vmapped tenant
cohort at N ∈ {1, 3, 8} vs sequential engines, the fused Pallas GNN
kernel (interpret parity, VMEM-refusal fallback event, the
GS_GNN_PALLAS pin), the analytic cost-model registration
(the repo's first MXU-class intensity rows), and the disarmed-default
digest pin."""

import hashlib
import json

import numpy as np
import pytest

from gelly_streaming_tpu.core.tenancy import GnnTenantCohort
from gelly_streaming_tpu.ops import gnn_window as gw
from gelly_streaming_tpu.ops import pallas_window as pw
from gelly_streaming_tpu.utils import faults, resilience, telemetry


def _stream(n, v, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, v, n).astype(np.int32),
            rng.integers(0, v, n).astype(np.int32))


def _digest(summaries, slab=None) -> str:
    h = hashlib.sha256()
    for s in summaries:
        h.update(json.dumps(s, sort_keys=True).encode())
    if slab is not None:
        h.update(np.ascontiguousarray(slab, np.float32).tobytes())
    return h.hexdigest()[:16]


def _mk(cls, eb, vb, F, act="relu", **kw):
    eng = cls(eb, vb, feature_dim=F, activation=act, **kw)
    rng = np.random.RandomState(3)
    eng.set_weights(rng.randn(F, F) * 0.3, rng.randn(F) * 0.1)
    eng.load_feature_units(gw.default_features(vb, F, seed=5))
    return eng


# ----------------------------------------------------------------------
# lattice helpers
# ----------------------------------------------------------------------
def test_shift_and_cap_laws():
    assert gw.agg_shift(2 ** 15) == 0
    assert gw.agg_shift(2 ** 16) == 1
    assert gw.agg_shift(8) == 0
    assert gw.weight_shift(64) == 0
    assert gw.weight_shift(65) == 1
    assert gw.weight_cap(64) == 512
    assert gw.weight_cap(128) == 256


def test_snap_weights_grid_and_shapes():
    W, b = gw.snap_weights(np.full((4, 4), 0.33), np.zeros(4), 4)
    # 0.33 * 32 = 10.56 → 11 units, exactly representable
    assert np.all(W == np.float32(11.0))
    assert W.dtype == np.float32 and b.shape == (4,)
    with pytest.raises(ValueError):
        gw.snap_weights(np.zeros((3, 4)), np.zeros(4), 4)


def test_snap_features_clips_and_pads():
    slab = gw.snap_features(np.full((3, 2), 99.0), vb=8, F=2)
    assert slab.shape == (9, 2)
    assert np.all(slab[:3] == gw.UNIT_CAP)
    assert np.all(slab[3:] == 0)
    with pytest.raises(ValueError):
        gw.snap_features(np.zeros((9, 2)), vb=8, F=2)


# ----------------------------------------------------------------------
# device ≡ numpy twin parity (the lattice bit-exactness contract)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("eb,vb,F,act", [
    (64, 128, 4, "relu"),
    (256, 512, 16, "abs"),
    (128, 64, 8, "identity"),
])
def test_engine_host_parity_ragged(eb, vb, F, act):
    n = 5 * eb - eb // 3  # ragged tail closes a partial window
    src, dst = _stream(n, vb, seed=eb + F)
    dev = _mk(gw.GnnSummaryEngine, eb, vb, F, act)
    host = _mk(gw.GnnHostEngine, eb, vb, F, act)
    got, want = dev.process(src, dst), host.process(src, dst)
    assert got == want
    assert np.array_equal(dev.state(), host.state())
    assert got[-1]["msg_edges"] == n - 4 * eb  # the partial tail


def test_resident_tier_parity():
    eb, vb, F = 64, 128, 8
    src, dst = _stream(6 * eb, vb, seed=2)
    res = _mk(gw.GnnResidentEngine, eb, vb, F, superbatch=4)
    host = _mk(gw.GnnHostEngine, eb, vb, F)
    assert res.process(src, dst) == host.process(src, dst)
    assert np.array_equal(res.state(), host.state())


def test_empty_window_holds_slab():
    """The padding-inertness foundation: a window with zero valid
    edges leaves the carry bit-identical (the dense layer must NOT
    tick), on both the XLA round and the numpy twin."""
    import jax.numpy as jnp

    eb, vb, F = 32, 64, 4
    round_ = gw._build_gnn_round(eb, vb, F, "relu")
    h0 = jnp.asarray(gw.default_features(vb, F, seed=1))
    W = jnp.asarray(gw.snap_weights(*gw.default_weights(F), F)[0])
    b = jnp.zeros(F)
    s = jnp.zeros(eb, jnp.int32)
    d = jnp.zeros(eb, jnp.int32)
    h1, (maxf, active, csum, nmsg) = round_(
        h0, W, b, s, d, jnp.zeros(eb, bool))
    assert np.array_equal(np.asarray(h1), np.asarray(h0))
    assert int(nmsg) == 0
    # a live window with the same slab DOES tick
    h2, _ = round_(h0, W, b, s, d, jnp.ones(eb, bool))
    assert not np.array_equal(np.asarray(h2), np.asarray(h0))


def test_engine_padding_inert_across_chunk_splits():
    """The chunk loop pads dispatches to bucketed window counts with
    all-invalid windows; feeding the same stream in different call
    granularities must be bit-identical."""
    eb, vb, F = 64, 128, 8
    n = 6 * eb
    src, dst = _stream(n, vb, seed=4)
    one = _mk(gw.GnnSummaryEngine, eb, vb, F)
    whole = one.process(src, dst)
    two = _mk(gw.GnnSummaryEngine, eb, vb, F)
    split = []
    for lo in range(0, n, 2 * eb):
        split += two.process(src[lo:lo + 2 * eb],
                             dst[lo:lo + 2 * eb])
    assert split == whole
    assert np.array_equal(one.state(), two.state())


# ----------------------------------------------------------------------
# weights / checkpoint layout
# ----------------------------------------------------------------------
def test_set_weights_never_recompiles_and_snaps():
    eb, vb, F = 64, 128, 4
    eng = _mk(gw.GnnSummaryEngine, eb, vb, F)
    W, b = eng.weights()
    assert np.all(W == np.rint(W))  # lattice units are integers
    src, dst = _stream(2 * eb, vb, seed=6)
    a = eng.process(src, dst)
    eng.set_weights(np.eye(F) * 2.0)
    bb = eng.process(src, dst)
    assert a != bb  # the new layer actually applied


def test_state_dict_roundtrip_and_f_mismatch():
    eb, vb, F = 64, 128, 8
    eng = _mk(gw.GnnSummaryEngine, eb, vb, F)
    src, dst = _stream(2 * eb, vb, seed=7)
    eng.process(src, dst)
    snap = eng.state_dict()
    assert snap["gnn"]["feat_dim"] == F
    eng2 = gw.GnnSummaryEngine(eb, vb, feature_dim=F)
    eng2.load_state_dict(snap)
    assert np.array_equal(eng2.state(), eng.state())
    assert np.array_equal(eng2.weights()[0], eng.weights()[0])
    wrong = gw.GnnSummaryEngine(eb, vb, feature_dim=4)
    with pytest.raises(ValueError):
        wrong.load_state_dict(snap)


def test_kill_resume_gnn_to_gnn(tmp_path):
    """Fatal kill mid-stream → auto-checkpoint resume, positional
    at-least-once combine ≡ the fault-free run, slab included."""
    eb, vb, F = 64, 128, 8
    num_w = 8
    src, dst = _stream(num_w * eb, vb, seed=9)
    oracle = _mk(gw.GnnSummaryEngine, eb, vb, F)
    baseline = oracle.process(src, dst)

    ckpt = str(tmp_path / "gnn.npz")
    eng = _mk(gw.GnnSummaryEngine, eb, vb, F)
    eng.enable_auto_checkpoint(ckpt, every_n_windows=2)
    out = eng.process(src[:4 * eb], dst[:4 * eb])
    with pytest.raises(faults.InjectedFault):
        with faults.inject(faults.FaultSpec(site="dispatch",
                                            on_call=1, fatal=True)):
            eng.process(src[4 * eb:], dst[4 * eb:])
    eng2 = _mk(gw.GnnSummaryEngine, eb, vb, F)
    assert eng2.try_resume(ckpt)
    off = eng2.resume_offset()
    assert off >= 4 * eb  # the checkpoint covered the delivered calls
    rest = eng2.process(src[off:], dst[off:])
    assert out[:off // eb] + rest == baseline
    assert np.array_equal(eng2.state(), oracle.state())


def test_demotion_gnn_to_host_twin():
    """The gnn→host hand-off: a host twin built from a device
    checkpoint continues the stream bit-exactly."""
    eb, vb, F = 64, 128, 8
    src, dst = _stream(6 * eb, vb, seed=10)
    oracle = _mk(gw.GnnSummaryEngine, eb, vb, F)
    baseline = oracle.process(src, dst)
    eng = _mk(gw.GnnSummaryEngine, eb, vb, F)
    head = eng.process(src[:2 * eb], dst[:2 * eb])
    twin = gw.GnnHostEngine.from_state(eng.state_dict())
    assert twin.act == eng.act and twin.F == F
    tail = twin.process(src[2 * eb:], dst[2 * eb:])
    assert head + tail == baseline
    assert np.array_equal(twin.state(), oracle.state())


# ----------------------------------------------------------------------
# tenant cohort
# ----------------------------------------------------------------------
def _cohort_streams(n_tenants, windows, eb, vb):
    streams = {}
    for i in range(n_tenants):
        n = windows * eb - (eb // 3 if i % 3 == 2 else 0)
        streams["t%02d" % i] = _stream(n, vb, seed=50 + i)
    return streams


def _sequential(streams, eb, vb, F):
    out, slabs = {}, {}
    for i, tid in enumerate(sorted(streams)):
        eng = gw.GnnSummaryEngine(eb, vb, feature_dim=F)
        eng.load_feature_units(gw.default_features(vb, F, seed=i))
        s, d = streams[tid]
        out[tid] = eng.process(s, d)
        slabs[tid] = eng.state()
    return out, slabs


@pytest.mark.parametrize("n_tenants", [1, 3, 8])
def test_cohort_parity_vs_sequential(n_tenants):
    eb, vb, F = 64, 128, 8
    streams = _cohort_streams(n_tenants, 4, eb, vb)
    want, _slabs = _sequential(streams, eb, vb, F)
    co = GnnTenantCohort(eb, vb, feature_dim=F)
    for i, tid in enumerate(sorted(streams)):
        co.admit(tid, feature_units=gw.default_features(vb, F,
                                                        seed=i))
    for tid, (s, d) in streams.items():
        co.feed(tid, s, d)
    got = co.pump()
    for tid in streams:
        got[tid] += co.close(tid)
        assert got[tid] == want[tid], tid


def test_cohort_demote_to_engine():
    """demote() pops a tenant into a single-stream GnnSummaryEngine:
    full queued windows fold through the engine (their summaries are
    returned, never dropped), the sub-window tail comes back UNFOLDED
    for the caller to prepend, a durable demotion record lands, and
    the continued stream stays bit-exact."""
    eb, vb, F = 64, 128, 8
    streams = _cohort_streams(2, 4, eb, vb)
    want, wslabs = _sequential(streams, eb, vb, F)
    resilience.reset_demotions()
    co = GnnTenantCohort(eb, vb, feature_dim=F)
    for i, tid in enumerate(sorted(streams)):
        co.admit(tid, feature_units=gw.default_features(vb, F,
                                                        seed=i))
    got = {tid: [] for tid in streams}
    for tid, (s, d) in streams.items():
        co.feed(tid, s[:2 * eb], d[:2 * eb])
    for tid, res in co.pump().items():
        got[tid] += res
    # leave t00 with one FULL window + a sub-window tail queued
    s, d = streams["t00"]
    cut = 2 * eb + eb + eb // 2
    co.feed("t00", s[2 * eb:cut], d[2 * eb:cut])
    eng, folded, (ts, td) = co.demote("t00")
    assert isinstance(eng, gw.GnnSummaryEngine)
    assert len(folded) == 1 and len(ts) == eb // 2
    got["t00"] += folded
    got["t00"] += eng.process(np.concatenate([ts, s[cut:]]),
                              np.concatenate([td, d[cut:]]))
    assert got["t00"] == want["t00"]
    assert np.array_equal(eng.state(), wslabs["t00"])
    assert any(e.get("tenant") == "t00"
               for e in resilience.demotion_events())
    assert "t00" not in co.tenants()
    # the remaining tenant is undisturbed
    s, d = streams["t01"]
    co.feed("t01", s[2 * eb:], d[2 * eb:])
    for tid, res in co.pump().items():
        got[tid] += res
    got["t01"] += co.close("t01")
    assert got["t01"] == want["t01"]


def test_cohort_state_dict_engine_interchange():
    eb, vb, F = 64, 128, 8
    co = GnnTenantCohort(eb, vb, feature_dim=F)
    co.admit("t", feature_units=gw.default_features(vb, F, seed=0))
    s, d = _stream(2 * eb, vb, seed=60)
    co.feed("t", s, d)
    co.pump()
    snap = co.tenant_state_dict("t")
    eng = gw.GnnSummaryEngine(eb, vb, feature_dim=F)
    eng.load_state_dict(snap)
    assert np.array_equal(eng.state(), co.state("t"))


# ----------------------------------------------------------------------
# fused Pallas GNN kernel
# ----------------------------------------------------------------------
@pytest.fixture
def gnn_pallas_on(monkeypatch):
    monkeypatch.setenv("GS_GNN_PALLAS", "on")
    monkeypatch.setenv("GS_AUTOTUNE", "0")
    pw._reset_pallas_window()
    yield
    pw._reset_pallas_window()


def test_pallas_interpret_parity(gnn_pallas_on):
    eb, vb, F = 64, 128, 8
    src, dst = _stream(5 * eb - eb // 3, vb, seed=11)
    eng = _mk(gw.GnnSummaryEngine, eb, vb, F)
    assert eng._pallas  # actually selected, not silently declined
    host = _mk(gw.GnnHostEngine, eb, vb, F)
    assert eng.process(src, dst) == host.process(src, dst)
    assert np.array_equal(eng.state(), host.state())


def test_pallas_vmem_refusal_falls_back_with_event(monkeypatch):
    """A pretend-chip refusing the VMEM budget must decline the
    kernel with a durable selection.fallback — the engine silently
    keeps the XLA round."""
    monkeypatch.setenv("GS_GNN_PALLAS", "on")
    monkeypatch.setenv("GS_TELEMETRY", "1")
    monkeypatch.delenv("GS_TRACE_DIR", raising=False)
    monkeypatch.setattr(pw, "_on_tpu", lambda: True)
    pw._reset_pallas_window()
    telemetry.reset()
    try:
        assert not pw.supports_gnn(32768, 65536, 128)
        assert pw.maybe_gnn_body(32768, 65536, 128, "relu") is None
        evs = [r for r in telemetry.records()
               if r["name"] == "selection.fallback"
               and r["a"].get("component") == "gnn_pallas"
               and "vmem budget" in r["a"].get("error", "")]
        assert evs
    finally:
        pw._reset_pallas_window()
        telemetry.reset()


def test_resolve_gnn_pallas_pins_and_evidence(monkeypatch):
    monkeypatch.setenv("GS_GNN_PALLAS", "on")
    assert pw.resolve_gnn_pallas() is True
    monkeypatch.setenv("GS_GNN_PALLAS", "off")
    assert pw.resolve_gnn_pallas() is False
    monkeypatch.delenv("GS_GNN_PALLAS")
    assert pw.resolve_gnn_pallas() is False


# ----------------------------------------------------------------------
# analytic cost model: the first MXU-class intensity rows
# ----------------------------------------------------------------------
def test_gnn_cost_model_intensity(monkeypatch):
    from gelly_streaming_tpu.utils import costmodel

    monkeypatch.setenv("GS_COSTMODEL", "1")
    costmodel.reset()
    try:
        pw.register_gnn_cost_model(32768, 65536, 16)
        rows = {r["program"]: r for r in costmodel.report("TPU v5 lite")
                if r.get("program", "").startswith("gnn")}
        assert set(rows) >= {"gnn_scan", "gnn_resident",
                             "gnn_pallas"}
        for r in rows.values():
            assert r["arith_intensity_flops_per_byte"] > 0.28
        # the fused kernel reads strictly fewer bytes than the scan
        assert (rows["gnn_pallas"]["bytes_accessed"]
                < rows["gnn_scan"]["bytes_accessed"])
        assert (rows["gnn_pallas"]["arith_intensity_flops_per_byte"]
                > rows["gnn_scan"]
                ["arith_intensity_flops_per_byte"])
    finally:
        costmodel.reset()


def test_gnn_flops_model_has_matmul_term():
    # doubling F must ~quadruple the dense term at fixed eb, vb
    f1 = pw.gnn_window_flops(1024, 4096, 32)
    f2 = pw.gnn_window_flops(1024, 4096, 64)
    dense1 = 2 * 4097 * 32 * 32
    dense2 = 2 * 4097 * 64 * 64
    assert f2 - f1 > (dense2 - dense1) * 0.9


# ----------------------------------------------------------------------
# disarmed-default digest pin
# ----------------------------------------------------------------------
def test_default_gate_digest_pin(monkeypatch):
    """No GS_GNN_* set: the XLA round is selected (no committed
    non-interpret gnn_ab chip rows on CPU) and the digest over
    summaries + slab is the committed pin — which the pinned Pallas
    kernel reproduces bit-for-bit (same stream and seeds as CI gate
    12, tools/gnn_smoke.py)."""
    for k in ("GS_GNN_PALLAS", "GS_GNN_F", "GS_GNN_ACT"):
        monkeypatch.delenv(k, raising=False)
    pw._reset_pallas_window()
    eb = vb = 256
    rng = np.random.default_rng(42)
    src = rng.integers(0, vb - 8, eb).astype(np.int32)
    dst = rng.integers(0, vb - 8, eb).astype(np.int32)
    eng = _mk(gw.GnnSummaryEngine, eb, vb, 16)
    assert not eng._pallas
    assert eng.F == 16 and eng.act == "relu"  # the knob defaults
    got = _digest(eng.process(src, dst), eng.state())
    assert got == "d1ee18e13dd6a744"
    monkeypatch.setenv("GS_GNN_PALLAS", "on")
    pw._reset_pallas_window()
    try:
        eng2 = _mk(gw.GnnSummaryEngine, eb, vb, 16)
        assert eng2._pallas
        assert _digest(eng2.process(src, dst), eng2.state()) == got
    finally:
        pw._reset_pallas_window()
