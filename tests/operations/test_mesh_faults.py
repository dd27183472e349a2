"""Mesh-scoped fault injection + the sharded demotion ladder
(utils/faults shard-aware plans, parallel/sharded guards,
core/driver sharded → scan → native → host): dead shards, ICI stalls,
corrupt shard wires — every mesh failure path exercised with a fixed
plan on the virtual CPU mesh, no randomness. Part of the tier-1
`faults` suite (the marker below), like the single-chip fault drills
in test_faults.py."""

import os

import numpy as np
import pytest

from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver
from gelly_streaming_tpu.ops import ingress_pipeline as ip
from gelly_streaming_tpu.parallel.host_twin import HostSummaryEngine
from gelly_streaming_tpu.parallel.mesh import make_mesh
from gelly_streaming_tpu.parallel.sharded import (
    ShardedSummaryEngine, ShardedTriangleWindowKernel, guard_wire)
from gelly_streaming_tpu.utils import faults, resilience

pytestmark = pytest.mark.faults

_KNOBS = ("GS_STAGE_TIMEOUT_S", "GS_STAGE_RETRIES",
          "GS_STAGE_BACKOFF_S", "GS_TIER_RETRY_WINDOWS",
          "GS_TIER_DEMOTE", "GS_MESH_DEMOTE", "GS_MESH_WIRE_CHECK")


@pytest.fixture(autouse=True)
def _clean_knobs():
    """Every test starts from inert knobs, leaves none behind, and
    clears the process demotion registry; the prep pool is dropped so
    a deliberately hung worker never serves a later test."""
    saved = {k: os.environ.pop(k, None) for k in _KNOBS}
    os.environ["GS_STAGE_BACKOFF_S"] = "0.01"
    resilience.reset_demotions()
    try:
        yield
    finally:
        for k in _KNOBS:
            os.environ.pop(k, None)
            if saved[k] is not None:
                os.environ[k] = saved[k]
        resilience.reset_demotions()
        ip.reset_pool()


EB, VB, V = 256, 512, 300


def _stream(num_w=8, seed=5):
    rng = np.random.default_rng(seed)
    n = num_w * EB
    return (rng.integers(0, V, n).astype(np.int64),
            rng.integers(0, V, n).astype(np.int64))


def _driver(mesh=None, **kw):
    kw.setdefault("analytics", ("degrees", "cc", "triangles"))
    return StreamingAnalyticsDriver(
        window_ms=0, edge_bucket=EB, vertex_bucket=VB, mesh=mesh, **kw)


def _key(results):
    return [(r.window_start, r.num_edges, r.degrees.tolist(),
             r.cc_labels.tolist(), r.triangles) for r in results]


def _arm(timeout="5", retries="2"):
    os.environ["GS_STAGE_TIMEOUT_S"] = timeout
    os.environ["GS_STAGE_RETRIES"] = retries


# ----------------------------------------------------------------------
# driver ladder: sharded → scan
# ----------------------------------------------------------------------
def test_dead_shard_demotes_mid_stream_with_parity():
    """A persistently dead shard demotes the mesh session to the
    single-chip scan tier MID-STREAM; results stay window-by-window
    identical to the fault-free run and the demotion record carries
    the mesh shape and the implicated shard."""
    _arm()
    src, dst = _stream()
    want = _key(_driver().run_arrays(src, dst))
    drv = _driver(mesh=make_mesh(4))
    with faults.inject(faults.FaultSpec(site="shard_dispatch",
                                        on_call=2, times=1 << 20,
                                        shard=3)):
        got = _key(drv.run_arrays(src[:4 * EB], dst[:4 * EB]))
        got += _key(drv.run_arrays(src[4 * EB:], dst[4 * EB:]))
    assert got == want
    assert not drv._mesh_live()
    evs = resilience.demotion_events()
    assert evs and evs[0]["from"] == "sharded" and evs[0]["to"] == "scan"
    assert evs[0]["mesh_shape"] == [4] and evs[0]["shard_id"] == 3


def test_mesh_demote_pin_raises_instead():
    """GS_MESH_DEMOTE=0 pins the mesh rung: the dead shard surfaces as
    the typed stage error instead of silently degrading."""
    _arm()
    os.environ["GS_MESH_DEMOTE"] = "0"
    src, dst = _stream(num_w=4)
    drv = _driver(mesh=make_mesh(4))
    with faults.inject(faults.FaultSpec(site="shard_dispatch",
                                        on_call=1, times=1 << 20)):
        with pytest.raises(resilience.StageError):
            drv.run_arrays(src, dst)
    assert drv._mesh_live()  # never demoted
    assert not resilience.demotion_events()


def test_ici_stall_cut_by_watchdog_and_retried():
    """A transient mesh stall (hang at the sharded scan dispatch) is
    cut by the GS_STAGE_TIMEOUT_S watchdog and the retried dispatch
    completes — no demotion, identical results."""
    src, dst = _stream(num_w=4)
    want = _key(_driver().run_arrays(src, dst))
    drv = _driver(mesh=make_mesh(4))
    drv.run_arrays(src[:2 * EB], dst[:2 * EB])  # compile OUTSIDE the
    drv2 = _driver(mesh=make_mesh(4))           # deadline (fresh twin
    drv2.run_arrays(src[:2 * EB], dst[:2 * EB])  # burns its counters)
    _arm(timeout="1")
    drv3 = _driver(mesh=make_mesh(4))
    with faults.inject(faults.FaultSpec(site="shard_dispatch",
                                        on_call=1, action="hang",
                                        seconds=5.0)):
        got = _key(drv3.run_arrays(src, dst))
    assert got == want
    assert drv3._mesh_live()
    assert not resilience.demotion_events()


def test_corrupt_shard_wire_caught_retried_then_demotes():
    """GS_MESH_WIRE_CHECK=1: a corrupt shard slice is caught BEFORE
    dispatch (typed failure naming the shard). Transient corruption is
    retried clean; persistent corruption exhausts the budget and rides
    the demotion ladder — results identical either way."""
    _arm()
    os.environ["GS_MESH_WIRE_CHECK"] = "1"
    src, dst = _stream()
    want = _key(_driver().run_arrays(src, dst))

    drv = _driver(mesh=make_mesh(4))
    with faults.inject(faults.FaultSpec(site="shard_wire", on_call=2,
                                        times=1, action="corrupt_shard",
                                        shard=1)):
        got = _key(drv.run_arrays(src, dst))
    assert got == want
    assert drv._mesh_live() and not resilience.demotion_events()

    drv2 = _driver(mesh=make_mesh(4))
    with faults.inject(faults.FaultSpec(site="shard_wire", on_call=2,
                                        times=1 << 20,
                                        action="corrupt_shard",
                                        shard=1)):
        got2 = _key(drv2.run_arrays(src, dst))
    assert got2 == want
    evs = resilience.demotion_events()
    assert evs and evs[0]["from"] == "sharded"
    # the reason carries the wire-check failure (directly, or inside
    # the worker traceback when the h2d stage caught it — the [:500]
    # reason cut can land mid-traceback)
    assert ("corrupt shard wire" in evs[0]["reason"]
            or "_check_wire" in evs[0]["reason"])


def test_guard_wire_names_the_offending_shard():
    os.environ["GS_MESH_WIRE_CHECK"] = "1"
    good = np.full((2, 16), 7, np.int32)
    assert guard_wire((good, good), 4, 10) == (good, good)
    bad = good.copy()
    bad[:, 8:12] = 1 << 20  # shard 2's slice of 4
    with pytest.raises(RuntimeError, match="shard 2 of 4"):
        guard_wire((good, bad), 4, 10)


def test_wire_check_disarmed_is_pass_through():
    """Default GS_MESH_WIRE_CHECK=0: guard_wire is a pure pass-through
    (no validation cost, no behavior change) when no plan is active."""
    bad = np.full((2, 16), 1 << 20, np.int32)
    out = guard_wire((bad, bad), 4, 10)
    assert out[0] is bad and out[1] is bad


def test_repromotion_after_probation_returns_to_mesh():
    """GS_TIER_RETRY_WINDOWS: after probation windows on the demoted
    single-chip tier, the session re-promotes to the sharded tier
    (mirrors → engine slabs) and keeps producing identical results."""
    _arm()
    os.environ["GS_TIER_RETRY_WINDOWS"] = "2"
    src, dst = _stream()
    want = _key(_driver().run_arrays(src, dst))
    drv = _driver(mesh=make_mesh(4))
    with faults.inject(faults.FaultSpec(site="shard_dispatch",
                                        on_call=2, times=2, shard=0)):
        got = _key(drv.run_arrays(src[:4 * EB], dst[:4 * EB]))
    got += _key(drv.run_arrays(src[4 * EB:6 * EB], dst[4 * EB:6 * EB]))
    got += _key(drv.run_arrays(src[6 * EB:], dst[6 * EB:]))
    assert got == want
    kinds = [(e["from"], e["to"]) for e in resilience.demotion_events()]
    assert ("sharded", "scan") in kinds and ("scan", "sharded") in kinds
    assert drv._mesh_live()


# ----------------------------------------------------------------------
# engine-level drain + twin hand-off
# ----------------------------------------------------------------------
def test_sharded_summary_drain_and_host_twin_handoff():
    """The satellite contract: an error escaping the sharded summary
    engine first drains the in-flight finalize — the finalized
    summaries land on `drained_partial`, the cursor sits exactly past
    them, and a host twin continues from there to the uninterrupted
    run's results."""
    _arm(retries="0")
    rng = np.random.default_rng(9)
    eb, v = 128, 100
    src = rng.integers(0, v, 8 * eb).astype(np.int32)
    dst = rng.integers(0, v, 8 * eb).astype(np.int32)
    from gelly_streaming_tpu.ops.scan_analytics import StreamSummaryEngine

    want = StreamSummaryEngine(edge_bucket=eb,
                               vertex_bucket=v).process(src, dst)
    eng = ShardedSummaryEngine(make_mesh(4), edge_bucket=eb,
                               vertex_bucket=v)
    # 8 windows dispatch as multiple chunks: kill the second dispatch
    eng.MAX_WINDOWS = 2
    with pytest.raises(resilience.StageError):
        with faults.inject(faults.FaultSpec(site="shard_dispatch",
                                            on_call=2,
                                            times=1 << 20)):
            eng.process(src, dst)
    drained = eng.drained_partial
    assert drained is not None
    assert len(drained) == eng.windows_done
    assert drained == want[:len(drained)]
    twin = HostSummaryEngine.from_sharded(eng)
    off = twin.resume_offset()
    tail = twin.process(src[off:], dst[off:])
    assert drained + tail == want


def test_sharded_triangle_kernel_drains_counts():
    _arm(retries="0")
    rng = np.random.default_rng(3)
    kern = ShardedTriangleWindowKernel(make_mesh(4), edge_bucket=128,
                                       vertex_bucket=64)
    kern.MAX_STREAM_WINDOWS = 2
    src = rng.integers(0, 60, 8 * 128).astype(np.int32)
    dst = rng.integers(0, 60, 8 * 128).astype(np.int32)
    want = kern.count_stream(src, dst)
    assert kern.drained_counts is None  # clean run leaves no stash
    with pytest.raises(resilience.StageError):
        with faults.inject(faults.FaultSpec(site="shard_dispatch",
                                            on_call=2,
                                            times=1 << 20)):
            kern.count_stream(src, dst)
    drained = kern.drained_counts
    assert drained is not None and 0 < len(drained) < 8
    assert drained == want[:len(drained)]


def test_sharded_collect_failure_hands_drained_counts_down(monkeypatch):
    """The sharded triangle stack, dispatched whole and read back
    later, fails at its second chunk's gather: the first chunk's counts
    are drained to `drained_counts`. In the driver, where the stack went
    out ahead of the call's last read-back, the flush's ladder demotes
    to one chip and counts only the windows not drained."""
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    _arm(retries="0")
    monkeypatch.setattr(ShardedTriangleWindowKernel,
                        "MAX_STREAM_WINDOWS", 2)
    rng = np.random.default_rng(4)
    windows = [(rng.integers(0, 40, 128).astype(np.int32),
                rng.integers(0, 40, 128).astype(np.int32))
               for _ in range(8)]
    kern = ShardedTriangleWindowKernel(make_mesh(4), edge_bucket=128,
                                       vertex_bucket=64)
    want = kern.count_windows(windows)
    handle = kern.dispatch_windows(windows)
    with pytest.raises(resilience.StageError):
        with faults.inject(faults.FaultSpec(site="shard_gather",
                                            on_call=2, times=1 << 20)):
            kern.collect_windows(handle)
    assert kern.drained_counts == want[:2]

    counted = []
    real = TriangleWindowKernel.count_windows

    def count_windows(self, ws):
        counted.append(len(ws))
        return real(self, ws)

    monkeypatch.setattr(TriangleWindowKernel, "count_windows",
                        count_windows)
    src, dst = _stream()
    full = ("degrees", "cc", "bipartite", "triangles")
    want = _key(_driver(analytics=full).run_arrays(src, dst))
    drv = _driver(mesh=make_mesh(4), analytics=full)
    # gathers: the engine state the scan's carry enters from, then the
    # stack's chunks
    with faults.inject(faults.FaultSpec(site="shard_gather", on_call=3,
                                        times=1 << 20)):
        got = _key(drv.run_arrays(src, dst))
    assert got == want
    assert [(e["from"], e["to"]) for e in drv.demotion_log()] == [
        ("sharded", "scan")]
    assert counted[-1] == 6


def test_dead_gather_still_demotes_off_the_mirrors():
    """The demotion hand-off must not depend on the failing mesh: with
    the d2h gather dead too (the realistic dead-chip model), the host
    mirrors — refreshed at every finalized boundary — carry the
    hand-off, results stay identical, and a checkpoint taken while
    demoted never touches the mesh."""
    from gelly_streaming_tpu.utils import checkpoint as ck

    _arm()
    src, dst = _stream()
    want = _key(_driver().run_arrays(src, dst))
    drv = _driver(mesh=make_mesh(4))
    with faults.inject(
            faults.FaultSpec(site="shard_dispatch", on_call=2,
                             times=1 << 20, shard=1),
            faults.FaultSpec(site="shard_gather", on_call=3,
                             times=1 << 20, shard=1)):
        head = _key(drv.run_arrays(src[:4 * EB], dst[:4 * EB]))
        assert not drv._mesh_live()
        state = drv.state_dict()  # still inside the dead-mesh plan
    assert _key(drv.run_arrays(src[4 * EB:], dst[4 * EB:])) \
        == want[4:]
    assert head == want[:4]
    # ... and the mesh-free checkpoint resumes bit-exactly off-mesh
    import tempfile, os as _os

    with tempfile.TemporaryDirectory() as wd:
        path = _os.path.join(wd, "demoted.npz")
        ck.save(path, state)
        res = _driver()
        assert res.try_resume(path)
        tail = _key(res.run_arrays(src[res.edges_done:],
                                   dst[res.edges_done:]))
        assert head + tail == want


def test_failed_repromotion_probe_restarts_probation():
    """A mesh still dead at probe time must RE-DEMOTE (restart
    probation, record the failed probe), never crash the stream."""
    _arm()
    os.environ["GS_TIER_RETRY_WINDOWS"] = "2"
    src, dst = _stream()
    want = _key(_driver().run_arrays(src, dst))
    drv = _driver(mesh=make_mesh(4))
    orig = StreamingAnalyticsDriver._sync_engine_from_mirrors
    calls = {"n": 0}

    def dying_sync(self):
        calls["n"] += 1
        if calls["n"] <= 2:  # the first probes find the mesh dead
            raise RuntimeError("mesh still dead")
        return orig(self)

    StreamingAnalyticsDriver._sync_engine_from_mirrors = dying_sync
    try:
        with faults.inject(faults.FaultSpec(site="shard_dispatch",
                                            on_call=2, times=2)):
            got = _key(drv.run_arrays(src[:4 * EB], dst[:4 * EB]))
        for lo in range(4, 8, 2):
            got += _key(drv.run_arrays(src[lo * EB:(lo + 2) * EB],
                                       dst[lo * EB:(lo + 2) * EB]))
    finally:
        StreamingAnalyticsDriver._sync_engine_from_mirrors = orig
    assert got == want, "probe-failure run diverged"
    kinds = [(e["from"], e["to"])
             for e in resilience.demotion_events()]
    assert ("scan", "scan") in kinds           # failed probe recorded
    assert drv._demoted_tier == "scan"          # still safely demoted


def test_host_demoted_triangles_use_numpy_twin():
    """Past the device rungs (native/host), the triangle flush must
    run the pure-numpy twin — never compile against the dead backend
    it demoted away from."""
    from gelly_streaming_tpu.parallel.host_twin import (
        HostTriangleWindowKernel)

    src, dst = _stream(num_w=4)
    want = _key(_driver().run_arrays(src, dst))
    drv = _driver(mesh=make_mesh(4))
    got = _key(drv.run_arrays(src[:2 * EB], dst[:2 * EB]))
    err = resilience.StageFailed("x", "dispatch", 0)
    err.__cause__ = RuntimeError("dead device")
    assert drv._maybe_demote("sharded", err)
    assert drv._maybe_demote("scan", err)
    assert drv._demoted_tier in ("native", "host")
    assert isinstance(drv._tri_kern(), HostTriangleWindowKernel)
    got += _key(drv.run_arrays(src[2 * EB:], dst[2 * EB:]))
    assert got == want


def test_per_window_event_time_path_demotes_too():
    """The PER-WINDOW dispatch path (event-time streaming, single
    window per call) rides the same ladder: a dead shard demotes
    mid-stream and the per-window analytics continue off the mirrors
    with identical results."""
    _arm()
    rng = np.random.default_rng(8)
    n = 6 * EB
    src = rng.integers(0, V, n).astype(np.int64)
    dst = rng.integers(0, V, n).astype(np.int64)
    ts = (np.arange(n, dtype=np.int64) // EB) * 1000  # 6 windows

    def mk(mesh=None):
        return StreamingAnalyticsDriver(
            window_ms=1000, edge_bucket=EB, vertex_bucket=VB,
            analytics=("degrees", "cc", "triangles"), mesh=mesh)

    def one_by_one(drv):
        out = []
        for w in range(6):  # one window per call → the _window path
            lo = w * EB
            out += drv.run_arrays(src[lo:lo + EB], dst[lo:lo + EB],
                                  ts[lo:lo + EB])
        return _key(out)

    want = one_by_one(mk())
    drv = mk(mesh=make_mesh(4))
    with faults.inject(faults.FaultSpec(site="shard_dispatch",
                                        on_call=4, times=1 << 20,
                                        shard=2)):
        got = one_by_one(drv)
    assert got == want
    assert not drv._mesh_live()
    evs = resilience.demotion_events()
    assert evs and evs[0]["from"] == "sharded"


def test_fault_event_carries_shard_metadata():
    """The injected-fault telemetry/exception surface names the
    shard, so a post-mortem can attribute the failure."""
    with faults.inject(faults.FaultSpec(site="shard_dispatch",
                                        shard=5)) as plan:
        with pytest.raises(faults.InjectedFault) as ei:
            faults.fire("shard_dispatch", 8)
    assert ei.value.shard == 5
    assert "shard 5" in str(ei.value)
    assert plan.fired == [("shard_dispatch", 1, "raise")]
