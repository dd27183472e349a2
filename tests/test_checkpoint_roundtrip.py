"""Checkpoint round-trips of every stateful engine: save → kill →
restore → continue must equal the uninterrupted run, THROUGH the .npz
file format (utils/checkpoint.save/restore — not just in-memory
state_dict hand-off), on every snapshot tier, plus the damaged-file
fallback paths. Tier-interchangeability is asserted explicitly: a
checkpoint taken on one tier resumes on another bit-exactly (the
carried layouts are shared by construction — DESIGN.md §9)."""

import os

import numpy as np
import pytest

from gelly_streaming_tpu import native
from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver
from gelly_streaming_tpu.ops.scan_analytics import StreamSummaryEngine
from gelly_streaming_tpu.utils import checkpoint as ck
from gelly_streaming_tpu.utils.candidates import (Candidates,
                                                  edge_to_candidate)
from gelly_streaming_tpu.utils.disjoint_set import DisjointSet

pytestmark = pytest.mark.faults

TIERS = ["resident", "scan", "host"] + (
    ["native"] if native.snapshot_available() else [])


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


@pytest.mark.parametrize("tier", TIERS)
def test_driver_save_kill_restore_continue(tier, tmp_path):
    src, dst = _stream()
    full = _key(_driver(tier).run_arrays(src, dst))

    path = str(tmp_path / "drv.npz")
    a = _driver(tier)
    half = len(src) // 2
    head = _key(a.run_arrays(src[:half], dst[:half]))
    ck.save(path, a.state_dict())
    del a  # the kill

    b = _driver(tier)
    assert b.try_resume(path)
    off = b.edges_done
    tail = _key(b.run_arrays(src[off:], dst[off:]))
    assert head + tail == full


@pytest.mark.parametrize("save_tier,resume_tier",
                         [(a, b) for a in TIERS for b in TIERS
                          if a != b])
def test_driver_checkpoints_are_tier_interchangeable(
        save_tier, resume_tier, tmp_path):
    src, dst = _stream()
    full = _key(_driver(save_tier).run_arrays(src, dst))
    path = str(tmp_path / "x.npz")
    a = _driver(save_tier)
    half = len(src) // 2
    head = _key(a.run_arrays(src[:half], dst[:half]))
    ck.save(path, a.state_dict())
    b = _driver(resume_tier)
    assert b.try_resume(path)
    tail = _key(b.run_arrays(src[b.edges_done:], dst[b.edges_done:]))
    assert head + tail == full


def test_summary_engine_save_kill_restore_continue(tmp_path):
    src, dst = _stream(n=2048, v=200)
    src32, dst32 = src.astype(np.int32), dst.astype(np.int32)
    eb, vb = 256, 256
    full = StreamSummaryEngine(edge_bucket=eb,
                               vertex_bucket=vb).process(src32, dst32)

    path = str(tmp_path / "eng.npz")
    a = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    head = a.process(src32[:4 * eb], dst32[:4 * eb])
    ck.save(path, a.state_dict())
    del a

    b = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    assert b.try_resume(path)
    off = b.resume_offset()
    tail = b.process(src32[off:], dst32[off:])
    assert head + tail == full


def test_summary_engine_auto_checkpoint_resume(tmp_path):
    src, dst = _stream(n=2048, v=200)
    src32, dst32 = src.astype(np.int32), dst.astype(np.int32)
    eb, vb = 256, 256
    full = StreamSummaryEngine(edge_bucket=eb,
                               vertex_bucket=vb).process(src32, dst32)
    path = str(tmp_path / "auto.npz")
    a = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    a.enable_auto_checkpoint(path, every_n_windows=2)
    head = a.process(src32[:5 * eb], dst32[:5 * eb])
    assert os.path.exists(path)
    b = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    assert b.try_resume(path)
    off = b.resume_offset()
    tail = b.process(src32[off:], dst32[off:])
    # positional at-least-once combine: keep the delivered prefix up
    # to the resume cursor, then the resumed suffix
    assert head[:off // eb] + tail == full


def test_resident_engine_cross_tier_resume(tmp_path):
    """A ResidentSummaryEngine checkpoint (device-resident donated
    carry, gathered at the super-batch boundary) resumes bit-exactly
    on (a) a fresh resident engine, (b) the scan-tier
    StreamSummaryEngine, and (c) the numpy HostSummaryEngine — the
    resident → resident / resident → scan / resident → host-twin legs
    of the ISSUE-9 acceptance bar (the carry layout is shared by
    construction, DESIGN.md §15)."""
    from gelly_streaming_tpu.ops.resident_engine import (
        ResidentSummaryEngine)
    from gelly_streaming_tpu.parallel.host_twin import HostSummaryEngine

    src, dst = _stream(n=2048, v=200)
    src32, dst32 = src.astype(np.int32), dst.astype(np.int32)
    eb, vb = 256, 256
    full = ResidentSummaryEngine(
        edge_bucket=eb, vertex_bucket=vb).process(src32, dst32)
    # the resident engine equals the scan engine window-for-window
    assert full == StreamSummaryEngine(
        edge_bucket=eb, vertex_bucket=vb).process(src32, dst32)

    path = str(tmp_path / "res.npz")
    a = ResidentSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    head = a.process(src32[:4 * eb], dst32[:4 * eb])
    ck.save(path, a.state_dict())
    del a  # the kill

    for make in (lambda: ResidentSummaryEngine(edge_bucket=eb,
                                               vertex_bucket=vb),
                 lambda: StreamSummaryEngine(edge_bucket=eb,
                                             vertex_bucket=vb),
                 lambda: HostSummaryEngine(edge_bucket=eb,
                                           vertex_bucket=vb)):
        b = make()
        assert b.try_resume(path)
        off = b.resume_offset()
        tail = b.process(src32[off:], dst32[off:])
        assert head + tail == full, type(b).__name__

    # and the reverse leg: a SCAN-tier checkpoint resumes on resident
    c = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    c.process(src32[:4 * eb], dst32[:4 * eb])
    ck.save(path, c.state_dict())
    d = ResidentSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    assert d.try_resume(path)
    off = d.resume_offset()
    assert head + d.process(src32[off:], dst32[off:]) == full


def _cohort_streams(n_tenants=3, windows=6, eb=256, vb=256):
    out = {}
    for i in range(n_tenants):
        n = windows * eb - (eb // 3 if i == 1 else 0)
        s, d = _stream(n=n, v=vb - 10, seed=30 + i)
        out["t%d" % i] = (s.astype(np.int32), d.astype(np.int32))
    return out


def _pump_all(co, streams, cursors, out, piece):
    live = True
    while live:
        live = False
        for tid, (s, d) in streams.items():
            c = cursors[tid]
            if c >= len(s):
                continue
            co.feed(tid, s[c:c + piece], d[c:c + piece])
            cursors[tid] = min(len(s), c + piece)
            live = True
        for tid, res in co.pump().items():
            out.setdefault(tid, []).extend(res)


def test_tenant_cohort_kill_resume_cohort_to_cohort(tmp_path):
    """Per-tenant auto-checkpoints through the .npz format: kill the
    cohort mid-stream, resume EVERY tenant independently into a fresh
    cohort (resume_all), re-feed from each tenant's own offset — the
    positional at-least-once combine equals the uninterrupted
    sequential runs."""
    from gelly_streaming_tpu.core.tenancy import TenantCohort

    eb, vb = 256, 256
    streams = _cohort_streams(eb=eb, vb=vb)
    full = {tid: StreamSummaryEngine(edge_bucket=eb,
                                     vertex_bucket=vb).process(s, d)
            for tid, (s, d) in streams.items()}

    co = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    for tid in streams:
        co.admit(tid)
    co.enable_auto_checkpoint(str(tmp_path / "tenants"),
                              every_n_windows=2)
    head, cursors = {}, {tid: 0 for tid in streams}
    # feed/pump only the first 4 windows' worth, then "die"
    for _ in range(4):
        for tid, (s, d) in streams.items():
            c = cursors[tid]
            co.feed(tid, s[c:c + eb], d[c:c + eb])
            cursors[tid] = min(len(s), c + eb)
        for tid, res in co.pump().items():
            head.setdefault(tid, []).extend(res)
    del co

    co2 = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    for tid in streams:
        co2.admit(tid)
    co2.enable_auto_checkpoint(str(tmp_path / "tenants"),
                               every_n_windows=2)
    resumed = co2.resume_all()
    assert all(resumed.values())
    final = {}
    for tid, (s, d) in streams.items():
        off = co2.resume_offset(tid)
        assert off > 0 and off <= len(head[tid]) * eb
        final[tid] = head[tid][:off // eb]
    cursors = {tid: co2.resume_offset(tid) for tid in streams}
    _pump_all(co2, streams, cursors, final, 2 * eb)
    for tid in streams:
        final[tid].extend(co2.close(tid))
    assert final == full


def test_tenant_checkpoint_demotes_to_single_engine(tmp_path):
    """The cohort→single demotion ladder THROUGH the file format: a
    per-tenant cohort checkpoint restores into a plain
    StreamSummaryEngine (the state layouts are shared by
    construction) and the single engine finishes the stream
    bit-exactly."""
    from gelly_streaming_tpu.core.tenancy import TenantCohort

    eb, vb = 256, 256
    streams = _cohort_streams(n_tenants=2, eb=eb, vb=vb)
    full = {tid: StreamSummaryEngine(edge_bucket=eb,
                                     vertex_bucket=vb).process(s, d)
            for tid, (s, d) in streams.items()}

    co = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    for tid in streams:
        co.admit(tid)
    head, cursors = {}, {tid: 0 for tid in streams}
    for _ in range(3):
        for tid, (s, d) in streams.items():
            c = cursors[tid]
            co.feed(tid, s[c:c + eb], d[c:c + eb])
            cursors[tid] = min(len(s), c + eb)
        for tid, res in co.pump().items():
            head.setdefault(tid, []).extend(res)
    path = str(tmp_path / "t0.npz")
    ck.save(path, co.tenant_state_dict("t0"))
    del co

    single = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    assert single.try_resume(path)
    off = single.resume_offset()
    s, d = streams["t0"]
    tail = single.process(s[off:], d[off:])
    assert head["t0"][:off // eb] + tail == full["t0"]


def test_single_engine_checkpoint_resumes_into_cohort(tmp_path):
    """The reverse ladder: a single-tenant StreamSummaryEngine
    checkpoint loads into a cohort tenant (load_tenant_state_dict)
    and the vmapped cohort finishes the stream bit-exactly — tenants
    can migrate INTO the cohort tier, not just fall out of it."""
    from gelly_streaming_tpu.core.tenancy import TenantCohort

    eb, vb = 256, 256
    s, d = _stream(n=6 * eb, v=vb - 10, seed=44)
    s, d = s.astype(np.int32), d.astype(np.int32)
    full = StreamSummaryEngine(edge_bucket=eb,
                               vertex_bucket=vb).process(s, d)

    eng = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    head = eng.process(s[:3 * eb], d[:3 * eb])
    state = eng.state_dict()

    co = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    co.admit("migrated")
    co.load_tenant_state_dict("migrated", state)
    off = co.resume_offset("migrated")
    assert off == 3 * eb
    co.feed("migrated", s[off:], d[off:])
    tail = co.pump().get("migrated", [])
    tail.extend(co.close("migrated"))
    assert head + tail == full


@pytest.mark.parametrize("target", ["resident", "scan", "single"])
def test_resident_cohort_kill_recovers_onto_any_tier(
        tmp_path, target, monkeypatch):
    """Resident-cohort migration contract: kill mid-super-batch (the
    donated [N, ...] stacked-carry state dies with the process; only
    the per-tenant super-batch-boundary checkpoint gathers survive)
    and recover onto (i) a fresh resident cohort, (ii) the scan-tier
    cohort with the tier pinned off, (iii) N plain single engines —
    every target finishes the streams bit-exactly equal to the
    fault-free oracle."""
    from gelly_streaming_tpu.core.tenancy import TenantCohort

    eb, vb = 256, 256
    streams = _cohort_streams(eb=eb, vb=vb)
    full = {tid: StreamSummaryEngine(edge_bucket=eb,
                                     vertex_bucket=vb).process(s, d)
            for tid, (s, d) in streams.items()}

    monkeypatch.setenv("GS_COHORT_RESIDENT", "on")
    co = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    for tid in streams:
        co.admit(tid)
    co.enable_auto_checkpoint(str(tmp_path / "tenants"),
                              every_n_windows=2)
    head, cursors = {}, {tid: 0 for tid in streams}
    for _ in range(4):
        for tid, (s, d) in streams.items():
            c = cursors[tid]
            co.feed(tid, s[c:c + eb], d[c:c + eb])
            cursors[tid] = min(len(s), c + eb)
        for tid, res in co.pump().items():
            head.setdefault(tid, []).extend(res)
    assert co.resident_dispatches > 0
    del co  # the kill: the resident stack is gone with it

    if target == "single":
        # (iii) demote-all: each tenant's checkpoint restores
        # into a plain single-stream engine
        for tid, (s, d) in streams.items():
            eng = StreamSummaryEngine(edge_bucket=eb,
                                      vertex_bucket=vb)
            assert eng.try_resume(str(
                tmp_path / "tenants" / ("tenant_%s.npz" % tid)))
            off = eng.resume_offset()
            assert 0 < off <= len(head[tid]) * eb
            tail = eng.process(s[off:], d[off:])
            assert head[tid][:off // eb] + tail == full[tid]
        return

    if target == "scan":
        monkeypatch.setenv("GS_COHORT_RESIDENT", "off")
    co2 = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    for tid in streams:
        co2.admit(tid)
    co2.enable_auto_checkpoint(str(tmp_path / "tenants"),
                               every_n_windows=2)
    resumed = co2.resume_all()
    assert all(resumed.values())
    final, cursors = {}, {}
    for tid in streams:
        off = co2.resume_offset(tid)
        assert 0 < off <= len(head[tid]) * eb
        final[tid] = head[tid][:off // eb]
        cursors[tid] = off
    _pump_all(co2, streams, cursors, final, 2 * eb)
    for tid in streams:
        final[tid].extend(co2.close(tid))
    if target == "resident":
        assert co2.resident_dispatches > 0
    else:
        assert co2.resident_dispatches == 0
    assert final == full


def test_sharded_engine_state_roundtrip_through_file(tmp_path):
    """ShardedWindowEngine state through the npz format (skipped when
    this jax build cannot run while_loops under shard_map — the
    pre-existing mesh limitation, not a checkpoint defect)."""
    from gelly_streaming_tpu.parallel.mesh import make_mesh
    from gelly_streaming_tpu.parallel.sharded import ShardedWindowEngine

    src, dst = _stream(n=512, v=100)
    try:
        mesh = make_mesh(8)
        a = ShardedWindowEngine(mesh, num_vertices_bucket=256)
        a.degrees(src[:256].astype(np.int32),
                  dst[:256].astype(np.int32))
    except NotImplementedError as e:
        pytest.skip(f"mesh unsupported in this jax: {e}")
    path = str(tmp_path / "sh.npz")
    ck.save(path, a.state_dict())
    b = ShardedWindowEngine(mesh, num_vertices_bucket=256)
    b.load_state_dict(ck.restore(path))
    ga = a.degrees(src[256:].astype(np.int32),
                   dst[256:].astype(np.int32))
    gb = b.degrees(src[256:].astype(np.int32),
                   dst[256:].astype(np.int32))
    assert np.array_equal(np.asarray(ga), np.asarray(gb))


def test_driver_mesh_checkpoint_resumes_on_one_device_and_host(
        tmp_path):
    """Cross-MESH resume, driver level: a checkpoint taken on a 4-way
    mesh resumes bit-exactly on 1 device (scan tier) AND on the numpy
    host tier — the engine slabs are gathered replicated state, so
    they convert to the single-chip mirrors on load."""
    from gelly_streaming_tpu.parallel.mesh import make_mesh

    src, dst = _stream(n=8 * 512, v=700)

    def mk(**kw):
        return StreamingAnalyticsDriver(
            window_ms=0, edge_bucket=512, vertex_bucket=1024,
            analytics=("degrees", "cc", "bipartite", "triangles"),
            **kw)

    full = _key(mk().run_arrays(src, dst))
    a = mk(mesh=make_mesh(4))
    head = _key(a.run_arrays(src[:4 * 512], dst[:4 * 512]))
    path = str(tmp_path / "mesh.npz")
    ck.save(path, a.state_dict())
    for tier in ("scan", "host"):
        b = mk(snapshot_tier=tier)
        assert b.try_resume(path)
        off = b.edges_done
        tail = _key(b.run_arrays(src[off:], dst[off:]))
        assert head + tail == full, tier
    # and the other direction: a single-chip checkpoint onto a mesh
    c = mk()
    head2 = _key(c.run_arrays(src[:4 * 512], dst[:4 * 512]))
    path2 = str(tmp_path / "single.npz")
    ck.save(path2, c.state_dict())
    d = mk(mesh=make_mesh(4))
    assert d.try_resume(path2)
    tail2 = _key(d.run_arrays(src[d.edges_done:], dst[d.edges_done:]))
    assert head2 + tail2 == full


def test_sharded_summary_checkpoint_cross_mesh_and_twin(tmp_path):
    """Cross-MESH resume, engine level: a 4-shard ShardedSummaryEngine
    checkpoint (through the npz format) continues bit-exactly on the
    single-chip engine, on the numpy host twin, and on a 2-shard mesh
    — the shard-count-independent gathered layout."""
    from gelly_streaming_tpu.parallel.host_twin import HostSummaryEngine
    from gelly_streaming_tpu.parallel.mesh import make_mesh
    from gelly_streaming_tpu.parallel.sharded import ShardedSummaryEngine

    src, dst = _stream(n=2048, v=200)
    src32, dst32 = src.astype(np.int32), dst.astype(np.int32)
    eb, vb = 256, 256
    full = StreamSummaryEngine(edge_bucket=eb,
                               vertex_bucket=vb).process(src32, dst32)
    a = ShardedSummaryEngine(make_mesh(4), edge_bucket=eb,
                             vertex_bucket=vb)
    head = a.process(src32[:4 * eb], dst32[:4 * eb])
    assert a.state_dict()["mesh_shape"] == [4]
    path = str(tmp_path / "sh4.npz")
    ck.save(path, a.state_dict())

    resumers = [
        StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb),
        HostSummaryEngine(edge_bucket=eb, vertex_bucket=vb),
        ShardedSummaryEngine(make_mesh(2), edge_bucket=eb,
                             vertex_bucket=vb),
    ]
    for eng in resumers:
        assert eng.try_resume(path), type(eng).__name__
        off = eng.resume_offset()
        assert off == 4 * eb
        tail = eng.process(src32[off:], dst32[off:])
        assert head + tail == full, type(eng).__name__


def test_disjoint_set_roundtrip_through_file(tmp_path):
    edges = [(1, 2), (3, 4), (2, 3), (7, 8), (9, 7), (4, 9)]
    full = DisjointSet()
    for a, b in edges:
        full.union(a, b)

    half = DisjointSet()
    for a, b in edges[:3]:
        half.union(a, b)
    path = str(tmp_path / "ds.npz")
    ck.save(path, half.state_dict())
    resumed = DisjointSet()
    resumed.load_state_dict(ck.restore(path))
    for a, b in edges[3:]:
        resumed.union(a, b)
    assert repr(resumed) == repr(full)


def test_candidates_roundtrip_through_file(tmp_path):
    edges = [(1, 2), (2, 3), (3, 4), (4, 1), (5, 6), (4, 5)]
    full = Candidates(True)
    for a, b in edges:
        full = full.merge(edge_to_candidate(a, b))

    half = Candidates(True)
    for a, b in edges[:3]:
        half = half.merge(edge_to_candidate(a, b))
    path = str(tmp_path / "cand.npz")
    ck.save(path, half.state_dict())
    resumed = Candidates(True)
    resumed.load_state_dict(ck.restore(path))
    for a, b in edges[3:]:
        resumed = resumed.merge(edge_to_candidate(a, b))
    assert repr(resumed) == repr(full)


def test_truncated_file_fallback_and_total_loss(tmp_path):
    path = str(tmp_path / "gen.npz")
    ck.save(path, {"v": np.arange(4), "n": 1})
    ck.save(path, {"v": np.arange(5), "n": 2})
    with open(path, "r+b") as f:
        f.truncate(10)  # external damage to the newest generation
    with pytest.raises(ck.CheckpointCorrupt) as ei:
        ck.restore(path)
    assert ei.value.path == path
    tree, used = ck.load_latest(path)
    assert tree["n"] == 1 and used == ck.prev_path(path)
    with open(used, "r+b") as f:
        f.truncate(10)  # both generations gone
    with pytest.raises(ck.CheckpointCorrupt):
        ck.load_latest(path)
    assert ck.load_latest(str(tmp_path / "missing.npz")) is None


def test_save_is_atomic_and_tmp_is_process_unique(tmp_path):
    path = str(tmp_path / "a.npz")
    ck.save(path, {"x": np.arange(3)})

    class Unsaveable:
        pass

    with pytest.raises(TypeError):
        ck.save(path, {"bad": Unsaveable()})
    # the failed save leaked no tmp and left the good file intact
    assert sorted(os.listdir(tmp_path)) == ["a.npz"]
    assert ck.restore(path)["x"].tolist() == [0, 1, 2]


# ======================================================================
# WAL kill→replay exactness (utils/wal.py; ISSUE 12): with a journal
# armed, a kill at ANY point — including BETWEEN the journal append
# and the queue enqueue — recovers to results bit-identical to the
# fault-free run, on the cohort, single-engine, and driver paths.
# ======================================================================
def _wal_stream(num_w, eb, vb, seed):
    rng = np.random.default_rng(seed)
    n = num_w * eb
    return (rng.integers(0, vb, n).astype(np.int32),
            rng.integers(0, vb, n).astype(np.int32))


def test_engine_wal_kill_and_replay_exact(tmp_path):
    from gelly_streaming_tpu.utils import faults

    eb, vb, num_w = 256, 512, 8
    src, dst = _wal_stream(num_w, eb, vb, seed=21)
    baseline = StreamSummaryEngine(edge_bucket=eb,
                                   vertex_bucket=vb).process(src, dst)

    ckpt = str(tmp_path / "eng.npz")
    a = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    assert a.enable_wal(str(tmp_path / "wal"))
    a.enable_auto_checkpoint(ckpt, every_n_windows=2)
    out = []
    killed = False
    try:
        with faults.inject(faults.FaultSpec(
                site="dispatch", on_call=3, fatal=True)):
            for w in range(0, num_w, 2):
                out += a.process(src[w * eb:(w + 2) * eb],
                                 dst[w * eb:(w + 2) * eb])
    except faults.InjectedFault:
        killed = True
    assert killed

    b = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    assert b.enable_wal(str(tmp_path / "wal"))
    replayed = b.resume_and_replay(ckpt)
    # positional at-least-once combine: checkpointed prefix + replay
    final = out[:b.windows_done - len(replayed)] + replayed
    # the caller's view: delivered windows + the recovered tail, then
    # feed the rest of the stream normally
    off = b.resume_offset()
    final += b.process(src[off:], dst[off:])
    assert final == baseline


def test_engine_wal_kill_between_append_and_fold(tmp_path):
    """The narrowest window: the journal append returned but the fold
    never ran (kill at the wal_enqueue site). Replay must recover the
    accepted-but-never-processed edges."""
    from gelly_streaming_tpu.utils import faults

    eb, vb, num_w = 256, 512, 4
    src, dst = _wal_stream(num_w, eb, vb, seed=22)
    baseline = StreamSummaryEngine(edge_bucket=eb,
                                   vertex_bucket=vb).process(src, dst)

    ckpt = str(tmp_path / "eng.npz")
    a = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    assert a.enable_wal(str(tmp_path / "wal"))
    a.enable_auto_checkpoint(ckpt, every_n_windows=2)
    out = a.process(src[:2 * eb], dst[:2 * eb])
    with pytest.raises(faults.InjectedFault):
        with faults.inject(faults.FaultSpec(
                site="wal_enqueue", on_call=1, fatal=True)):
            a.process(src[2 * eb:], dst[2 * eb:])

    b = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    assert b.enable_wal(str(tmp_path / "wal"))
    replayed = b.resume_and_replay(ckpt)
    assert len(replayed) == 2  # the journaled-but-unfolded windows
    assert out + replayed == baseline


def test_cohort_wal_kill_between_append_and_enqueue(tmp_path):
    """Cohort flavor of the narrowest window: feed() journaled the
    batch, the kill landed before the queue concatenate. recover()
    must replay it; the caller was told nothing (no ack), so the
    at-least-once re-send of the SAME batch must not double-fold
    (replay already covers it — the re-send is what a real producer
    does only for un-acked batches, so here the recovered run feeds
    the NEXT batches only)."""
    from gelly_streaming_tpu.core.tenancy import TenantCohort
    from gelly_streaming_tpu.utils import faults

    eb, vb, num_w = 256, 512, 4
    src, dst = _wal_stream(num_w, eb, vb, seed=23)
    oracle = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    oracle.admit("t")
    oracle.feed("t", src, dst)
    want = oracle.pump()["t"]

    wal_dir = str(tmp_path / "wal")
    a = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    assert a.enable_wal(wal_dir)
    a.enable_auto_checkpoint(str(tmp_path / "ck"), every_n_windows=2)
    a.admit("t")
    a.feed("t", src[:2 * eb], dst[:2 * eb])
    got = a.pump()["t"]
    with pytest.raises(faults.InjectedFault):
        with faults.inject(faults.FaultSpec(
                site="wal_enqueue", on_call=1, fatal=True)):
            a.feed("t", src[2 * eb:], dst[2 * eb:])

    b = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    assert b.enable_wal(wal_dir)
    b.enable_auto_checkpoint(str(tmp_path / "ck"), every_n_windows=2)
    info = b.recover()
    assert info["resumed"]["t"] is True
    assert info["replayed_edges"]["t"] == 2 * eb
    got += b.pump()["t"]
    assert got == want


def test_cohort_wal_kill_mid_dispatch_replay_exact(tmp_path):
    """Kill mid-cohort-dispatch (after several checkpointed rounds):
    recover() + continued feeding equals the fault-free run, window
    for window, for every tenant."""
    from gelly_streaming_tpu.core.tenancy import TenantCohort
    from gelly_streaming_tpu.utils import faults

    eb, vb, num_w = 256, 512, 8
    streams = {"a": _wal_stream(num_w, eb, vb, 24),
               "b": _wal_stream(num_w, eb, vb, 25)}
    want = {}
    oracle = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    for tid in streams:
        oracle.admit(tid)
    for w in range(num_w):
        for tid, (s, d) in streams.items():
            oracle.feed(tid, s[w * eb:(w + 1) * eb],
                        d[w * eb:(w + 1) * eb])
        for tid, res in oracle.pump().items():
            want.setdefault(tid, []).extend(res)

    wal_dir = str(tmp_path / "wal")
    a = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    assert a.enable_wal(wal_dir)
    a.enable_auto_checkpoint(str(tmp_path / "ck"), every_n_windows=2)
    for tid in streams:
        a.admit(tid)
    got = {tid: {} for tid in streams}
    killed_at = None
    try:
        with faults.inject(faults.FaultSpec(
                site="cohort_dispatch", on_call=5, fatal=True)):
            for w in range(num_w):
                for tid, (s, d) in sorted(streams.items()):
                    a.feed(tid, s[w * eb:(w + 1) * eb],
                           d[w * eb:(w + 1) * eb])
                for tid, res in a.pump().items():
                    base = a.windows_done(tid) - len(res)
                    for i, r in enumerate(res):
                        got[tid][base + i] = r
    except faults.InjectedFault:
        killed_at = w
    assert killed_at is not None

    b = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    assert b.enable_wal(wal_dir)
    b.enable_auto_checkpoint(str(tmp_path / "ck"), every_n_windows=2)
    info = b.recover()
    assert any(info["resumed"].values())
    for tid, res in b.pump().items():  # the replayed suffix
        base = b.windows_done(tid) - len(res)
        for i, r in enumerate(res):
            got[tid][base + i] = r
    for w in range(killed_at + 1, num_w):
        for tid, (s, d) in sorted(streams.items()):
            b.feed(tid, s[w * eb:(w + 1) * eb],
                   d[w * eb:(w + 1) * eb])
        for tid, res in b.pump().items():
            base = b.windows_done(tid) - len(res)
            for i, r in enumerate(res):
                got[tid][base + i] = r
    for tid in streams:
        final = [got[tid][k] for k in sorted(got[tid])]
        assert final == want[tid], tid


def test_driver_wal_kill_and_replay_exact(tmp_path):
    """The driver's LIVE feed path (run_arrays, count-based windows)
    with the journal armed: kill mid-stream, resume_and_replay
    reproduces the un-checkpointed windows bit-exactly."""
    from gelly_streaming_tpu.utils import faults

    src, dst = _stream(n=4096, v=384, seed=26)
    eb = 512
    full = _key(StreamingAnalyticsDriver(
        window_ms=0, edge_bucket=eb,
        vertex_bucket=1024).run_arrays(src, dst))

    ckpt = str(tmp_path / "drv.npz")
    a = StreamingAnalyticsDriver(window_ms=0, edge_bucket=eb,
                                 vertex_bucket=1024)
    assert a.enable_wal(str(tmp_path / "wal"))
    a.enable_auto_checkpoint(ckpt, every_n_windows=2)
    out = []
    killed = False
    try:
        with faults.inject(faults.FaultSpec(
                site="dispatch", on_call=3, fatal=True)):
            for i in range(0, len(src), 2 * eb):
                out += _key(a.run_arrays(src[i:i + 2 * eb],
                                         dst[i:i + 2 * eb]))
    except faults.InjectedFault:
        killed = True
    assert killed

    b = StreamingAnalyticsDriver(window_ms=0, edge_bucket=eb,
                                 vertex_bucket=1024)
    assert b.enable_wal(str(tmp_path / "wal"))
    replayed = _key(b.resume_and_replay(ckpt))
    final = out[:b.windows_done - len(replayed)] + replayed
    off = b.edges_done
    final += _key(b.run_arrays(src[off:], dst[off:]))
    assert final == full


def test_driver_wal_checkpoint_offset_contract(tmp_path):
    """The checkpoint carries wal_offset == edges_done, and a
    hand-edited divergence is refused loudly."""
    src, dst = _stream(n=1024, v=128, seed=27)
    a = StreamingAnalyticsDriver(window_ms=0, edge_bucket=512,
                                 vertex_bucket=1024)
    a.run_arrays(src, dst)
    state = a.state_dict()
    assert state["wal_offset"] == state["edges_done"] == len(src)
    state["wal_offset"] = 7
    b = StreamingAnalyticsDriver(window_ms=0, edge_bucket=512,
                                 vertex_bucket=1024)
    with pytest.raises(ValueError, match="wal_offset"):
        b.load_state_dict(state)
