"""Columnar streaming driver: end-to-end ingest→device analytics with
carried state, bucket growth, sharding, and checkpoint/resume."""

import re

import numpy as np
import pytest

from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver
from gelly_streaming_tpu.ops import triangles as tri_ops
from gelly_streaming_tpu.ops import unionfind
from gelly_streaming_tpu.parallel.mesh import make_mesh

# the single-chip snapshot tiers: None is the unpinned default (the
# scan, as on the chip); native and host are the demotion ladder's
# rungs, reached otherwise only through a demotion
TIERS = pytest.mark.parametrize("tier", [None, "native", "host"])


def _stream(seed=0, n=3000, v=500):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, n)
    dst = rng.integers(0, v, n)
    ts = np.sort(rng.integers(0, 5000, n))
    return src, dst, ts


def _reference_results(src, dst, ts, window_ms):
    """Independent per-window analytics over external ids."""
    starts = ts - ts % window_ms
    out = []
    seen_edges_s, seen_edges_d = [], []
    for w in np.unique(starts):
        m = starts == w
        seen_edges_s.append(src[m])
        seen_edges_d.append(dst[m])
        all_s = np.concatenate(seen_edges_s)
        all_d = np.concatenate(seen_edges_d)
        nv = int(max(all_s.max(), all_d.max())) + 1
        deg = np.bincount(all_s, minlength=nv) + np.bincount(all_d,
                                                            minlength=nv)
        tri = tri_ops.triangle_count_sparse(src[m], dst[m], nv)
        _, _, odd = unionfind.bipartite_labels(all_s, all_d, nv)
        out.append((int(w), deg, tri, odd))
    return out


# the mesh case plus each single-chip tier; the unpinned ids keep
# their pre-ladder names
MESH_AND_TIERS = pytest.mark.parametrize("sharded,tier", [
    pytest.param(False, None, id="False"),
    pytest.param(True, None, id="True"),
    pytest.param(False, "native", id="False-native"),
    pytest.param(False, "host", id="False-host"),
])


@MESH_AND_TIERS
def test_driver_matches_independent_analytics(sharded, tier):
    src, dst, ts = _stream()
    mesh = make_mesh() if sharded else None
    drv = StreamingAnalyticsDriver(window_ms=1000, mesh=mesh,
                                   vertex_bucket=64, edge_bucket=64,
                                   snapshot_tier=tier)
    results = drv.run_arrays(src, dst, ts)  # buckets must grow en route
    refs = _reference_results(src, dst, ts, 1000)
    assert len(results) == len(refs)
    for res, (w, deg, tri, odd) in zip(results, refs):
        assert res.window_start == w
        ids = res.vertex_ids
        # driver state is dense-slot indexed; compare via external ids
        got_deg = np.zeros_like(deg)
        got_deg[ids] = res.degrees[: len(ids)]
        np.testing.assert_array_equal(got_deg[deg > 0], deg[deg > 0])
        assert res.triangles == tri
        got_odd = np.zeros_like(odd)
        got_odd[ids] = res.bipartite_odd[: len(ids)]
        np.testing.assert_array_equal(got_odd[deg > 0], odd[deg > 0])
        # cc labels: same partition as host labels over touched ids
        labels = res.cc_labels[: len(ids)]
        assert labels.min() >= 0


def test_unpinned_driver_runs_the_chip_path_on_cpu(monkeypatch):
    """With no pin, the CPU runs the chip's program: the device
    snapshot scan and the device triangle program at K = 128."""
    import jax

    monkeypatch.delenv("GS_RESIDENT", raising=False)
    assert jax.default_backend() == "cpu"
    drv = StreamingAnalyticsDriver(window_ms=0, edge_bucket=32768)
    assert drv._base_tier() == "scan"
    assert drv._effective_tier() == "scan"
    assert tri_ops._resolve_stream_impl(drv.eb) == "device"
    assert tri_ops._tuned_kb(drv.eb) == 128


@TIERS
def test_driver_cc_partition_matches_host(tier):
    src = np.array([1, 2, 10, 20, 2])
    dst = np.array([2, 3, 11, 21, 10])
    drv = StreamingAnalyticsDriver(window_ms=100,
                                   analytics=("cc",), snapshot_tier=tier)
    (res,) = drv.run_arrays(src, dst, np.zeros(5, np.int64))
    ids = res.vertex_ids
    lab = res.cc_labels
    by_label = {}
    for slot, ext in enumerate(ids):
        by_label.setdefault(int(lab[slot]), set()).add(int(ext))
    groups = sorted(sorted(g) for g in by_label.values())
    assert groups == [[1, 2, 3, 10, 11], [20, 21]]


@TIERS
def test_driver_count_windows_without_timestamps(tier):
    src, dst, _ = _stream(n=300)
    drv = StreamingAnalyticsDriver(window_ms=1000, edge_bucket=128,
                                   analytics=("triangles",),
                                   snapshot_tier=tier)
    results = drv.run_arrays(src, dst)
    assert [r.num_edges for r in results] == [128, 128, 44]
    for r, s in zip(results, range(0, 300, 128)):
        assert r.triangles == tri_ops.triangle_count_sparse(
            src[s:s + 128], dst[s:s + 128], 500)


@TIERS
def test_driver_checkpoint_resume(tier):
    src, dst, ts = _stream(seed=3)
    half = len(src) // 2
    a = StreamingAnalyticsDriver(window_ms=500, vertex_bucket=64,
                                 edge_bucket=64, snapshot_tier=tier)
    a.run_arrays(src[:half], dst[:half], ts[:half])
    state = a.state_dict()

    b = StreamingAnalyticsDriver(window_ms=500, vertex_bucket=64,
                                 edge_bucket=64, snapshot_tier=tier)
    b.load_state_dict(state)
    out_b = b.run_arrays(src[half:], dst[half:], ts[half:])
    out_a = a.run_arrays(src[half:], dst[half:], ts[half:])
    for ra, rb in zip(out_a, out_b):
        np.testing.assert_array_equal(ra.degrees, rb.degrees)
        np.testing.assert_array_equal(ra.cc_labels, rb.cc_labels)
        np.testing.assert_array_equal(ra.bipartite_odd, rb.bipartite_odd)
        assert ra.triangles == rb.triangles
        np.testing.assert_array_equal(ra.vertex_ids, rb.vertex_ids)


def test_driver_ascending_timestamp_contract():
    drv = StreamingAnalyticsDriver(window_ms=100)
    with pytest.raises(ValueError, match="ascending"):
        drv.run_arrays(np.array([1, 2]), np.array([2, 3]),
                       np.array([500, 100]))


@TIERS
def test_driver_tracing_and_file(tmp_path, tier):
    p = tmp_path / "edges.txt"
    p.write_text("1 2 100\n2 3 150\n1 3 180\n3 4 300\n")
    drv = StreamingAnalyticsDriver(window_ms=200, tracing=True,
                                   snapshot_tier=tier)
    results = drv.run_file(str(p))
    assert [r.triangles for r in results] == [1, 0]
    report = drv.trace_report()
    assert {row["op"] for row in report} >= {"intern", "triangles"}


@TIERS
def test_driver_cross_mode_checkpoint_converts(tier):
    """A single-chip checkpoint now CONVERTS onto a mesh driver (and
    vice versa — the engine slabs are gathered replicated state): the
    resumed sharded session continues with the checkpointed analytics
    instead of refusing. Full round-trip equality is pinned by
    tests/test_checkpoint_roundtrip.py's cross-mode suite."""
    a = StreamingAnalyticsDriver(window_ms=500, snapshot_tier=tier)
    a.run_arrays(np.array([1, 2]), np.array([2, 3]),
                 np.array([100, 200]))
    state = a.state_dict()
    b = StreamingAnalyticsDriver(window_ms=500, mesh=make_mesh())
    b.load_state_dict(state)
    assert b.windows_done == a.windows_done
    st = b._engine.state_dict()
    np.testing.assert_array_equal(
        np.asarray(st["degree_state"])[:len(a._degrees)], a._degrees)


@TIERS
def test_driver_auto_checkpoint_failure_recovery(tmp_path, tier):
    """Crash/recover: a driver checkpointing every 2 windows dies; a
    fresh driver resumes from the snapshot cursor and the final state
    matches an uninterrupted run."""
    ckpt = str(tmp_path / "state.ckpt")
    src, dst, _ = _stream(seed=7, n=1024)
    eb = 128  # count-based windows: 8 windows of 128 edges

    a = StreamingAnalyticsDriver(window_ms=0, edge_bucket=eb,
                                 snapshot_tier=tier)
    a.enable_auto_checkpoint(ckpt, every_n_windows=2)
    a.run_arrays(src[: 6 * eb], dst[: 6 * eb])  # "crash" after 6 windows

    b = StreamingAnalyticsDriver(window_ms=0, edge_bucket=eb,
                                 snapshot_tier=tier)
    assert b.try_resume(ckpt)
    assert b.windows_done == 6  # checkpoint fired at window 6
    out_b = b.run_arrays(src[b.windows_done * eb:],
                         dst[b.windows_done * eb:])

    c = StreamingAnalyticsDriver(window_ms=0, edge_bucket=eb,
                                 snapshot_tier=tier)
    out_c = c.run_arrays(src, dst)
    np.testing.assert_array_equal(out_b[-1].degrees, out_c[-1].degrees)
    np.testing.assert_array_equal(out_b[-1].cc_labels, out_c[-1].cc_labels)
    assert out_b[-1].triangles == out_c[-1].triangles
    assert not StreamingAnalyticsDriver(
        window_ms=0, snapshot_tier=tier).try_resume(
            str(tmp_path / "missing.ckpt"))


def test_stream_file_matches_run_file(tmp_path):
    """Chunked streaming ingestion (bounded memory) produces the exact
    same windows as whole-file processing, for event-time and
    count-based streams, across tiny chunk sizes."""
    rng = np.random.default_rng(13)
    n = 700
    src = rng.integers(0, 80, n)
    dst = rng.integers(0, 80, n)
    ts = np.sort(rng.integers(0, 2000, n))
    p_ts = tmp_path / "ts.txt"
    p_ts.write_text("".join(f"{s} {d} {t}\n" for s, d, t in
                            zip(src, dst, ts)))
    p_plain = tmp_path / "plain.txt"
    p_plain.write_text("".join(f"{s} {d}\n" for s, d in zip(src, dst)))

    for path in (p_ts, p_plain):
        base = StreamingAnalyticsDriver(window_ms=300, edge_bucket=128)
        want = base.run_file(str(path))
        for chunk_bytes in (64, 1 << 20):
            drv = StreamingAnalyticsDriver(window_ms=300, edge_bucket=128)
            got = list(drv.stream_file(str(path), chunk_bytes=chunk_bytes))
            assert [r.window_start for r in got] == \
                   [r.window_start for r in want]
            assert [r.triangles for r in got] == \
                   [r.triangles for r in want]
            np.testing.assert_array_equal(got[-1].degrees,
                                          want[-1].degrees)
            np.testing.assert_array_equal(got[-1].cc_labels,
                                          want[-1].cc_labels)


def test_stream_file_resume_skips_processed_edges(tmp_path):
    """Crash/resume over an event-time file: resume=True replays
    nothing (carried state equals the uninterrupted run's)."""
    rng = np.random.default_rng(31)
    n = 900
    src = rng.integers(0, 90, n)
    dst = rng.integers(0, 90, n)
    ts = np.sort(rng.integers(0, 3000, n))
    p = tmp_path / "s.txt"
    p.write_text("".join(f"{s} {d} {t}\n" for s, d, t in
                         zip(src, dst, ts)))
    ck = str(tmp_path / "c.ckpt")

    want = StreamingAnalyticsDriver(window_ms=300).run_file(str(p))

    a = StreamingAnalyticsDriver(window_ms=300)
    a.enable_auto_checkpoint(ck, every_n_windows=2)
    seen = []
    for i, res in enumerate(a.stream_file(str(p), chunk_bytes=2048)):
        seen.append(res)
        if i == 4:
            break  # crash; last checkpoint covers windows 1..4

    b = StreamingAnalyticsDriver(window_ms=300)
    assert b.try_resume(ck)
    # the staged-checkpoint contract (driver._stage_ckpt): a FLUSHED
    # checkpoint never covers windows the consumer wasn't handed, and
    # lags the consumer by at most one checkpoint interval — so resume
    # can re-emit delivered windows (at-least-once) but never skip
    # undelivered ones
    done = b.windows_done  # capture: processing advances the cursor
    assert done <= len(seen)
    assert done >= len(seen) - 2
    rest = list(b.stream_file(str(p), chunk_bytes=2048, resume=True))
    # resume continues at exactly the first un-checkpointed window…
    assert [r.window_start for r in rest] == \
           [r.window_start for r in want[done:]]
    assert [r.triangles for r in rest] == \
           [r.triangles for r in want[done:]]
    # …and carried state ends identical to the uninterrupted run
    np.testing.assert_array_equal(rest[-1].degrees, want[-1].degrees)
    np.testing.assert_array_equal(rest[-1].cc_labels, want[-1].cc_labels)
    np.testing.assert_array_equal(rest[-1].bipartite_odd,
                                  want[-1].bipartite_odd)


def test_checkpoint_never_covers_unyielded_windows(tmp_path):
    """At-least-once delivery under ANY crash point: for every prefix
    length K of consumed windows, the checkpoint on disk covers at
    most K windows, and a resumed re-feed emits exactly the
    uninterrupted run's suffix from the checkpoint on — computed
    windows are re-emitted, never dropped (the batched path used to
    checkpoint ahead of emission; found by tools/endurance_run.py)."""
    rng = np.random.default_rng(7)
    n = 1600
    src = rng.integers(0, 60, n)
    dst = rng.integers(0, 60, n)
    ts = np.sort(rng.integers(0, 4000, n))
    p = tmp_path / "s.txt"
    p.write_text("".join(f"{s} {d} {t}\n" for s, d, t in
                         zip(src, dst, ts)))
    want = StreamingAnalyticsDriver(window_ms=250).run_file(str(p))

    for crash_after in (1, 3, 6, len(want) - 1):
        ck = str(tmp_path / f"c{crash_after}.ckpt")
        a = StreamingAnalyticsDriver(window_ms=250)
        a.enable_auto_checkpoint(ck, every_n_windows=2)
        seen = 0
        # big chunk_bytes: the whole file is ONE batch, the shape that
        # used to checkpoint far ahead of what was yielded
        for res in a.stream_file(str(p), chunk_bytes=1 << 20):
            seen += 1
            if seen > crash_after:
                break
        b = StreamingAnalyticsDriver(window_ms=250)
        if not b.try_resume(ck):
            continue  # crashed before the first flush: fresh start
        done = b.windows_done
        assert done <= seen, (crash_after, done, seen)
        rest = list(b.stream_file(str(p), chunk_bytes=1 << 20,
                                  resume=True))
        assert [r.window_start for r in rest] == \
               [r.window_start for r in want[done:]]
        assert [r.triangles for r in rest] == \
               [r.triangles for r in want[done:]]
        np.testing.assert_array_equal(rest[-1].degrees,
                                      want[-1].degrees)


def test_sharded_bucket_growth_carries_engine_state():
    """Vertex-bucket growth AFTER the sharded engine exists must carry
    degree/label/bipartite state into the wider bucket (regression:
    read-only state_dict views + remap correctness)."""
    drv = StreamingAnalyticsDriver(window_ms=0, mesh=make_mesh(),
                                   vertex_bucket=8, edge_bucket=16)
    # window 1: a full 16-edge bucket over vertices 0..7 only, so the
    # engine is built at vb=8 before any growth
    s1 = np.tile(np.arange(4), 4)
    d1 = s1 + 4                                              # nv = 8
    # window 2: new vertices force growth with live engine state
    s2, d2 = np.arange(16), np.arange(16) + 16               # nv = 32
    drv.run_arrays(s1, d1)
    out = drv.run_arrays(s2, d2)
    single = StreamingAnalyticsDriver(window_ms=0, vertex_bucket=8,
                                      edge_bucket=16)
    single.run_arrays(s1, d1)
    want = single.run_arrays(s2, d2)
    np.testing.assert_array_equal(out[-1].degrees[:32],
                                  want[-1].degrees[:32])
    np.testing.assert_array_equal(out[-1].bipartite_odd[:32],
                                  want[-1].bipartite_odd[:32])
    assert out[-1].triangles == want[-1].triangles


@TIERS
def test_driver_count_based_partial_window_guard(tier):
    # ADVICE r1: a chunked count-based feed whose chunk is not an
    # edge_bucket multiple closes a short window and would silently
    # shift every later boundary — the driver must refuse more input
    drv = StreamingAnalyticsDriver(window_ms=0, edge_bucket=8,
                                   analytics=("degrees",), snapshot_tier=tier)
    src = np.arange(12) % 5
    drv.run_arrays(src, (src + 1) % 5)  # closes an 8 + partial-4 window
    with pytest.raises(ValueError, match="partial window"):
        drv.run_arrays(src[:8], src[:8])
    drv.reset()
    drv.run_arrays(src[:8], (src[:8] + 1) % 5)  # multiples stay fine
    drv.run_arrays(src[:8], (src[:8] + 1) % 5)


@TIERS
def test_partial_window_flag_not_persisted_before_final_window(tmp_path, tier):
    """A mid-call checkpoint taken BEFORE the call's short final window
    must not record closed_partial: a crash between that checkpoint and
    the short window would otherwise leave a state that refuses an
    exact replay of the remaining edges (code-review r2 finding)."""
    ckpt = str(tmp_path / "ck.npz")
    drv = StreamingAnalyticsDriver(window_ms=0, edge_bucket=8,
                                   analytics=("degrees",), snapshot_tier=tier)
    drv.enable_auto_checkpoint(ckpt, every_n_windows=1)
    src = np.arange(20) % 5  # 2 full windows + partial 4-edge window
    drv.run_arrays(src, (src + 1) % 5)
    assert drv._closed_partial  # live driver did close the short window

    # "crash" after window 2's checkpoint: simulate by resuming a
    # checkpoint cut at windows_done=2 (the every-window cadence means
    # the final checkpoint has 3 windows; rebuild the 2-window one)
    fresh = StreamingAnalyticsDriver(window_ms=0, edge_bucket=8,
                                     analytics=("degrees",),
                                     snapshot_tier=tier)
    fresh.enable_auto_checkpoint(ckpt, every_n_windows=1)
    fresh.run_arrays(src[:16], (src[:16] + 1) % 5)  # exactly 2 windows
    resumed = StreamingAnalyticsDriver(window_ms=0, edge_bucket=8,
                                       analytics=("degrees",),
                                       snapshot_tier=tier)
    assert resumed.try_resume(ckpt)
    assert not resumed._closed_partial
    # replaying the remaining edges must succeed and close the stream
    out = resumed.run_arrays(src[16:], (src[16:] + 1) % 5)
    assert len(out) == 1 and out[-1].num_edges == 4


@TIERS
def test_driver_reset_gives_clean_rerun(tier):
    drv = StreamingAnalyticsDriver(window_ms=0, edge_bucket=8,
                                   analytics=("degrees", "cc"),
                                   snapshot_tier=tier)
    src = np.arange(16) % 7
    dst = (src + 2) % 7
    first = drv.run_arrays(src, dst)
    drv.reset()
    assert drv.windows_done == 0 and drv.edges_done == 0
    again = drv.run_arrays(src, dst)
    np.testing.assert_array_equal(first[-1].degrees, again[-1].degrees)
    np.testing.assert_array_equal(first[-1].cc_labels, again[-1].cc_labels)


@TIERS
def test_driver_checkpoint_carries_vertex_bucket(tmp_path, tier):
    # ADVICE r1: resume must adopt the checkpointed vertex bucket up
    # front instead of dying deep in the engine with a mismatch error
    p = str(tmp_path / "ck.npz")
    a = StreamingAnalyticsDriver(window_ms=0, vertex_bucket=16,
                                 edge_bucket=8, analytics=("degrees",),
                                 snapshot_tier=tier)
    src = np.arange(64) % 40  # grows the vertex bucket past 16
    a.run_arrays(src, (src + 3) % 40)
    import gelly_streaming_tpu.utils.checkpoint as ckpt
    ckpt.save(p, a.state_dict())
    b = StreamingAnalyticsDriver(window_ms=0, vertex_bucket=1 << 12,
                                 edge_bucket=8, analytics=("degrees",),
                                 snapshot_tier=tier)
    assert b.try_resume(p)
    # single-chip keeps the LARGER pre-sized constructor bucket (so a
    # caller who pre-sized to avoid bucket-doubling recompiles doesn't
    # get them back after resume); a smaller constructor adopts the
    # checkpoint's grown bucket (code-review r2 finding)
    assert b.vb == 1 << 12
    c = StreamingAnalyticsDriver(window_ms=0, vertex_bucket=16,
                                 edge_bucket=8, analytics=("degrees",),
                                 snapshot_tier=tier)
    assert c.try_resume(p)
    assert c.vb == a.vb
    ra = a.run_arrays(src[:8], (src[:8] + 3) % 40)
    rb = b.run_arrays(src[:8], (src[:8] + 3) % 40)
    rc = c.run_arrays(src[:8], (src[:8] + 3) % 40)
    np.testing.assert_array_equal(ra[-1].degrees, rb[-1].degrees)
    np.testing.assert_array_equal(ra[-1].degrees, rc[-1].degrees)


@MESH_AND_TIERS
def test_batched_scan_path_matches_per_window_path(sharded, tier):
    """The batched snapshot-scan fast path (one dispatch per call,
    single-chip jit or shard_map over the mesh) must produce
    bit-identical per-window snapshots to the per-window path
    (one-window calls), including mid-call vertex growth, for both
    count-based and event-time windows."""
    mesh = make_mesh() if sharded else None
    rng = np.random.default_rng(17)
    n, eb = 1024, 128
    # growing vertex domain forces bucket doubling inside the call
    src = np.concatenate([rng.integers(0, 40, n // 2),
                          rng.integers(0, 900, n // 2)])
    dst = np.concatenate([rng.integers(0, 40, n // 2),
                          rng.integers(0, 900, n // 2)])
    ts = (np.arange(n) // eb) * 1000  # event-time: eb edges per window

    for mode in ("count", "event"):
        a = StreamingAnalyticsDriver(window_ms=1000, edge_bucket=eb,
                                     vertex_bucket=16, mesh=mesh,
                                     snapshot_tier=tier)
        b = StreamingAnalyticsDriver(window_ms=1000, edge_bucket=eb,
                                     vertex_bucket=16, mesh=mesh,
                                     snapshot_tier=tier)
        if mode == "count":
            batched = a.run_arrays(src, dst)
            single = []
            for i in range(0, n, eb):
                single += b.run_arrays(src[i:i + eb], dst[i:i + eb])
        else:
            batched = a.run_arrays(src, dst, ts)
            single = []
            for i in range(0, n, eb):
                single += b.run_arrays(src[i:i + eb], dst[i:i + eb],
                                       ts[i:i + eb])
        assert len(batched) == len(single) == n // eb
        for x, y in zip(batched, single):
            assert x.window_start == y.window_start
            assert x.num_edges == y.num_edges
            np.testing.assert_array_equal(x.vertex_ids, y.vertex_ids)
            np.testing.assert_array_equal(x.degrees, y.degrees)
            np.testing.assert_array_equal(x.cc_labels, y.cc_labels)
            np.testing.assert_array_equal(x.bipartite_odd,
                                          y.bipartite_odd)
            assert x.triangles == y.triangles
        # carried mirrors end identical: further feeding agrees too
        extra_s = rng.integers(0, 900, eb)
        extra_d = rng.integers(0, 900, eb)
        ra = a.run_arrays(extra_s, extra_d)[-1]
        rb = b.run_arrays(extra_s, extra_d)[-1]
        np.testing.assert_array_equal(ra.degrees, rb.degrees)
        np.testing.assert_array_equal(ra.cc_labels, rb.cc_labels)
        np.testing.assert_array_equal(ra.bipartite_odd, rb.bipartite_odd)


def test_stream_file_multi_crash_resume_fuzz(tmp_path):
    """Repeated random crashes + resumes over one event-time file must
    end in EXACTLY the uninterrupted run's carried state, regardless of
    chunk sizes, checkpoint cadences, and kill points (the reference
    delegates this whole axis to Flink; SURVEY.md §5.3-5.4)."""
    for seed in (5, 17):
        rng = np.random.default_rng(seed)
        n = 1200
        src = rng.integers(0, 120, n)
        dst = rng.integers(0, 120, n)
        ts = np.sort(rng.integers(0, 4000, n))
        p = tmp_path / f"fuzz{seed}.txt"
        p.write_text("".join(f"{s} {d} {t}\n"
                             for s, d, t in zip(src, dst, ts)))
        ck = str(tmp_path / f"fuzz{seed}.ckpt")

        ref = StreamingAnalyticsDriver(window_ms=400)
        ref.run_file(str(p))
        want = ref.state_dict()

        first = True
        for attempt in range(50):
            d = StreamingAnalyticsDriver(window_ms=400)
            resumed = (not first) and d.try_resume(ck)
            d.enable_auto_checkpoint(
                ck, every_n_windows=int(rng.integers(1, 4)))
            kill_after = int(rng.integers(1, 5))
            finished = True
            for i, _res in enumerate(d.stream_file(
                    str(p), chunk_bytes=int(rng.integers(256, 4096)),
                    resume=resumed)):
                if i + 1 >= kill_after and rng.random() < 0.6:
                    finished = False
                    break
            first = False
            if finished:
                break
        assert finished, "fuzz never completed the stream in 50 attempts"

        got = d.state_dict()
        assert got["windows_done"] == want["windows_done"]
        assert got["edges_done"] == want["edges_done"]
        for key in ("vertex_ids", "degrees", "cc", "bip"):
            np.testing.assert_array_equal(got[key], want[key], err_msg=key)


@TIERS
def test_try_resume_corrupt_checkpoint_starts_fresh(tmp_path, tier):
    """A truncated/corrupt checkpoint file (external damage — save()
    itself is atomic) must behave like a missing one: warn, return
    False, full reprocess stays correct. Semantic mismatches (e.g.
    cross-mode) still raise — covered by
    test_driver_cross_mode_checkpoint_refused."""
    import warnings

    from gelly_streaming_tpu.utils import checkpoint

    d = StreamingAnalyticsDriver(window_ms=100, snapshot_tier=tier)
    d.run_arrays(np.array([1, 2, 3]), np.array([2, 3, 4]))
    ck = str(tmp_path / "c.ckpt")
    checkpoint.save(ck, d.state_dict())
    raw = open(ck, "rb").read()
    open(ck, "wb").write(raw[:len(raw) // 2])

    e = StreamingAnalyticsDriver(window_ms=100, snapshot_tier=tier)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert e.try_resume(ck) is False
    assert any("corrupt" in str(w.message) for w in caught)
    assert e.windows_done == 0  # clean fresh state

    # bit-flip INSIDE the compressed payload (valid zip structure,
    # mangled deflate stream -> zlib.error, a different failure shape
    # than truncation's BadZipFile)
    ck2 = str(tmp_path / "c2.ckpt")
    checkpoint.save(ck2, d.state_dict())
    raw2 = bytearray(open(ck2, "rb").read())
    mid = len(raw2) // 2
    raw2[mid] ^= 0xFF
    raw2[mid + 1] ^= 0xFF
    open(ck2, "wb").write(bytes(raw2))
    f = StreamingAnalyticsDriver(window_ms=100, snapshot_tier=tier)
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        assert f.try_resume(ck2) is False


@TIERS
def test_stream_file_tolerates_malformed_lines(tmp_path, tier):
    """The ingest parser drops malformed lines (native and Python
    fallbacks agree — tests/test_native.py pins that); the driver sees
    only the valid records, and an all-garbage file behaves like an
    empty one."""
    g = tmp_path / "garbage.txt"
    g.write_text("hello world\nfoo bar baz\n# comment\n")
    d = StreamingAnalyticsDriver(window_ms=100, snapshot_tier=tier)
    assert list(d.stream_file(str(g))) == []
    assert d.windows_done == 0

    m = tmp_path / "mixed.txt"
    m.write_text("x\n1 2 100\nbad line\n3 4 200\n")
    e = StreamingAnalyticsDriver(window_ms=100, snapshot_tier=tier)
    res = list(e.stream_file(str(m)))
    assert [(r.window_start, int(r.degrees.sum())) for r in res] == \
        [(100, 2), (200, 4)]


# ----------------------------------------------------------------------
# the snapshot scan's counters, read back with the snapshots
# ----------------------------------------------------------------------
def _np_fixpoint_rounds(labels, s, d):
    """The carried fixpoint in numpy: cc_round (the window's edges plus
    the forest's parent links; both endpoints and both roots take the
    smaller label) then pointer jumping, until a round changes
    nothing. Returns (labels, rounds)."""
    src = np.concatenate([s, np.arange(len(labels))])
    dst = np.concatenate([d, labels])
    rounds = 0
    while True:
        ls, ld = labels[src], labels[dst]
        m = np.minimum(ls, ld)
        new = labels.copy()
        for idx in (src, dst, ls, ld):
            np.minimum.at(new, idx, m)
        new = new[new]
        rounds += 1
        if np.array_equal(new, labels):
            return new, rounds
        labels = new


def _np_fold_rooted_rounds(labels, s, d):
    """The scan's fold in numpy (ops/unionfind.cc_fold_rooted): the
    window's edges contracted to their roots, cc_round over them, then
    pointer jumping over the touched roots, until a round changes none
    of them; one flatten after. Returns (labels, rounds)."""
    rs, rd = labels[s], labels[d]
    touched = np.concatenate([rs, rd])
    lab = labels.copy()
    rounds = 0
    while True:
        before = lab[touched]
        ls, ld = lab[rs], lab[rd]
        m = np.minimum(ls, ld)
        for idx in (rs, rd, ls, ld):
            np.minimum.at(lab, idx, m)
        lab[touched] = lab[lab[touched]]
        rounds += 1
        if np.array_equal(lab[touched], before):
            return lab[labels], rounds


def _counters(name):
    from gelly_streaming_tpu.utils import telemetry

    return [(r["value"], r["a"]["windows"]) for r in telemetry.records()
            if r["t"] == "counter" and r["name"] == name]


@pytest.fixture
def recorder(monkeypatch):
    from gelly_streaming_tpu.utils import telemetry

    monkeypatch.setenv("GS_TELEMETRY", "1")
    monkeypatch.delenv("GS_TRACE_DIR", raising=False)
    monkeypatch.setenv("GS_AUTOTUNE", "0")
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.mark.parametrize("tier,egress", [
    ("scan", "full"), ("scan", "delta"), ("resident", "full")])
def test_scan_fixpoint_rounds_match_numpy(recorder, tier, egress):
    eb, calls, per_call = 16, 3, 8
    rng = np.random.default_rng(11)
    src = rng.integers(0, 40, eb * per_call * calls)
    dst = rng.integers(0, 40, eb * per_call * calls)
    drv = StreamingAnalyticsDriver(
        window_ms=0, analytics=("cc", "bipartite"), vertex_bucket=64,
        edge_bucket=eb, snapshot_tier=tier, egress=egress)
    out = []
    for k in range(calls):
        part = slice(k * eb * per_call, (k + 1) * eb * per_call)
        out += drv.run_arrays(src[part], dst[part])
    vb = drv.vb
    slot = {int(x): i for i, x in enumerate(out[-1].vertex_ids)}
    lab = np.arange(vb + 1)
    cov = np.arange(2 * vb + 1)
    want_cc, want_cover = [], []
    for w in range(len(out)):
        s = np.array([slot[int(x)] for x in src[w * eb:(w + 1) * eb]])
        d = np.array([slot[int(x)] for x in dst[w * eb:(w + 1) * eb]])
        s2, d2 = np.concatenate([s, s + vb]), np.concatenate([d + vb, d])
        # rounds: the fold through the roots, never more than the
        # carried fixpoint's; labels: both give the same, bit for bit
        folded, n = _np_fold_rooted_rounds(lab, s, d)
        lab, n_old = _np_fixpoint_rounds(lab, s, d)
        np.testing.assert_array_equal(folded, lab)
        assert n <= n_old
        want_cc.append(n)
        folded, n = _np_fold_rooted_rounds(cov, s2, d2)
        cov, n_old = _np_fixpoint_rounds(cov, s2, d2)
        np.testing.assert_array_equal(folded, cov)
        assert n <= n_old
        want_cover.append(n)
        # the numpy loop is the device's fixpoint: same labels
        nv = len(out[w].vertex_ids)
        np.testing.assert_array_equal(out[w].cc_labels, lab[:nv])
        np.testing.assert_array_equal(out[w].bipartite_odd,
                                      (cov[:vb] == cov[vb:2 * vb])[:nv])
    for name, want in (("driver.cc_rounds", want_cc),
                       ("driver.cover_rounds", want_cover)):
        got, at = _counters(name), 0
        assert sum(w for _v, w in got) == len(out)
        for value, windows in got:
            assert value == sum(want[at:at + windows]), name
            at += windows
    assert max(want_cc) > 1 and max(want_cover) > 1


def test_window_that_changes_nothing_takes_one_round(recorder):
    drv = StreamingAnalyticsDriver(
        window_ms=0, analytics=("degrees", "cc", "bipartite"),
        vertex_bucket=64, edge_bucket=8, snapshot_tier="scan",
        egress="full")
    # two windows a call: one window alone takes the per-window path
    s = np.tile([1, 2, 3, 4, 5, 6, 7, 1], 2)
    d = np.tile([2, 3, 4, 5, 6, 7, 1, 3], 2)
    drv.run_arrays(s, d)
    from gelly_streaming_tpu.utils import telemetry

    telemetry.reset()
    drv.run_arrays(s, d)   # every edge already folded
    assert _counters("driver.cc_rounds") == [(2, 2)]
    assert _counters("driver.cover_rounds") == [(2, 2)]


def test_readback_bytes_follow_the_output_shapes(recorder):
    vb, eb = 64, 16
    rng = np.random.default_rng(5)
    src, dst = rng.integers(0, 40, 8 * eb), rng.integers(0, 40, 8 * eb)
    drv = StreamingAnalyticsDriver(
        window_ms=0, analytics=("degrees", "cc", "bipartite"),
        vertex_bucket=vb, edge_bucket=eb, snapshot_tier="scan",
        egress="full")
    drv.run_arrays(src, dst)
    # a window: degrees and labels [vb+1] int32, the odd flag [vb]
    # bool, two int32 rounds and two int32 counts of relabelled roots;
    # a chunk: the final cover [2vb+1] int32, once
    per_window = 4 * 2 * (drv.vb + 1) + drv.vb + 16
    assert _counters("driver.readback_bytes") == [
        (8 * per_window + 4 * (2 * drv.vb + 1), 8)]
    assert _counters("driver.readback_sentinel_rows") == [(0, 8)]


@pytest.mark.parametrize("tier,windows", [
    ("scan", 4), ("scan", 7), ("resident", 4), ("mesh", 4)])
def test_readback_leaves_sentinel_rows_on_the_device(recorder, tier,
                                                     windows):
    """A call of fewer windows than the smallest W-bucket (8 rows)
    reads back its real rows only, on one chip, the resident tier and
    the mesh (whose tables carry one more sentinel slot), and counts
    the rows it left on the device; its windows are the host twin's."""
    eb = 16
    rng = np.random.default_rng(windows)
    src = rng.integers(0, 40, windows * eb)
    dst = rng.integers(0, 40, windows * eb)
    kw = ({"mesh": make_mesh(4)} if tier == "mesh"
          else {"snapshot_tier": tier, "egress": "full"})
    drv = StreamingAnalyticsDriver(
        window_ms=0, analytics=("degrees", "cc", "bipartite"),
        vertex_bucket=64, edge_bucket=eb, **kw)
    got = drv.run_arrays(src, dst)
    pad = 2 if tier == "mesh" else 1
    vb = drv.vb
    per_window = 4 * 2 * (vb + pad) + vb + 16
    assert _counters("driver.readback_bytes") == [
        (windows * per_window + 4 * (2 * vb + pad), windows)]
    assert _counters("driver.readback_sentinel_rows") == [
        (8 - windows, windows)]
    twin = StreamingAnalyticsDriver(
        window_ms=0, analytics=("degrees", "cc", "bipartite"),
        vertex_bucket=64, edge_bucket=eb, snapshot_tier="host")
    want = twin.run_arrays(src, dst)
    assert len(got) == len(want) == windows
    for g, w in zip(got, want):
        for field in ("degrees", "cc_labels", "bipartite_odd"):
            np.testing.assert_array_equal(getattr(g, field),
                                          getattr(w, field))
    np.testing.assert_array_equal(drv._bip, twin._bip)


def test_multi_chunk_call_with_padded_tail_matches_host_tier(
        recorder, monkeypatch):
    """A call of 8 + 8 + 5 windows, the last chunk padded to its 8-row
    W-bucket, on the scan tier and on the host twin: every window's
    degrees, labels and odd flags are equal, with their dtypes and
    result digests; every array is read-only; and the mirrors after
    the call (degrees, labels, the cover from the last chunk's final
    carry) are the twin's."""
    from gelly_streaming_tpu.utils import provenance

    monkeypatch.setattr(StreamingAnalyticsDriver, "_SCAN_CHUNK", 8)
    eb, windows = 16, 21
    rng = np.random.default_rng(29)
    src = rng.integers(0, 120, windows * eb)
    dst = rng.integers(0, 120, windows * eb)
    out = {}
    drivers = {}
    for tier in ("scan", "host"):
        drv = StreamingAnalyticsDriver(
            window_ms=0, analytics=("degrees", "cc", "bipartite"),
            vertex_bucket=64, edge_bucket=eb, snapshot_tier=tier,
            egress="full")
        out[tier] = drv.run_arrays(src, dst)
        drivers[tier] = drv
    assert _counters("driver.readback_sentinel_rows") == [
        (0, 8), (0, 8), (3, 5)]
    assert len(out["scan"]) == len(out["host"]) == windows
    for got, want in zip(out["scan"], out["host"]):
        for field in ("vertex_ids", "degrees", "cc_labels",
                      "bipartite_odd"):
            a, b = getattr(got, field), getattr(want, field)
            assert a.dtype == b.dtype, field
            np.testing.assert_array_equal(a, b)
            assert not a.flags.writeable and not b.flags.writeable
        assert got.degrees.dtype == np.int64
        assert got.bipartite_odd.dtype == bool
        assert provenance.result_digest(got) == \
            provenance.result_digest(want)
    scan, host = drivers["scan"], drivers["host"]
    for mirror in ("_degrees", "_cc", "_bip"):
        np.testing.assert_array_equal(getattr(scan, mirror),
                                      getattr(host, mirror))
        assert getattr(scan, mirror).dtype == getattr(host, mirror).dtype


def _np_relabels(table, s, d, seen_slot):
    """A fold's relabel counts in numpy: (distinct touched roots that
    move and have members, the touched entries of such roots)."""
    new, _n = _np_fixpoint_rounds(table, s, d)
    touched = np.concatenate([table[s], table[d]])
    keep = (new[touched] != touched) & seen_slot(touched)
    return new, len(np.unique(touched[keep])), int(keep.sum())


@pytest.mark.parametrize("k_max", [unionfind.RELABEL_K_MAX, 3])
@pytest.mark.parametrize("layout", ["one_chip", "mesh"])
def test_relabel_counters_match_numpy(recorder, monkeypatch, layout, k_max):
    """`driver.relabel_roots` and `driver.relabel_gathers` are recorded
    once a chunk, on one chip and on the CPU mesh, and equal a numpy
    count of the moved roots with members (a root of degree > 0 before
    the window) over the chunk's windows; with the list cut to 3 the
    folds past it gather, and count their entries."""
    monkeypatch.setattr(unionfind, "RELABEL_K_MAX", k_max)
    eb, calls, per_call = 16, 3, 8
    rng = np.random.default_rng(17)
    src = rng.integers(0, 48, eb * per_call * calls)
    dst = rng.integers(0, 48, eb * per_call * calls)
    kw = {"mesh": make_mesh(4)} if layout == "mesh" else {
        "snapshot_tier": "scan", "egress": "full"}
    drv = StreamingAnalyticsDriver(
        window_ms=0, analytics=("degrees", "cc", "bipartite"),
        vertex_bucket=64, edge_bucket=eb, **kw)
    out = []
    for k in range(calls):
        part = slice(k * eb * per_call, (k + 1) * eb * per_call)
        out += drv.run_arrays(src[part], dst[part])
    vb = drv.vb
    slot = {int(x): i for i, x in enumerate(out[-1].vertex_ids)}
    deg = np.zeros(vb + 1, np.int64)
    lab, cov = np.arange(vb + 1), np.arange(2 * vb + 1)
    roots, gathers = [], []
    for w in range(len(out)):
        s = np.array([slot[int(x)] for x in src[w * eb:(w + 1) * eb]])
        d = np.array([slot[int(x)] for x in dst[w * eb:(w + 1) * eb]])
        s2, d2 = np.concatenate([s, s + vb]), np.concatenate([d + vb, d])
        lab, n_cc, raw_cc = _np_relabels(lab, s, d, lambda r: deg[r] > 0)
        cov, n_cov, raw_cov = _np_relabels(
            cov, s2, d2, lambda r: deg[np.where(r >= vb, r - vb, r)] > 0)
        roots.append(sum(raw if raw > k_max else n
                         for n, raw in ((n_cc, raw_cc), (n_cov, raw_cov))))
        gathers.append(int(raw_cc > k_max) + int(raw_cov > k_max))
        np.add.at(deg, s, 1)
        np.add.at(deg, d, 1)
        np.testing.assert_array_equal(out[w].cc_labels,
                                      lab[:len(out[w].vertex_ids)])
    assert sum(roots) > 0
    assert (sum(gathers) > 0) == (k_max == 3)
    chunks = [w for _v, w in _counters("driver.cc_rounds")]
    for name, want in (("driver.relabel_roots", roots),
                       ("driver.relabel_gathers", gathers)):
        got, at = _counters(name), 0
        assert [w for _v, w in got] == chunks and sum(chunks) == len(out)
        for value, windows in got:
            assert value == sum(want[at:at + windows]), name
            at += windows


# ----------------------------------------------------------------------
# the scan folds each window through the carry's roots
# ----------------------------------------------------------------------
def test_snapshot_scan_matches_host_snapshot_with_sentinel_windows():
    """The scan over a [W, eb] stack whose rows include all-invalid
    (sentinel) windows and a ragged one gives the host twin's
    snapshots window for window, the cover as its odd flag, from a
    carry the twin folded; its final carry is the twin's final cover
    (`cover_final`), its sentinel slots untouched."""
    import jax.numpy as jnp

    from gelly_streaming_tpu.core.driver import _build_snapshot_scan
    from gelly_streaming_tpu.ops import host_snapshot

    vb, eb, w = 64, 16, 8
    rng = np.random.default_rng(21)
    deg = np.zeros(vb, np.int32)
    lab = np.arange(vb, dtype=np.int32)
    cov = np.arange(2 * vb, dtype=np.int32)
    host_snapshot.snapshot_windows(   # folds the carry in place
        rng.integers(0, 50, 6 * eb), rng.integers(0, 50, 6 * eb),
        np.arange(0, 6 * eb + 1, eb), vb, deg, lab, cov)
    s_w = rng.integers(0, 60, (w, eb)).astype(np.int32)
    d_w = rng.integers(0, 60, (w, eb)).astype(np.int32)
    valid = np.ones((w, eb), bool)
    valid[[2, 5]] = False
    valid[7, eb // 2:] = False
    run = _build_snapshot_scan(vb, ("degrees", "cc", "bipartite"))
    carry = (jnp.asarray(np.append(deg, 0)), jnp.asarray(np.append(lab, vb)),
             jnp.asarray(np.append(cov, 2 * vb)))
    new_carry, outs = run(carry, jnp.asarray(s_w), jnp.asarray(d_w),
                          jnp.asarray(valid))
    offs = np.concatenate([[0], np.cumsum(valid.sum(1))])
    want = host_snapshot.snapshot_windows(s_w[valid], d_w[valid], offs, vb,
                                          deg, lab, cov)
    for key, n in (("deg", vb), ("labels", vb), ("odd", vb)):
        np.testing.assert_array_equal(np.asarray(outs[key])[:, :n],
                                      want[key])
    assert "cover" not in outs
    assert np.asarray(outs["odd"]).shape == (w, vb)
    assert np.asarray(outs["odd"]).dtype == want["odd"].dtype == bool
    np.testing.assert_array_equal(np.asarray(outs["labels"])[:, vb], vb)
    cover_final = np.asarray(new_carry[2])
    np.testing.assert_array_equal(cover_final[:2 * vb], want["cover_final"])
    assert cover_final[2 * vb] == 2 * vb
    for key in ("cc_rounds", "cover_rounds"):
        assert list(np.asarray(outs[key])[[2, 5]]) == [1, 1]


def _flat_min_rooted(lab):
    lab = np.asarray(lab)
    return bool(np.array_equal(lab[lab], lab)
                and np.all(lab <= np.arange(len(lab))))


@pytest.mark.parametrize("entry", [
    "fresh", "load_state_dict", "bucket_growth",
    "engine_state_from_mirrors"])
def test_scan_carries_enter_flat_and_min_rooted(monkeypatch, entry):
    """The scan's fold needs a flat, min-rooted carry: every way one
    enters the scan gives one — a fresh driver, a restored checkpoint,
    the cover re-laid over a grown vertex bucket, and the engine-layout
    state built from the host mirrors."""
    from gelly_streaming_tpu.core import driver as driver_mod

    carries = []
    real = driver_mod._build_snapshot_scan

    def spy(*args, **kwargs):
        fn = real(*args, **kwargs)

        def run(carry, *xs):
            carries.append([np.asarray(c) for c in carry])
            return fn(carry, *xs)
        return run

    monkeypatch.setattr(driver_mod, "_build_snapshot_scan", spy)
    monkeypatch.setenv("GS_AUTOTUNE", "0")

    def make():
        return StreamingAnalyticsDriver(
            window_ms=0, analytics=("degrees", "cc", "bipartite"),
            vertex_bucket=64, edge_bucket=16, snapshot_tier="scan",
            egress="full")

    rng = np.random.default_rng(7)
    half = 12 * 16
    late = 200 if entry == "bucket_growth" else 50
    src = np.concatenate([rng.integers(0, 50, half),
                          rng.integers(0, late, half)])
    dst = np.concatenate([rng.integers(0, 50, half),
                          rng.integers(0, late, half)])
    drv = make()
    drv.run_arrays(src[:half], dst[:half])
    if entry == "load_state_dict":
        drv2 = make()
        drv2.load_state_dict(drv.state_dict())
        drv = drv2
        carries.clear()
    if entry == "engine_state_from_mirrors":
        st = drv._engine_state_from_mirrors()
        assert _flat_min_rooted(st["labels"])
        assert _flat_min_rooted(st["bip_labels"])
    drv.run_arrays(src[half:], dst[half:])
    assert carries
    for _deg, lab, cov in carries:
        assert _flat_min_rooted(lab) and _flat_min_rooted(cov)
    if entry == "bucket_growth":
        assert drv.vb > 64
        assert {len(lab) for _d, lab, _c in carries} == {65, drv.vb + 1}


def _loop_ops_past(lowered, v):
    """The ops inside each while loop that scatters into a table (the
    fixpoint loops) that produce a tensor with a dimension of `v` or
    more — a scatter's in-place result aside — and the scatters whose
    updates have one. Returns (number of such loops, offenders)."""
    def dims(t):
        m = re.match(r"tensor<([0-9x]*)x?[a-z]", str(t))
        return [int(x) for x in m.group(1).split("x") if x] if m else []

    def walk(op):
        for region in op.regions:
            for block in region:
                for child in block:
                    yield child.operation
                    yield from walk(child.operation)

    module = lowered.compiler_ir("stablehlo")
    loops, bad = 0, []
    for loop in walk(module.operation):
        if loop.name != "stablehlo.while":
            continue
        body = list(walk(loop))
        if not any(o.name == "stablehlo.scatter" for o in body):
            continue
        loops += 1
        for o in body:
            if o.name == "stablehlo.scatter":
                shapes = [dims(o.operands[2].type)]
            else:
                shapes = [dims(r.type) for r in o.results]
            if any(d >= v for s in shapes for d in s):
                bad.append((o.name, shapes))
    return loops, bad


@pytest.mark.parametrize("program", ["driver_scan", "carried_fixpoint"])
def test_fold_loop_has_no_table_sized_ops(program):
    """Inside the scan's CC and double-cover fixpoint loops nothing is
    as large as the table: every scatter updates the window's touched
    slots, every gather and compare reads them. The carried fixpoint
    (forest links as edges, whole-table pointer jumping) is the
    control that the check finds such ops in."""
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.core.driver import _build_snapshot_scan

    vb, eb, w = 1000, 16, 2
    i32 = jnp.int32
    table = jax.ShapeDtypeStruct((vb + 1,), i32)
    edges = jax.ShapeDtypeStruct((eb,), i32)
    if program == "driver_scan":
        fn = _build_snapshot_scan(vb, ("degrees", "cc", "bipartite"))
        stack = jax.ShapeDtypeStruct((w, eb), i32)
        lowered = fn.lower(
            (table, table, jax.ShapeDtypeStruct((2 * vb + 1,), i32)),
            stack, stack, jax.ShapeDtypeStruct((w, eb), jnp.bool_))
        assert _loop_ops_past(lowered, vb) == (2, [])
        # compiled, the fold loop keeps the table in place: the only
        # copies in its body are of the touched slots' labels. The
        # relabel's loops after it (its passes over the table, the
        # search that packs its list) are other while loops: pick the
        # fold loop by its own op name
        compiled = jax.jit(unionfind.cc_fold_rooted).lower(
            table, edges, edges).compile().as_text()
        bodies = re.findall(r"body=%?([\w.\-]+), metadata=\{op_name="
                            r"\"jit\(cc_fold_rooted\)/([^\"]*)\"", compiled)
        assert len(bodies) == 3
        body = [b for b, name in bodies if name == "while"]
        assert len(body) == 1
        block = compiled.split("\n%" + body[0] + " ", 1)[1].split(
            "\n}", 1)[0]
        assert "scatter" in block
        assert re.search(r"s32\[%d\]\S* copy\(" % (vb + 1), block) is None
    else:
        fn = jax.jit(lambda l, s, d: unionfind.cc_fixpoint(l, s, d))
        loops, bad = _loop_ops_past(fn.lower(table, edges, edges), vb)
        assert loops == 1 and bad


# ----------------------------------------------------------------------
# the call's triangle stack goes on the device ahead of the last
# read-back (_triangles_ahead), and the flush reads it back
# ----------------------------------------------------------------------
def _tri_stream(windows, eb, seed, ragged=False):
    """A count-window stream of `windows` windows over ids < 12, dense
    enough to close triangles; with `ragged` the last window holds a
    third of the edges."""
    rng = np.random.default_rng(seed)
    n = windows * eb - (eb - eb // 3 if ragged else 0)
    return rng.integers(0, 12, n), rng.integers(0, 12, n)


def _end_flush_only(monkeypatch):
    """The driver as it was: every window counted at the flush."""
    monkeypatch.setattr(StreamingAnalyticsDriver, "_triangles_ahead",
                        lambda self, lead, interned: None)


@pytest.mark.parametrize("ragged", [False, True], ids=["full", "ragged"])
@pytest.mark.parametrize("chunk", [4, 8], ids=["wb4", "wb8"])
@pytest.mark.parametrize("layout", ["one_chip", "mesh"])
def test_triangles_ahead_equal_end_flush_and_count_windows(
        recorder, monkeypatch, layout, chunk, ragged):
    """Two calls of 8 windows, in chunks of 4 (two scan launches a
    call) or 8 (one), the second ending on a ragged window, on one
    chip and on the mesh: the counts of the stack dispatched ahead
    equal the end-of-call flush's, count_windows' called directly and
    the exact host count; every window of each call is counted
    ahead."""
    from gelly_streaming_tpu.parallel.sharded import (
        ShardedTriangleWindowKernel)

    monkeypatch.setattr(StreamingAnalyticsDriver, "_SCAN_CHUNK", chunk)
    eb, vb = 16, 64
    src, dst = _tri_stream(16, eb, seed=chunk, ragged=ragged)
    mesh = make_mesh(4) if layout == "mesh" else None

    def run():
        drv = StreamingAnalyticsDriver(window_ms=0, vertex_bucket=vb,
                                       edge_bucket=eb, mesh=mesh)
        return (drv.run_arrays(src[:8 * eb], dst[:8 * eb])
                + drv.run_arrays(src[8 * eb:], dst[8 * eb:]))

    ahead = [r.triangles for r in run()]
    assert _counters("driver.triangles_ahead") == [(8, 8), (8, 8)]
    with monkeypatch.context() as m:
        _end_flush_only(m)
        flushed = [r.triangles for r in run()]
    assert _counters("driver.triangles_ahead")[2:] == [(0, 8), (0, 8)]
    windows = [(src[i:i + eb], dst[i:i + eb])
               for i in range(0, len(src), eb)]
    kern = (ShardedTriangleWindowKernel(mesh, eb, vb) if mesh is not None
            else tri_ops.TriangleWindowKernel(eb, vb))
    exact = [tri_ops.triangle_count_sparse(s, d, vb) for s, d in windows]
    assert ahead == flushed == kern.count_windows(windows) == exact
    assert sum(exact) > 0
    assert len(windows[-1][0]) == (eb // 3 if ragged else eb)


@pytest.mark.parametrize("chunk", [4, 8], ids=["wb4", "wb8"])
@pytest.mark.parametrize("layout", ["one_chip", "mesh"])
def test_triangle_stack_goes_ahead_of_the_last_read_back(
        recorder, monkeypatch, layout, chunk):
    """A spy on the kernel and on the driver's steps: each call
    dispatches its one triangle stack after its last scan chunk and
    before the wait on that chunk's read-back, and reads the counts
    back after the last extraction, in the flush."""
    from gelly_streaming_tpu.parallel.sharded import (
        ShardedTriangleWindowKernel)

    monkeypatch.setattr(StreamingAnalyticsDriver, "_SCAN_CHUNK", chunk)
    kind = (ShardedTriangleWindowKernel if layout == "mesh"
            else tri_ops.TriangleWindowKernel)
    log = []
    real_step = StreamingAnalyticsDriver._step
    real_dispatch, real_collect = kind.dispatch_windows, kind.collect_windows

    def step(self, name, n):
        log.append(name)
        return real_step(self, name, n)

    def dispatch(self, windows):
        log.append("tri_dispatch:%d" % len(windows))
        return real_dispatch(self, windows)

    def collect(self, handle):
        log.append("tri_collect")
        return real_collect(self, handle)

    monkeypatch.setattr(StreamingAnalyticsDriver, "_step", step)
    monkeypatch.setattr(kind, "dispatch_windows", dispatch)
    monkeypatch.setattr(kind, "collect_windows", collect)
    eb = 16
    src, dst = _tri_stream(16, eb, seed=3)
    drv = StreamingAnalyticsDriver(
        window_ms=0, vertex_bucket=64, edge_bucket=eb,
        mesh=make_mesh(4) if layout == "mesh" else None)
    for k in range(2):
        del log[:]
        part = slice(8 * k * eb, 8 * (k + 1) * eb)
        drv.run_arrays(src[part], dst[part])
        calls = [e for e in log if e.startswith("tri_dispatch")]
        assert calls == ["tri_dispatch:8"]
        at = log.index(calls[0])
        last = lambda name: len(log) - 1 - log[::-1].index(name)
        assert log.count("snapshot_scan") == 8 // chunk
        # right behind the last scan chunk: no read-back wait between
        assert "snapshot_wait" not in log[last("snapshot_scan"):at]
        assert at < last("snapshot_wait")
        assert log.count("snapshot_wait") == 8 // chunk
        assert last("snapshot_extract") < log.index("triangles") \
            < log.index("tri_collect")
        assert log.count("tri_collect") == 1


def _per_window_calls(drv, src, dst, eb):
    out = []
    for i in range(0, len(src), eb):
        out += drv.run_arrays(src[i:i + eb], dst[i:i + eb])
    return out


@pytest.mark.parametrize("path", [
    "scan", "resident", "mesh", "per_window", "sliding", "no_triangles",
    "native", "host", "delta"])
def test_triangles_ahead_counter_by_path(recorder, monkeypatch, path):
    """`driver.triangles_ahead` counts every window of a call on the
    batched device-scan path (scan and resident tiers, the mesh), and
    0 where the call's windows are counted at the flush: single-window
    calls, sliding panes, the native and host tiers and the delta
    emit. With no triangles analytic no flush records it."""
    from gelly_streaming_tpu.utils import telemetry

    monkeypatch.setenv("GS_RESIDENT_SPB", "8")
    eb = 16
    src, dst = _tri_stream(8, eb, seed=5)
    kw = {"window_ms": 0, "vertex_bucket": 64, "edge_bucket": eb}
    if path in ("scan", "resident", "native", "host"):
        kw["snapshot_tier"] = path
    elif path == "mesh":
        kw["mesh"] = make_mesh(4)
    elif path == "sliding":
        kw["slide"] = eb // 2
    elif path == "no_triangles":
        kw["analytics"] = ("degrees", "cc", "bipartite")
    elif path == "delta":
        kw.update(snapshot_tier="scan", egress="delta")
    drv = StreamingAnalyticsDriver(**kw)
    if path == "per_window":
        out = _per_window_calls(drv, src, dst, eb)
    else:
        out = drv.run_arrays(src, dst)
    got = _counters("driver.triangles_ahead")
    if path == "no_triangles":
        assert got == [] and all(r.triangles is None for r in out)
        return
    assert all(r.triangles is not None for r in out)
    n = len(out)
    if path in ("scan", "resident", "mesh"):
        assert got == [(n, n)]
    elif path == "per_window":
        assert got == [(0, 1)] * n
    else:
        assert got == [(0, n)]
    assert sum(1 for r in telemetry.records()
               if r["t"] == "counter"
               and r["name"] == "driver.triangles_ahead") == len(got)
