"""Deterministic fault-injection suite (utils/faults + the resilient
runtime): stage watchdogs, bounded retry, error-path drain, tier
demotion, and checkpoint damage — every failure path exercised with a
fixed plan, no randomness, CPU-only.

Real sleeps are bounded by sub-second watchdog deadlines; the one
deliberately long (10 s) injected stall is never WAITED on — the
watchdog cuts it at its 1 s deadline (the acceptance shape: a hung h2d
surfaces as a typed StageTimeout naming the chunk within ~2× the
deadline) and the sleeping helper thread is abandoned as a daemon.
"""

import os
import time

import numpy as np
import pytest

from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver
from gelly_streaming_tpu.ops import ingress_pipeline as ip
from gelly_streaming_tpu.utils import checkpoint as ck
from gelly_streaming_tpu.utils import faults, resilience

pytestmark = pytest.mark.faults

_KNOBS = ("GS_STAGE_TIMEOUT_S", "GS_STAGE_RETRIES", "GS_STAGE_BACKOFF_S",
          "GS_TIER_RETRY_WINDOWS", "GS_TIER_DEMOTE")


@pytest.fixture(autouse=True)
def _clean_knobs():
    """Every test starts from inert knobs and leaves none behind; the
    pool is dropped afterwards so a worker a test deliberately hung
    never serves a later test."""
    saved = {k: os.environ.pop(k, None) for k in _KNOBS}
    os.environ["GS_STAGE_BACKOFF_S"] = "0.01"
    try:
        yield
    finally:
        for k in _KNOBS:
            os.environ.pop(k, None)
            if saved[k] is not None:
                os.environ[k] = saved[k]
        ip.reset_pool()


def _run(n_chunks=4, **kw):
    """Tiny run_pipeline harness: chunk i -> prep doubles, h2d +1,
    finalize collects. Returns the collected list."""
    out = []
    ip.run_pipeline(range(n_chunks), lambda i: i * 2, lambda p: p + 1,
                    lambda d: d, out.append, **kw)
    return out


# ----------------------------------------------------------------------
# watchdog + retry on the shared ingress pipeline
# ----------------------------------------------------------------------
def test_transient_prep_failure_retried():
    os.environ["GS_STAGE_RETRIES"] = "2"
    with faults.inject(faults.FaultSpec(site="prep", on_call=2)) as plan:
        assert _run() == [1, 3, 5, 7]
    assert ("prep", 2, "raise") in plan.fired


def test_transient_h2d_failure_retried_forced_sync():
    os.environ["GS_STAGE_RETRIES"] = "1"
    with ip.forced_sync():
        with faults.inject(faults.FaultSpec(site="h2d", on_call=3)):
            assert _run() == [1, 3, 5, 7]


def test_prep_failure_exhausts_retries_typed():
    os.environ["GS_STAGE_RETRIES"] = "1"
    with faults.inject(faults.FaultSpec(site="prep", on_call=2,
                                        times=99)):
        with pytest.raises(resilience.StageFailed) as ei:
            _run()
    err = ei.value
    assert err.stage == "prep" and err.chunk == 1
    assert len(err.attempts) == 2
    assert all(a["outcome"] == "PrepError" for a in err.attempts)


def test_hung_h2d_surfaces_typed_within_deadline():
    """The acceptance shape: a 10 s injected h2d stall under
    GS_STAGE_TIMEOUT_S=1 surfaces as StageTimeout NAMING the chunk
    within ~2× the deadline instead of blocking the stream for 10 s."""
    os.environ["GS_STAGE_TIMEOUT_S"] = "1"
    t0 = time.perf_counter()
    with faults.inject(faults.FaultSpec(site="h2d", on_call=2,
                                        action="hang", seconds=10.0)):
        with pytest.raises(resilience.StageTimeout) as ei:
            _run()
    elapsed = time.perf_counter() - t0
    assert elapsed < 2.0, elapsed
    assert ei.value.stage == "h2d" and ei.value.chunk == 1
    assert ei.value.attempts[0]["outcome"] == "timeout"
    assert "chunk 1" in str(ei.value)


def test_hung_h2d_forced_sync_also_enforced():
    os.environ["GS_STAGE_TIMEOUT_S"] = "0.15"
    t0 = time.perf_counter()
    with ip.forced_sync():
        with faults.inject(faults.FaultSpec(site="h2d", on_call=1,
                                            action="hang", seconds=2.0)):
            with pytest.raises(resilience.StageTimeout) as ei:
                _run()
    assert time.perf_counter() - t0 < 1.0
    assert ei.value.stage == "h2d" and ei.value.chunk == 0


def test_hang_then_timeout_then_retry_succeeds():
    """A once-hung h2d is cut by the deadline and the retry (a fresh
    dedicated thread, the pool worker stays abandoned) completes the
    stream with identical results."""
    os.environ["GS_STAGE_TIMEOUT_S"] = "0.15"
    os.environ["GS_STAGE_RETRIES"] = "1"
    with faults.inject(faults.FaultSpec(site="h2d", on_call=2,
                                        action="hang", seconds=0.6)):
        assert _run() == [1, 3, 5, 7]


def test_queued_chunk_behind_wedged_pool_still_times_out():
    """A task no worker ever picks up must count its QUEUE wait
    against the deadline: with the pool's only worker wedged on an
    abandoned hang, the next chunk would otherwise spin forever in
    the consumer's poll loop (review finding on _await_attempt)."""
    saved = os.environ.get("GS_PIPELINE_WORKERS")
    os.environ["GS_PIPELINE_WORKERS"] = "1"
    ip.reset_pool()
    os.environ["GS_STAGE_TIMEOUT_S"] = "0.2"
    os.environ["GS_STAGE_RETRIES"] = "1"
    try:
        t0 = time.perf_counter()
        with faults.inject(faults.FaultSpec(site="h2d", on_call=1,
                                            action="hang",
                                            seconds=1.2)):
            # chunk 0's pooled h2d wedges the lone worker; its retry
            # runs on a dedicated thread, and every later chunk's
            # pooled attempt times out of the QUEUE and retries the
            # same way — the stream completes, bounded by deadlines
            assert _run() == [1, 3, 5, 7]
        assert time.perf_counter() - t0 < 3.0
    finally:
        if saved is None:
            os.environ.pop("GS_PIPELINE_WORKERS", None)
        else:
            os.environ["GS_PIPELINE_WORKERS"] = saved
        ip.reset_pool()


def test_dispatch_failure_typed_and_not_retried():
    os.environ["GS_STAGE_RETRIES"] = "3"
    with faults.inject(faults.FaultSpec(site="dispatch", on_call=2,
                                        times=99)) as plan:
        with pytest.raises(resilience.StageFailed) as ei:
            _run()
    assert ei.value.stage == "dispatch"
    # dispatch folds into carried state in the real engines: exactly
    # one firing means exactly one attempt (never re-run)
    assert [f for f in plan.fired if f[0] == "dispatch"] \
        == [("dispatch", 2, "raise")]


def test_pipeline_drains_pending_on_failure():
    """Satellite: a mid-run failure no longer abandons the
    already-dispatched chunk — its finalize runs (best-effort) before
    the error surfaces, in both the pooled and sync forms."""
    for sync in (False, True):
        out = []
        ctx = ip.forced_sync() if sync else _null()
        with ctx:
            with faults.inject(faults.FaultSpec(site="prep", on_call=3)):
                with pytest.raises(ip.PrepError):
                    ip.run_pipeline(range(4), lambda i: i * 2,
                                    lambda p: p + 1, lambda d: d,
                                    out.append)
        # chunks 0 AND 1 finalized: 1 was in flight (dispatched, not
        # yet finalized) when chunk 2's prep died
        assert out == [1, 3], (sync, out)


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def test_fatal_fault_never_retried():
    """fatal=True is the chaos harness's simulated hard kill: it must
    pierce the retry budget and surface raw."""
    os.environ["GS_STAGE_RETRIES"] = "5"
    with faults.inject(faults.FaultSpec(site="finalize", on_call=1,
                                        fatal=True)) as plan:
        with pytest.raises(faults.InjectedFault):
            _run()
    assert [f for f in plan.fired if f[0] == "finalize"] \
        == [("finalize", 1, "raise")]


def test_stage_timers_reset_locked():
    """Satellite: reset() takes the accumulator lock (a concurrent
    add() can no longer interleave a partial erase)."""
    t = ip.StageTimers()
    t.add("prep", 0.5)
    t.reset()
    assert t.prep_ms == 0.0 and t.chunks == 0
    # the lock object is shared by add/reset — a reset inside an add's
    # critical section is impossible by construction
    assert t._lock is not None


# ----------------------------------------------------------------------
# parse-corruption robustness
# ----------------------------------------------------------------------
def test_corrupt_edge_line_dropped_without_misalignment(tmp_path):
    from gelly_streaming_tpu.io.sources import iter_edge_chunks

    p = tmp_path / "edges.txt"
    lines = [f"{i} {i + 1}" for i in range(100)]
    p.write_text("\n".join(lines) + "\n")
    with faults.inject(faults.FaultSpec(site="parse",
                                        action="corrupt_bytes")):
        chunks = list(iter_edge_chunks(str(p), chunk_bytes=1 << 20,
                                       prefetch=0))
    src = np.concatenate([c[0] for c in chunks])
    dst = np.concatenate([c[1] for c in chunks])
    # the torn first line is DROPPED, never misread: remaining pairs
    # stay aligned
    assert len(src) == 99
    assert src[0] == 1 and dst[0] == 2
    assert np.array_equal(dst, src + 1)


# ----------------------------------------------------------------------
# driver: demotion ladder + checkpoint damage
# ----------------------------------------------------------------------
def _stream(n=4096, v=512, seed=5):
    rng = np.random.default_rng(seed)
    return rng.integers(0, v, size=n), rng.integers(0, v, size=n)


def _snap_key(results):
    return [(r.window_start, r.num_edges,
             None if r.degrees is None else r.degrees.tolist(),
             None if r.cc_labels is None else r.cc_labels.tolist(),
             None if r.bipartite_odd is None
             else r.bipartite_odd.tolist(),
             None if r.delta_cc is None
             else [a.tolist() for a in r.delta_cc],
             None if r.delta_degrees is None
             else [a.tolist() for a in r.delta_degrees],
             None if r.delta_bipartite is None
             else [a.tolist() for a in r.delta_bipartite])
            for r in results]


def _driver(**kw):
    kw.setdefault("analytics", ("degrees", "cc", "bipartite"))
    return StreamingAnalyticsDriver(window_ms=0, edge_bucket=512,
                                    vertex_bucket=1024,
                                    emit_deltas=True, **kw)


def test_mid_stream_demotion_preserves_state_bit_exactly():
    """Acceptance: a persistent device failure mid-stream demotes
    scan→native, carrying degrees/cc/bipartite (and the delta streams)
    bit-exactly, and the demotion lands in the tracing layer and the
    process registry."""
    src, dst = _stream()
    ref = _driver(snapshot_tier="scan")
    want = _snap_key(ref.run_arrays(src, dst))

    resilience.reset_demotions()
    drv = _driver(snapshot_tier="scan", tracing=True)
    half = len(src) // 2
    got = drv.run_arrays(src[:half], dst[:half])
    with faults.inject(faults.FaultSpec(site="dispatch", on_call=1)):
        got += drv.run_arrays(src[half:], dst[half:])
    assert _snap_key(got) == want
    (event,) = drv.demotion_log()
    assert event["from"] == "scan" and event["to"] == "native"
    assert any(e["event"] == "tier_demotion"
               for e in drv.timer.event_log())
    assert any(e["to"] == "native"
               for e in resilience.demotion_events())


def test_demotion_ladder_falls_through_to_host():
    """Two persistent failures walk the whole ladder: the host-numpy
    tier finishes the stream with identical counts."""
    src, dst = _stream()
    want = _snap_key(_driver(snapshot_tier="scan").run_arrays(src, dst))
    drv = _driver(snapshot_tier="scan")
    # the fold sites of scan AND native both fail once: scan→native,
    # native→host, host completes
    with faults.inject(faults.FaultSpec(site="dispatch", on_call=1,
                                        times=2)):
        got = drv.run_arrays(src, dst)
    assert _snap_key(got) == want
    tiers = [(e["from"], e["to"]) for e in drv.demotion_log()]
    assert tiers == [("scan", "native"), ("native", "host")]


def test_demotion_disabled_raises_typed():
    os.environ["GS_TIER_DEMOTE"] = "0"
    src, dst = _stream()
    drv = _driver(snapshot_tier="scan")
    with faults.inject(faults.FaultSpec(site="dispatch", on_call=1)):
        with pytest.raises(resilience.StageFailed):
            drv.run_arrays(src, dst)


def test_semantic_errors_never_demote():
    """A programming bug (non-runtime error) must surface, not be
    'cured' by silently falling off the fast tier."""
    src, dst = _stream()
    drv = _driver(snapshot_tier="scan")
    with faults.inject(faults.FaultSpec(site="dispatch", on_call=1,
                                        exc=TypeError)):
        with pytest.raises(resilience.StageFailed) as ei:
            drv.run_arrays(src, dst)
    assert isinstance(ei.value.__cause__, TypeError)
    assert drv.demotion_log() == []


def test_probation_repromotion():
    os.environ["GS_TIER_RETRY_WINDOWS"] = "4"
    src, dst = _stream()
    want = _snap_key(_driver(snapshot_tier="scan").run_arrays(src, dst))
    drv = _driver(snapshot_tier="scan")
    with faults.inject(faults.FaultSpec(site="dispatch", on_call=1)):
        got = drv.run_arrays(src, dst)  # demotes at window 0
    assert _snap_key(got) == want
    assert drv._demoted_tier == "native"
    # probation served during those 8 windows: the next call probes
    # the scan tier again and stays there
    got2 = drv.run_arrays(src, dst)
    assert drv._demoted_tier is None
    events = [e for e in drv.demotion_log()]
    assert events[-1]["to"] == "scan"  # the re-promotion probe


def test_retry_cures_transient_device_failure_without_demotion():
    os.environ["GS_STAGE_RETRIES"] = "1"
    src, dst = _stream()
    want = _snap_key(_driver(snapshot_tier="scan").run_arrays(src, dst))
    drv = _driver(snapshot_tier="scan")
    with faults.inject(faults.FaultSpec(site="dispatch", on_call=1)):
        got = drv.run_arrays(src, dst)
    assert _snap_key(got) == want
    assert drv.demotion_log() == []  # the retry absorbed it


def test_driver_prefetch_prep_failure_retried():
    """A transient prep failure in the snapshot-scan PREFETCH worker
    gets the guard's retry budget like every other prep consumer
    (review finding: only _FutureTimeout was caught)."""
    os.environ["GS_STAGE_RETRIES"] = "1"
    rng = np.random.default_rng(7)
    w, eb = 66, 128  # two scan chunks (64 + 2): chunk 2 is prefetched
    src = rng.integers(0, 300, size=w * eb)
    dst = rng.integers(0, 300, size=w * eb)

    def run(plan_specs):
        drv = StreamingAnalyticsDriver(
            window_ms=0, edge_bucket=eb, vertex_bucket=512,
            analytics=("degrees", "cc", "bipartite"))
        with faults.inject(*plan_specs) as plan:
            out = drv.run_arrays(src, dst)
        return out, plan

    want, _ = run([])
    # prep-site call accounting: parallel interning fires once per
    # window array (2·w), then the first prefetch (chunk 2's stack
    # build) is the next firing
    got, plan = run([faults.FaultSpec(site="prep", on_call=2 * w + 1)])
    assert ("prep", 2 * w + 1, "raise") in plan.fired
    assert [(r.window_start, r.degrees.tolist(), r.cc_labels.tolist())
            for r in got] \
        == [(r.window_start, r.degrees.tolist(), r.cc_labels.tolist())
            for r in want]


def test_engine_reset_reanchors_checkpoint_cadence(tmp_path):
    """reset() must re-anchor the surviving CheckpointPolicy: a stale
    high-water mark silently disabled checkpointing for the next
    stream (review finding)."""
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)

    eb, vb = 64, 128
    rng = np.random.default_rng(3)
    s = rng.integers(0, vb, size=4 * eb).astype(np.int32)
    d = rng.integers(0, vb, size=4 * eb).astype(np.int32)
    path = str(tmp_path / "e.npz")
    eng = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    eng.enable_auto_checkpoint(path, every_n_windows=2)
    eng.process(s, d)  # marks the policy at window 4
    os.unlink(path)
    eng.reset()
    eng.process(s[:2 * eb], d[:2 * eb])
    assert os.path.exists(path)  # due at window 2 of the NEW stream


def test_checkpoint_truncation_falls_back_to_rotation(tmp_path):
    """Satellite: external damage to the newest checkpoint generation
    resumes from the rotated previous one with a warning — and only
    when EVERY generation is damaged does resume start fresh."""
    src, dst = _stream()
    ckpt = str(tmp_path / "drv.npz")
    a = _driver()
    a.enable_auto_checkpoint(ckpt, every_n_windows=2)
    half = len(src) // 2
    a.run_arrays(src[:half], dst[:half])  # two calls: two checkpoint
    a.run_arrays(src[half:], dst[half:])  # generations (rotation)
    assert os.path.exists(ckpt) and os.path.exists(ck.prev_path(ckpt))

    with faults.inject(faults.FaultSpec(site="ckpt_save",
                                        action="truncate_file")):
        ck.save(ckpt, a.state_dict())  # newest generation now damaged
    b = _driver()
    with pytest.warns(UserWarning, match="rotated previous"):
        assert b.try_resume(ckpt)
    assert 0 < b.windows_done <= a.windows_done

    # damage the rotation too: resume refuses politely
    with open(ck.prev_path(ckpt), "r+b") as f:
        f.truncate(8)
    c = _driver()
    with pytest.warns(UserWarning, match="starting fresh"):
        assert not c.try_resume(ckpt)


def test_checkpoint_policy_every_seconds_fake_clock(tmp_path):
    clock = [0.0]
    pol = ck.CheckpointPolicy(every_seconds=30.0, clock=lambda: clock[0])
    src, dst = _stream()
    drv = _driver()
    drv.enable_auto_checkpoint(str(tmp_path / "t.npz"), policy=pol)
    drv.run_arrays(src[:2048], dst[:2048])
    assert not os.path.exists(str(tmp_path / "t.npz"))  # clock frozen
    clock[0] = 31.0
    drv.run_arrays(src[2048:], dst[2048:])
    assert os.path.exists(str(tmp_path / "t.npz"))
    e = _driver()
    assert e.try_resume(str(tmp_path / "t.npz"))
    assert e.windows_done > 0


# ----------------------------------------------------------------------
# the call's triangle stack, dispatched ahead of the last read-back
# ----------------------------------------------------------------------
def _tri_stream(windows=8, seed=13):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 12, windows * 16),
            rng.integers(0, 12, windows * 16))


def _tri_driver(tier="scan"):
    return StreamingAnalyticsDriver(window_ms=0, edge_bucket=16,
                                    vertex_bucket=64, snapshot_tier=tier)


def _exact(src, dst):
    from gelly_streaming_tpu.ops.triangles import triangle_count_sparse

    return [triangle_count_sparse(src[i:i + 16], dst[i:i + 16], 64)
            for i in range(0, len(src), 16)]


def _tri_spy(monkeypatch) -> dict:
    """Windows the triangle kernels took: the device kernel's dispatch
    and read-back halves (count_windows runs both), and the host
    twin's count."""
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel
    from gelly_streaming_tpu.parallel.host_twin import (
        HostTriangleWindowKernel)

    seen = {"dispatch": 0, "collect": 0, "host": 0}

    def spy(cls, name, key, size):
        real = getattr(cls, name)

        def method(self, arg):
            out = real(self, arg)
            seen[key] += size(arg, out)
            return out
        monkeypatch.setattr(cls, name, method)

    spy(TriangleWindowKernel, "dispatch_windows", "dispatch",
        lambda windows, _out: len(windows))
    spy(TriangleWindowKernel, "collect_windows", "collect",
        lambda _handle, out: len(out))
    spy(HostTriangleWindowKernel, "count_windows", "host",
        lambda windows, _out: len(windows))
    return seen


def _ahead_counters():
    from gelly_streaming_tpu.utils import telemetry

    return [(r["value"], r["a"]["windows"]) for r in telemetry.records()
            if r["t"] == "counter" and r["name"] == "driver.triangles_ahead"]


@pytest.fixture
def _recorder(monkeypatch):
    from gelly_streaming_tpu.utils import telemetry

    monkeypatch.setenv("GS_TELEMETRY", "1")
    monkeypatch.delenv("GS_TRACE_DIR", raising=False)
    monkeypatch.setenv("GS_AUTOTUNE", "0")
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.mark.parametrize("site,on_call", [("finalize", 2),
                                          ("dispatch", 3)])
def test_failure_after_the_early_dispatch_leaves_triangles_none(
        _recorder, monkeypatch, site, on_call):
    """Two scan chunks a call, no demotion: the last chunk's read-back
    (`finalize` 2, after the triangle stack went out) or the stack's
    own dispatch (`dispatch` 3, behind the two scan chunks) fails. The
    call raises; no window it made has a triangle count, and nothing
    was read back. The next call counts its own windows ahead."""
    os.environ["GS_TIER_DEMOTE"] = "0"
    os.environ["GS_STAGE_TIMEOUT_S"] = "5"
    monkeypatch.setattr(StreamingAnalyticsDriver, "_SCAN_CHUNK", 4)
    seen = _tri_spy(monkeypatch)
    made = []
    real = StreamingAnalyticsDriver._scan_interned

    def scan_interned(self, interned, results, *args):
        made.append(results)
        return real(self, interned, results, *args)

    monkeypatch.setattr(StreamingAnalyticsDriver, "_scan_interned",
                        scan_interned)
    src, dst = _tri_stream()
    drv = _tri_driver()
    with faults.inject(faults.FaultSpec(site=site, on_call=on_call)):
        with pytest.raises(resilience.StageFailed) as ei:
            drv.run_arrays(src, dst)
    assert ei.value.stage == site
    (results,) = made
    assert len(results) == drv.windows_done == (4 if site == "finalize"
                                                else 8)
    assert all(r.triangles is None for r in results)
    assert seen["collect"] == seen["host"] == 0
    assert seen["dispatch"] == (8 if site == "finalize" else 0)
    assert _ahead_counters() == []
    # the stack the failed call left behind is never read back
    more = _tri_stream(windows=4, seed=14)
    got = drv.run_arrays(*more)
    assert [r.triangles for r in got] == _exact(*more)
    assert _ahead_counters() == [(4, 4)]


@pytest.mark.parametrize("tier", ["resident", "scan"])
def test_demotion_reentry_counts_each_window_once(_recorder, monkeypatch,
                                                  tier):
    """The last chunk's read-back fails after the call's stack went
    out; the call demotes and re-enters for the windows not finalized
    (resident → scan, which dispatches a stack of its own; scan →
    native, which counts at the flush). Every window is counted once,
    exactly: the failed entry's stack is never read back."""
    monkeypatch.setenv("GS_RESIDENT_SPB", "8")
    monkeypatch.setattr(StreamingAnalyticsDriver, "_SCAN_CHUNK", 4)
    windows = 16 if tier == "resident" else 8
    seen = _tri_spy(monkeypatch)
    src, dst = _tri_stream(windows)
    drv = _tri_driver(tier)
    with faults.inject(faults.FaultSpec(site="finalize", on_call=2)):
        got = drv.run_arrays(src, dst)
    assert [(e["from"], e["to"]) for e in drv.demotion_log()] == [
        (tier, "scan" if tier == "resident" else "native")]
    assert [r.triangles for r in got] == _exact(src, dst)
    assert seen["collect"] + seen["host"] == windows
    if tier == "resident":
        assert seen["dispatch"] == 2 * windows
        assert _ahead_counters() == [(windows, windows)]
    else:
        assert seen["dispatch"] == windows
        assert _ahead_counters() == [(0, windows)]


@pytest.mark.parametrize("site,on_call", [("dispatch", 3),
                                          ("finalize", 3)])
def test_triangle_stack_failure_takes_the_ladder(_recorder, monkeypatch,
                                                 site, on_call):
    """With the guard armed, the stack's dispatch (behind the two scan
    chunks) or its read-back (behind the two chunks' read-backs) fails
    once: the flush's ladder demotes scan → native, and the host twin
    counts every window once, exactly."""
    os.environ["GS_STAGE_TIMEOUT_S"] = "5"
    monkeypatch.setattr(StreamingAnalyticsDriver, "_SCAN_CHUNK", 4)
    seen = _tri_spy(monkeypatch)
    src, dst = _tri_stream()
    drv = _tri_driver()
    with faults.inject(faults.FaultSpec(site=site, on_call=on_call)):
        got = drv.run_arrays(src, dst)
    assert [(e["from"], e["to"]) for e in drv.demotion_log()] == [
        ("scan", "native")]
    assert [r.triangles for r in got] == _exact(src, dst)
    assert seen["host"] == 8 and seen["collect"] == 0
    assert _ahead_counters() == [(0, 8)]
