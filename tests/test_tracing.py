"""The recorder's second sink, the live profiler session, and the
StepTimer adapter (utils/tracing, utils/telemetry).

- Bridge: under a CPU `jax.profiler` capture with the recorder
  disarmed (`GS_TELEMETRY=0`), spans (plain, nested, raising, with
  attributes set inside), counters, profiler-only scopes and
  `StepTimer.step` all reach the host plane with their stats, and the
  ring stays empty.
- The ingress pipeline's pool-side prep/h2d stages show on worker
  thread lines; dispatch and finalize on the caller's.
- Off the capture no annotation is ever constructed: the guard is one
  `is_enabled()` call.
- StepTimer: `step()` yields the telemetry span (so dispatch-owning
  steps can attach program/sig attrs) while report()/event_log() keep
  their accumulation semantics.
"""

import glob
import os
import time

import pytest

from gelly_streaming_tpu.utils import telemetry, tracing


@pytest.fixture
def armed(tmp_path, monkeypatch):
    monkeypatch.setenv("GS_TELEMETRY", "1")
    monkeypatch.setenv("GS_TRACE_DIR", str(tmp_path / "ledger"))
    telemetry.reset()
    yield
    telemetry.reset()


def _host_events(log_dir: str) -> dict:
    """{name: [(line, start_ns, end_ns, stats)]} over the host plane."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out, n = {}, 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (n, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)))
            n += 1
    return out


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One CPU capture of every bridged hook, the recorder disarmed;
    returns (host events, the ring's records after the capture)."""
    import jax

    from gelly_streaming_tpu.ops import ingress_pipeline

    saved = {k: os.environ.get(k)
             for k in ("GS_TELEMETRY", "GS_PIPELINE_WORKERS")}
    os.environ.update(GS_TELEMETRY="0", GS_PIPELINE_WORKERS="2")
    telemetry.reset()
    ingress_pipeline.reset_pool()
    log_dir = str(tmp_path_factory.mktemp("capture"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with telemetry.span("bridge.main"):
            with telemetry.span("bridge.span", records=3):
                pass
            with telemetry.span("bridge.outer"):
                with telemetry.span("bridge.inner", depth=2):
                    pass
            with pytest.raises(ValueError):
                with telemetry.span("bridge.raises", records=1):
                    raise ValueError("stage died")
            with telemetry.span("bridge.after"):
                pass
            with telemetry.span("bridge.late", records=4) as sp:
                sp.attrs.update(program="snapshot_scan", sig="i32[4]")
            telemetry.counter("bridge.count", 7, windows=2)
            with telemetry.trace_scope("bridge.scope", chunk=5):
                pass
            with tracing.StepTimer().step("bridge_step", 9):
                pass

            def prep(item):
                time.sleep(0.002)
                return item

            ingress_pipeline.run_pipeline(
                range(4), prep, lambda p: p, lambda d: d,
                lambda raw: None)
        records = telemetry.records()
    finally:
        jax.profiler.stop_trace()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        ingress_pipeline.reset_pool()
        telemetry.reset()
    return _host_events(log_dir), records


def _one(events, name):
    got = events.get(name, [])
    assert len(got) == 1, (name, got)
    return got[0]


def _within(inner, outer) -> bool:
    return (inner[0] == outer[0] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def test_span_reaches_host_plane_with_stats(capture):
    events, _ = capture
    line, start, end, stats = _one(events, "bridge.span")
    assert stats == {"records": 3} and end >= start
    assert _within(_one(events, "bridge.span"), _one(events, "bridge.main"))


def test_nested_span_inside_its_parent(capture):
    events, _ = capture
    inner = _one(events, "bridge.inner")
    assert inner[3] == {"depth": 2}
    assert _within(inner, _one(events, "bridge.outer"))


def test_raising_span_closes_with_error(capture):
    events, _ = capture
    raised = _one(events, "bridge.raises")
    assert raised[3] == {"records": 1, "error": "ValueError"}
    after = _one(events, "bridge.after")
    # closed on the exception path: the next span is not inside it
    assert raised[2] <= after[1]


def test_attributes_set_inside_go_on_as_metadata(capture):
    events, _ = capture
    assert _one(events, "bridge.late")[3] == {
        "records": 4, "program": "snapshot_scan", "sig": "i32[4]"}


def test_counter_is_an_event_with_value_and_attrs(capture):
    events, _ = capture
    count = _one(events, "bridge.count")
    assert count[3] == {"value": 7, "windows": 2}
    assert _within(count, _one(events, "bridge.main"))


def test_profiler_scope_and_steptimer_reach_the_capture(capture):
    events, _ = capture
    assert _one(events, "bridge.scope")[3] == {"chunk": 5}
    assert _one(events, "step.bridge_step")[3] == {"records": 9}


def test_bridge_needs_no_armed_recorder(capture):
    _events, records = capture
    assert records == []


def test_ingress_worker_stages_on_worker_lines(capture):
    events, _ = capture
    main = _one(events, "bridge.main")
    for name in ("ingress.dispatch", "ingress.finalize"):
        got = events[name]
        assert len(got) == 4 and all(_within(e, main) for e in got), name
    for name in ("ingress.prep", "ingress.h2d"):
        got = events[name]
        assert len(got) == 4, name
        assert all(e[0] != main[0] for e in got), name
        assert sorted(e[3]["chunk"] for e in got) == [0, 1, 2, 3]


class _CountingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: counts what the
    hooks construct; `live` plays the session."""

    live = False
    made = []

    @classmethod
    def is_enabled(cls):
        return cls.live

    def __init__(self, name, **attrs):
        self.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **attrs):
        pass


def _every_hook():
    with telemetry.span("a", n=1):
        with tracing.StepTimer().step("b", 2):
            pass
    telemetry.counter("c", 3)
    with telemetry.trace_scope("d"):
        pass


@pytest.mark.parametrize("live,made", [
    (False, []), (True, ["a", "step.b", "c", "d"])])
def test_annotations_only_inside_a_session(monkeypatch, live, made):
    stub = type("Stub", (_CountingAnnotation,), {"live": live, "made": []})
    monkeypatch.setattr(telemetry, "_ANNOTATION", stub)
    monkeypatch.setenv("GS_TELEMETRY", "0")
    telemetry.reset()
    try:
        _every_hook()
        assert stub.made == made
        assert telemetry.records() == []
    finally:
        telemetry.reset()


# ----------------------------------------------------------------------
# StepTimer: span-yield + unchanged accumulation semantics
# ----------------------------------------------------------------------
def test_steptimer_step_yields_span_for_attrs(armed):
    timer = tracing.StepTimer()
    with timer.step("snapshot_scan", num_records=4) as sp:
        sp.attrs.update(program="fused_scan", sig="i32[4]")
    rec = next(r for r in telemetry.records()
               if r.get("name") == "step.snapshot_scan")
    assert rec["a"]["program"] == "fused_scan"
    assert rec["a"]["sig"] == "i32[4]"
    rows = {r["op"]: r for r in timer.report()}
    assert rows["snapshot_scan"]["records"] == 4
    assert rows["snapshot_scan"]["calls"] == 1


def test_steptimer_disarmed_report_unchanged(monkeypatch):
    monkeypatch.setenv("GS_TELEMETRY", "0")
    telemetry.reset()
    try:
        timer = tracing.StepTimer()
        for _ in range(3):
            with timer.step("intern", num_records=10):
                pass
        assert telemetry.records() == []
        rows = {r["op"]: r for r in timer.report()}
        assert rows["intern"]["calls"] == 3
        assert rows["intern"]["records"] == 30
    finally:
        telemetry.reset()
