"""The program's spans and counters in a trace (`benchmark/spans.py`)
and the five readers over them: the innermost-span labelling and the
idle split on synthetic intervals, the split summing to `idle_pct`,
counters per window, a real CPU capture parsed, and no reading at all
from a trace without program events (the chip fixture, recorded
before the program wrote any)."""

import os
import types

import pytest

from benchmark import harness, spans
from benchmark import trace as trace_mod

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
READERS = ["cc_rounds_per_window", "cover_rounds_per_window",
           "readback_mb_per_window", "readback_idle_pct.bulk",
           "ingest_idle_pct.bulk"]


def _reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "metrics", name + ".py"),
        "test_metric_" + name.replace(".", "_"))


def _run(trace_dir):
    return types.SimpleNamespace(trace_dir=trace_dir, counters={},
                                 cell=types.SimpleNamespace(chips=1))


def test_innermost_span_labels_each_piece():
    got = spans.innermost([(0, 100, "step.triangles"),
                           (10, 30, "ingress.prep"),
                           (30, 40, "ingress.h2d"),
                           (60, 120, "step.intern")])
    assert got == [(0, 10, "step.triangles"), (10, 30, "ingress.prep"),
                   (30, 40, "ingress.h2d"), (40, 60, "step.triangles"),
                   (60, 120, "step.intern")]
    assert spans.innermost([]) == []


def test_split_puts_uncovered_idle_under_none():
    pieces = [(0, 10, "a"), (10, 30, "b"), (50, 60, "a")]
    got = spans.split([[5, 15], [40, 55], [70, 80]], pieces)
    assert got == {"a": 10, "b": 5, None: 20}
    assert sum(got.values()) == 35   # every gap ns, once


def _synthetic():
    """A one-device trace over [0, 1000) ns and the program's events:
    the device idles over [100, 300), [500, 700) and [900, 1000)."""
    tr = object.__new__(trace_mod.Trace)
    dev = trace_mod.Device("/device:TPU:0")
    dev.ops = [(0, 100, "fusion.1"), (300, 500, "fusion.2"),
               (700, 900, "while.3"), (720, 880, "fusion.4")]
    tr.devices, tr.host, tr.lo, tr.hi = [dev], [], 0, 1000
    ev = spans.Event
    events = [
        ev("step.intern", 0, 50, 150, {"records": 8}),
        ev("step.snapshot_scan", 0, 150, 200, {}),
        ev("step.snapshot_wait", 0, 200, 260, {}),
        ev("step.snapshot_extract", 0, 260, 280, {}),
        ev("driver.cc_rounds", 0, 262, 262, {"value": 12, "windows": 4}),
        ev("step.triangles", 0, 520, 690, {}),
        ev("ingress.prep", 0, 530, 560, {"chunk": 0}),
        ev("ingress.h2d", 0, 560, 580, {"chunk": 0}),
        ev("ingress.finalize", 0, 600, 680, {"chunk": 0}),
        ev("ingress.prep", 1, 100, 1000, {"chunk": 1}),   # a worker
    ]
    return tr, spans.Capture(events, 0, 0, 1000)


def test_idle_split_sums_to_idle_pct():
    tr, cap = _synthetic()
    got = spans.idle_split(tr, cap)
    ns = {k: round(v * 1e9) for k, v in got.items()}
    # the counter is no span, and the worker's line is not the window's
    assert ns == {"step.intern": 50, "step.snapshot_scan": 50,
                  "step.snapshot_wait": 60, "step.snapshot_extract": 20,
                  "step.triangles": 40, "ingress.prep": 30,
                  "ingress.h2d": 20, "ingress.finalize": 80, None: 150}
    idle = trace_mod.idle_pct(_run(None), tr)
    assert 100.0 * sum(got.values()) / tr.window_s == pytest.approx(
        idle, rel=1e-12)


def test_idle_readers_partition_the_idle_share(monkeypatch):
    tr, cap = _synthetic()
    monkeypatch.setattr(spans, "of_run", lambda run: cap)
    run = _run(None)
    ingest = _reader("ingest_idle_pct.bulk").read(run, tr)
    readback = _reader("readback_idle_pct.bulk").read(run, tr)
    assert ingest == pytest.approx(100.0 * 100e-9 / tr.window_s)
    assert readback == pytest.approx(100.0 * 160e-9 / tr.window_s)
    split = spans.idle_split(tr, cap)
    other = sum(v for k, v in split.items()
                if k is not None and k not in spans.INGEST + spans.READBACK)
    none = split[None]
    total = ingest + readback + 100.0 * (other + none) / tr.window_s
    assert total == pytest.approx(trace_mod.idle_pct(run, tr), rel=1e-12)


def test_counters_per_window():
    ev = spans.Event
    cap = spans.Capture([
        ev("driver.cc_rounds", 0, 10, 10, {"value": 12, "windows": 4}),
        ev("driver.cc_rounds", 0, 20, 20, {"value": 20, "windows": 4}),
        ev("driver.readback_bytes", 0, 10, 10,
           {"value": 4000, "windows": 4}),
        ev("driver.readback_bytes", 0, 30, 30,        # triangle counts
           {"value": 40, "windows": 0}),
        ev("step.snapshot_wait", 0, 5, 15, {"records": 4}),
    ], 0, 0, 100)
    assert cap.counter_per_window("driver.cc_rounds") == 4.0
    assert cap.counter_per_window("driver.readback_bytes") == 1010.0
    assert cap.counter_per_window("driver.cover_rounds") is None
    assert [e.name for e in cap.spans] == ["step.snapshot_wait"]


def test_parse_keeps_program_events_of_the_window(tmp_path):
    """A real CPU capture: program spans on the window thread and a
    worker, a counter, JAX's own events left out, and what lies
    outside `bench.window` dropped."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from gelly_streaming_tpu.utils import telemetry

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with telemetry.span("step.intern"):
            pass                                   # before the window
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            with telemetry.span("step.snapshot_wait", records=2):
                np.asarray(jnp.arange(4) + 1)
            telemetry.counter("driver.cc_rounds", 6, windows=2)

            def prep():
                with telemetry.span("ingress.prep"):
                    pass

            worker = threading.Thread(target=prep)
            worker.start()
            worker.join()
    finally:
        jax.profiler.stop_trace()
    cap = spans.parse(trace_mod.find_xplane(str(tmp_path)))
    names = sorted(e.name for e in cap.events)
    assert names == ["driver.cc_rounds", "ingress.prep",
                     "step.snapshot_wait"]
    lines = {e.name: e.line for e in cap.events}
    assert lines["step.snapshot_wait"] == cap.line
    assert lines["ingress.prep"] != cap.line
    assert [s[2] for s in cap.window_spans()] == ["step.snapshot_wait"]
    assert cap.counter_per_window("driver.cc_rounds") == 3.0


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_a_trace_without_program_events(name):
    """The chip fixture holds device planes and benchmark spans but no
    program event: every new reader returns None, never 0."""
    tr = trace_mod.Trace(os.path.join(FIXTURES, "chip-trace.xplane.pb"))
    assert _reader(name).read(_run(FIXTURES), tr) is None
    assert _reader(name).read(_run(os.path.join(FIXTURES, "none")),
                              tr) is None
