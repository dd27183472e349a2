"""The triangles-ahead reader (`benchmark/metrics/triangles_ahead_pct.py`):
the program's `driver.triangles_ahead` counter over the windows each
flush counted, and no reading where the program keeps no such
counter."""

import os
import types

import pytest

from benchmark import harness, spans
from benchmark import trace as trace_mod

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _reader():
    return harness.load_module(
        os.path.join(harness.HERE, "metrics", "triangles_ahead_pct.py"),
        "test_metric_triangles_ahead_pct")


def _run(trace_dir=None):
    return types.SimpleNamespace(trace_dir=trace_dir, counters={},
                                 cell=types.SimpleNamespace(chips=1))


def test_windows_ahead_over_windows_flushed(monkeypatch):
    ev = spans.Event
    cap = spans.Capture([
        ev("driver.triangles_ahead", 0, 10, 10, {"value": 8, "windows": 8}),
        ev("driver.triangles_ahead", 0, 20, 20, {"value": 0, "windows": 1}),
        ev("driver.triangles_ahead", 0, 30, 30, {"value": 7, "windows": 7}),
        ev("driver.relabel_gathers", 0, 30, 30, {"value": 5, "windows": 4}),
    ], 0, 0, 100)
    monkeypatch.setattr(spans, "of_run", lambda run: cap)
    assert _reader().read(_run(), None) == pytest.approx(93.75)


def test_end_flush_alone_reads_zero_not_none(monkeypatch):
    cap = spans.Capture([spans.Event(
        "driver.triangles_ahead", 0, 10, 10, {"value": 0, "windows": 8})],
        0, 0, 100)
    monkeypatch.setattr(spans, "of_run", lambda run: cap)
    assert _reader().read(_run(), None) == 0.0


def test_no_reading_without_the_counter():
    """The chip fixture holds no program event, as a program without
    the counter would write none: no reading, never 0."""
    tr = trace_mod.Trace(os.path.join(FIXTURES, "chip-trace.xplane.pb"))
    assert _reader().read(_run(FIXTURES), tr) is None
    assert _reader().read(_run(os.path.join(FIXTURES, "none")), tr) is None
