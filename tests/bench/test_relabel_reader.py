"""The relabel reader (`benchmark/metrics/relabel_gather_pct.py`): the
program's `driver.relabel_gathers` counter over the two folds of each
window, and no reading where the program keeps no such counter."""

import os
import types

import pytest

from benchmark import harness, spans
from benchmark import trace as trace_mod

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _reader():
    return harness.load_module(
        os.path.join(harness.HERE, "metrics", "relabel_gather_pct.py"),
        "test_metric_relabel_gather_pct")


def _run(trace_dir=None):
    return types.SimpleNamespace(trace_dir=trace_dir, counters={},
                                 cell=types.SimpleNamespace(chips=1))


def test_gathers_over_both_folds_of_each_window(monkeypatch):
    ev = spans.Event
    cap = spans.Capture([
        ev("driver.relabel_gathers", 0, 10, 10, {"value": 3, "windows": 8}),
        ev("driver.relabel_gathers", 0, 20, 20, {"value": 0, "windows": 4}),
        ev("driver.relabel_roots", 0, 20, 20, {"value": 900, "windows": 4}),
    ], 0, 0, 100)
    monkeypatch.setattr(spans, "of_run", lambda run: cap)
    assert _reader().read(_run(), None) == pytest.approx(12.5)


def test_no_gather_reads_zero_not_none(monkeypatch):
    cap = spans.Capture([spans.Event(
        "driver.relabel_gathers", 0, 10, 10, {"value": 0, "windows": 8})],
        0, 0, 100)
    monkeypatch.setattr(spans, "of_run", lambda run: cap)
    assert _reader().read(_run(), None) == 0.0


def test_no_reading_without_the_counter():
    """The chip fixture holds no program event, as a program without
    the counter would write none: no reading, never 0."""
    tr = trace_mod.Trace(os.path.join(FIXTURES, "chip-trace.xplane.pb"))
    assert _reader().read(_run(FIXTURES), tr) is None
    assert _reader().read(_run(os.path.join(FIXTURES, "none")), tr) is None

