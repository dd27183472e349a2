"""The mesh cell's roofline readers on a small synthetic trace: the
same least work over the same device-0 time, against the peaks of all
the cell's chips, so a four-chip share is a quarter of one chip's and
never above 100 %; and no reading where the program did not run."""

import os
import types

import pytest

from benchmark import harness
from benchmark import trace as trace_mod
from benchmark import work

METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark", "metrics")
READERS = [("snapshot_scan_roofline", "jit_run"),
           ("triangle_roofline", "jit_run_stream")]


def _reader(name):
    return harness.load_module(os.path.join(METRICS, name + ".py"),
                               "test_metric_" + name.replace(".", "_"))


def _trace(program, seconds):
    """A one-window trace whose device 0 ran `program` for `seconds`
    in two launches, and something else in between."""
    tr = object.__new__(trace_mod.Trace)
    dev = trace_mod.Device("/device:TPU:0")
    half = int(seconds * 1e9) // 2
    dev.modules = [(0, half, program), (half, half + 10, "jit_other"),
                   (half + 10, 2 * half + 10, program)]
    tr.devices, tr.host = [dev], []
    tr.lo, tr.hi = 0, 2 * half + 10
    return tr


def _run(chips, eb=32768, vb=1 << 20, windows=64):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(chips=chips),
        counters={"windows": windows, "eb": eb, "vb": vb,
                  "analytics": ["degrees", "cc", "bipartite",
                                "triangles"]},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])


@pytest.mark.parametrize("name,program", READERS)
@pytest.mark.parametrize("chips", [1, 4])
def test_mesh_share_is_one_chip_share_over_chips(name, program, chips):
    tr = _trace(program, 0.5)
    single = _reader(name).read(_run(1), tr)
    mesh = _reader(name + ".mesh").read(_run(chips), tr)
    assert single > 0
    assert mesh == pytest.approx(single / chips)
    assert 0 < mesh <= 100


@pytest.mark.parametrize("name,program", READERS)
def test_mesh_share_stays_a_share(name, program):
    # device time equal to the least time the work needs on one chip:
    # one chip would read 100 %, four chips read a quarter of it
    run = _run(4, windows=4096)
    c = run.counters
    ops, nbytes = (work.snapshot_scan(c["eb"], c["vb"], c["analytics"])
                   if program == "jit_run" else work.triangles(c["eb"],
                                                               c["vb"]))
    peak = work.peaks("TPU v5 lite")
    least = c["windows"] * max(ops / peak["ops_per_s"],
                               nbytes / peak["bytes_per_s"])
    share = _reader(name + ".mesh").read(run, _trace(program, least))
    assert share == pytest.approx(25.0, rel=1e-6)


@pytest.mark.parametrize("name,program", READERS)
def test_no_reading_without_the_program(name, program):
    tr = _trace("jit_something_else", 0.5)
    assert _reader(name + ".mesh").read(_run(4), tr) is None
    assert _reader(name + ".mesh").read(_run(4, windows=0),
                                        _trace(program, 0.5)) is None
