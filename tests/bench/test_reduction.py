"""The trace reduction on a small trace recorded on the chip, the
interval arithmetic under it, the per-window work functions and the
peaks lookup."""

import os

import pytest

from benchmark import trace as trace_mod
from benchmark import work

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "chip-trace.xplane.pb")


def test_intervals():
    u = trace_mod.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [[0, 3], [5, 9]]
    assert trace_mod.subtract(u, [[1, 6]]) == [[0, 1], [6, 9]]
    assert trace_mod.clip(u, 2, 8) == [[2, 3], [5, 8]]
    assert trace_mod.total(u) == 7


def test_names():
    assert trace_mod.op_name("%fusion.13 = s32[4] fusion(x)") == "fusion.13"
    assert trace_mod.op_kind("all-reduce-start.2") == "all-reduce-start"
    assert trace_mod.program_name("jit_run(1625867)") == "jit_run"


def test_work_per_window():
    vb, eb = 65536, 32768
    ops, nbytes = work.snapshot_scan(eb, vb, ["degrees", "cc", "bipartite",
                                              "triangles"])
    assert work.id_bytes(vb) == 2
    assert ops == 2 * eb * 3
    assert nbytes == 2 * eb * 2 + vb * (12 + 12 + 17)
    t_ops, t_bytes = work.triangles(eb, vb)
    assert t_ops == eb * 15 and t_bytes == 6 * eb * 2
    share, bound = work.roofline_pct(0, 819e9, 2.0, "TPU v5 lite")
    assert share == pytest.approx(50.0) and bound == "bytes"


def test_unknown_device_kind_is_an_error():
    assert work.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v99")
    with pytest.raises(KeyError):
        work.roofline_pct(1, 1, 1.0, "cpu")


def test_chip_trace_fixture():
    tr = trace_mod.Trace(FIXTURE)
    assert len(tr.devices) == 1
    assert 0 < tr.busy_s() < tr.window_s
    assert tr.launches({"jit_run"}) == 3
    assert tr.launches({"jit_run_stream"}) == 3
    assert tr.launches() >= 6
    assert tr.program_time_s("jit_run") > 0
    assert tr.exposed_collective_s() == 0
    ops = tr.top_ops()
    assert ops and all(k.split("/")[0].startswith("jit_") for k, _ in ops)
    gaps = tr.idle_gaps()
    assert gaps and gaps[0][1] >= gaps[-1][1] > 0
    assert any(label.startswith("bench.run_arrays") for label, _ in gaps)
