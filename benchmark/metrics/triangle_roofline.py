"""The device triangle program's share of its roofline: the least time
the chip needs for the window's triangle work (benchmark/work.py, from
eb and vb) over that program's summed device time in the trace. Absent
where the program did not run on the device (a host tier took it)."""

from benchmark import work

PROGRAM = "jit_run_stream"   # TriangleWindowKernel's stream program


def read(run, trace):
    seconds = trace.program_time_s(PROGRAM)
    windows = run.counters.get("windows")
    if seconds <= 0 or not windows:
        return None
    ops, nbytes = work.triangles(run.counters["eb"], run.counters["vb"])
    share, _bound = work.roofline_pct(windows * ops, windows * nbytes,
                                      seconds, run.devices[0].device_kind)
    return share
