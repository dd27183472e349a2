"""The sharded triangle stream program (parallel/sharded.py
`ShardedTriangleWindowKernel`, jitted as `run_stream`) against the
mesh's roofline: the same least work for the window's triangle count
as `triangle_roofline` (benchmark/work.py) over the program's device-0
time, against the peaks of all the cell's chips (`chips` × one
chip's)."""

from benchmark import work

PROGRAM = "jit_run_stream"


def read(run, trace):
    seconds = trace.program_time_s(PROGRAM)
    windows = run.counters.get("windows")
    if seconds <= 0 or not windows:
        return None
    ops, nbytes = work.triangles(run.counters["eb"], run.counters["vb"])
    share, _bound = work.roofline_pct(windows * ops, windows * nbytes,
                                      seconds * run.cell.chips,
                                      run.devices[0].device_kind)
    return share
