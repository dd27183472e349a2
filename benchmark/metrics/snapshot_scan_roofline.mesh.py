"""The mesh snapshot scan (parallel/sharded.py
`make_sharded_snapshot_scan`, jitted as `run`) against the mesh's
roofline: the same least work for the window's degree / CC / bipartite
fold as `snapshot_scan_roofline` (benchmark/work.py) over the
program's device-0 time, against the peaks of all the cell's chips
(`chips` × one chip's), so one chip and four read the same
yardstick."""

from benchmark import work

PROGRAM = "jit_run"


def read(run, trace):
    seconds = trace.program_time_s(PROGRAM)
    windows = run.counters.get("windows")
    if seconds <= 0 or not windows:
        return None
    ops, nbytes = work.snapshot_scan(run.counters["eb"], run.counters["vb"],
                                     run.counters["analytics"])
    share, _bound = work.roofline_pct(windows * ops, windows * nbytes,
                                      seconds * run.cell.chips,
                                      run.devices[0].device_kind)
    return share
