"""The driver's snapshot scan (core/driver.py `_build_snapshot_scan`,
jitted as `run`) against its roofline: the least time the chip needs
for the window's degree / CC / bipartite fold (benchmark/work.py) over
the program's summed device time in the trace."""

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
                                      seconds, run.devices[0].device_kind)
    return share
