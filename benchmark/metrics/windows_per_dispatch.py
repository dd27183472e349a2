"""Windows the driver delivered in the traced window over the program
launches on the device in it (every program the calls launched)."""


def read(run, trace):
    launches = trace.launches()
    windows = run.counters.get("windows")
    if not launches or not windows:
        return None
    return windows / launches
