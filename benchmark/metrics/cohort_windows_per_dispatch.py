"""Windows the server delivered while the trace ran over launches of
the cohort program (tenancy `_program`, jitted as `run`)."""

PROGRAM = "jit_run"


def read(run, trace):
    launches = trace.launches({PROGRAM})
    windows = run.counters.get("windows_traced")
    if not launches or not windows:
        return None
    return windows / launches
