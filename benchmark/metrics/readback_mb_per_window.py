"""Megabytes the driver reads back from the device per window: Σ of the
program's `driver.readback_bytes` counter (the snapshot scan's
materialized outputs, and the triangle program's counts with no window
of their own) over Σ its `windows`, in 10^6 bytes."""

from benchmark import spans


def read(run, trace):
    per = spans.counter_per_window(run, "driver.readback_bytes")
    return None if per is None else per / 1e6
