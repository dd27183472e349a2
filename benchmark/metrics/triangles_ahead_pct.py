"""Share of the flushed windows whose triangle program the driver put
on the device ahead of the call's last read-back, so that it ran
while the host waited on the scan's d2h and extracted the windows:
Σ of the program's `driver.triangles_ahead` counter over Σ its
`windows`, in %. None where the program keeps no such counter."""

from benchmark import spans


def read(run, trace):
    per = spans.counter_per_window(run, "driver.triangles_ahead")
    return None if per is None else 100.0 * per
