"""Rounds the snapshot scan's CC fixpoint (`ops/unionfind.cc_fixpoint`)
took per window: Σ of the program's `driver.cc_rounds` counter over Σ
its `windows`, counted on the device and read back with the window's
snapshots."""

from benchmark import spans


def read(run, trace):
    return spans.counter_per_window(run, "driver.cc_rounds")
