"""Rounds the snapshot scan's double-cover fixpoint (bipartiteness,
`ops/unionfind.cc_fixpoint` over the 2·vb-slot cover) took per window:
Σ of the program's `driver.cover_rounds` counter over Σ its
`windows`."""

from benchmark import spans


def read(run, trace):
    return spans.counter_per_window(run, "driver.cover_rounds")
