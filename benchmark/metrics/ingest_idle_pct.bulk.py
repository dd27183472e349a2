"""Share of the traced window in which device 0 runs no operation while
the window thread's innermost program span is host ingest: interning
(`step.intern`), stack building (`ingress.prep`) or a transfer
(`ingress.h2d`)."""

from benchmark import spans


def read(run, trace):
    return spans.idle_pct(run, trace, spans.INGEST)
