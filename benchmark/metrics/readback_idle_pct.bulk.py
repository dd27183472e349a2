"""Share of the traced window in which device 0 runs no operation while
the window thread's innermost program span is the read-back: the
snapshot d2h (`step.snapshot_wait`), the host extraction of the
windows' results (`step.snapshot_extract`) or a pipeline's finalize
(`ingress.finalize`)."""

from benchmark import spans


def read(run, trace):
    return spans.idle_pct(run, trace, spans.READBACK)
