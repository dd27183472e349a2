"""Share of the snapshot scan's folds (a CC and a double-cover fold a
window) that relabelled their table by the whole-table gather, because
more roots with members moved than the relabel's list holds: Σ of the
program's `driver.relabel_gathers` counter over 2 × Σ its `windows`,
in %. None where the program keeps no such counter."""

from benchmark import spans


def read(run, trace):
    per = spans.counter_per_window(run, "driver.relabel_gathers")
    return None if per is None else 100.0 * per / 2
