"""Share of the traced window in which a collective runs on a device
and no other operation does, averaged over the cell's chips."""


def read(run, trace):
    if len(trace.devices) < 2 or trace.window_s <= 0:
        return None
    return 100.0 * trace.exposed_collective_s(run.cell.chips) / trace.window_s
