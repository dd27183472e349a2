"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (the reading both idle metrics share)."""

from benchmark.trace import idle_pct as read  # noqa: F401
