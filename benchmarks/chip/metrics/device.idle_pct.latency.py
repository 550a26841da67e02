"""Share of the window in which the device ran no op, from the profiler
trace, mean over the cell's chips (the latency cells)."""
from benchmarks.chip.metrics_common import idle_pct


def read(run):
    return idle_pct(run)
