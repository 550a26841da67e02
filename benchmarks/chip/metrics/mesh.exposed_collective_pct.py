"""Share of the window in which a collective runs on a device with no other
op overlapping it, from the profiler trace, mean over the cell's chips.

Reads 0 where the devices ran ops but no collective was left exposed; reads
nothing only where the trace holds no op of the cell's devices."""
from benchmarks.chip import trace_reduce


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0 or not any(
            len(trace_reduce.busy(tr, d)) for d in run.devices):
        return None
    return 100.0 * trace_reduce.exposed_collective_s(tr, run.devices) \
        / tr.window_s
