"""Device time of the multistep kernel per PE-step the engine advanced.

Sums the durations of the kernel's events in the trace of the window and
divides by the engine's row-steps over the same window (``ServiceStats``)
times the ring length, in picoseconds.  The window holds whole requests
only, so both count the same work.  A trace whose devices ran ops but none
of the kernel's is an error, not a missing reading: the kernel's name has
changed, or another path served the window.
"""
import re

from benchmarks.chip import trace_reduce

#: HLO name of the ``pdes_multistep_counter`` Pallas kernel's custom call
KERNEL = re.compile(r"pdes_multistep")


def read(run):
    tr = run.trace
    if tr is None or not any(len(trace_reduce.busy(tr, d))
                             for d in run.devices):
        return None
    secs = trace_reduce.op_seconds(tr, run.devices, KERNEL)
    if secs <= 0:
        raise ValueError(f"no device op matches {KERNEL.pattern!r}; busiest: "
                         f"{trace_reduce.top_ops(tr, run.devices, 5)}")
    pe_steps = run.stats["engine_row_steps"] * int(run.config["L"])
    return secs / pe_steps * 1e12 if pe_steps else None
