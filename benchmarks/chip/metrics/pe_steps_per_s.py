"""PE-steps per second: work the answered requests asked for, over the time
from the window's start to the last answer (host clock)."""
from benchmarks.chip.traffic import pe_steps


def read(run):
    work = sum(pe_steps(s.req.spec) for s in run.served if s.ok)
    return work / run.window_end if work and run.window_end > 0 else None
