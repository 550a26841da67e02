"""Arithmetic that several metric readers share."""
from __future__ import annotations

import numpy as np

from . import trace_reduce

#: the latency a failed or unanswered request counts with: past any limit
FAILED_S = 1e9


def latency_percentile(run, q: float) -> float | None:
    """Percentile ``q`` of answer minus due time over the window's requests."""
    lat = [s.answered - s.req.due if s.ok else FAILED_S for s in run.served]
    return float(np.percentile(np.array(lat), q)) if lat else None


def idle_pct(run) -> float | None:
    """Percent of the traced window with no op on the device, chip mean."""
    tr = run.trace
    if tr is None or tr.window_s <= 0 or not any(
            d in tr.ops for d in run.devices):
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(tr, run.devices) / tr.window_s)
