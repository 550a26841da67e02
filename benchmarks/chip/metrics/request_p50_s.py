"""Median of answer time minus due time, over every request due in the
window (host clock); a failed request counts as beyond any limit."""
from benchmarks.chip.metrics_common import latency_percentile


def read(run):
    return latency_percentile(run, 50)
