"""Rows requested over rows computed in the window (``ServiceStats`` diff):
what the scheduler's union and dedup saved."""


def read(run):
    rows = run.stats["rows_computed"]
    return run.stats["rows_requested"] / rows if rows else None
