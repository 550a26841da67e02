"""Share of the window's measured rows whose burned state reached the
measurement without a host round trip: the service's
``rows_state_on_device`` over ``rows_computed`` (``ServiceStats`` diff), in
percent.  Reads nothing from a program that does not count such rows."""


def read(run):
    n = run.stats.get("rows_state_on_device")
    rows = run.stats.get("rows_computed")
    return 100.0 * n / rows if n is not None and rows else None
