"""Jaxpr traces per answered request: the service's ``n_traces`` counter
(``ServiceStats`` diff over the window) over the requests answered in it.

A pass whose shapes were warmed up should trace nothing; a sharded pass
that wraps a fresh ``jax.jit`` on every call traces on every call.  Reads
nothing from a program that does not count traces."""


def read(run):
    n = run.stats.get("n_traces")
    answered = sum(1 for s in run.served if s.ok)
    return n / answered if n is not None and answered else None
