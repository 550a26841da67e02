"""Process start to window start: JAX and TPU start-up, executables built or
loaded from the compile cache, and the warm-up requests (host clock)."""


def read(run):
    return run.setup_s
