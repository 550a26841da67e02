"""Executables built or loaded from the compile cache inside the window, from
JAX's monitoring events (the harness's own listener)."""


def read(run):
    return run.compiles
