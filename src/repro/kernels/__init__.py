"""Pallas TPU kernels for the PDES hot loop (interpreted off the TPU)."""
from .ops import (  # noqa: F401
    pdes_multistep,
    pdes_multistep_counter,
    pdes_step,
    ring_halo,
    simulate,
    step_ring,
)
