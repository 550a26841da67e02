"""vmem-budget: per-BlockSpec VMEM footprint of every Pallas kernel call.

Each program instance of ``pdes_step`` / ``pdes_multistep`` /
``pdes_multistep_counter`` owns one VMEM tile per operand/output BlockSpec.
The footprint is fully static — block shapes x dtypes off the
``grid_mapping`` the call was traced with — so exceeding the budget is a
compile-time fact, not a runtime surprise.  The default budget (16 MiB)
matches a TPU v5e core's scoped VMEM; tune with ``--vmem-budget``.  This
counts the blocks once and unpadded; the engine's auto-tiler
(``kernels.tiling.vmem_bytes``) also counts padding, double buffering and
body scratch.
"""
from __future__ import annotations

import numpy as np

from ..probes import Probe
from ..report import Finding
from .common import where

RULE = "vmem-budget"

DEFAULT_BUDGET = 16 << 20          # bytes; one TPU core's VMEM


def _block_bytes(bm) -> int:
    shape = getattr(bm, "block_shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        d = getattr(d, "block_size", d)      # pallas ``Blocked(n)`` dims
        n *= int(d) if isinstance(d, (int, np.integer)) else 1
    asd = getattr(bm, "array_shape_dtype", None)
    itemsize = np.dtype(getattr(asd, "dtype", np.float32)).itemsize
    return n * itemsize


def check(probe: Probe, vmem_budget: int = DEFAULT_BUDGET, **_) -> list:
    findings = []
    for n in probe.graph.find("pallas_call"):
        gm = n.params.get("grid_mapping")
        mappings = getattr(gm, "block_mappings", None)
        if not mappings:
            continue
        per_block = [_block_bytes(bm) for bm in mappings]
        total = sum(per_block)
        if total > vmem_budget:
            kname = n.params.get("name") or "pallas_call"
            biggest = max(per_block)
            findings.append(Finding(
                rule=RULE, op=kname, path=where(n),
                message=f"kernel tiles need {total / 2**20:.1f} MiB VMEM "
                        f"(largest block {biggest / 2**20:.1f} MiB) > "
                        f"budget {vmem_budget / 2**20:.1f} MiB across "
                        f"{len(per_block)} BlockSpecs"))
    return findings
