#!/usr/bin/env python3
"""The control of ``correct``: the reference in bfloat16 in the program's place.

For each seed, the first requests a cell's traffic sends (as many as the
cell's check samples) are computed by the configuration's own plain
reference (its ``reference`` module) twice, in the configuration's
float32 and in bfloat16, the nearest precision below, and the bfloat16
records are compared with the float32 ones exactly as a run compares the
served records.  The smallest gap over the seeds is the upper
reading the cell's limit must stay below.

Usage, on the chip, from the root of a checkout::

    python3 benchmarks/chip/control.py --workload dstudy.study --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def first_requests(cell: str, seed: int, layout) -> tuple[list, dict]:
    """The cell's first distinct request specs for ``seed``, and its config."""
    from benchmarks.chip import traffic
    spec = layout.read("workloads", cell)
    config = layout.read("configs", spec["config"])
    mix = layout.read("traffic", spec["traffic"])
    n = int(spec["check"]["requests"])
    src = traffic.Source(mix, config, seed, seconds=1e9 if mix["loop"] ==
                         "closed" else max(1.0, n / float(
                             mix["session_rate_per_s"])))
    out, t = [], 0.0
    while len(out) < n and src.next_due() is not None:
        for r in src.take_due(src.next_due()):
            if r.spec not in out:
                out.append(r.spec)
            src.answered(r, t)
        t += 1.0
    return out[:n], config


def control_gap(cell: str, seed: int, layout) -> float:
    """Widest gap of the bfloat16 reference against the float32 one."""
    import jax.numpy as jnp
    from benchmarks.chip import compare
    specs, config = first_requests(cell, seed, layout)
    ref = layout.reference(config)
    pad = compare.pad_rows(specs)
    worst = 0.0
    for spec in specs:
        want = compare.reference_records(ref, config, spec, pad)
        got = compare.reference_records(ref, config, spec, pad, jnp.bfloat16)
        worst = max(worst, compare.records_gap(got, want,
                                               config["compare_fields"]))
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness
    harness.check_platform(1)
    harness.enable_compile_cache()
    layout = harness.Layout()
    for seed in args.seeds:
        gap = control_gap(args.workload, seed, layout)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_records_rel_gap": gap}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
