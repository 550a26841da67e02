#!/usr/bin/env python3
"""Find the highest session rate an open-loop cell sustains: a rate sweep.

For each rate, in one process after one warm-up, the cell's mix is served
at that rate for ``--seconds`` and the backlog is read: how late answers
come as the window goes on (the slope of latency against due time) and how
many requests due in the window are still unanswered when it closes.  The
knee is the highest rate whose backlog does not grow; the cell's mix then
states 0.8 of it as a number.

Usage, on the chip, from the root of a checkout::

    python3 benchmarks/chip/knee.py --workload <open-loop cell> \\
        --rates 1 2 3 4 --seconds 20 --seed 5
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]


def sweep_rate(config, mix, rate, seed, seconds, clock0):
    from benchmarks.chip import harness, traffic
    service = harness.make_service(config)

    def clock():
        return time.perf_counter() - clock0[0]

    src = traffic.Source(dict(mix, session_rate_per_s=rate), config, seed,
                         seconds)
    client = harness.Client(service, clock, src)
    clock0[0] = time.perf_counter()
    client.run_window(lambda name: contextlib.nullcontext())
    due = np.array([s.req.due for s in client.served])
    lat = np.array([s.answered - s.req.due for s in client.served])
    late = sum(s.answered > seconds for s in client.served)
    slope = float(np.polyfit(due, lat, 1)[0]) if len(due) > 2 else None
    return {"rate": rate, "requests": len(lat),
            "sessions": sum(s.req.kind == "study" for s in client.served),
            "p50_s": float(np.percentile(lat, 50)),
            "p95_s": float(np.percentile(lat, 95)),
            "latency_slope": slope, "unanswered_at_close": int(late),
            "drain_s": float(max(s.answered for s in client.served)
                             - seconds)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness
    layout = harness.Layout()
    cell = layout.read("workloads", args.workload)
    config = layout.read("configs", cell["config"])
    mix = layout.read("traffic", cell["traffic"])
    harness.check_platform(int(cell["chips"]))
    harness.enable_compile_cache()
    clock0 = [time.perf_counter()]
    harness.warm_up(config, mix, lambda: time.perf_counter() - clock0[0])
    for rate in args.rates:
        print(json.dumps(sweep_rate(config, mix, rate, args.seed,
                                    args.seconds, clock0)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
