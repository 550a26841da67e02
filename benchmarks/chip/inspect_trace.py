#!/usr/bin/env python3
"""Print the structure of a profiler trace: planes, lines, busiest event names.

Usage::

    python3 benchmarks/chip/inspect_trace.py TRACE.xplane.pb [--top 25]

For reading a trace by hand before changing what ``trace_reduce.py``
matches on (device plane and line names, kernel and collective op names).
"""
from __future__ import annotations

import argparse
import collections


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData
    with open(args.path, "rb") as fh:
        data = ProfileData.from_serialized_xspace(fh.read())
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            tot = collections.Counter()
            cnt = collections.Counter()
            n = 0
            for e in line.events:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
                n += 1
            print(f"  line {line.name!r}: {n} events")
            for name, ns in tot.most_common(args.top):
                print(f"    {ns / 1e6:12.3f} ms  x{cnt[name]:<6d} {name[:160]}")


if __name__ == "__main__":
    main()
