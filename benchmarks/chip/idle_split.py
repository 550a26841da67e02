#!/usr/bin/env python3
"""Split a traced window's device idle time by the program's own spans.

The harness labels idle gaps with its own host spans only, and the sweep
service's whole pass falls inside one of them (``service.step``).  This
script serves one cell's window as the harness does, under the profiler,
with the program's span sink installed (``repro.obs.ProfilerRecorder``): the
service's phase spans (``pass.state``, ``pass.burn``, ``pass.stats.fetch``,
...) then land on the trace's host plane, on the device ops' clock.  Each
instant in which a device runs no op is attributed to the innermost program
span open at that instant, else to the innermost harness span, else to
``"none"``, and the attributions are grouped into the shares that say what
the host was doing: moving burned state, moving and reducing stats, or
dispatching (tracing, lowering, enqueueing) the engine's passes.

Usage, on the chip, from the root of a checkout::

    python3 benchmarks/chip/idle_split.py --workload dstudy.study \\
        --seed 7 --seconds 20 [--trace 0]

Prints one JSON line.  ``--trace 0`` serves the same window with neither
the profiler nor the sink, for what tracing costs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]

#: every span the sweep service emits (``repro.service.api``), and the
#: share of idle time it falls in; "other" counts in no share
PROGRAM_SPANS = {
    "service.schedule": "other",
    "pass": "other",
    "pass.state": "state",
    "pass.state.lookup": "state",
    "pass.state.fetch": "state",
    "pass.state.put": "state",
    "pass.state.splice": "state",
    "pass.state.upload": "state",
    "pass.burn": "dispatch",
    "pass.measure": "dispatch",
    "pass.stats.fetch": "stats",
    "pass.stats.reduce": "stats",
    "service.flush": "stats",
}
#: per-layer metric name of each share
SHARES = {"state": "service.idle_pct.state",
          "stats": "service.idle_pct.stats",
          "dispatch": "engine.idle_pct.dispatch"}


def program_spans(path: str) -> list:
    """[(name, start, end)] of the program's spans in a trace file, in
    seconds from the start of its ``window`` span (clipped to it)."""
    from jax.profiler import ProfileData

    from benchmarks.chip import trace_reduce
    with open(path, "rb") as fh:
        data = ProfileData.from_serialized_xspace(fh.read())
    window, raw = None, []
    for plane in data.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == trace_reduce.WINDOW_SPAN and window is None:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name in PROGRAM_SPANS:
                    raw.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"{path}: no {trace_reduce.WINDOW_SPAN!r} span")
    t0, t1 = window
    return [(n, (max(s, t0) - t0) * 1e-9, (min(e, t1) - t0) * 1e-9)
            for n, s, e in raw if e > t0 and s < t1]


def _labels(window_s: float, spans: list, host: list) -> dict:
    """Label -> disjoint intervals of the window in which it is innermost.

    The innermost span at an instant is the latest-starting program span
    open there, else the latest-starting harness span, else ``"none"``.
    """
    def innermost(group, m):
        best = None
        for name, s, e in group:
            if s <= m < e and (best is None or s > best[1]
                               or (s == best[1] and e < best[2])):
                best = (name, s, e)
        return best and best[0]

    cuts = sorted({0.0, window_s, *(t for _, s, e in (*spans, *host)
                                    for t in (s, e)
                                    if 0.0 < t < window_s)})
    out: dict[str, list] = {}
    for a, b in zip(cuts[:-1], cuts[1:]):
        m = 0.5 * (a + b)
        label = innermost(spans, m) or innermost(host, m) or "none"
        iv = out.setdefault(label, [])
        if iv and iv[-1][1] == a:
            iv[-1][1] = b
        else:
            iv.append([a, b])
    return {k: np.array(v, np.float64).reshape(-1, 2)
            for k, v in out.items()}


def idle_by_span(trace, spans: list, devs) -> list:
    """[[label, seconds]] of device idle time by the innermost open span,
    averaged over ``devs``, the largest first."""
    from benchmarks.chip import trace_reduce
    labels = _labels(trace.window_s, spans, trace.host)
    tot: dict[str, float] = {}
    for d in devs:
        busy = trace_reduce.busy(trace, d)
        for label, iv in labels.items():
            tot[label] = tot.get(label, 0.0) + trace_reduce.length(
                trace_reduce.subtract(iv, busy)) / len(devs)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            if v > 0]


def idle_shares(trace, spans: list, devs, answered: int) -> dict:
    """The three idle shares and the rest, in % of the window.

    A window that answered requests but holds no program span is an error,
    not a zero: the sink was not installed, or the spans were renamed.
    """
    if answered and not spans:
        raise ValueError(f"{answered} requests answered but the trace holds "
                         f"none of the program's spans {sorted(PROGRAM_SPANS)}")
    split = idle_by_span(trace, spans, devs)
    pct = {name: 0.0 for name in SHARES.values()}
    pct["other"] = 0.0
    for label, secs in split:
        group = PROGRAM_SPANS.get(label, "other")
        pct[SHARES.get(group, "other")] += 100.0 * secs / trace.window_s
    idle = sum(secs for _, secs in split)
    # bare "pass" is the host work between its phases: not a named phase
    named = sum(secs for label, secs in split
                if label in PROGRAM_SPANS and label != "pass")
    return {"shares": pct, "idle_by_span": split,
            "named_pct_of_idle": 100.0 * named / idle if idle else None}


def run(cell: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, layout=None, require_tpu: bool = True) -> dict:
    """Serve one window of ``cell`` and return what this script prints."""
    from benchmarks.chip import harness
    from repro.obs import ProfilerRecorder, set_tracer
    layout = layout or harness.Layout()
    spec = layout.read("workloads", cell)
    config = layout.read("configs", spec["config"])
    mix = layout.read("traffic", spec["traffic"])
    chips = int(spec["chips"])
    if require_tpu:
        harness.check_platform(chips)
        harness.enable_compile_cache()
    keep = tempfile.mkdtemp(prefix="idle-split-") if trace else None
    prev = set_tracer(ProfilerRecorder()) if trace else None
    try:
        with harness.compile_events() as compile_times:
            r, peak = harness.measure(cell, config, mix, chips, seed,
                                      seconds, trace, t_start, keep,
                                      compile_times)
        spans = program_spans(str(next(pathlib.Path(keep).glob(
            "*.xplane.pb")))) if trace else []
    finally:
        if trace:
            set_tracer(prev)
            shutil.rmtree(keep, ignore_errors=True)
    names = ["pe_steps_per_s", "engine.traces_per_request"]
    if trace:
        names.append("device.idle_pct.rate")
    out = {"workload": cell, "seed": seed, "seconds": seconds,
           "trace": int(trace),
           "metrics": {n: layout.reader(n)(r) for n in names},
           "answered": sum(1 for s in r.served if s.ok),
           "n_program_spans": len(spans),
           "stats": r.stats, "compiles_in_window": r.compiles,
           "memory_peak_bytes": peak}
    if trace and r.trace is not None and r.trace.ops:
        split = idle_shares(r.trace, spans, r.devices, out["answered"])
        out["metrics"].update(split.pop("shares"))
        out.update(split)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start=T_START)
    except harness.BenchError as e:
        print(f"idle_split: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
