"""Reduction of a profiler trace to device busy time, kernel and collective time.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but JAX.
Device planes are named ``/device:TPU:<n>``; each op the device ran is an
event on its ``XLA Ops`` line.  The host's plane carries the benchmark's own
``jax.profiler.TraceAnnotation`` spans, on the same clock, and the span
named ``window`` bounds the measured window.

All times are seconds.  Intervals are clipped to the window before they are
merged, so an op that straddles an edge counts only inside it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"
#: host spans that label what the host was doing in a device idle gap
HOST_SPANS = ("intake", "flush_ready", "service.step", "generator_wait")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"ppermute|psum|pmin|pmax")


@dataclasses.dataclass
class Trace:
    """Events of one traced window, in seconds from the window's start."""

    window_s: float
    #: per device id: (names, starts, ends) of its ops
    ops: dict
    #: host spans: (name, start, end), innermost last among equals
    host: list


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` that ``jax.profiler`` wrote under a dir."""
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read a trace file into window-relative op and host-span intervals."""
    from jax.profiler import ProfileData
    with open(path, "rb") as fh:
        data = ProfileData.from_serialized_xspace(fh.read())
    host, window = [], None
    dev_raw = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev_raw[int(m.group(1))] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN and window is None:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in HOST_SPANS:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} host span")
    t0, t1 = window
    ops = {}
    for dev, evs in dev_raw.items():
        names = [n for n, s, e in evs if e > t0 and s < t1]
        se = np.array([(max(s, t0), min(e, t1)) for n, s, e in evs
                       if e > t0 and s < t1], np.float64).reshape(-1, 2)
        ops[dev] = (names, (se[:, 0] - t0) * 1e-9, (se[:, 1] - t0) * 1e-9)
    host = [(n, (s - t0) * 1e-9, (e - t0) * 1e-9) for n, s, e in host
            if e > t0 and s < t1]
    return Trace(window_s=(t1 - t0) * 1e-9, ops=ops, host=host)


def merge(starts, ends) -> np.ndarray:
    """Union of intervals as a sorted (n, 2) array of disjoint intervals."""
    if len(starts) == 0:
        return np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts)[order], np.asarray(ends)[order]
    out = []
    cs, ce = s[0], e[0]
    for a, b in zip(s[1:], e[1:]):
        if a <= ce:
            ce = max(ce, b)
        else:
            out.append((cs, ce))
            cs, ce = a, b
    out.append((cs, ce))
    return np.array(out)


def length(iv: np.ndarray) -> float:
    """Total length of disjoint intervals."""
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Parts of the disjoint intervals ``a`` not covered by disjoint ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return np.array(out).reshape(-1, 2)


def _ops(trace: Trace, dev: int):
    return trace.ops.get(dev, ([], np.zeros(0), np.zeros(0)))


def busy(trace: Trace, dev: int) -> np.ndarray:
    """Disjoint intervals in which ``dev`` ran an op."""
    names, s, e = _ops(trace, dev)
    return merge(s, e)


def busy_s(trace: Trace, devs) -> float:
    """Busy seconds, the union of op intervals, averaged over ``devs``."""
    return float(np.mean([length(busy(trace, d)) for d in devs]))


def op_seconds(trace: Trace, devs, pattern: re.Pattern) -> float:
    """Summed durations of ops whose name matches, totalled over ``devs``."""
    tot = 0.0
    for d in devs:
        names, s, e = _ops(trace, d)
        tot += sum(float(b - a) for n, a, b in zip(names, s, e)
                   if pattern.search(n))
    return tot


def exposed_collective_s(trace: Trace, devs) -> float:
    """Seconds in which a collective runs and no other op, mean over devs."""
    out = []
    for d in devs:
        names, s, e = _ops(trace, d)
        coll = np.array([bool(COLLECTIVE.search(n)) for n in names], bool)
        c = merge(s[coll], e[coll]) if coll.any() else np.zeros((0, 2))
        other = merge(s[~coll], e[~coll]) if (~coll).any() \
            else np.zeros((0, 2))
        out.append(length(subtract(c, other)))
    return float(np.mean(out))


def top_ops(trace: Trace, devs, n: int = 10) -> list:
    """[[op name, seconds]] of the ops that took most time, mean over devs."""
    tot: dict[str, float] = {}
    for d in devs:
        names, s, e = _ops(trace, d)
        for name, a, b in zip(names, s, e):
            tot[name] = tot.get(name, 0.0) + float(b - a) / len(devs)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, dev: int, n: int = 10) -> list:
    """[[host span, seconds]] of the longest idle gaps on ``dev``.

    A gap is labelled with the host span that covers most of it (the
    innermost on a tie), or ``"none"``.
    """
    b = busy(trace, dev)
    whole = np.array([[0.0, trace.window_s]])
    gaps = subtract(whole, b)
    if not len(gaps):
        return []
    order = np.argsort(gaps[:, 1] - gaps[:, 0])[::-1][:n]
    out = []
    for s, e in gaps[order]:
        best, cover = "none", 0.0
        for name, hs, he in trace.host:
            c = min(e, he) - max(s, hs)
            if c > 0 and c >= cover:
                best, cover = name, c
        out.append([best, float(e - s)])
    return out
