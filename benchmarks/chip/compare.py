"""The comparison that decides ``correct``: served records against the reference.

A sample of the answered requests, drawn from the run's seed and always
holding the one that asked for the most PE-steps, is recomputed by the
configuration's plain reference from its spec alone.  The number compared
is ``records_rel_gap``: the widest relative gap, over the sampled requests,
their per-Δ records and the configuration's ``compare_fields``, between what
the requester received and what the reference computes.  A record that is
missing, has another Δ, or is not finite where the reference is, reads as an
infinite gap.  A list-valued field (a time series) compares element by
element; another length, or a list where the reference has a scalar, reads
as infinite.  Where the check names ``limit`` it comes from the cell file.

The reference is the module the configuration names under ``reference``
(default ``reference``, i.e. ``reference.py``), found by
``harness.Layout.reference``.  It imports nothing of the program and
declares what it implements: ``SWEEP_FIELDS``, the ``sweep_fields`` keys it
reads, and ``WINDOWS``, the ``window`` values.  Its
``sweep_records(spec, *, pad_rows, dtype)`` gets ``L``, ``n_v``,
``deltas``, ``replicas``, ``burn_in``, ``n_steps``, ``k_fuse``, ``seed``,
``steady_frac``, ``window`` and every ``sweep_fields`` key of the
configuration, and returns one record (a dict of fields) per Δ.
"""
from __future__ import annotations

import math
import sys

import numpy as np

from . import traffic

_SEQ = (list, tuple)


def rel_gap(got, want) -> float:
    """Relative gap of two numbers, or the widest of two lists' elements."""
    if isinstance(got, _SEQ) or isinstance(want, _SEQ):
        if not (isinstance(got, _SEQ) and isinstance(want, _SEQ)
                and len(got) == len(want)):
            return math.inf
        return max(map(rel_gap, got, want), default=0.0)
    if not math.isfinite(got):
        return 0.0 if got == want else math.inf
    return abs(got - want) / abs(want) if want else abs(got - want)


def field_gaps(got: list[dict], want: list[dict], fields) -> dict:
    """Widest relative gap per field between two lists of per-Δ records."""
    if len(got) != len(want) or any(a["delta"] != b["delta"]
                                    for a, b in zip(got, want)):
        return {f: math.inf for f in fields}
    return {f: max(rel_gap(a[f], b[f]) for a, b in zip(got, want))
            for f in fields}


def records_gap(got: list[dict], want: list[dict], fields) -> float:
    """Widest relative gap between two lists of per-Δ records."""
    return max(field_gaps(got, want, fields).values())


def sample(served, n: int, seed: int) -> list:
    """Up to ``n`` answered requests of distinct specs, the longest first."""
    by_spec = {}
    for s in served:
        if s.ok:
            by_spec.setdefault(repr(sorted(s.req.spec.items())), s)
    pool = list(by_spec.values())
    if not pool:
        return []
    longest = max(pool, key=lambda s: traffic.pe_steps(s.req.spec))
    rest = [s for s in pool if s is not longest]
    rng = np.random.default_rng([seed, 0x5eed])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_records(ref, config: dict, spec: dict, pad_rows: int,
                      dtype=None) -> list[dict]:
    """The records reference module ``ref`` computes for request ``spec``."""
    import jax.numpy as jnp
    ref_spec = dict(L=spec["Ls"][0], n_v=spec["n_vs"][0],
                    deltas=spec["deltas"], replicas=spec["replicas"],
                    burn_in=spec["burn_in"], n_steps=spec["n_steps"],
                    k_fuse=spec["k_fuse"], seed=spec["seed"],
                    steady_frac=spec["steady_frac"], window=spec["window"])
    ref_spec.update(config.get("sweep_fields", {}))
    recs = ref.sweep_records(
        ref_spec, pad_rows=pad_rows,
        dtype=jnp.float32 if dtype is None else dtype)
    return [dict(r, delta=float(d)) for r, d in zip(recs, spec["deltas"])]


def pad_rows(specs) -> int:
    """The row count every reference run of a cell is padded to."""
    return max(len(s["deltas"]) * s["replicas"] for s in specs)


def check(served, config: dict, check_spec: dict, seed: int, ref) -> dict:
    """``{"records_rel_gap": {"value": ..., "limit": ...}}`` for one run."""
    fields = config["compare_fields"]
    worst = dict.fromkeys(fields, 0.0 if served else math.inf)
    if served:
        pad = pad_rows(s.req.spec for s in served)
    for s in sample(served, int(check_spec["requests"]), seed):
        want = reference_records(ref, config, s.req.spec, pad)
        got = [vars(r) for r in s.response.result.records]
        for f, g in field_gaps(got, want, fields).items():
            worst[f] = max(worst[f], g)
    print("chipbench: widest gap per field "
          + " ".join(f"{f}={g:.3e}" for f, g in worst.items()),
          file=sys.stderr, flush=True)
    return {"records_rel_gap": {"value": max(worst.values()),
                                "limit": float(check_spec["limit"])}}
