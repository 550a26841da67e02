"""Plain reference of a served Δ-window sweep, independent of the program.

A straightforward implementation of what one sweep request means, written
from the paper and the service's documented request semantics, importing
nothing of ``repro``:

* the counter event stream: two uint32 words per (seed, step, trial, PE)
  from murmur3 fmix32 absorb rounds, decoded to a site pick (uniform over
  the N_V sites; the border sites are 0 and N_V - 1) and an Exp(1) time
  increment ``-log(u + 2**-25)`` with ``u`` from the top 24 bits;
* Eq. (1), the conservative rule: a PE that picked a border site updates
  only if its local time does not exceed the adjacent neighbour's;
* Eq. (3), the moving window: ``tau_k <= Δ + GVT`` with ``GVT = min tau``;
* the rebasing schedule: every ``k_fuse`` steps the ring minimum is
  subtracted and added to an offset with Kahan compensation;
* the per-step observables (utilization, Eq. (4) width, Eq. (5) absolute
  width, GVT, extreme deviations) and their steady-state reduction per Δ:
  the trailing ``steady_frac`` of the measured steps, mean and standard
  error over replicas, GVT growth rate by least squares.

Rows of a request are laid out Δ-major, replica-minor on the trial axis
(trial ``w * replicas + r`` runs window ``w``).  The reference runs them
from scratch: burn-in, then the measured steps, in chunks of ``k_fuse``
steps, padded to a fixed row count so that each shape compiles once.

``dtype`` is the precision of the virtual times; ``jnp.bfloat16`` is the
control that the comparison must reject.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_FMIX1 = np.uint32(0x85EBCA6B)
_FMIX2 = np.uint32(0xC2B2AE35)
_SEED_C = np.uint32(0x9E3779B9)
_STEP_C = np.uint32(0x27D4EB2F)
_TRIAL_C = np.uint32(0x165667B1)
_PE_C = np.uint32(0xD3A2646C)
_WORD0_C = np.uint32(0x68E31DA4)
_WORD1_C = np.uint32(0xB5297A4D)

#: what this reference implements (the contract in ``compare.py``): the
#: exact window, and no configuration ``sweep_fields``
WINDOWS = ("exact",)
SWEEP_FIELDS = ()

FIELDS = ("u", "u_err", "w2", "w2_err", "w", "wa", "spread", "rate",
          "rate_err")


def _fmix32(h):
    h = h ^ (h >> np.uint32(16))
    h = h * _FMIX1
    h = h ^ (h >> np.uint32(13))
    h = h * _FMIX2
    return h ^ (h >> np.uint32(16))


def event_words(seed, step, trial, pe):
    """The two uint32 event words at (seed, step, trial, PE); all uint32."""
    h = _fmix32(seed ^ _SEED_C)
    h = _fmix32(h ^ (step * _STEP_C))
    h = _fmix32(h ^ (trial * _TRIAL_C))
    h = _fmix32(h ^ (pe * _PE_C))
    return _fmix32(h ^ _WORD0_C), _fmix32(h ^ _WORD1_C)


@functools.partial(jax.jit, static_argnames=("n_v", "k_steps", "record"))
def _chunk(tau, offset, comp, step0, seed, trials, deltas, *, n_v: int,
           k_steps: int, record: bool):
    """``k_steps`` steps of every ring, then one rebase.

    tau: (B, L) rebased local times; offset/comp: (B,) Kahan pair;
    trials: (B,) uint32 stream rows; deltas: (B,) window widths.
    Returns the new state and, when ``record``, per-step (k, B) columns.
    """
    dtype = tau.dtype
    B, L = tau.shape
    pe = jnp.arange(L, dtype=jnp.uint32)[None, :]
    rows = trials[:, None]
    delta = deltas.astype(dtype)[:, None]
    seed = seed.astype(jnp.uint32)

    def one(tau, s):
        w0, w1 = event_words(seed, s.astype(jnp.uint32), rows, pe)
        site = w0 % np.uint32(n_v)
        picks_left = site == 0
        picks_right = site == n_v - 1
        u = (w1 >> np.uint32(8)).astype(jnp.int32).astype(dtype) * 2.0**-24
        eta = -jnp.log(u + 2.0**-25)
        left = jnp.roll(tau, 1, axis=1)
        right = jnp.roll(tau, -1, axis=1)
        gvt = jnp.min(tau, axis=1, keepdims=True)
        eq1 = (~picks_left | (tau <= left)) & (~picks_right | (tau <= right))
        eq3 = tau <= delta + gvt
        moves = eq1 & eq3
        tau = tau + jnp.where(moves, eta, 0.0)
        if not record:
            return tau, None
        mean = jnp.mean(tau, axis=1, keepdims=True)
        dev = tau - mean
        lo = jnp.min(tau, axis=1)
        obs = (jnp.mean(moves.astype(dtype), axis=1),
               jnp.mean(dev * dev, axis=1),
               jnp.mean(jnp.abs(dev), axis=1),
               lo + offset,
               jnp.max(dev, axis=1),
               mean[:, 0] - lo)
        return tau, obs

    tau, obs = lax.scan(one, tau, step0 + jnp.arange(k_steps, dtype=jnp.int32))
    shift = jnp.min(tau, axis=1)
    tau = tau - shift[:, None]
    y = shift - comp
    t = offset + y
    comp = (t - offset) - y
    return tau, t, comp, obs


def _chunks(n_steps: int, k: int):
    k = max(1, min(k, n_steps))
    q, rem = divmod(n_steps, k)
    return [k] * q + ([rem] if rem else [])


def simulate(trials, deltas, *, L: int, n_v: int, k_fuse: int, seed: int,
             burn_in: int, n_steps: int, dtype=jnp.float32, device=None):
    """Burn in, then measure; returns per-step observables as float64 numpy.

    The dict holds (n_steps, B) arrays: ``u``, ``w2``, ``wa``, ``gvt``,
    ``max_dev``, ``min_dev``.
    """
    B = len(trials)
    put = functools.partial(jax.device_put, device=device)
    tau = put(jnp.zeros((B, L), dtype))
    off = put(jnp.zeros((B,), dtype))
    comp = put(jnp.zeros((B,), dtype))
    tr = put(jnp.asarray(np.asarray(trials, np.uint32)))
    dl = put(jnp.asarray(np.asarray(deltas, np.float32)))
    sd = jnp.uint32(seed)
    step = 0
    for k in _chunks(burn_in, k_fuse) if burn_in else []:
        tau, off, comp, _ = _chunk(tau, off, comp, jnp.int32(step), sd, tr,
                                   dl, n_v=n_v, k_steps=k, record=False)
        step += k
    pieces = []
    for k in _chunks(n_steps, k_fuse):
        tau, off, comp, obs = _chunk(tau, off, comp, jnp.int32(step), sd, tr,
                                     dl, n_v=n_v, k_steps=k, record=True)
        pieces.append(obs)
        step += k
    names = ("u", "w2", "wa", "gvt", "max_dev", "min_dev")
    return {name: np.concatenate(
        [np.asarray(p[i], np.float64) for p in pieces], axis=0)
        for i, name in enumerate(names)}


def _slope(g):
    """Least-squares slope of each column of ``g`` against its row index."""
    t = np.arange(g.shape[0], dtype=np.float64)
    t = t - t.mean()
    return (t[:, None] * (g - g.mean(axis=0))).sum(axis=0) / (t * t).sum()


def reduce_records(obs: dict, n_windows: int, replicas: int,
                   steady_frac: float) -> list[dict]:
    """Per-Δ steady-state estimates from (T, n_windows * replicas) columns."""
    T = obs["u"].shape[0]
    t0 = min(T - 1, int(round(T * (1.0 - steady_frac))))

    def per_row(x):
        return x[t0:].mean(axis=0).reshape(n_windows, replicas)

    def mean_err(x):
        e = (x.std(axis=1, ddof=1) / math.sqrt(replicas) if replicas > 1
             else np.zeros(n_windows))
        return x.mean(axis=1), e

    u, u_err = mean_err(per_row(obs["u"]))
    w2_rows = per_row(obs["w2"])
    w2, w2_err = mean_err(w2_rows)
    rate, rate_err = mean_err(_slope(obs["gvt"][t0:]).reshape(n_windows,
                                                              replicas))
    spread = per_row(obs["max_dev"] + obs["min_dev"]).mean(axis=1)
    cols = dict(u=u, u_err=u_err, w2=w2, w2_err=w2_err,
                w=np.sqrt(w2_rows).mean(axis=1),
                wa=per_row(obs["wa"]).mean(axis=1), spread=spread,
                rate=rate, rate_err=rate_err)
    return [{f: float(cols[f][w]) for f in FIELDS} for w in range(n_windows)]


def sweep_records(spec: dict, *, pad_rows: int | None = None,
                  dtype=jnp.float32, device=None) -> list[dict]:
    """The records one single-(L, N_V) sweep request should be answered with.

    ``spec`` holds ``L``, ``n_v``, ``deltas`` (floats, ``inf`` allowed),
    ``replicas``, ``burn_in``, ``n_steps``, ``k_fuse``, ``seed`` and
    ``steady_frac``.  ``pad_rows`` pads the batch with unconstrained rows on
    stream rows past the request's, which are dropped: the shape then does
    not depend on the request.
    """
    R, deltas = int(spec["replicas"]), [float(d) for d in spec["deltas"]]
    n = len(deltas) * R
    trials = list(range(n))
    dcol = [d for d in deltas for _ in range(R)]
    if pad_rows is not None and pad_rows > n:
        trials += list(range(n, pad_rows))
        dcol += [math.inf] * (pad_rows - n)
    obs = simulate(trials, dcol, L=int(spec["L"]), n_v=int(spec["n_v"]),
                   k_fuse=int(spec["k_fuse"]), seed=int(spec["seed"]),
                   burn_in=int(spec["burn_in"]), n_steps=int(spec["n_steps"]),
                   dtype=dtype, device=device)
    obs = {k: v[:, :n] for k, v in obs.items()}
    return reduce_records(obs, len(deltas), R, float(spec["steady_frac"]))
