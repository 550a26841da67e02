"""Ensemble orchestration: steady-state sweeps over (L, N_V, Δ).

Host-side drivers around the jitted scan kernels in ``horizon``.  These are
what the paper calls "simulations of the simulations": each call simulates an
ensemble of independent PDES rings and extracts configurational averages.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np

from . import horizon
from .horizon import PDESConfig
from ..obs.trace import span as _span


def _sync_if_traced(sp, tree) -> None:
    """Await device work inside a live span (honest phase attribution).

    Inert when no ambient tracer is installed, so untraced runs keep
    JAX's async dispatch; values are identical either way.
    """
    if sp is not None:
        jax.block_until_ready(tree)


@dataclasses.dataclass
class SteadyState:
    """Time- and ensemble-averaged steady-state observables."""

    cfg: PDESConfig
    n_trials: int
    burn_in_steps: int
    measure_steps: int
    utilization: float
    utilization_err: float
    w: float          # <w> = <sqrt(w2)>  (ensemble avg of per-trial widths)
    w2: float         # <w^2>
    wa: float         # <w_a>
    rate: float       # GVT growth rate per parallel step


def default_burn_in(cfg: PDESConfig) -> int:
    """Heuristic burn-in long enough to pass the crossover.

    Unconstrained KPZ: t_x ~ L^{3/2}; constrained: saturation at t_p = O(Δ·N_V)
    (width reaches ~Δ after ~Δ mean increments, each taking ~N_V picks to hit
    a border).  We take a safety factor over both.
    """
    if math.isinf(cfg.delta):
        t = 4.0 * (cfg.L ** 1.5)
    else:
        t = 60.0 * max(cfg.delta, 1.0) * max(1.0, math.sqrt(cfg.n_v)) + 2.0 * cfg.L
    return int(min(max(t, 200), 2_000_000))


def steady_state(
    cfg: PDESConfig,
    *,
    n_trials: int = 64,
    seed: int = 0,
    burn_in_steps: int | None = None,
    measure_steps: int | None = None,
    backend: str | None = None,
    engine_opts: dict | None = None,
) -> SteadyState:
    """Burn in, then time-average StepStats over ``measure_steps``.

    ``backend=None`` keeps the legacy jax.random-keyed ``horizon`` scan
    (trajectories identical to prior releases); any engine backend name
    ("reference", "pallas", "pallas_multistep", "sharded") routes through
    ``PDESEngine`` on the counter event stream — statistically equivalent,
    and the fused backends are the fast path at scale.  ``engine_opts`` is
    forwarded to the ``PDESEngine`` constructor (window, k_fuse, mesh, ...).
    """
    if burn_in_steps is None:
        burn_in_steps = default_burn_in(cfg)
    if measure_steps is None:
        measure_steps = max(200, burn_in_steps // 4)
    point = {"L": cfg.L, "n_v": cfg.n_v, "rows": n_trials}
    if backend is None:
        key = jax.random.key(seed)
        k_burn, k_meas = jax.random.split(key)
        state = horizon.init_state(cfg, n_trials)
        with _span("burn", args=dict(point, steps=burn_in_steps)) as sp:
            state = horizon.burn_in(state, k_burn, cfg, burn_in_steps)
            _sync_if_traced(sp, state)
        g0 = np.asarray(state.offset)  # GVT at measurement start (tau rebased)
        with _span("measure", args=dict(point, steps=measure_steps)) as sp:
            state, stats = horizon.run_mean(state, k_meas, cfg,
                                            measure_steps)
            _sync_if_traced(sp, stats)
    else:
        from .engine import PDESEngine
        eng = PDESEngine(cfg, backend=backend, **(engine_opts or {}))
        with _span("burn", args=dict(point, steps=burn_in_steps)) as sp:
            state = eng.burn_in(eng.init(n_trials), seed, burn_in_steps)
            _sync_if_traced(sp, state)
        g0 = np.asarray(state.offset) + np.asarray(state.tau).min(axis=-1)
        with _span("measure", args=dict(point, steps=measure_steps)) as sp:
            state, stats = eng.run_mean(state, seed, measure_steps)
            _sync_if_traced(sp, stats)
    with _span("reduce", args=point):
        u = np.asarray(stats.utilization)
        w2 = np.asarray(stats.w2)
        g1 = np.asarray(state.offset) + np.asarray(state.tau).min(axis=-1)
    return SteadyState(
        cfg=cfg,
        n_trials=n_trials,
        burn_in_steps=burn_in_steps,
        measure_steps=measure_steps,
        utilization=float(u.mean()),
        utilization_err=float(u.std(ddof=1) / np.sqrt(n_trials)),
        w=float(np.sqrt(w2).mean()),
        w2=float(w2.mean()),
        wa=float(np.asarray(stats.wa).mean()),
        rate=float((g1 - g0).mean() / measure_steps),
    )


def steady_state_sweep(
    cfg: PDESConfig,
    deltas: Sequence[float],
    *,
    n_trials: int = 64,
    seed: int = 0,
    burn_in_steps: int | None = None,
    measure_steps: int | None = None,
    backend: str = "reference",
    engine_opts: dict | None = None,
) -> list[SteadyState]:
    """Per-Δ steady states from ONE batched engine pass (window-sweep path).

    Thin ``SteadyState`` adapter over ``repro.experiments``: the Δ axis
    rides on the ensemble axis, so all ``len(deltas) * n_trials``
    trajectories advance together instead of looping ``steady_state`` per
    Δ.  ``cfg.delta`` is ignored; each returned ``SteadyState`` carries its
    own ``cfg`` with the row's Δ.  The whole recorded measurement span is
    averaged (``steady_frac=1.0``), matching the ``steady_state``
    convention; ``rate`` is the least-squares GVT slope of
    ``measurement.progress_rate`` rather than the endpoint quotient.

    ``engine_opts`` accepts the engine options a batched sweep supports —
    ``window``, ``k_fuse``, and (for ``backend="sharded"``) ``mesh`` /
    ``dist``, which route to ``experiments.sweep.run_window_sweep``'s mesh
    execution path.  ``steady_state``'s remaining engine options
    (``block_b``: not spec-level) are rejected explicitly
    rather than silently dropped.
    """
    from ..experiments.sweep import WindowSweep, run_window_sweep
    if burn_in_steps is None:
        burn_in_steps = max(
            default_burn_in(dataclasses.replace(cfg, delta=float(d)))
            for d in deltas)
    if measure_steps is None:
        measure_steps = max(200, burn_in_steps // 4)
    opts = dict(engine_opts or {})
    mesh = opts.pop("mesh", None)
    dist = opts.pop("dist", None)
    unsupported = sorted(set(opts) - {"window", "k_fuse"})
    if unsupported:
        raise ValueError(
            f"steady_state_sweep supports engine_opts 'window', 'k_fuse', "
            f"'mesh' and 'dist' only; got {unsupported}")
    spec = WindowSweep(
        Ls=(cfg.L,), n_vs=(cfg.n_v,), deltas=tuple(float(d) for d in deltas),
        replicas=n_trials, n_steps=measure_steps, burn_in=burn_in_steps,
        backend=backend, rd_mode=cfg.rd_mode,
        border_both=cfg.border_both, steady_frac=1.0, seed=seed, **opts)
    result = run_window_sweep(spec, mesh=mesh, dist=dist)
    out = []
    for d in deltas:
        (rec,) = result.select(delta=float(d))
        out.append(SteadyState(
            cfg=dataclasses.replace(cfg, delta=float(d)),
            n_trials=n_trials,
            burn_in_steps=burn_in_steps,
            measure_steps=measure_steps,
            utilization=rec.u,
            utilization_err=rec.u_err,
            w=rec.w,
            w2=rec.w2,
            wa=rec.wa,
            rate=rec.rate,
        ))
    return out


def utilization_vs_L(
    Ls: Sequence[int],
    *,
    n_v: int = 1,
    delta: float = math.inf,
    rd_mode: bool = False,
    n_trials: int = 64,
    seed: int = 0,
    burn_in_steps: int | None = None,
    measure_steps: int | None = None,
    backend: str | None = None,
    engine_opts: dict | None = None,
):
    """Steady-state utilization for a range of ring sizes (Figs. 2, 5)."""
    out = []
    for i, L in enumerate(Ls):
        cfg = PDESConfig(L=int(L), n_v=n_v, delta=delta, rd_mode=rd_mode)
        out.append(
            steady_state(
                cfg,
                n_trials=n_trials,
                seed=seed + i,
                burn_in_steps=burn_in_steps,
                measure_steps=measure_steps,
                backend=backend,
                engine_opts=engine_opts,
            )
        )
    return out


def width_evolution(
    cfg: PDESConfig,
    *,
    n_steps: int,
    n_trials: int = 64,
    seed: int = 0,
    backend: str | None = None,
    engine_opts: dict | None = None,
):
    """Full <w(t)>, <w_a(t)>, <u(t)> series (Figs. 2, 4, 8).

    Returns dict of numpy arrays with leading time axis.  ``backend`` routes
    through ``PDESEngine`` exactly as in ``steady_state``.
    """
    with _span("measure", args={"L": cfg.L, "n_v": cfg.n_v,
                                "rows": n_trials, "steps": n_steps}) as sp:
        if backend is None:
            key = jax.random.key(seed)
            state = horizon.init_state(cfg, n_trials)
            _, stats = horizon.run(state, key, cfg, n_steps)
        else:
            from .engine import PDESEngine
            eng = PDESEngine(cfg, backend=backend, **(engine_opts or {}))
            _, stats = eng.run(eng.init(n_trials), seed, n_steps)
        _sync_if_traced(sp, stats)
    w2 = np.asarray(stats.w2)
    return {
        "t": np.arange(1, n_steps + 1),
        "u": np.asarray(stats.utilization).mean(axis=1),
        "w": np.sqrt(w2).mean(axis=1),
        "w2": w2.mean(axis=1),
        "wa": np.asarray(stats.wa).mean(axis=1),
        "gvt": np.asarray(stats.gvt).mean(axis=1),
        "max_dev": np.asarray(stats.max_dev).mean(axis=1),
        "min_dev": np.asarray(stats.min_dev).mean(axis=1),
    }
