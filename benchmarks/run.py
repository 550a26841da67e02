"""Benchmark harness: one function per paper figure/table.

Prints ``name,us_per_call,derived`` CSV lines and writes full JSON records to
results/benchmarks/.  Ensemble sizes are scaled to a single-host CPU run
(documented per entry); all qualitative paper claims (C1-C7, DESIGN.md §1)
are asserted here and summarized in EXPERIMENTS.md.

Every record carries machine metadata (jax version, device kind, Pallas
interpret-mode flag) so baselines are only ever compared apples-to-apples.

Run:  PYTHONPATH=src python -m benchmarks.run [--only fig2,eq8] [--fast]

Regression-gate mode (CI): compare a fresh run against committed baselines::

    python -m benchmarks.run --check results/benchmarks --tolerance 0.25

re-runs every benchmark found in the baseline file/directory (intersected
with ``--only``) and fails if a gate metric regresses beyond the tolerance.
Benches that publish a hardware-portable ``gate`` ratio (e.g. the fused
kernel's speedup over the reference scan) are gated on that ratio; the rest
fall back to wall time, which is only compared when the machine metadata
matches the baseline.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import subprocess
import sys
import tempfile
import textwrap
import time

import numpy as np

from repro import compile_cache
from repro.obs.trace import TraceRecorder, set_tracer

OUT = pathlib.Path("results/benchmarks")

#: ambient span recorder, installed by ``main``.  Every bench JSON gets a
#: ``phases_us`` burn/measure/reduce breakdown from the spans the library
#: emits (``ensemble.steady_state``, ``sweep.run_window_sweep``); pass
#: ``--trace FILE`` to also keep the full Chrome-trace JSON.  Gate ratios
#: are computed exactly as before — the breakdown is payload-only.
_TRACER: TraceRecorder | None = None
_PHASE_MARK = {"n": 0}


def _phase_breakdown() -> dict | None:
    """Sum burn/measure/reduce span µs recorded since the previous call.

    Each ``_emit`` consumes the spans its bench produced, so concurrent
    phases never leak across records.  Subprocess benches (pdes_comm,
    window_sweep_sharded) trace nothing here and simply carry no
    breakdown.
    """
    if _TRACER is None:
        return None
    events = _TRACER.events[_PHASE_MARK["n"]:]
    _PHASE_MARK["n"] += len(events)
    out: dict[str, float] = {}
    for ev in events:
        if ev["name"] in ("burn", "measure", "reduce"):
            out[ev["name"]] = out.get(ev["name"], 0.0) + ev["dur"]
    return {k: round(v, 1) for k, v in out.items()} or None

#: CLI workload knobs of the current invocation (set by ``main``), stamped
#: into the metadata: a ``--fast`` or ``--backend``-narrowed run is a
#: different workload and must never be gated against a full-run baseline.
_RUN_CONFIG = {"fast": False, "cli_backend": None}


def machine_meta() -> dict:
    """Machine/runtime + workload metadata stamped into every result JSON.

    ``--check`` uses this to keep baseline comparisons apples-to-apples:
    gates are skipped when platform / device kind / interpret mode / CLI
    workload knobs differ from the baseline's.
    """
    import platform

    import jax

    from repro.core.engine import interpret_mode
    dev = jax.devices()[0]
    return {
        "jax_version": jax.__version__,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        # as the engine resolves it: a TPU baseline never gates a CPU run
        "interpret_mode": interpret_mode(),
        # host identity: "cpu/cpu" is the same on every x86 box, so wall-time
        # gates additionally require the same hostname/core count — i.e. they
        # only ever fire on the machine that recorded the baseline.
        "hostname": platform.node(),
        "cpu_count": os.cpu_count(),
        "python_version": platform.python_version(),
        **_RUN_CONFIG,
    }


_ANALYSIS_VERDICT: dict | None = None


def analysis_verdict() -> dict:
    """Causality-linter verdict stamped into every bench record.

    Computed once per process (the linter itself caches per backend tuple);
    a crashed linter is recorded as a failing verdict rather than aborting
    the benchmark run — perf numbers from an unverified tree are still worth
    keeping, they just carry the stain.
    """
    global _ANALYSIS_VERDICT
    if _ANALYSIS_VERDICT is None:
        try:
            from repro.analysis import analysis_verdict as verdict
            _ANALYSIS_VERDICT = verdict()
        except Exception as e:  # pragma: no cover - defensive
            _ANALYSIS_VERDICT = {"ok": False, "error": repr(e)}
    return _ANALYSIS_VERDICT


def _emit(name: str, us_per_call: float, derived: str, payload: dict,
          gate: dict | None = None, meta: dict | None = None):
    """Print the CSV line and write the JSON record.

    ``gate`` optionally names a hardware-portable regression-gate metric,
    e.g. ``{"metric": "speedup", "value": 2.2, "higher_is_better": True}``;
    ``--check`` prefers it over raw wall time.  Every record also carries the
    causality-linter verdict (``analysis`` key) so a perf baseline can never
    silently come from a tree that violates the protocol invariants.
    ``meta`` overrides the machine metadata of benches measured elsewhere
    (``_run_cpu_child``).
    """
    print(f"{name},{us_per_call:.1f},{derived}")
    OUT.mkdir(parents=True, exist_ok=True)
    payload = dict(payload, name=name, us_per_call=us_per_call,
                   derived=derived, meta=meta or machine_meta(),
                   analysis=analysis_verdict())
    phases = _phase_breakdown()
    if phases is not None:
        payload["phases_us"] = phases
    if gate is not None:
        payload["gate"] = gate
    (OUT / f"{name}.json").write_text(json.dumps(payload, indent=1))


def _timed(fn, *args, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    return out, (time.time() - t0) * 1e6


# ---------------------------------------------------------------------------
# Fig. 2 — unconstrained utilization evolution reaches a nonzero steady state
# ---------------------------------------------------------------------------


def fig2_utilization_evolution(fast=False):
    from repro.core import PDESConfig, ensemble
    trials = 32 if fast else 64
    rows = {}
    t0 = time.time()
    for L in (10, 100, 1000):
        for nv in (1, 10, 100):
            cfg = PDESConfig(L=L, n_v=nv)
            ev = ensemble.width_evolution(cfg, n_steps=600 if fast else 1500,
                                          n_trials=trials, seed=L + nv)
            rows[f"L{L}_nv{nv}"] = {
                "u_first": float(ev["u"][0]),
                "u_steady": float(ev["u"][-200:].mean()),
            }
    # claims: u(0) = 1 (synchronized start), steady state > 0, grows with nv
    assert all(abs(r["u_first"] - 1.0) < 1e-6 for r in rows.values())
    assert all(r["u_steady"] > 0.1 for r in rows.values())
    assert rows["L1000_nv100"]["u_steady"] > rows["L1000_nv1"]["u_steady"]
    _emit("fig2_utilization_evolution", (time.time() - t0) * 1e6,
          f"u_steady(L=1000,nv=1)={rows['L1000_nv1']['u_steady']:.4f}", rows,
          gate={"metric": "u_steady_L1000_nv1",
                "value": rows["L1000_nv1"]["u_steady"],
                "higher_is_better": True})


# ---------------------------------------------------------------------------
# Eq. (8) / Fig. 2 — u_inf = 24.6461(7)% via Krug-Meakin extrapolation  [C1]
# ---------------------------------------------------------------------------


def eq8_uinf_extrapolation(fast=False):
    from repro.core import PDESConfig, ensemble, scaling, theory
    Ls = [16, 32, 64, 128, 256] + ([] if fast else [512])
    us, t0 = [], time.time()
    for L in Ls:
        ss = ensemble.steady_state(
            PDESConfig(L=L, n_v=1), n_trials=32 if fast else 64, seed=L,
            burn_in_steps=int(5 * L ** 1.5) + 500,
            measure_steps=2000 if fast else 6000)
        us.append(ss.utilization)
    ex = scaling.krug_meakin_extrapolate(Ls, us, alpha=0.5)
    err = abs(ex.u_inf - theory.U_INF_KPZ_NV1)
    rec = {"Ls": Ls, "u_L": us, "u_inf": ex.u_inf,
           "paper": theory.U_INF_KPZ_NV1, "abs_err": err,
           "const": ex.coeffs["const"]}
    assert err < 0.01, rec        # C1: within 1% absolute of 24.6461%
    _emit("eq8_uinf_extrapolation", (time.time() - t0) * 1e6,
          f"u_inf={ex.u_inf:.4f} (paper 0.2465, err {err:.4f})", rec,
          gate={"metric": "abs_err_u_inf", "value": err,
                "higher_is_better": False})


# ---------------------------------------------------------------------------
# Fig. 4 + Eqs. (6,7,9) — KPZ growth and roughness exponents            [C2,C3]
# ---------------------------------------------------------------------------


def fig4_kpz_exponents(fast=False):
    """KPZ exponents at single-host-reachable scales.

    The asymptotic KPZ values (beta = 1/3, alpha = 1/2) emerge slowly: at
    L <= a few thousand the *effective* exponents sit below them and rise
    monotonically with scale (well-known corrections to scaling; the paper's
    own values come from L up to 1e4, t up to 1e6).  We therefore check
    (a) the monotone approach, and (b) the correction-extrapolated values.
    """
    from repro.core import PDESConfig, ensemble, scaling
    t0 = time.time()
    # effective growth exponent over increasing time windows
    L = 1024 if fast else 2048
    ev = ensemble.width_evolution(PDESConfig(L=L, n_v=1),
                                  n_steps=3000 if fast else 4000,
                                  n_trials=16, seed=0)
    # windows stay well inside the growth regime: the measured crossover is
    # t_x ~ 1.5 L^{3/2} (≈12k steps at L=2048), and the local slope bends
    # down within a factor ~3 of t_x.
    windows = [(30, 120), (120, 600), (600, 3000)]
    betas = [scaling.fit_power_law(ev["t"], ev["w2"], lo, hi)[0] / 2
             for lo, hi in windows]
    # effective roughness exponent from successive saturated-width pairs
    Ls = [16, 32, 64, 128, 256]
    sats = []
    for Li in Ls:
        ss = ensemble.steady_state(
            PDESConfig(L=Li, n_v=1), n_trials=32, seed=Li,
            burn_in_steps=int(8 * Li ** 1.5) + 1000,
            measure_steps=1500 if fast else 3000)
        sats.append(ss.w2)
    alpha_pairs = [math.log(b / a) / math.log(2) / 2
                   for a, b in zip(sats, sats[1:])]
    # extrapolate alpha_eff against 1/sqrt(L): intercept ~ alpha_inf
    x = np.array([1 / math.sqrt(math.sqrt(a * b))
                  for a, b in zip(Ls, Ls[1:])])
    A = np.stack([np.ones_like(x), x], 1)
    alpha_inf = float(np.linalg.lstsq(A, np.array(alpha_pairs), rcond=None)[0][0])
    # large-N_V initial growth is RD-like (beta ~ 1/2)               [C3]
    ev_rd = ensemble.width_evolution(PDESConfig(L=256, n_v=100),
                                     n_steps=400, n_trials=32, seed=7)
    beta_rd, _ = scaling.growth_exponent(ev_rd["t"], ev_rd["w2"],
                                         fit_lo_frac=0.02, fit_hi_frac=0.3)
    rec = {"beta_eff_windows": betas, "alpha_eff_pairs": alpha_pairs,
           "alpha_extrapolated": alpha_inf, "beta_early_nv100": beta_rd,
           "w2_sat": dict(zip(map(str, Ls), sats))}
    # C2: effective exponents rise toward the KPZ values
    assert betas[-1] > betas[0] - 0.02 and 0.22 <= betas[-1] <= 0.45, rec
    assert all(b >= a - 0.03 for a, b in zip(alpha_pairs, alpha_pairs[1:])), rec
    assert 0.38 <= alpha_inf <= 0.62, rec
    # C3: early growth at large N_V is RD-like, well above the KPZ beta
    assert beta_rd > 0.4, rec
    _emit("fig4_kpz_exponents", (time.time() - t0) * 1e6,
          f"beta_eff={betas[-1]:.3f}->1/3, alpha_pairs "
          f"{alpha_pairs[0]:.2f}->{alpha_pairs[-1]:.2f}, "
          f"alpha_inf={alpha_inf:.2f} (KPZ 0.5), beta_rd={beta_rd:.2f}", rec,
          gate={"metric": "beta_eff_late_window", "value": betas[-1],
                "higher_is_better": True})


# ---------------------------------------------------------------------------
# Fig. 5 — constrained utilization vs system size; RD limit             [C5]
# ---------------------------------------------------------------------------


def fig5_util_vs_L(fast=False):
    from repro.core import PDESConfig, ensemble
    t0 = time.time()
    Ls = [16, 32, 64, 128] + ([] if fast else [256])
    out = {}
    for delta in (10.0, 100.0):
        for nv in (1, 10, 100, "rd"):
            us = []
            for L in Ls:
                cfg = PDESConfig(L=L, n_v=1 if nv == "rd" else nv,
                                 delta=delta, rd_mode=(nv == "rd"))
                ss = ensemble.steady_state(cfg, n_trials=32, seed=L)
                us.append(ss.utilization)
            out[f"d{delta}_nv{nv}"] = dict(zip(map(str, Ls), us))
    # C5: for fixed L, u grows with N_V toward the RD curve
    for delta in (10.0, 100.0):
        u1 = out[f"d{delta}_nv1"][str(Ls[-1])]
        u100 = out[f"d{delta}_nv100"][str(Ls[-1])]
        urd = out[f"d{delta}_nvrd"][str(Ls[-1])]
        assert u1 < u100 <= urd + 0.03, (delta, u1, u100, urd)
    # gate: the N_V=100 over N_V=1 utilization lift at the largest L, Δ=10 —
    # a pure physics ratio (paper's central "many volatilities help" effect)
    lift = (out["d10.0_nv100"][str(Ls[-1])]
            / max(out["d10.0_nv1"][str(Ls[-1])], 1e-9))
    _emit("fig5_util_vs_L", (time.time() - t0) * 1e6,
          f"u(L=128,d=10): nv1={out['d10.0_nv1']['128']:.3f} "
          f"nv100={out['d10.0_nv100']['128']:.3f} "
          f"rd={out['d10.0_nvrd']['128']:.3f}", out,
          gate={"metric": "u_lift_nv100_over_nv1_d10", "value": lift,
                "higher_is_better": True})


# ---------------------------------------------------------------------------
# Fig. 6 + Appendix — u_inf(N_V, Δ) surface vs fits A.1/A.2/Eq.(12)     [C6]
# ---------------------------------------------------------------------------


def fig6_uinf_surface(fast=False):
    from repro.core import PDESConfig, ensemble, scaling, theory
    t0 = time.time()
    Ls = [64, 128, 256, 512] + ([] if fast else [1024, 2048])
    grid = {}
    for delta in (1.0, 10.0, 100.0):
        for nv in (1, 10, 100, "rd"):
            us = []
            for L in Ls:
                cfg = PDESConfig(L=L, n_v=1 if nv == "rd" else nv,
                                 delta=delta, rd_mode=(nv == "rd"))
                ss = ensemble.steady_state(
                    cfg, n_trials=16, seed=L,
                    burn_in_steps=None, measure_steps=1200)
                us.append(ss.utilization)
            ex = scaling.rational_extrapolate(Ls, us)
            nv_eff = 1e8 if nv == "rd" else nv
            pred = float(theory.u_composite(nv_eff, delta))
            grid[f"d{delta}_nv{nv}"] = {
                "u_inf": ex.u_inf, "paper_fit": pred,
                "abs_err": abs(ex.u_inf - pred), "u_L": us}
    errs = [v["abs_err"] for v in grid.values()]
    rec = {"grid": grid, "max_abs_err": max(errs),
           "mean_abs_err": float(np.mean(errs))}
    # C6: paper fit (12) is ±5-10%; finite-L extrapolation adds its own error
    assert rec["mean_abs_err"] < 0.08, rec["mean_abs_err"]
    _emit("fig6_uinf_surface", (time.time() - t0) * 1e6,
          f"mean|u_inf - fit|={rec['mean_abs_err']:.3f} "
          f"max={rec['max_abs_err']:.3f}", rec,
          gate={"metric": "mean_abs_err_vs_fit", "value": rec["mean_abs_err"],
                "higher_is_better": False})


# ---------------------------------------------------------------------------
# Figs. 7-9 — Δ-window bounds the width for any system size             [C4]
# ---------------------------------------------------------------------------


def fig9_width_saturation(fast=False):
    from repro.core import PDESConfig, ensemble
    t0 = time.time()
    Ls = [32, 64, 128, 256] + ([] if fast else [512])
    out = {}
    for delta in (1.0, 5.0, 10.0, 100.0):
        for nv in (1, 10):
            ws, was = [], []
            for L in Ls:
                ss = ensemble.steady_state(
                    PDESConfig(L=L, n_v=nv, delta=delta),
                    n_trials=16, seed=L)
                ws.append(ss.w)
                was.append(ss.wa)
            out[f"d{delta}_nv{nv}"] = {"w": ws, "wa": was}
            # C4: width bounded by O(Δ) for every L ...
            assert max(ws) <= delta + 4.0, (delta, nv, ws)
            # ... and saturates to a Δ-ceiling: once the unconstrained KPZ
            # width would exceed the window, w(L) flattens (<=12% change per
            # L-doubling at the top end) instead of growing as sqrt(L).
            if ws[-1] > 0.8 * delta:
                assert abs(ws[-1] - ws[-2]) <= 0.12 * ws[-2] + 0.05, \
                    (delta, nv, ws)
            else:                         # far from the ceiling: bounded rise
                assert ws[-1] <= ws[0] * math.sqrt(Ls[-1] / Ls[0]), \
                    (delta, nv, ws)
    # contrast: unconstrained width DOES grow with L (the paper's Fig. 4)
    w_unc = [ensemble.steady_state(PDESConfig(L=L, n_v=1), n_trials=8,
                                   seed=L).w for L in (32, 128)]
    assert w_unc[1] > w_unc[0] * 1.3
    rec = dict(out, Ls=Ls, w_unconstrained=w_unc)
    # gate: saturated width over the window size at Δ=10, largest L — the
    # paper's measurability claim is exactly that this ratio stays O(1)
    w_over_delta = out["d10.0_nv1"]["w"][-1] / 10.0
    _emit("fig9_width_saturation", (time.time() - t0) * 1e6,
          f"w_sat(d=10,nv=1): {out['d10.0_nv1']['w'][0]:.2f}->"
          f"{out['d10.0_nv1']['w'][-1]:.2f} over L={Ls[0]}->{Ls[-1]} "
          f"(Δ-ceiling); unconstrained {w_unc[0]:.2f}->{w_unc[1]:.2f}", rec,
          gate={"metric": "w_sat_over_delta_d10", "value": w_over_delta,
                "higher_is_better": False})


# ---------------------------------------------------------------------------
# Fig. 10 — slow/fast simplex decomposition; double-peak transient      [C7]
# ---------------------------------------------------------------------------


def fig10_slow_fast(fast=False):
    import jax
    from repro.core import (PDESConfig, group_decomposition, horizon,
                            recombine_w2, recombine_wa)
    t0 = time.time()
    cfg = PDESConfig(L=1000, n_v=1000, delta=10.0)
    n_steps = 300 if fast else 500
    state = horizon.init_state(cfg, 16)
    key = jax.random.key(0)
    series = {"f_slow": [], "wa_slow": [], "wa_fast": [], "wa": [], "u": []}
    for t in range(n_steps):
        state, stats = horizon.run(state, key, cfg, 1)
        g = group_decomposition(state.tau)
        series["f_slow"].append(float(np.asarray(g.f_slow).mean()))
        series["wa_slow"].append(float(np.asarray(g.wa_slow).mean()))
        series["wa_fast"].append(float(np.asarray(g.wa_fast).mean()))
        series["wa"].append(float(np.asarray(stats.wa).mean()))
        series["u"].append(float(np.asarray(stats.utilization).mean()))
        # Eqs. (17)-(18) recombination identity holds at every step
        w2 = np.asarray(recombine_w2(g))
        wa = np.asarray(recombine_wa(g))
        if t % 100 == 0:
            dev = np.asarray(state.tau) - np.asarray(state.tau).mean(1)[:, None]
            np.testing.assert_allclose(w2, (dev ** 2).mean(1), rtol=1e-4)
            np.testing.assert_allclose(wa, np.abs(dev).mean(1), rtol=1e-4)
    wa_f = np.array(series["wa_fast"])
    peak_t = int(wa_f.argmax())
    # C7: fast-group width peaks early then decays to a plateau; the slow
    # fraction starts majority (~63% in the paper) and relaxes
    rec = dict(series, peak_t=peak_t)
    assert series["f_slow"][0] > 0.55
    assert 1 <= peak_t < n_steps // 2
    assert wa_f[-1] < wa_f[peak_t]
    # gate: how far the fast-group width has decayed from its transient peak
    # by the end of the run — the double-peak relaxation signature of Fig. 10
    decay = float(wa_f[-1] / wa_f[peak_t])
    _emit("fig10_slow_fast", (time.time() - t0) * 1e6,
          f"f_slow(0)={series['f_slow'][0]:.2f}, wa_fast peak at t={peak_t}, "
          f"u_steady={np.mean(series['u'][-100:]):.3f}", rec,
          gate={"metric": "wa_fast_decay_from_peak", "value": decay,
                "higher_is_better": False})


# ---------------------------------------------------------------------------
# Kernel table — engine backends: fused Pallas vs per-step reference  [B1,B2]
# ---------------------------------------------------------------------------


def bench_kernel_fused(fast=False, backend=None):
    """Wall-time of PDESEngine backends on the identical trajectory.

    All backends consume the same counter event stream (bit-identical tau),
    so this is a pure execution-path comparison: per-step reference scan vs
    fused one-step kernel vs K-fused VMEM-resident kernel with in-kernel
    event generation.  Asserts the multistep backend >= 1.3x the reference
    at B=64, L=1024, K=16 (interpret-mode CPU numbers; on TPU the gap is
    the analytic HBM ratio below).
    """
    import jax
    from repro.core import PDESConfig
    from repro.core.engine import PDESEngine
    t0 = time.time()
    cfg = PDESConfig(L=1024, n_v=10, delta=10.0)
    B, T, K = 64, 64, 16
    # --backend narrows the comparison to reference vs that backend; the
    # multistep speedup claim is only asserted when multistep is timed.
    backends = ["reference", "pallas", "pallas_multistep"] if backend is None \
        else ["reference"] + ([backend] if backend != "reference" else [])
    us_per_step, tau_check = {}, {}
    for b in backends:
        eng = PDESEngine(cfg, backend=b, k_fuse=K)
        state = eng.init(B)
        run = lambda: jax.block_until_ready(eng.run(state, 0, T))
        out = run()                             # compile + parity capture
        tau_check[b] = np.asarray(out[0].tau)
        best = min(_timed(run)[1] for _ in range(3))
        us_per_step[b] = best / T
    for b in backends[1:]:                      # identical trajectories
        assert (tau_check[b] == tau_check["reference"]).all(), b
    speedup = (us_per_step["reference"] / us_per_step["pallas_multistep"]
               if "pallas_multistep" in us_per_step else None)
    # derived: HBM bytes/PE/step — XLA path vs fused kernel vs K-fused kernel
    # with in-kernel events (analytic; see kernels/*.py docstrings)
    xla_bytes = 7 * 4 + 8          # ~7 tau-sized round trips + bits read
    fused_bytes = 2 * 4 + 8        # tau r/w + bits
    kfused_bytes = 2 * 4 / K       # tau r/w amortized; bits generated in VMEM
    rec = {"B": B, "L": cfg.L, "K": K, "n_steps": T,
           "us_per_step": us_per_step,
           "speedup_multistep_vs_reference": speedup,
           "bytes_per_pe_step": {"xla": xla_bytes, "fused": fused_bytes,
                                 "fused_k16_inkernel": kfused_bytes},
           "reduction_fused": xla_bytes / fused_bytes,
           "reduction_k16": xla_bytes / kfused_bytes}
    if speedup is not None:
        assert speedup >= 1.3, rec
    fastest = min(us_per_step, key=us_per_step.get)
    _emit("bench_kernel_fused", us_per_step[fastest],
          f"{fastest} {us_per_step[fastest]:.0f}us/step vs reference "
          f"{us_per_step['reference']:.0f}"
          + (f" (multistep x{speedup:.2f})" if speedup is not None else "")
          + f"; bytes/PE/step {xla_bytes}->{fused_bytes}->{kfused_bytes:.1f}",
          rec,
          gate=None if speedup is None else {
              "metric": "speedup_multistep_vs_reference", "value": speedup,
              "higher_is_better": True})


# ---------------------------------------------------------------------------
# Window-sweep table — batched Δ-axis vs serial per-Δ engine loop
# ---------------------------------------------------------------------------


def bench_window_sweep(fast=False, backend=None):
    """Batched window sweep vs the serial per-Δ loop on identical physics.

    The batched path advances all ``n_windows x replicas`` trajectories in
    one engine pass per grid point (Δ as a per-row operand down to the
    kernel); the serial oracle makes one engine call per Δ on the same
    counter-stream rows, so both produce bit-identical records
    (asserted).  The gate metric is the batched-over-serial speedup — a
    hardware-portable ratio.
    """
    from repro.experiments import (WindowSweep, run_window_sweep,
                                   serial_window_sweep)
    spec = WindowSweep(
        Ls=(128 if fast else 256,), n_vs=(10,),
        deltas=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, math.inf),
        replicas=8, n_steps=128, burn_in=96,
        backend=backend or "pallas_multistep", seed=3)
    res = run_window_sweep(spec)       # compile both paths before timing
    ser = serial_window_sweep(spec)
    assert res.records == ser.records  # bit-identical, not just statistical
    t_batched = min(_timed(run_window_sweep, spec)[1] for _ in range(3))
    t_serial = min(_timed(serial_window_sweep, spec)[1] for _ in range(3))
    speedup = t_serial / t_batched
    rec = {"spec": {"L": spec.Ls[0], "n_v": 10, "n_windows": spec.n_windows,
                    "replicas": spec.replicas, "n_steps": spec.n_steps,
                    "burn_in": spec.burn_in, "backend": spec.backend},
           "us_batched": t_batched, "us_serial": t_serial,
           "speedup_batched_vs_serial": speedup,
           "u_by_delta": {str(r.delta): r.u for r in res.records}}
    # the bench itself only insists the batched pass is measurably faster;
    # regression *depth* is governed by the --check gate and its --tolerance,
    # not a hard-coded floor here (the ratio baseline is ~2x).
    assert speedup >= 1.05, rec
    _emit("bench_window_sweep", t_batched,
          f"batched {t_batched / 1e3:.0f}ms vs serial {t_serial / 1e3:.0f}ms "
          f"(x{speedup:.2f}) over {spec.n_windows} windows x "
          f"{spec.replicas} replicas, {spec.backend}",
          rec,
          gate={"metric": "speedup_batched_vs_serial", "value": speedup,
                "higher_is_better": True})


# ---------------------------------------------------------------------------
# PDES comm table — exact vs comm-avoiding GVT (B3/B4/B5)
# ---------------------------------------------------------------------------

_COMM_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, math
    import jax
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.horizon import PDESConfig
    from repro.core import distributed as D
    from repro.core.engine import PDESEngine
    from repro.launch.hlo_cost import analyze_hlo

    backend = "__BACKEND__"
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = PDESConfig(L=4096, n_v=10, delta=100.0)
    out = {}
    for mode, K in [("exact", 16), ("commavoid", 4), ("commavoid", 16),
                    ("commavoid", 64)]:
        dist = D.DistConfig(ens_axes=("data",), ring_axis="model",
                            mode=mode, k_chunk=K)
        lowered = D.lower_sharded(cfg, mesh, n_trials=8, n_steps=64,
                                  dist=dist)
        c = analyze_hlo(lowered.compile().as_text())
        # utilization cost of stale GVT, measured through the engine on the
        # identical counter event stream (exact-GVT modes may use any
        # single-device backend; stale needs a window-base input, so it
        # falls back to the reference backend when the chosen one can't)
        window = "exact" if mode == "exact" else "stale"
        b = backend
        if window == "stale" and b == "pallas_multistep":
            b = "reference"
        eng = PDESEngine(cfg, backend=b, window=window, k_fuse=K)
        st = eng.init(8)
        st = eng.burn_in(st, 1, 200)
        _, mean = eng.run_mean(st, 1, 200)
        out[f"{mode}_K{K}"] = {
            "coll_bytes_per_step": c.coll_bytes / 64,
            "coll_msgs_per_step": c.coll_msgs / 64,
            "utilization": float(np.asarray(mean.utilization).mean()),
        }
    print("RESULT " + json.dumps(out))
""")


def _run_cpu_child(script: str) -> tuple[dict, dict]:
    """Run a fake-CPU-mesh bench script; returns (its RESULT, its metadata).

    The child is pinned to the CPU: this process may hold the chip, and a
    second process reaching for it would fail or hang.
    """
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    meta = dict(machine_meta(), platform="cpu", device_kind="cpu",
                interpret_mode=True)
    return json.loads(line[len("RESULT "):]), meta


def bench_pdes_comm(fast=False, backend=None):
    t0 = time.time()
    script = _COMM_SCRIPT.replace("__BACKEND__", backend or "reference")
    rec, meta = _run_cpu_child(script)
    ex = rec["exact_K16"]
    cv = rec["commavoid_K16"]
    msgs_ratio = ex["coll_msgs_per_step"] / max(cv["coll_msgs_per_step"], 1e-9)
    du = ex["utilization"] - cv["utilization"]
    _emit("bench_pdes_comm", (time.time() - t0) * 1e6,
          f"msgs/step {ex['coll_msgs_per_step']:.2f}->"
          f"{cv['coll_msgs_per_step']:.2f} (x{msgs_ratio:.1f} fewer), "
          f"utilization cost {du:+.4f} at K=16, Δ=100", rec,
          gate={"metric": "msgs_reduction_commavoid_K16", "value": msgs_ratio,
                "higher_is_better": True}, meta=meta)


# ---------------------------------------------------------------------------
# Sharded window sweep — batched Δ-axis on a 2x4 mesh vs serial per-Δ loop
# ---------------------------------------------------------------------------

_SWEEP_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, math, time
    import numpy as np
    from repro.compat import make_mesh
    from repro.experiments import (WindowSweep, run_window_sweep,
                                   serial_window_sweep)

    fast = __FAST__
    mesh = make_mesh((2, 4), ("data", "model"))
    spec = WindowSweep(
        Ls=(128 if fast else 256,), n_vs=(10,),
        deltas=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, math.inf),
        replicas=8, n_steps=64, burn_in=64, backend="sharded",
        k_fuse=8, seed=3)
    res = run_window_sweep(spec, mesh=mesh)       # compile both paths
    ser = serial_window_sweep(spec, mesh=mesh)
    # bit-identical records (wa is NaN by the sharded stats contract, and
    # NaN != NaN, so compare field-wise)
    for a, b in zip(res.records, ser.records):
        da, db = a.as_dict(), b.as_dict()
        wa_a, wa_b = da.pop("wa"), db.pop("wa")
        assert da == db, (da, db)
        assert math.isnan(wa_a) and math.isnan(wa_b)

    def timed(fn):
        best = math.inf
        for _ in range(3):
            t0 = time.time()
            fn()
            best = min(best, (time.time() - t0) * 1e6)
        return best

    t_batched = timed(lambda: run_window_sweep(spec, mesh=mesh))
    t_serial = timed(lambda: serial_window_sweep(spec, mesh=mesh))
    out = {
        "spec": {"L": spec.Ls[0], "n_v": 10, "n_windows": spec.n_windows,
                 "replicas": spec.replicas, "n_steps": spec.n_steps,
                 "burn_in": spec.burn_in, "backend": spec.backend,
                 "mesh": {"data": 2, "model": 4}},
        "us_batched": t_batched, "us_serial": t_serial,
        "speedup_batched_vs_serial_sharded": t_serial / t_batched,
        "u_by_delta": {str(r.delta): r.u for r in res.records},
    }
    print("RESULT " + json.dumps(out))
""")


def bench_window_sweep_sharded(fast=False):
    """Mesh-sharded batched window sweep vs the serial per-Δ sharded loop.

    Same contract as ``bench_window_sweep``, one level up the scaling
    ladder: the (Δ, replica) rows shard over a 2x4 CPU mesh (8 fake
    devices, hence the subprocess — the main process keeps the 1-device
    platform), and the batched pass advances all rows in one shard_map
    call per grid point while the serial baseline makes one mesh pass per
    Δ on the same counter-stream rows.  Records are asserted bit-identical
    before timing; the gate metric is the batched-over-serial speedup — a
    hardware-portable ratio.
    """
    t0 = time.time()
    script = _SWEEP_SHARDED_SCRIPT.replace("__FAST__", repr(bool(fast)))
    rec, meta = _run_cpu_child(script)
    speedup = rec["speedup_batched_vs_serial_sharded"]
    # as with bench_window_sweep: the bench only insists batching wins at
    # all; regression depth is the --check gate's job.
    assert speedup >= 1.05, rec
    rec["us_subprocess_total"] = (time.time() - t0) * 1e6
    _emit("bench_window_sweep_sharded", rec["us_batched"],
          f"batched {rec['us_batched'] / 1e3:.0f}ms vs serial "
          f"{rec['us_serial'] / 1e3:.0f}ms (x{speedup:.2f}) over "
          f"{rec['spec']['n_windows']} windows x {rec['spec']['replicas']} "
          f"replicas on a 2x4 mesh",
          rec,
          gate={"metric": "speedup_batched_vs_serial_sharded",
                "value": speedup, "higher_is_better": True}, meta=meta)


# ---------------------------------------------------------------------------
# Sweep service — multiplexed request queue vs one-sweep-per-user serial loop
# ---------------------------------------------------------------------------


def bench_sweep_service(fast=False, backend=None):
    """Coalesced service drain vs running each user's sweep separately.

    A queue of six users requests nested Δ grids over the same study
    (prefix-structured, one exact duplicate): the service unions their
    (trial, Δ) rows into a single device pass, computing shared rows once
    and deduping the duplicate spec entirely, while the serial baseline is
    what those users would do without the service — one
    ``run_window_sweep`` each.  Every response is asserted bit-identical
    to its direct run *before* timing, so the speedup is bought by
    coalescing alone, never by changed physics.  The gate metric is the
    coalesced-over-serial speedup (hardware-portable ratio, floor 1.5x).
    """
    from repro.experiments import WindowSweep, run_window_sweep
    from repro.service import SweepService
    G = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, math.inf)
    common = dict(Ls=(128 if fast else 256,), n_vs=(10,), replicas=8,
                  n_steps=128, burn_in=96,
                  backend=backend or "pallas_multistep", seed=3)
    queue = [("alice", G), ("bob", G[:3]), ("carol", G[:5]),
             ("dana", G), ("erin", G[:2]), ("frank", G[:4])]
    specs = [(who, WindowSweep(deltas=d, **common)) for who, d in queue]

    def serve():
        svc = SweepService()
        for who, s in specs:
            svc.submit(s, requester=who)
        return svc, svc.drain()

    def serial():
        return [run_window_sweep(s) for _, s in specs]

    svc, responses = serve()            # compile + identity capture
    directs = serial()
    for resp, direct in zip(responses, directs):
        assert resp.result.records == direct.records, resp.requester
    t_coalesced = min(_timed(lambda: serve())[1] for _ in range(3))
    t_serial = min(_timed(lambda: serial())[1] for _ in range(3))
    speedup = t_serial / t_coalesced
    stats = svc.stats.as_dict()
    rec = {"spec": {"L": common["Ls"][0], "n_v": 10,
                    "replicas": common["replicas"],
                    "n_steps": common["n_steps"],
                    "burn_in": common["burn_in"],
                    "backend": common["backend"],
                    "queue": [(who, len(d)) for who, d in queue]},
           "us_coalesced": t_coalesced, "us_serial": t_serial,
           "speedup_coalesced_vs_serial": speedup,
           "service_stats": stats}
    assert stats["n_passes"] == 1, stats          # one shared device pass
    assert stats["n_deduped"] == 1, stats         # dana rode alice's rows
    assert stats["rows_computed"] < stats["rows_requested"], stats
    assert speedup >= 1.5, rec
    _emit("bench_sweep_service", t_coalesced,
          f"coalesced {t_coalesced / 1e3:.0f}ms vs serial "
          f"{t_serial / 1e3:.0f}ms (x{speedup:.2f}) for "
          f"{stats['n_requests']} requests -> {stats['rows_computed']} "
          f"union rows ({stats['rows_requested']} requested)",
          rec,
          gate={"metric": "speedup_coalesced_vs_serial", "value": speedup,
                "higher_is_better": True})


BENCHES = {
    "fig2": fig2_utilization_evolution,
    "eq8": eq8_uinf_extrapolation,
    "fig4": fig4_kpz_exponents,
    "fig5": fig5_util_vs_L,
    "fig6": fig6_uinf_surface,
    "fig9": fig9_width_saturation,
    "fig10": fig10_slow_fast,
    "kernel": bench_kernel_fused,
    "kernel_fused": bench_kernel_fused,
    "pdes_comm": bench_pdes_comm,
    "window_sweep": bench_window_sweep,
    "window_sweep_sharded": bench_window_sweep_sharded,
    "sweep_service": bench_sweep_service,
}

# ---------------------------------------------------------------------------
# --check: regression gate against committed baselines
# ---------------------------------------------------------------------------


def record_to_bench(record_name: str) -> str | None:
    """BENCHES key for an ``_emit`` record name, by naming convention.

    ``bench_<key>`` records come from the perf-table benches; the figure
    benches are named ``<key>_<description>`` (e.g. ``fig2_utilization_...``).
    Derived rather than hand-mapped so a future bench can never be silently
    dropped from gating by a stale lookup table.
    """
    if record_name.startswith("bench_") and record_name[6:] in BENCHES:
        return record_name[6:]
    head = record_name.split("_", 1)[0]
    return head if head in BENCHES else None


def load_baselines(path: str) -> dict:
    """Baseline records keyed by BENCHES name, from a JSON file or directory."""
    p = pathlib.Path(path)
    files = sorted(p.glob("*.json")) if p.is_dir() else [p]
    out = {}
    for f in files:
        try:
            rec = json.loads(f.read_text())
        except (json.JSONDecodeError, OSError) as e:
            print(f"check: skipping unreadable baseline {f}: {e}")
            continue
        key = record_to_bench(rec.get("name", "")) if isinstance(rec, dict) \
            else None
        if key is not None:
            out[key] = rec
    return out


_META_GATE_KEYS = ("platform", "device_kind", "interpret_mode", "hostname",
                   "cpu_count")


def compare_to_baseline(name: str, baseline: dict, tolerance: float) -> str:
    """One gate decision: "ok", "regressed", or "skipped".

    Prefers the hardware-portable ``gate`` ratio when the baseline and the
    fresh record both carry one with the same metric name.  Otherwise falls
    back to wall time — but only when the machine metadata matches the
    baseline (``_META_GATE_KEYS``), because wall time on different hardware
    classes is not a regression signal.
    """
    fresh = json.loads((OUT / f"{baseline['name']}.json").read_text())
    # workload knobs first: a --fast or --backend-narrowed run measures a
    # different workload, so neither the gate ratio nor wall time compares.
    b_cfg = {k: (baseline.get("meta") or {}).get(k)
             for k in ("fast", "cli_backend")}
    f_cfg = {k: (fresh.get("meta") or {}).get(k)
             for k in ("fast", "cli_backend")}
    if b_cfg != f_cfg:
        print(f"check: {name} skipped — run workload differs from baseline "
              f"({b_cfg} vs {f_cfg})")
        return "skipped"
    b_gate, f_gate = baseline.get("gate"), fresh.get("gate")
    if bool(b_gate) != bool(f_gate):
        # one side measured its gate ratio and the other didn't (e.g. a
        # --backend narrowing skipped the multistep timing): the wall-time
        # fallback would compare different workloads, so don't gate at all.
        print(f"check: {name} skipped — gate metric present on only one "
              f"side (baseline: {bool(b_gate)}, fresh: {bool(f_gate)}); "
              f"run configurations differ")
        return "skipped"
    if b_gate and f_gate and b_gate["metric"] == f_gate["metric"]:
        old, new = float(b_gate["value"]), float(f_gate["value"])
        if b_gate.get("higher_is_better", True):
            ok, floor = new >= old * (1.0 - tolerance), old * (1.0 - tolerance)
            print(f"check: {name} {b_gate['metric']} {old:.3f} -> {new:.3f} "
                  f"(floor {floor:.3f}) {'ok' if ok else 'REGRESSED'}")
        else:
            ok, ceil = new <= old * (1.0 + tolerance), old * (1.0 + tolerance)
            print(f"check: {name} {b_gate['metric']} {old:.3f} -> {new:.3f} "
                  f"(ceiling {ceil:.3f}) {'ok' if ok else 'REGRESSED'}")
        return "ok" if ok else "regressed"
    if b_gate and f_gate:                # both gated, different metrics
        print(f"check: {name} skipped — gate metrics differ "
              f"({b_gate['metric']} vs {f_gate['metric']})")
        return "skipped"
    b_meta, f_meta = baseline.get("meta"), fresh.get("meta")
    if not b_meta or any(b_meta.get(k) != f_meta.get(k)
                         for k in _META_GATE_KEYS):
        print(f"check: {name} skipped — no portable gate metric and machine "
              f"metadata differs from baseline "
              f"({b_meta and {k: b_meta.get(k) for k in _META_GATE_KEYS}} "
              f"vs {({k: f_meta.get(k) for k in _META_GATE_KEYS})})")
        return "skipped"
    if b_meta.get("jax_version") != f_meta.get("jax_version"):
        print(f"check: {name} note — jax {b_meta.get('jax_version')} -> "
              f"{f_meta.get('jax_version')}")
    old, new = float(baseline["us_per_call"]), float(fresh["us_per_call"])
    ok = new <= old * (1.0 + tolerance)
    print(f"check: {name} us_per_call {old:.1f} -> {new:.1f} "
          f"(ceiling {old * (1 + tolerance):.1f}) "
          f"{'ok' if ok else 'REGRESSED'}")
    return "ok" if ok else "regressed"


def main(argv=None) -> None:
    import inspect
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--backend", default=None,
                    choices=["reference", "pallas", "pallas_multistep"],
                    help="route engine-aware benches (kernel_fused, "
                         "pdes_comm, window_sweep) through this PDESEngine "
                         "backend")
    ap.add_argument("--check", default=None, metavar="BASELINE",
                    help="baseline JSON file or directory (e.g. "
                         "results/benchmarks); re-run the benchmarks found "
                         "there and fail on perf regressions beyond "
                         "--tolerance")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed relative regression of the gate metric "
                         "(default 0.25)")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="save the full Chrome-trace JSON of the run (the "
                         "per-bench phases_us breakdown is recorded either "
                         "way)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    _RUN_CONFIG.update(fast=args.fast, cli_backend=args.backend)
    global _TRACER
    _TRACER = TraceRecorder()
    set_tracer(_TRACER)           # library burn/measure/reduce spans
    baselines = None
    if args.check is not None:
        baselines = load_baselines(args.check)
        if not baselines:
            raise SystemExit(f"--check: no readable baselines in "
                             f"{args.check}")
        # every --only name still RUNS (its claim asserts execute); only the
        # gate comparison needs a baseline.  Gating nothing is an error, not
        # a green job.
        names = args.only.split(",") if args.only else list(baselines)
        unknown = sorted(set(names) - set(BENCHES))
        if unknown:
            raise SystemExit(f"--check: unknown benchmark(s) {unknown}; "
                             f"known: {sorted(set(BENCHES))}")
        # normalize aliases that share one record/baseline (kernel -> _fused)
        names = list(dict.fromkeys(
            "kernel_fused" if n == "kernel" else n for n in names))
        missing = sorted(set(names) - set(baselines))
        if missing:
            print(f"check: no baseline for {missing}; run but not gated")
        if not set(names) & set(baselines):
            raise SystemExit("--check: none of the requested benchmarks "
                             "have a baseline — nothing would be gated")
        # fresh records go to a scratch dir so the committed baselines on
        # disk are never overwritten by the very run that gates against them
        global OUT
        OUT = pathlib.Path(tempfile.mkdtemp(prefix="bench-fresh-"))
        print(f"check: fresh records -> {OUT}")
    else:
        names = args.only.split(",") if args.only else list(BENCHES)
        if args.only is None:
            names.remove("kernel")    # alias of kernel_fused; run once
    print("name,us_per_call,derived")
    failures, regressions, gated = [], [], 0
    for n in names:
        fn = BENCHES[n]
        kw = {"fast": args.fast}
        if args.backend and "backend" in inspect.signature(fn).parameters:
            kw["backend"] = args.backend
        try:
            with _TRACER.span(f"bench:{n}", cat="bench"):
                fn(**kw)
        except AssertionError as e:  # report, keep going
            failures.append((n, str(e)[:200]))
            print(f"{n},0,FAILED: {str(e)[:120]}")
            _phase_breakdown()     # drop the failed bench's spans
            continue
        if baselines is not None and n in baselines:
            verdict = compare_to_baseline(n, baselines[n], args.tolerance)
            if verdict == "regressed":
                regressions.append(n)
            if verdict != "skipped":
                gated += 1
    if args.trace:
        _TRACER.save(args.trace)
        print(f"trace: {len(_TRACER)} span(s) -> {args.trace}")
    if failures:
        raise SystemExit(f"{len(failures)} benchmark claims failed: "
                         f"{[f[0] for f in failures]}")
    if regressions:
        raise SystemExit(f"perf regression beyond tolerance "
                         f"{args.tolerance} in: {regressions}")
    if baselines is not None and not gated:
        # every comparison was skipped (workload/machine mismatch): a green
        # exit would claim a gate that never ran.
        raise SystemExit("--check: every baseline comparison was skipped — "
                         "nothing was gated (workload or machine mismatch)")


if __name__ == "__main__":
    main()
