#!/usr/bin/env python3
"""Chip smoke: the served Δ-window sweep path, compiled, on a TPU.

Drives the system's main path once, at the paper's scale (PRE 67, 046703,
Figs. 5-9): rings of L = 10^4 PEs with N_V = 10, the window grid
Δ ∈ {1, 5, 10, 100, ∞} and 256 replicas per Δ, i.e. 1280 rings (τ is about
51 MB on the device), on the ``pallas_multistep`` backend in ``exact``
window mode.  Three overlapping requests from three requesters go through
``SweepService`` and the ``wire.serve_queue`` drain that
``python -m repro.service`` uses.  Then it checks, on the chip:

* no response is an ``error``, and every response is bit-identical to a
  direct ``run_window_sweep`` of its spec (the service's contract);
* the lowered pass holds a ``tpu_custom_call`` (the kernel is compiled, not
  interpreted);
* against the ``reference`` backend on the same rows: per-Δ ⟨u⟩ and ⟨w²⟩
  agree within 4 standard errors, and the τ mismatch is reported.

``--chips 4`` runs only the four-chip phase instead: the ``sharded``
backend on a ``data=1, model=4`` mesh (2,500 PEs of each ring per chip), in
``exact`` and ``stale`` window modes, against the single-device
``reference`` backend on the same rows.

Usage::

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip phase

It fails, printing no result, where JAX finds no TPU.  Progress lines go to
stdout; the last line is ``{"ok": true, "device": {...}}`` and is printed
only when every phase passed.  The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache`` of the checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import io
import json
import math
import pathlib
import sys
import tempfile
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent

DELTAS = (1.0, 5.0, 10.0, 100.0, math.inf)
L, N_V, REPLICAS = 10_000, 10, 256
K = 16                       # engine chunk depth (kernel fuse depth)
SEED = 11
#: burn-in and measurement steps of the served study; multiples of K
BURN, STEPS, LONG_STEPS = 4096, 2048, 4096
#: four-chip phase: steps per window mode
SHARDED_BURN, SHARDED_STEPS = 512, 512
#: statistical agreement bound, in combined standard errors
N_SIGMA = 4.0


class PhaseError(AssertionError):
    """A check of the smoke failed."""


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def study_specs(L: int, replicas: int, burn: int, steps: int,
                long_steps: int) -> dict:
    """The three requests: a full Δ study, a nested prefix, a longer run."""
    from repro.experiments.sweep import WindowSweep
    alice = WindowSweep(Ls=(L,), n_vs=(N_V,), deltas=DELTAS,
                        replicas=replicas, n_steps=steps, burn_in=burn,
                        backend="pallas_multistep", window="exact", k_fuse=K,
                        seed=SEED)
    return {
        "alice": alice,
        # its rows are alice's first three Δ blocks: coalesced into her pass
        "bob": dataclasses.replace(alice, deltas=DELTAS[:3]),
        # same stream, measured longer: resumes from the burned-state cache
        "carol": dataclasses.replace(alice, n_steps=long_steps),
    }


def compile_pass(spec, long_steps: int) -> float:
    """Compile the service's passes ahead of time; returns the seconds.

    Also checks that the measurement pass holds the compiled kernel.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.engine import PDESEngine, _run_single
    from repro.core.horizon import PDESConfig, SimState

    cfg = PDESConfig(L=spec.Ls[0], n_v=spec.n_vs[0], delta=math.inf)
    eng = PDESEngine(cfg, backend=spec.backend, window=spec.window,
                     k_fuse=spec.k_fuse)
    if eng.interpret:
        raise PhaseError("the engine resolved interpret mode on this device")
    B, Lr = spec.n_trajectories, cfg.L
    f32 = jnp.float32
    S = jax.ShapeDtypeStruct
    state = SimState(S((B, Lr), f32), S((B,), f32), S((B,), f32),
                     S((), jnp.int32))
    args = (state, S((), jnp.uint32), cfg, eng.ecfg)
    rows = (S((B,), f32), S((B,), jnp.int32))
    t0 = time.perf_counter()
    for n_steps, mode in ((spec.burn_in, "burn"), (spec.n_steps, "record"),
                          (long_steps, "record")):
        lowered = _run_single.lower(*args, n_steps, mode, *rows,
                                    interpret=eng.interpret)
        if mode == "record" and "tpu_custom_call" not in lowered.as_text():
            raise PhaseError("the lowered pass holds no tpu_custom_call")
        lowered.compile()
    return time.perf_counter() - t0


def serve(specs: dict):
    """Submit the requests as a JSONL queue through ``serve_queue``."""
    from repro.service.api import SweepService
    from repro.service.wire import decode_response, encode_request, serve_queue

    with tempfile.TemporaryDirectory() as tmp:
        queue = pathlib.Path(tmp) / "queue.jsonl"
        queue.write_text("".join(
            json.dumps(encode_request(spec, who)) + "\n"
            for who, spec in specs.items()))
        out = io.StringIO()
        t0 = time.perf_counter()
        stats = serve_queue(queue, out, service=SweepService())
        secs = time.perf_counter() - t0
    responses = [decode_response(json.loads(line))
                 for line in out.getvalue().splitlines()]
    errors = [r for r in responses if r.error is not None]
    if errors:
        raise PhaseError(f"error responses: {[r.error for r in errors]}")
    if len(responses) != len(specs):
        raise PhaseError(f"{len(responses)} responses to {len(specs)} "
                         f"requests")
    return responses, stats, secs


def check_direct(responses) -> float:
    """Every response equals a direct ``run_window_sweep`` of its spec."""
    from repro.experiments.sweep import run_window_sweep
    t0 = time.perf_counter()
    for resp in responses:
        direct = run_window_sweep(resp.spec)
        if resp.result.records != direct.records:
            raise PhaseError(f"{resp.requester}: served records differ from "
                             f"a direct run of the same spec")
    return time.perf_counter() - t0


def compare_records(got, want, what: str) -> str:
    """'exact', or per-Δ ⟨u⟩, ⟨w²⟩ within ``N_SIGMA`` standard errors."""
    if got == want:
        return "exact"
    worst = 0.0
    for a, b in zip(got, want):
        for name in ("u", "w2"):
            x, y = getattr(a, name), getattr(b, name)
            err = math.hypot(getattr(a, name + "_err"),
                             getattr(b, name + "_err"))
            if not (math.isfinite(x) and math.isfinite(y)):
                raise PhaseError(f"{what}: non-finite {name} at Δ={a.delta}")
            z = abs(x - y) / err if err > 0 else (0.0 if x == y else math.inf)
            say(f"{what} Δ={a.delta:g} {name}: {x!r} vs {y!r} "
                f"({z:.2f} standard errors)")
            worst = max(worst, z)
    if worst > N_SIGMA:
        raise PhaseError(f"{what}: differs by {worst:.2f} standard errors "
                         f"(bound {N_SIGMA})")
    return f"statistical (worst {worst:.2f} standard errors)"


def tau_mismatch(tau, tau_ref) -> str:
    import numpy as np
    a, b = np.asarray(tau), np.asarray(tau_ref)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        raise PhaseError("non-finite τ")
    diff = a != b
    if not diff.any():
        return "exact"
    return (f"{int(diff.sum())} of {diff.size} elements differ, "
            f"max |Δτ| = {float(np.abs(a - b).max())!r}")


def check_reference(spec, served) -> None:
    """The kernel backend against the ``reference`` backend, same rows."""
    from repro.core.engine import PDESEngine
    from repro.core.horizon import PDESConfig
    from repro.experiments.sweep import run_window_sweep

    ref = run_window_sweep(dataclasses.replace(spec, backend="reference"))
    say(f"reference sweep parity: "
        f"{compare_records(served.records, ref.records, 'u/w2 vs reference')}")
    cfg = PDESConfig(L=spec.Ls[0], n_v=spec.n_vs[0], delta=math.inf)
    n = spec.burn_in + spec.n_steps
    taus = []
    for backend in (spec.backend, "reference"):
        eng = PDESEngine(cfg, backend=backend, k_fuse=spec.k_fuse)
        state, drows = eng.init_sweep(spec.deltas, spec.replicas)
        taus.append(eng.burn_in(state, spec.seed, n, deltas=drows).tau)
    say(f"τ after {n} steps vs reference: {tau_mismatch(*taus)}")


def one_chip_phases() -> None:
    import jax
    specs = study_specs(L, REPLICAS, BURN, STEPS, LONG_STEPS)
    alice = specs["alice"]
    say(f"study: L={L} N_V={N_V} Δ={list(DELTAS)} replicas={REPLICAS} "
        f"rows={alice.n_trajectories} burn_in={BURN} steps={STEPS} "
        f"(carol: {LONG_STEPS}) backend={alice.backend} k_fuse={K}")
    say(f"compile seconds: {compile_pass(alice, LONG_STEPS):.3f}")
    responses, stats, secs = serve(specs)
    say(f"served {len(responses)} responses in {secs:.3f} s: "
        f"{stats.n_passes} passes, {stats.rows_computed} rows computed, "
        f"{stats.rows_from_state_cache} rows from the state cache, "
        f"{stats.engine_row_steps} engine row-steps")
    say(f"direct runs bit-identical to responses "
        f"({check_direct(responses):.3f} s)")
    served = {r.requester: r.result for r in responses}
    t0 = time.perf_counter()
    check_reference(alice, served["alice"])
    say(f"reference comparison took {time.perf_counter() - t0:.3f} s")
    mem = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in mem:
        say(f"peak_bytes_in_use: {mem['peak_bytes_in_use']}")


def sharded_phase(n_chips: int, L: int, replicas: int, burn: int,
                  steps: int) -> None:
    """The ``sharded`` backend over a ring spanning ``n_chips`` devices."""
    import numpy as np

    from repro.compat import make_mesh
    from repro.core import measurement
    from repro.core.engine import PDESEngine
    from repro.core.horizon import PDESConfig

    mesh = make_mesh((1, n_chips), ("data", "model"))
    cfg = PDESConfig(L=L, n_v=N_V, delta=math.inf)
    say(f"sharded: L={L} ({L // n_chips} PEs per chip) Δ={list(DELTAS)} "
        f"replicas={replicas} burn_in={burn} steps={steps} mesh=data=1,"
        f"model={n_chips}")
    for window in ("exact", "stale"):
        out = {}
        for backend in ("sharded", "reference"):
            eng = PDESEngine(cfg, backend=backend, window=window, k_fuse=K,
                             mesh=mesh if backend == "sharded" else None)
            state, drows = eng.init_sweep(DELTAS, replicas)
            t0 = time.perf_counter()
            state = eng.burn_in(state, SEED, burn, deltas=drows)
            state, stats = eng.run(state, SEED, steps, deltas=drows)
            state.tau.block_until_ready()
            secs = time.perf_counter() - t0
            red = measurement.sweep_reduce(stats, len(DELTAS), replicas)
            out[backend] = (state, red)
            say(f"{window}/{backend}: {secs:.3f} s (compile included)")
        (sh, red_sh), (ref, red_ref) = out["sharded"], out["reference"]
        devices = {s.device for s in sh.tau.addressable_shards}
        if len(devices) != n_chips or any(
                s.data.shape != (sh.tau.shape[0], L // n_chips)
                for s in sh.tau.addressable_shards):
            raise PhaseError(f"{window}: τ is not split over {n_chips} "
                             f"devices: {sh.tau.sharding}")
        say(f"{window}: τ sharding {sh.tau.sharding} over "
            f"{sorted(d.id for d in devices)}")
        say(f"{window}: τ vs reference: {tau_mismatch(sh.tau, ref.tau)}")
        for name in ("u", "w2"):
            z = np.abs(red_sh[name] - red_ref[name]) / np.hypot(
                red_sh[name + "_err"], red_ref[name + "_err"])
            say(f"{window}: per-Δ {name} sharded {red_sh[name].tolist()} vs "
                f"reference {red_ref[name].tolist()}")
            if not np.all((red_sh[name] == red_ref[name]) | (z <= N_SIGMA)):
                raise PhaseError(f"{window}: {name} differs from reference "
                                 f"by {np.max(z):.2f} standard errors")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path on one chip (default); "
                         "4: only the sharded phase across four chips")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro sources under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    cache = compile_cache.enable()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    say(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache}")

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            sharded_phase(4, L, REPLICAS, SHARDED_BURN, SHARDED_STEPS)
        else:
            one_chip_phases()
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    say(f"total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
