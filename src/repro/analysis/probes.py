"""Traced entry points ("probes") the rules run against.

A probe is one backend step function traced to a jaxpr at analysis shapes,
plus the metadata the rules need to interpret it: which flat input/output is
``tau``, which array extents are ring widths, the total ring size (for
mod-L wrap normalization), the per-shard ring length of each mesh axis, and
where the window inputs live.

Probe shapes are chosen so that ring widths collide with no other extent
(``B=4`` trials, ``n_v=4``, ``k=2`` fused steps against rings of 16/32
sites), making the "which axis is the ring" lookup in ``graph.ring_axis_of``
unambiguous.

All tracing happens under ``compat.enable_x64`` — with 64-bit
types *available*, any silent f32→f64 / i32→i64 promotion in the traced code
materializes as a 64-bit aval, which is exactly what the dtype-drift rule
scans for.  The clean tree is dtype-disciplined, so the graphs stay pure
f32/i32/u32.

The ``sharded`` backend is traced on an :class:`jax.sharding.AbstractMesh`
(no devices needed); its HLO text (with ``collective-permute``
``source_target_pairs``) comes from the same abstract lowering.  Every
backend — ``sharded`` included, since multi-device sweep sharding landed —
yields a sweep probe whose per-row Δ column is a traced operand, so the
window-bound rule can prove the guard compares against *that* operand on
every advance site.  The ``service`` probe traces the coalesced-batch form
on top of that (``repro.service``): the per-row trial-index vector rides
along as a traced operand, so the invariants are proven for multiplexed
passes too — rows with arbitrary global stream indices and mixed Δs.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..core.engine import BACKENDS, EngineConfig, _make_advance
from ..core.horizon import PDESConfig
from .graph import Graph, build_graph

DEFAULT_DELTA = 8.0


@dataclasses.dataclass
class Probe:
    """One traced entry point + the metadata rules interpret it with."""

    name: str                 # "step" | "sweep" | "stale" | "service" | "vmem"
    backend: str
    graph: Graph
    tau_in: int               # flat input index of tau
    tau_out: int              # flat output index of tau
    ring_widths: frozenset    # array extents that mean "ring axis"
    L_ring: int               # total ring size (mod-L wrap normalization)
    delta: float | None       # static window width (None = inf)
    delta_input: int | None   # flat input index of the per-row Δ column
    shard_L: dict = dataclasses.field(default_factory=dict)  # axis -> L_local
    hlo: str | None = None    # lowered HLO text (sharded probes)
    dtype: str = "float32"    # declared base dtype of tau
    trial_input: int | None = None   # flat input index of the trial vector


def _trace(fn, *args):
    from ..compat import enable_x64
    with enable_x64():
        return build_graph(jax.make_jaxpr(fn)(*args))


def _single_probes(backend: str):
    """step/sweep (+ production-shape vmem) probes for one-device backends."""
    B, L, K = 4, 16, 2
    cfg = PDESConfig(L=L, n_v=4, delta=DEFAULT_DELTA)
    for name, window in (("step", "exact"), ("stale", "stale")):
        if backend == "pallas_multistep" and window == "stale":
            continue       # rejected by EngineConfig: exact-GVT only
        ecfg = EngineConfig(backend=backend, window=window, k_fuse=K)
        advance = _make_advance(cfg, ecfg, B, L, interpret=True)

        def fn(tau, step0, seed, b0, advance=advance):
            return advance(tau, step0, seed, K, None, b0)

        g = _trace(fn, jnp.zeros((B, L), jnp.float32), jnp.int32(0),
                   jnp.uint32(0), jnp.int32(0))
        yield Probe(name, backend, g, tau_in=0, tau_out=0,
                    ring_widths=frozenset({L, L + 2}), L_ring=L,
                    delta=cfg.delta, delta_input=None)

    ecfg = EngineConfig(backend=backend, window="exact", k_fuse=K)
    advance = _make_advance(cfg, ecfg, B, L, interpret=True)

    def fn(tau, step0, seed, delta_col, b0, advance=advance):
        return advance(tau, step0, seed, K, delta_col, b0)

    g = _trace(fn, jnp.zeros((B, L), jnp.float32), jnp.int32(0),
               jnp.uint32(0), jnp.full((B, 1), DEFAULT_DELTA, jnp.float32),
               jnp.int32(0))
    yield Probe("sweep", backend, g, tau_in=0, tau_out=0,
                ring_widths=frozenset({L, L + 2}), L_ring=L,
                delta=0.0, delta_input=3)

    # the coalesced-batch form (repro.service): per-row Δ column plus a
    # per-row trial-index vector instead of a scalar stream base
    g = _trace(fn, jnp.zeros((B, L), jnp.float32), jnp.int32(0),
               jnp.uint32(0), jnp.full((B, 1), DEFAULT_DELTA, jnp.float32),
               jnp.arange(B, dtype=jnp.int32))
    yield Probe("service", backend, g, tau_in=0, tau_out=0,
                ring_widths=frozenset({L, L + 2}), L_ring=L,
                delta=0.0, delta_input=3, trial_input=4)

    if backend in ("pallas", "pallas_multistep"):
        # production-shape trace: the VMEM rule sizes real BlockSpecs here
        Bp, Lp, Kp = 64, 1024, 16
        cfgp = PDESConfig(L=Lp, n_v=4, delta=DEFAULT_DELTA)
        ecfg = EngineConfig(backend=backend, window="exact", k_fuse=Kp)
        advance = _make_advance(cfgp, ecfg, Bp, Lp, interpret=True)

        def fn(tau, step0, seed, b0, advance=advance, Kp=Kp):
            return advance(tau, step0, seed, Kp, None, b0)

        g = _trace(fn, jnp.zeros((Bp, Lp), jnp.float32), jnp.int32(0),
                   jnp.uint32(0), jnp.int32(0))
        yield Probe("vmem", backend, g, tau_in=0, tau_out=0,
                    ring_widths=frozenset({Lp, Lp + 2}), L_ring=Lp,
                    delta=cfgp.delta, delta_input=None)


def _abstract_mesh(ens: int, ring: int):
    from jax.sharding import AbstractMesh
    return AbstractMesh((ens, ring), ("data", "model"))


def _sharded_probes():
    from jax.sharding import PartitionSpec as P

    from ..compat import shard_map
    from ..core.distributed import STAT_KEYS, DistConfig, _shard_body

    B, L, ens, ring = 4, 32, 2, 4
    L_l = L // ring
    cfg = PDESConfig(L=L, n_v=4, delta=DEFAULT_DELTA)
    mesh = _abstract_mesh(ens, ring)
    # (name, mode, K, with Δ-column sweep operand, with trial-vector operand)
    for name, mode, K, sweep, trial in (
            ("step", "exact", 2, False, False),
            ("stale", "commavoid", 4, False, False),
            ("sweep", "exact", 2, True, False),
            ("service", "exact", 2, True, True)):
        dist = DistConfig(mode=mode, k_chunk=K)
        if trial:
            def fn(tau0, off0, comp0, seed, step0, b0, dcol, tcol,
                   dist=dist):
                return _shard_body(tau0, off0, comp0, seed, step0, b0,
                                   dcol, tcol, cfg=cfg, dist=dist,
                                   n_steps=K, L_total=L)
        else:
            fn = functools.partial(_shard_body, cfg=cfg, dist=dist,
                                   n_steps=K, L_total=L)
        in_specs = (P(dist.ens_axes, dist.ring_axis), P(dist.ens_axes),
                    P(dist.ens_axes), P(), P(), P())
        shapes = [jax.ShapeDtypeStruct((B, L), jnp.float32),
                  jax.ShapeDtypeStruct((B,), jnp.float32),
                  jax.ShapeDtypeStruct((B,), jnp.float32),
                  jax.ShapeDtypeStruct((), jnp.uint32),
                  jax.ShapeDtypeStruct((), jnp.int32),
                  jax.ShapeDtypeStruct((), jnp.int32)]
        if sweep:
            # the Δ column shards over the ensemble axes like the tau rows
            in_specs += (P(dist.ens_axes),)
            shapes.append(jax.ShapeDtypeStruct((B,), jnp.float32))
        if trial:
            # ...as does the coalesced-batch per-row trial-index vector
            in_specs += (P(dist.ens_axes),)
            shapes.append(jax.ShapeDtypeStruct((B,), jnp.int32))
        shard_fn = shard_map(
            fn, mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(dist.ens_axes, dist.ring_axis), P(dist.ens_axes),
                       P(dist.ens_axes),
                       (P(None, dist.ens_axes),) * len(STAT_KEYS)),
            check_rep=False)
        args = [jnp.zeros(s.shape, s.dtype) for s in shapes]
        if trial:
            args[7] = jnp.arange(B, dtype=jnp.int32)
        if sweep:
            args[6] = jnp.full((B,), DEFAULT_DELTA, jnp.float32)
        g = _trace(shard_fn, *args)
        hlo = None
        try:
            hlo = jax.jit(shard_fn).lower(*shapes).as_text(dialect="hlo")
        except Exception:  # lowering is best-effort; jaxpr rules still run
            pass
        widths = {L, L_l, L_l + 2}
        if mode == "commavoid":
            widths |= {L_l + 2 * K, L_l + 2 * K + 2}
        yield Probe(name, "sharded", g, tau_in=0, tau_out=0,
                    ring_widths=frozenset(widths), L_ring=L,
                    delta=0.0 if sweep else cfg.delta,
                    delta_input=6 if sweep else None,
                    trial_input=7 if trial else None,
                    shard_L={"model": L_l}, hlo=hlo)


def iter_probes(backend: str):
    """Yield every :class:`Probe` of one backend."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    if backend == "sharded":
        yield from _sharded_probes()
    else:
        yield from _single_probes(backend)
