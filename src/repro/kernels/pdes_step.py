"""Fused one-step PDES update kernel (Pallas, TPU target).

The paper's hot spot is the per-step horizon sweep: in unfused form XLA emits
~7 HBM round trips per step (two rolls, two compares, a select, a min
reduction, stats).  This kernel performs them in a single VMEM pass:
read tau + event bits once, write tau' + per-row partial stats once.

Layout: the caller passes a *haloed* chunk ``tau`` of shape ``(B, Lc + 2)``
whose first/last columns hold the left/right neighbor values (wrap-around
columns for a full ring, or the halo received from neighbor shards in the
distributed runtime).  The window base ``gvt`` is supplied by the caller
(exact current minimum, or a stale/conservative bound — DESIGN.md B3), which
is how the engine exposes both window modes through one kernel.

The update rule itself is the shared core (``horizon.decode_words`` +
``horizon.conservative_update``) — the same traced code as the reference
scan and the sharded runtime, so cross-backend bit-parity is structural.
Per-row stats are the shared ``horizon.ring_moments`` reductions; ``sumabs``
is about the tile-local mean and is meaningful when the tile spans a full
ring (always the case for the engine and ``ops.step_ring``).

Grid/tiling: grid is over ensemble-row blocks; each program instance owns a
``(block_b, Lc + 2)`` VMEM tile.  Row blocks are independent, so the grid is
embarrassingly parallel ("parallel" dimension semantics).  The lane dimension
(Lc) is kept whole per tile because the neighbor stencil couples the entire
ring; the tile height comes from the VMEM model in ``tiling``.

TPU note: on CPU we validate with ``interpret=True``; on real TPU hardware
the uint32->exponential decode happens in VREGs and the kernel is purely
HBM-bandwidth-bound (arithmetic intensity ~1 flop/byte — see the roofline
discussion in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from ..core.horizon import (MOMENT_KEYS as STAT_KEYS, conservative_update,
                            decode_words, ring_moments)
from .tiling import pick_divisor_block


def _kernel(tau_ref, w0_ref, w1_ref, gvt_ref, out_ref, *stat_refs,
            n_v: int, delta: float, rd_mode: bool, border_both: bool):
    tau_h = tau_ref[...]                      # (b, Lc + 2) haloed
    tau = tau_h[:, 1:-1]

    is_left, is_right, eta = decode_words(
        w0_ref[...], w1_ref[...], n_v, out_ref.dtype)
    tau_next, update = conservative_update(
        tau, tau_h[:, :-2], tau_h[:, 2:], is_left, is_right, eta,
        gvt_ref[...],                         # (b, 1) broadcast window base
        delta=delta, rd_mode=rd_mode, border_both=border_both)

    out_ref[...] = tau_next
    moments = ring_moments(tau_next, update)
    for key, ref in zip(STAT_KEYS, stat_refs):
        ref[...] = moments[key][:, None]


@functools.partial(
    jax.jit,
    static_argnames=("n_v", "delta", "rd_mode", "border_both", "block_b",
                     "interpret"),
)
def pdes_step(
    tau_haloed: jax.Array,
    bits: jax.Array,
    gvt: jax.Array,
    *,
    n_v: int,
    delta: float,
    rd_mode: bool = False,
    border_both: bool = False,
    block_b: int = 8,
    interpret: bool,
):
    """One fused PDES step on a haloed chunk.

    Args:
      tau_haloed: (B, Lc + 2) local times with neighbor halo columns.
      bits: (B, Lc, 2) uint32 event bits.  The kernel reads them as two
        lane-dense (B, Lc) word planes: a trailing pair axis would sit on
        the TPU's 128-lane axis and take 64x its size in VMEM.
      gvt: (B, 1) window base.
      block_b: ensemble rows per VMEM tile (see ``tiling.pick_divisor_block``).
      interpret: run the kernel body in the Pallas interpreter (required off
        the TPU; ``PDESEngine`` resolves it from the platform).

    Returns:
      (tau_next (B, Lc), stats dict of (B,): ucount/min/max/sum/sumsq/sumabs).
    """
    B, Lc2 = tau_haloed.shape
    Lc = Lc2 - 2
    assert bits.shape == (B, Lc, 2), (bits.shape, (B, Lc, 2))
    assert gvt.shape == (B, 1)
    bb = pick_divisor_block(B, block_b)
    grid = (B // bb,)
    kern = functools.partial(_kernel, n_v=n_v, delta=delta, rd_mode=rd_mode,
                             border_both=border_both)
    out_shape = [jax.ShapeDtypeStruct((B, Lc), tau_haloed.dtype)] + [
        jax.ShapeDtypeStruct((B, 1), tau_haloed.dtype) for _ in STAT_KEYS]
    col = pl.BlockSpec((bb, 1), lambda i: (i, 0))
    plane = pl.BlockSpec((bb, Lc), lambda i: (i, 0))
    outs = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((bb, Lc2), lambda i: (i, 0)), plane, plane,
                  col],
        out_specs=[plane] + [col] * len(STAT_KEYS),
        out_shape=out_shape,
        interpret=interpret,
    )(tau_haloed, bits[..., 0], bits[..., 1], gvt)
    tau_next = outs[0]
    stats = {k: v[:, 0] for k, v in zip(STAT_KEYS, outs[1:])}
    return tau_next, stats
