"""K-step VMEM-resident PDES kernels (Pallas, TPU target).

Beyond-paper optimization B2 (DESIGN.md §5): the one-step kernel is
HBM-bandwidth-bound at ~12 bytes of traffic per PE-step (tau in/out + bits).
Keeping the ring resident in VMEM across K steps removes the tau round trips:

    traffic/step ≈ 8 bytes(bits) + 8/K bytes(tau)   → ~1.5× less at K = 16.

Two variants share one step body (``_fused_step``, built on the shared core
in ``horizon``):

* ``pdes_multistep`` — event bits streamed from HBM one step at a time
  (arbitrary external streams, e.g. the jax.random stream of ``horizon``).
* ``pdes_multistep_counter`` — event bits generated **inside the kernel**
  from the counter-based stream (``events.counter_words`` on index iotas).
  No bits array exists at all: traffic drops to ~8/K bytes/PE-step, a K×
  intensity gain, and on CPU/interpret the murmur32 hash is far cheaper
  than host-side threefry.  This is the engine's fast path.

Because each program instance owns *entire rings* ``(block_b, L)``, the exact
global virtual time is available locally every step (a lane-wise min), so
these kernels implement the *paper-faithful* exact-GVT algorithm, not the
stale-GVT approximation.

Grid/tiling: grid = (ensemble blocks, K).  The K dimension is sequential
("arbitrary"): the tau tile is revisited — written at step k, re-read at
k + 1 — which Pallas guarantees for the same output block across grid steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.events import counter_words
from ..core.horizon import (MOMENT_KEYS as STAT_KEYS, conservative_update,
                            decode_words, ring_moments)
from .tiling import pick_divisor_block


def _fused_step(tau, w0, w1, *, n_v, delta, rd_mode, border_both):
    """One in-VMEM update on full rings; returns (tau_next, moments)."""
    is_left, is_right, eta = decode_words(w0, w1, n_v, tau.dtype)
    left = jnp.roll(tau, 1, axis=-1)        # periodic: full ring resident
    right = jnp.roll(tau, -1, axis=-1)
    gvt = jnp.min(tau, axis=-1, keepdims=True)   # exact GVT, in-VMEM
    tau_next, update = conservative_update(
        tau, left, right, is_left, is_right, eta, gvt,
        delta=delta, rd_mode=rd_mode, border_both=border_both)
    return tau_next, ring_moments(tau_next, update)


def _write_step(tau_ref, stat_refs, tau_next, moments):
    tau_ref[...] = tau_next
    for key, ref in zip(STAT_KEYS, stat_refs):
        ref[...] = moments[key][None, :, None]


def _kernel_bits(tau_in_ref, w0_ref, w1_ref, tau_ref, *stat_refs,
                 n_v: int, delta: float, rd_mode: bool, border_both: bool):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        tau_ref[...] = tau_in_ref[...]

    tau = tau_ref[...]                      # (b, L) full rings
    tau_next, moments = _fused_step(
        tau, w0_ref[0], w1_ref[0],          # this step's (b, L) event words
        n_v=n_v, delta=delta, rd_mode=rd_mode, border_both=border_both)
    _write_step(tau_ref, stat_refs, tau_next, moments)


def _kernel_counter(ctr_ref, tau_in_ref, *refs,
                    n_v: int, delta: float, rd_mode: bool, border_both: bool,
                    block_b: int, has_delta_col: bool, has_trial_col: bool):
    refs = list(refs)
    if has_delta_col:
        delta = refs.pop(0)[...]            # (b, 1) per-row window widths
    trial_ref = refs.pop(0) if has_trial_col else None
    tau_ref, *stat_refs = refs
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        tau_ref[...] = tau_in_ref[...]

    tau = tau_ref[...]                      # (b, L) full rings
    b, L = tau.shape
    seed, step0, b0, l0 = (ctr_ref[0, i] for i in range(4))
    step = step0 + k.astype(jnp.uint32)
    if has_trial_col:
        bi = trial_ref[...]                 # (b, 1) per-row trial indices
    else:
        row0 = (pl.program_id(0) * block_b).astype(jnp.uint32)
        bi = b0 + row0 + jax.lax.broadcasted_iota(jnp.uint32, (b, L), 0)
    li = l0 + jax.lax.broadcasted_iota(jnp.uint32, (b, L), 1)
    w0, w1 = counter_words(seed, step, bi, li)
    tau_next, moments = _fused_step(
        tau, w0, w1,
        n_v=n_v, delta=delta, rd_mode=rd_mode, border_both=border_both)
    _write_step(tau_ref, stat_refs, tau_next, moments)


def _call_multistep(kern, inputs, in_specs, B, L, K, bb, dtype, interpret):
    # per-step stats are written as (K, B, 1) columns: a (1, bb) row block
    # of a (K, B) array breaks the TPU's (8, 128) block rule.
    out_shape = [jax.ShapeDtypeStruct((B, L), dtype)] + [
        jax.ShapeDtypeStruct((K, B, 1), dtype) for _ in STAT_KEYS]
    row = pl.BlockSpec((1, bb, 1), lambda i, k: (k, i, 0))
    outs = pl.pallas_call(
        kern,
        grid=(B // bb, K),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bb, L), lambda i, k: (i, 0))]
        + [row] * len(STAT_KEYS),
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)
    return outs[0], {key: o[..., 0] for key, o in zip(STAT_KEYS, outs[1:])}


@functools.partial(
    jax.jit,
    static_argnames=("n_v", "delta", "rd_mode", "border_both", "block_b",
                     "interpret"),
)
def pdes_multistep(
    tau: jax.Array,
    bits: jax.Array,
    *,
    n_v: int,
    delta: float,
    rd_mode: bool = False,
    border_both: bool = False,
    block_b: int = 8,
    interpret: bool,
):
    """K fused exact-GVT PDES steps on full rings, bits streamed from HBM.

    Args:
      tau: (B, L) full rings (periodic).
      bits: (K, B, L, 2) uint32 event bits for the K steps.

    Returns:
      (tau_final (B, L), stats dict of (K, B): ucount/min/max/sum/sumsq/
      sumabs), per-step stats measured after each step's update.
    """
    B, L = tau.shape
    K = bits.shape[0]
    assert bits.shape == (K, B, L, 2)
    bb = pick_divisor_block(B, block_b)
    kern = functools.partial(_kernel_bits, n_v=n_v, delta=delta,
                             rd_mode=rd_mode, border_both=border_both)
    # the words travel as lane-dense (K, B, L) planes (see pdes_step)
    plane = pl.BlockSpec((1, bb, L), lambda i, k: (k, i, 0))
    in_specs = [pl.BlockSpec((bb, L), lambda i, k: (i, 0)), plane, plane]
    return _call_multistep(kern, (tau, bits[..., 0], bits[..., 1]), in_specs,
                           B, L, K, bb, tau.dtype, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("k_steps", "n_v", "delta", "rd_mode", "border_both",
                     "block_b", "interpret"),
)
def pdes_multistep_counter(
    tau: jax.Array,
    ctr: jax.Array,
    delta_col: jax.Array | None = None,
    trial_col: jax.Array | None = None,
    *,
    k_steps: int,
    n_v: int,
    delta: float,
    rd_mode: bool = False,
    border_both: bool = False,
    block_b: int = 8,
    interpret: bool,
):
    """K fused exact-GVT steps with the event stream generated in-kernel.

    Args:
      tau: (B, L) full rings (periodic).
      ctr: (1, 4) uint32 ``[seed, step0, b0, l0]`` — counter-stream seed,
        first step index, and global (trial, PE) offsets of this block.
        Steps k = 0..k_steps-1 consume stream step ``step0 + k``; the
        trajectory is bit-identical to feeding ``events.counter_bits`` into
        ``pdes_multistep``.
      delta_col: optional (B, 1) per-row window widths.  When given, the
        window bound becomes a *batched operand*: each ensemble row applies
        its own Δ (``inf`` rows = unconstrained) and the static ``delta``
        is ignored.  This is how one kernel pass serves a whole window
        sweep — the Δ grid rides on the ensemble axis.
      trial_col: optional (B, 1) uint32 per-row *global trial indices*.
        When given, row r's event stream is keyed on ``trial_col[r]``
        instead of ``b0 + r`` — the coalesced-batch operand of
        ``repro.service``, letting one pass pack rows from many requests on
        arbitrary (possibly duplicate) stream coordinates.  ``trial_col =
        b0 + arange(B)`` with ``ctr`` b0 zeroed is bit-identical to the
        scalar form.
      k_steps: number of fused steps (static).

    Returns: same as ``pdes_multistep``.
    """
    B, L = tau.shape
    assert ctr.shape == (1, 4) and ctr.dtype == jnp.uint32, (ctr.shape,
                                                             ctr.dtype)
    bb = pick_divisor_block(B, block_b)
    kern = functools.partial(_kernel_counter, n_v=n_v, delta=delta,
                             rd_mode=rd_mode, border_both=border_both,
                             block_b=bb, has_delta_col=delta_col is not None,
                             has_trial_col=trial_col is not None)
    in_specs = [
        pl.BlockSpec((1, 4), lambda i, k: (0, 0)),
        pl.BlockSpec((bb, L), lambda i, k: (i, 0)),
    ]
    inputs = [ctr, tau]
    if delta_col is not None:
        assert delta_col.shape == (B, 1), delta_col.shape
        in_specs.append(pl.BlockSpec((bb, 1), lambda i, k: (i, 0)))
        inputs.append(delta_col.astype(tau.dtype))
    if trial_col is not None:
        assert trial_col.shape == (B, 1), trial_col.shape
        in_specs.append(pl.BlockSpec((bb, 1), lambda i, k: (i, 0)))
        inputs.append(trial_col.astype(jnp.uint32))
    return _call_multistep(kern, tuple(inputs), in_specs, B, L, k_steps, bb,
                           tau.dtype, interpret)
