"""Public jit'd wrappers around the Pallas PDES kernels.

These present the same semantics as ``repro.core.horizon`` (identical event
stream, identical update rule) so the kernel path is a drop-in replacement
for the pure-XLA path — cross-validated in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import horizon
from ..core.horizon import PDESConfig
from .pdes_step import pdes_step
from .pdes_multistep import pdes_multistep, pdes_multistep_counter  # noqa: F401  (re-export)


def ring_halo(tau: jax.Array) -> jax.Array:
    """(B, L) -> (B, L + 2) with periodic wrap columns."""
    return jnp.concatenate([tau[:, -1:], tau, tau[:, :1]], axis=1)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret", "block_b"))
def step_ring(tau: jax.Array, bits: jax.Array, cfg: PDESConfig,
              *, interpret: bool, block_b: int = 8):
    """One fused step on full rings via the one-step kernel.

    Computes the exact GVT outside the kernel (one XLA reduction), then does
    the fused sweep.  Returns (tau_next, update-count stats dict).
    """
    gvt = jnp.min(tau, axis=-1, keepdims=True)
    return pdes_step(
        ring_halo(tau), bits, gvt,
        n_v=cfg.n_v, delta=cfg.delta, rd_mode=cfg.rd_mode,
        block_b=block_b, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("cfg", "n_steps", "interpret",
                                             "block_b", "k_fuse"))
def simulate(state: horizon.SimState, key: jax.Array, cfg: PDESConfig,
             n_steps: int, *, interpret: bool, block_b: int = 8,
             k_fuse: int = 16):
    """Kernel-path equivalent of ``horizon.run`` (exact algorithm).

    Runs ``n_steps`` in K-fused chunks via ``pdes_multistep``; emits per-step
    (utilization, w2, gvt) derived from the kernel's fused partial reductions
    through the shared ``horizon.stats_from_moments`` post-processing.

    Kept for the jax.random (threefry) event stream; the counter-stream
    engine (``repro.core.engine.PDESEngine``) supersedes this as the one
    entry point for multi-backend runs.

    Returns (final SimState, dict of (n_steps, B) arrays: u, w2, gvt).
    """
    B, L = state.tau.shape
    n_chunks, rem = divmod(n_steps, k_fuse)

    def chunk_body(carry, k):
        """k fused steps; k is static per call site."""
        tau, off, comp, step0 = carry
        # event bits for the k steps, keyed exactly like horizon._one_step
        steps = step0 + jnp.arange(k, dtype=jnp.int32)
        bits = jax.vmap(lambda s: horizon.event_bits(key, s, (B, L)))(steps)
        tau, moments = pdes_multistep(
            tau, bits, n_v=cfg.n_v, delta=cfg.delta, rd_mode=cfg.rd_mode,
            block_b=block_b, interpret=interpret)
        st = horizon.stats_from_moments(moments, off[None, :], L)
        # rebase once per chunk (fp32 hygiene; see horizon.SimState docstring)
        shift = jnp.min(tau, axis=-1)
        tau = tau - shift[:, None]
        off, comp = horizon._kahan_add(off, comp, shift)
        return (tau, off, comp, step0 + k), (st.utilization, st.w2, st.gvt)

    carry = (state.tau, state.offset, state.offset_comp, state.step)
    outs = []
    if n_chunks:
        carry, (u, w2, gvt) = jax.lax.scan(
            lambda c, _: chunk_body(c, k_fuse), carry, None, length=n_chunks)
        outs.append((u.reshape(-1, B), w2.reshape(-1, B), gvt.reshape(-1, B)))
    if rem:
        carry, (u, w2, gvt) = chunk_body(carry, rem)
        outs.append((u, w2, gvt))
    tau, off, comp, step = carry
    cat = lambda i: jnp.concatenate([o[i] for o in outs], axis=0)
    out = {"u": cat(0), "w2": cat(1), "gvt": cat(2)}
    return horizon.SimState(tau, off, comp, step), out

