"""Virtual time horizon dynamics for conservative PDES with a moving Δ-window.

Implements the update rules of Kolakowska, Novotny & Korniss, PRE 67, 046703:

* short-range (conservative) causality rule, Eq. (1):
  a PE that picked a *border* site may update only if its local virtual time
  does not exceed that of the neighbor(s) adjacent to the chosen border;
* moving-window global constraint, Eq. (3):
  ``tau_k <= delta + GVT`` with ``GVT = min_k tau_k`` (the global virtual
  time).  ``delta = inf`` recovers the unconstrained scheme; ``delta = 0``
  serializes the ring;
* random-deposition (RD) mode: the causality rule is dropped entirely,
  modelling the infinite-``N_V`` limit (Sec. IV.A of the paper).

All state is dense:  ``tau`` has shape ``(B, L)`` for an ensemble of ``B``
independent rings of ``L`` processing elements.  One parallel step ``t``
is one vectorized sweep.  The event stream (site picks and Poisson time
increments) is derived from counter-based uint32 bits so that every
consumer (pure-jnp reference, Pallas kernel, sharded runtime) reproduces
bit-identical trajectories.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PDESConfig:
    """Static parameters of one PDES ensemble.

    Attributes:
      L: number of processing elements on the ring.
      n_v: number of lattice sites (operation volumes) per PE, ``N_V`` in the
        paper.  Border sites are site ``0`` (left) and site ``n_v - 1``
        (right); for ``n_v == 1`` the single site is both borders and the
        causality rule compares against *both* neighbors, exactly Eq. (1).
      delta: width of the moving window, ``inf`` disables the constraint.
      rd_mode: if True, drop the causality rule (random deposition limit —
        the paper's ``N_V -> inf`` limit; only the window rule acts).
      border_both: if True, any border pick checks both neighbors (the
        literal reading of Eq. (1) for ``n_v > 1``); default False checks
        only the neighbor adjacent to the picked border, the standard model
        used in the paper's own N_V > 1 simulations (cf. Eq. (13), where a
        border pick inquires about *its* neighboring PE).
      dtype: dtype of the virtual times.
    """

    L: int
    n_v: int = 1
    delta: float = math.inf
    rd_mode: bool = False
    border_both: bool = False
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.L < 2:
            raise ValueError(f"need at least 2 PEs, got L={self.L}")
        if self.n_v < 1:
            raise ValueError(f"need at least one site per PE, got n_v={self.n_v}")
        if not (self.delta >= 0):
            raise ValueError(f"delta must be >= 0 (or inf), got {self.delta}")


class StepStats(NamedTuple):
    """Per-step per-trial observables (each ``(B,)``)."""

    utilization: jax.Array   # fraction of PEs that updated, <u(t)> per trial
    w2: jax.Array            # surface variance, Eq. (4) (before sqrt)
    wa: jax.Array            # absolute width, Eq. (5)
    gvt: jax.Array           # global virtual time min_k tau_k (absolute)
    mean_tau: jax.Array      # mean virtual time (absolute)
    max_dev: jax.Array       # extreme fluctuation above the mean
    min_dev: jax.Array       # extreme fluctuation below the mean (>= 0)


class SimState(NamedTuple):
    """Scan carry.

    ``tau`` is kept *rebased* (GVT subtracted every step) so that float32
    resolution never degrades: the dynamics only depend on differences of
    local times, and widths are O(delta) or O(L^alpha) while absolute times
    grow without bound.  The accumulated offset is carried with Kahan
    compensation so absolute observables (GVT growth rate, mean time) stay
    accurate over millions of steps.
    """

    tau: jax.Array           # (B, L) rebased virtual times, min == 0
    offset: jax.Array        # (B,) accumulated rebasing offset (Kahan sum)
    offset_comp: jax.Array   # (B,) Kahan compensation term
    step: jax.Array          # () int32 parallel step index t


# ---------------------------------------------------------------------------
# event stream: counter-based bits -> (border flags, exponential increments)
# ---------------------------------------------------------------------------


def event_bits(key: jax.Array, step: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """uint32 event bits for one parallel step, shape ``shape + (2,)``.

    Keyed on (key, step) so owner and halo-redundant shards reproduce the
    same events (communication-avoidance, DESIGN.md B4).
    """
    k = jax.random.fold_in(key, step)
    return jax.random.bits(k, shape + (2,), dtype=jnp.uint32)


def decode_words(w0: jax.Array, w1: jax.Array, n_v: int, dtype):
    """Event decode from two uint32 words -> (is_left, is_right, eta).

    site ~ Uniform{0..n_v-1} from ``w0`` (modulo; bias < 2**-16 for the
    paper's n_v range), eta ~ Exp(1) from ``w1`` via inverse CDF.

    This is THE event decode: the reference scan, both Pallas kernel bodies,
    and the sharded runtime all call it, so every backend interprets the
    event stream identically (bit-exact trajectories by construction).
    Pure jnp on plain arrays — safe inside Pallas kernel bodies.
    """
    site = jnp.remainder(w0, jnp.uint32(n_v)).astype(jnp.int32)
    is_left = site == 0
    is_right = site == (n_v - 1)
    # uniform in (0, 1]: use the top 24 bits, then add 2^-25 to avoid log(0).
    # The 24-bit value fits int32 exactly; Mosaic has no uint32 -> f32 cast,
    # so convert through int32 (same bits on every backend).
    u = (w1 >> jnp.uint32(8)).astype(jnp.int32).astype(dtype) * 2.0**-24
    eta = -jnp.log(u + 2.0**-25)
    return is_left, is_right, eta


def decode_events(bits: jax.Array, cfg: PDESConfig):
    """bits ``(..., 2)`` -> (is_left, is_right, eta) (see ``decode_words``)."""
    return decode_words(bits[..., 0], bits[..., 1], cfg.n_v, cfg.dtype)


def conservative_update(
    tau: jax.Array,
    left: jax.Array,
    right: jax.Array,
    is_left: jax.Array,
    is_right: jax.Array,
    eta: jax.Array,
    gvt: jax.Array,
    *,
    delta: float | jax.Array,
    rd_mode: bool = False,
    border_both: bool = False,
):
    """Causality rule Eq. (1) + window rule Eq. (3) + update, in one place.

    ``left``/``right`` are the neighbor values however the caller obtained
    them (rolls on a full ring, halo columns on a shard, VMEM-resident rolls
    inside a kernel).  ``gvt`` is the window base — exact current minimum or
    a stale/conservative bound — and is ignored when ``delta`` is inf.

    ``delta`` may be a static Python float (the single-window case; inf
    short-circuits the window rule) or a *traced array* broadcastable
    against ``tau`` — e.g. a ``(B, 1)`` per-trajectory column for batched
    window sweeps, where each ensemble row carries its own Δ.  Array rows
    holding ``inf`` recover the unconstrained rule bit-for-bit, since
    ``tau <= inf + gvt`` is identically True for finite ``gvt``.

    Returns ``(tau_next, update)``.  Pure jnp — shared by the reference
    scan (``step_core``), the Pallas kernel bodies, and the sharded runtime.
    """
    # boolean algebra rather than ``where(flag, ok, True)``: Mosaic cannot
    # lower a select between boolean vectors.
    if rd_mode:
        causal_ok = jnp.ones(tau.shape, dtype=bool)
    elif border_both:
        is_border = is_left | is_right
        causal_ok = ~is_border | ((tau <= left) & (tau <= right))
    else:
        causal_ok = (~is_left | (tau <= left)) & (~is_right | (tau <= right))
    if isinstance(delta, (int, float)) and math.isinf(delta):
        window_ok = jnp.ones(tau.shape, dtype=bool)
    else:
        window_ok = tau <= delta + gvt
    update = causal_ok & window_ok
    return tau + jnp.where(update, eta, 0.0), update


# ---------------------------------------------------------------------------
# one parallel update attempt (pure, RNG-free)
# ---------------------------------------------------------------------------


def step_core(
    tau: jax.Array,
    is_left: jax.Array,
    is_right: jax.Array,
    eta: jax.Array,
    cfg: PDESConfig,
    *,
    gvt_for_window: jax.Array | None = None,
    delta_override: jax.Array | None = None,
):
    """One conservative update attempt on every PE of every trial.

    Args:
      tau: (B, L) local virtual times.
      is_left/is_right: (B, L) bool, whether the picked site is the
        left/right border site (both True when n_v == 1).
      eta: (B, L) exponential(1) candidate time increments.
      gvt_for_window: optional (B, 1)-broadcastable *stale* GVT to use in the
        window rule instead of the exact current minimum.  Because GVT is
        non-decreasing, a stale value yields a stricter window and the scheme
        stays conservative (DESIGN.md B3).
      delta_override: optional (B, 1) per-trajectory window widths replacing
        the static ``cfg.delta`` — the batched window-sweep path, where the
        Δ axis rides on the ensemble axis (``inf`` rows = unconstrained).

    Returns:
      (tau_next, update_mask, gvt) with gvt the exact current minimum
      (always computed; it is also the rebasing amount).
    """
    left_nbr = jnp.roll(tau, 1, axis=-1)    # tau_{k-1}
    right_nbr = jnp.roll(tau, -1, axis=-1)  # tau_{k+1}
    gvt = jnp.min(tau, axis=-1, keepdims=True)  # (B, 1) exact global minimum
    base = gvt if gvt_for_window is None else gvt_for_window
    delta = cfg.delta if delta_override is None else delta_override
    tau_next, update = conservative_update(
        tau, left_nbr, right_nbr, is_left, is_right, eta, base,
        delta=delta, rd_mode=cfg.rd_mode, border_both=cfg.border_both)
    return tau_next, update, gvt[..., 0]


def measure(tau: jax.Array, update: jax.Array, offset: jax.Array) -> StepStats:
    """Paper observables from one post-update state (Eqs. 4-5 + utilization)."""
    dtype = tau.dtype
    mean = jnp.mean(tau, axis=-1, keepdims=True)
    dev = tau - mean
    return StepStats(
        utilization=jnp.mean(update.astype(dtype), axis=-1),
        w2=jnp.mean(dev * dev, axis=-1),
        wa=jnp.mean(jnp.abs(dev), axis=-1),
        gvt=jnp.min(tau, axis=-1) + offset,
        mean_tau=mean[..., 0] + offset,
        max_dev=jnp.max(dev, axis=-1),
        min_dev=-jnp.min(dev, axis=-1),
    )


#: Key order of ``ring_moments`` output — load-bearing for the kernels,
#: which zip it against their pallas_call output refs.
MOMENT_KEYS = ("ucount", "min", "max", "sum", "sumsq", "sumabs")


def ring_moments(tau: jax.Array, update: jax.Array) -> dict:
    """Per-ring partial reductions of one post-update state.

    Returns the raw moments every backend records per step — ``ucount``,
    ``min``, ``max``, ``sum``, ``sumsq``, ``sumabs`` (each reduced over the
    last axis) — from which ``stats_from_moments`` rebuilds the full
    ``StepStats``.  Pure jnp, usable inside Pallas kernel bodies; ``sumabs``
    (and hence ``wa``) assumes the last axis spans a complete ring, since
    the absolute width is measured about the ring mean.
    """
    dtype = tau.dtype
    s = jnp.sum(tau, axis=-1)
    mean = s / tau.shape[-1]
    return dict(
        ucount=jnp.sum(update.astype(dtype), axis=-1),
        min=jnp.min(tau, axis=-1),
        max=jnp.max(tau, axis=-1),
        sum=s,
        sumsq=jnp.sum(tau * tau, axis=-1),
        sumabs=jnp.sum(jnp.abs(tau - mean[..., None]), axis=-1),
    )


def stats_from_moments(moments: dict, offset: jax.Array, L: int) -> StepStats:
    """Assemble ``StepStats`` from ``ring_moments`` output.

    ``offset`` is the accumulated rebasing offset, broadcastable against the
    moment arrays (e.g. ``off[None, :]`` for per-chunk ``(K, B)`` moments).
    The single place where moment post-processing lives — the engine, the
    kernel-path driver, and the benchmarks all route through it.
    """
    mean = moments["sum"] / L
    return StepStats(
        utilization=moments["ucount"] / L,
        w2=moments["sumsq"] / L - mean * mean,
        wa=moments["sumabs"] / L,
        gvt=moments["min"] + offset,
        mean_tau=mean + offset,
        max_dev=moments["max"] - mean,
        min_dev=mean - moments["min"],
    )


# ---------------------------------------------------------------------------
# scan drivers
# ---------------------------------------------------------------------------


def init_state(cfg: PDESConfig, n_trials: int) -> SimState:
    """Fully synchronized initial condition (all local clocks equal; Sec. IV.B)."""
    z = jnp.zeros((n_trials,), dtype=cfg.dtype)
    return SimState(
        tau=jnp.zeros((n_trials, cfg.L), dtype=cfg.dtype),
        offset=z,
        offset_comp=z,
        step=jnp.zeros((), dtype=jnp.int32),
    )


def _kahan_add(total, comp, x):
    y = x - comp
    t = total + y
    comp = (t - total) - y
    return t, comp


def _one_step(state: SimState, key: jax.Array, cfg: PDESConfig):
    bits = event_bits(key, state.step, state.tau.shape)
    is_left, is_right, eta = decode_events(bits, cfg)
    tau, update, gvt = step_core(state.tau, is_left, is_right, eta, cfg)
    stats = measure(tau, update, state.offset)
    # rebase so the minimum returns to zero; dynamics are shift-invariant.
    shift = jnp.min(tau, axis=-1, keepdims=True)
    tau = tau - shift
    offset, comp = _kahan_add(state.offset, state.offset_comp, shift[..., 0])
    return SimState(tau, offset, comp, state.step + 1), stats


@partial(jax.jit, static_argnames=("cfg", "n_steps"))
def run(state: SimState, key: jax.Array, cfg: PDESConfig, n_steps: int):
    """Advance ``n_steps`` parallel steps, recording StepStats per step.

    Returns (final_state, StepStats with leading time axis (n_steps, B)).
    """

    def _body(st, _):
        return _one_step(st, key, cfg)

    return jax.lax.scan(_body, state, None, length=n_steps)


@partial(jax.jit, static_argnames=("cfg", "n_steps"))
def run_mean(state: SimState, key: jax.Array, cfg: PDESConfig, n_steps: int):
    """Advance ``n_steps`` steps, returning only time-averaged stats.

    Used for steady-state estimation after burn-in: O(1) memory in n_steps.
    """

    def _body(carry, _):
        st, acc = carry
        st, stats = _one_step(st, key, cfg)
        acc = jax.tree.map(lambda a, s: a + s, acc, stats)
        return (st, acc), None

    zeros = StepStats(*(jnp.zeros((state.tau.shape[0],), state.tau.dtype)
                        for _ in StepStats._fields))
    (state, acc), _ = jax.lax.scan(_body, (state, zeros), None, length=n_steps)
    mean_stats = jax.tree.map(lambda a: a / n_steps, acc)
    return state, mean_stats


@partial(jax.jit, static_argnames=("cfg", "n_steps"))
def burn_in(state: SimState, key: jax.Array, cfg: PDESConfig, n_steps: int):
    """Advance without recording (for reaching the steady state)."""

    def _body(st, _):
        st, _ = _one_step(st, key, cfg)
        return st, None

    state, _ = jax.lax.scan(_body, state, None, length=n_steps)
    return state
