"""Unified multi-backend PDES engine: one API, four execution backends.

Every way this codebase can advance the Δ-window constrained PDES — the
pure-XLA reference scan, the fused Pallas kernels, and the shard_map
runtime — used to carry its own copy of the init/rebase/Kahan/stats logic.
``PDESEngine`` owns that logic once and dispatches the inner sweep to a
backend; all backends consume the *same counter-based event stream*
(``events.counter_words`` keyed on ``(seed, step, trial, pe)``), so
trajectories are **bit-identical across backends** and cross-backend parity
is a test (tests/test_engine.py), not a hope.

Backend matrix::

    backend            device   window modes    event stream source
    -----------------  -------  --------------  --------------------------
    reference          single   exact, stale    host counter_bits
    pallas             single   exact, stale    host counter_bits -> HBM
    pallas_multistep   single   exact only      generated in-kernel (VMEM)
    sharded            mesh     exact, stale    per-shard counter_bits

* ``window="exact"`` recomputes the global virtual time ``GVT = min_k tau_k``
  every step (the paper's Eq. (3) verbatim).
* ``window="stale"`` refreshes the window base only once per ``k_fuse``-step
  chunk.  GVT is non-decreasing, so a stale base gives a *stricter* window:
  the scheme stays conservative (DESIGN.md B3) — this is the
  communication-avoiding mode whose utilization cost the scaling studies
  sweep (cf. the desynchronization protocol study, cs/0409032).
* ``pallas_multistep`` keeps whole rings VMEM-resident for ``k_fuse`` steps
  (one ``lax.scan`` over K-step chunks drives arbitrarily long runs while
  amortizing the tau HBM round trips K-fold) and generates its event bits
  in-kernel, so no bits array ever touches HBM.  The exact GVT is a cheap
  lane-wise min in VMEM, hence exact-window only.
* ``sharded`` maps ``window="exact"``/``"stale"`` onto the ``exact``/
  ``commavoid`` modes of ``core.distributed`` (per-step vs per-chunk halo
  exchange + GVT all-reduce).  ``wa`` is returned as NaN on this backend:
  the absolute width needs the global ring mean *before* the deviation
  reduction — a second all-reduce per step that the one-collective-per-chunk
  layout deliberately avoids.  All other StepStats fields are computed from
  shard-local partial reductions; run-level parity with ``reference`` is
  covered by tests/test_distributed_pdes.py and tests/test_sharded_sweep.py.

State is the same ``SimState`` as ``horizon``: rebased ``tau`` (min == 0
after every chunk), Kahan-compensated offset, step counter.  All backends
rebase once per chunk on the identical schedule, which is what makes the
trajectories comparable bit-for-bit.

**Window sweeps** (``init_sweep`` + the ``deltas=`` kwarg): the Δ grid of a
window sweep is laid out on the ensemble axis — ``B = n_windows * replicas``
rows with a per-row Δ column fed to the backends as a *batched operand*
(array window rule in the reference scan, window base folding in the
one-step kernel, a ``(B, 1)`` VMEM column in the multistep kernel, and an
ensemble-sharded ``(B,)`` column on the ``sharded`` backend — each shard
sees exactly its own rows' window widths, no extra communication).  One
device pass advances every (Δ, replica) trajectory; ``repro.experiments``
builds the paper's full (L, N_V, Δ) studies on top of this entry point,
and ``experiments.sweep.plan_mesh_sweep`` packs ragged Δ grids onto the
mesh ensemble axes.

Example::

    from repro.core import PDESConfig
    from repro.core.engine import PDESEngine

    eng = PDESEngine(PDESConfig(L=1024, n_v=10, delta=10.0),
                     backend="pallas_multistep", k_fuse=16)
    state = eng.init(n_trials=64)
    state = eng.burn_in(state, seed=0, n_steps=512)
    state, stats = eng.run(state, seed=0, n_steps=256)   # StepStats (256, B)
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import horizon
from .events import counter_bits_block
from .horizon import PDESConfig, SimState, StepStats

BACKENDS = ("reference", "pallas", "pallas_multistep", "sharded")
WINDOWS = ("exact", "stale")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine parameters (hashable: used as a jit static argument).

    Attributes:
      backend: one of ``BACKENDS``.
      window: "exact" (per-step GVT) or "stale" (per-chunk GVT base).
      k_fuse: steps per chunk — the multistep fuse depth, the stale-window
        refresh period, and the rebase cadence.
      block_b: ensemble rows per kernel tile (None = auto from VMEM budget).
    """

    backend: str = "reference"
    window: str = "exact"
    k_fuse: int = 16
    block_b: int | None = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, "
                             f"got {self.backend!r}")
        if self.window not in WINDOWS:
            raise ValueError(f"window must be one of {WINDOWS}, "
                             f"got {self.window!r}")
        if self.k_fuse < 1:
            raise ValueError("k_fuse must be >= 1")
        if self.backend == "pallas_multistep" and self.window == "stale":
            raise ValueError(
                "pallas_multistep computes the exact GVT in-VMEM each step; "
                "use backend='pallas' or 'reference' for window='stale'")


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: on every platform but TPU.

    Host-side only.  Called under ``jit`` the answer would be baked into a
    trace that is not keyed on it; the engine resolves it once per engine
    and passes it to ``_run_single`` as a static argument.
    """
    return jax.default_backend() != "tpu"


def _auto_block_b(B: int, L: int, block_b: int | None,
                  in_kernel_bits: bool = False) -> int:
    """Kernel tile rows: shared VMEM model and tile rule (kernels.tiling)."""
    from ..kernels.tiling import pick_divisor_block, pick_vmem_block
    if block_b is None:
        return pick_vmem_block(B, L, in_kernel_bits=in_kernel_bits)
    return pick_divisor_block(B, block_b)


def _make_advance(cfg: PDESConfig, ecfg: EngineConfig, B: int, L: int,
                  interpret: bool):
    """Backend-specific K-step chunk advance.

    Returns ``advance(tau, step0, seed, k, delta_col, b0)`` ->
    ``(tau_k, moments (k, B))`` with ``k`` static.  ``delta_col`` is either
    None (static ``cfg.delta`` window) or a traced ``(B, 1)`` column of
    per-row window widths — the batched window-sweep operand; ``b0`` is the
    counter-stream trial coordinate: a scalar global trial index of row 0
    (rows consume ``b0 + r``) or a ``(B,)`` vector of per-row indices — the
    coalesced-batch operand of ``repro.service``, where rows packed from
    different requests address arbitrary (possibly duplicate) stream
    coordinates.  No rebasing inside — the shared driver owns that.
    """
    stale = ecfg.window == "stale"

    if ecfg.backend == "reference":

        def advance(tau, step0, seed, k, delta_col, b0):
            gvt0 = jnp.min(tau, axis=-1, keepdims=True)

            def one(tau, s):
                bits = counter_bits_block(
                    seed, s, b0, jnp.int32(0), B, L)
                is_l, is_r, eta = horizon.decode_events(bits, cfg)
                tau, update, _ = horizon.step_core(
                    tau, is_l, is_r, eta, cfg,
                    gvt_for_window=gvt0 if stale else None,
                    delta_override=delta_col)
                return tau, horizon.ring_moments(tau, update)

            return lax.scan(one, tau, step0 + jnp.arange(k, dtype=jnp.int32))

    elif ecfg.backend == "pallas":
        from ..kernels.ops import ring_halo
        from ..kernels.pdes_step import pdes_step
        bb = _auto_block_b(B, L, ecfg.block_b)

        def advance(tau, step0, seed, k, delta_col, b0):
            gvt0 = jnp.min(tau, axis=-1, keepdims=True)

            def one(tau, s):
                bits = counter_bits_block(
                    seed, s, b0, jnp.int32(0), B, L)
                gvt = gvt0 if stale else jnp.min(tau, axis=-1, keepdims=True)
                # per-row Δ folds into the window base: the kernel's rule is
                # ``tau <= delta + gvt``, so passing ``gvt + delta_col`` with
                # a static delta of 0 applies each row's own window — same
                # fp32 add, bit-identical to the static-delta path.
                if delta_col is None:
                    gvt_eff, d = gvt, cfg.delta
                else:
                    gvt_eff, d = gvt + delta_col, 0.0
                return pdes_step(
                    ring_halo(tau), bits, gvt_eff,
                    n_v=cfg.n_v, delta=d, rd_mode=cfg.rd_mode,
                    border_both=cfg.border_both, block_b=bb,
                    interpret=interpret)

            return lax.scan(one, tau, step0 + jnp.arange(k, dtype=jnp.int32))

    elif ecfg.backend == "pallas_multistep":
        from ..kernels.pdes_multistep import pdes_multistep_counter
        bb = _auto_block_b(B, L, ecfg.block_b, in_kernel_bits=True)

        def advance(tau, step0, seed, k, delta_col, b0):
            # a (B,) b0 becomes the per-row trial column; ctr's scalar slot
            # is then unused (zeroed) — the kernel reads the column instead.
            vec = getattr(b0, "ndim", 0) == 1
            b0_scalar = jnp.uint32(0) if vec else b0.astype(jnp.uint32)
            trial_col = b0.astype(jnp.uint32)[:, None] if vec else None
            ctr = jnp.stack([
                seed.astype(jnp.uint32), step0.astype(jnp.uint32),
                b0_scalar, jnp.uint32(0)])[None, :]
            return pdes_multistep_counter(
                tau, ctr, delta_col, trial_col, k_steps=k,
                n_v=cfg.n_v, delta=cfg.delta, rd_mode=cfg.rd_mode,
                border_both=cfg.border_both, block_b=bb,
                interpret=interpret)

    else:  # pragma: no cover - sharded handled outside the single-device jit
        raise ValueError(ecfg.backend)

    return advance


@functools.partial(jax.jit, static_argnames=("cfg", "ecfg", "n_steps", "mode",
                                             "interpret"))
def _run_single(state: SimState, seed, cfg: PDESConfig, ecfg: EngineConfig,
                n_steps: int, mode: str, deltas=None, trial_base=0, *,
                interpret: bool):
    """Shared chunked driver for the single-device backends.

    mode: "record" -> StepStats with leading (n_steps,) axis;
          "mean"   -> time-averaged StepStats (O(1) memory in n_steps);
          "burn"   -> state only (stats math dead-code-eliminated).
    deltas: optional (B,) per-row window widths (sweep mode, see ``run``).
    trial_base: counter-stream trial coordinate — scalar index of row 0,
      or a (B,) vector of per-row global trial indices (see ``run``).
    interpret: run the Pallas kernels in the interpreter (``interpret_mode``).
    """
    B, L = state.tau.shape
    K = max(1, min(ecfg.k_fuse, n_steps))
    n_chunks, rem = divmod(n_steps, K)
    advance = _make_advance(cfg, ecfg, B, L, interpret)
    dtype = state.tau.dtype
    delta_col = None if deltas is None else deltas.astype(dtype)[:, None]
    b0 = jnp.asarray(trial_base, jnp.int32)

    def chunk(carry, k):
        tau, off, comp, step0 = carry
        tau, moments = advance(tau, step0, seed, k, delta_col, b0)
        stats = horizon.stats_from_moments(moments, off[None, :], L)
        # rebase once per chunk: identical schedule on every backend, so
        # trajectories stay bitwise comparable (fp32 hygiene per SimState).
        shift = jnp.min(tau, axis=-1)
        tau = tau - shift[:, None]
        off, comp = horizon._kahan_add(off, comp, shift)
        return (tau, off, comp, step0 + k), stats

    carry = (state.tau, state.offset, state.offset_comp, state.step)
    zeros = StepStats(*(jnp.zeros((B,), dtype) for _ in StepStats._fields))
    pieces, acc = [], zeros
    if n_chunks:
        if mode == "record":
            carry, st = lax.scan(lambda c, _: chunk(c, K), carry, None,
                                 length=n_chunks)
            pieces.append(jax.tree.map(
                lambda a: a.reshape(n_chunks * K, B), st))
        else:
            def body(c_acc, _):
                c, a = c_acc
                c, st = chunk(c, K)
                a = jax.tree.map(lambda x, s: x + jnp.sum(s, axis=0), a, st)
                return (c, a), None

            (carry, acc), _ = lax.scan(body, (carry, acc), None,
                                       length=n_chunks)
    if rem:
        carry, st = chunk(carry, rem)
        if mode == "record":
            pieces.append(st)
        else:
            acc = jax.tree.map(lambda x, s: x + jnp.sum(s, axis=0), acc, st)

    tau, off, comp, step = carry
    out_state = SimState(tau, off, comp, step)
    if mode == "burn":
        return out_state, None
    if mode == "record":
        stats = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *pieces)
    else:
        stats = jax.tree.map(lambda a: a / n_steps, acc)
    return out_state, stats


class PDESEngine:
    """One entry point for every PDES execution path (see module docstring).

    Args:
      cfg: the physics (``PDESConfig``).
      backend: one of ``BACKENDS``.
      window: "exact" | "stale" (see module docstring).
      k_fuse: chunk depth (fuse/refresh/rebase cadence).
      block_b: kernel tile rows (None = auto).
      mesh / dist: required/optional for ``backend="sharded"`` — the device
        mesh and ``DistConfig``.  When ``dist`` is omitted it is derived
        from ``window`` (exact -> "exact", stale -> "commavoid" with
        ``k_chunk=k_fuse``).
    """

    def __init__(self, cfg: PDESConfig, backend: str = "reference", *,
                 window: str = "exact", k_fuse: int = 16,
                 block_b: int | None = None, mesh=None, dist=None):
        self.cfg = cfg
        self.ecfg = EngineConfig(backend=backend, window=window,
                                 k_fuse=k_fuse, block_b=block_b)
        #: compiled kernels on a TPU, the Pallas interpreter elsewhere
        self.interpret = interpret_mode()
        self.mesh = mesh
        self.dist = dist
        if backend == "sharded":
            if mesh is None:
                raise ValueError("backend='sharded' requires a mesh")
            if dist is None:
                from .distributed import DistConfig
                self.dist = DistConfig(
                    mode="exact" if window == "exact" else "commavoid",
                    k_chunk=k_fuse)
            elif (self.dist.mode == "exact") != (window == "exact"):
                raise ValueError(
                    f"window={window!r} conflicts with dist.mode="
                    f"{self.dist.mode!r}")

    # -- state ------------------------------------------------------------

    def init(self, n_trials: int) -> SimState:
        """Fully synchronized initial condition (all clocks equal)."""
        return horizon.init_state(self.cfg, n_trials)

    def init_sweep(self, deltas, replicas: int):
        """Per-Δ window state for a batched window sweep.

        Lays the Δ grid out on the ensemble axis: ``B = n_windows * replicas``
        rows, window ``w`` owning rows ``[w*replicas, (w+1)*replicas)`` —
        exactly the flattened form of vmapping the window state over the Δ
        axis on top of the replica batch.  Rows with ``inf`` run
        unconstrained.  Pass the returned ``deltas`` row array to ``run`` /
        ``run_mean`` / ``burn_in``; one device pass then advances all
        ``n_windows x replicas`` trajectories.

        Returns:
          (state, deltas_rows) with ``deltas_rows`` of shape ``(B,)``.
        """
        d = jnp.repeat(jnp.asarray(deltas, self.cfg.dtype), replicas)
        return self.init(int(d.shape[0])), d

    # -- drivers ----------------------------------------------------------

    def run(self, state: SimState, seed, n_steps: int, *,
            deltas=None, trial_base=0):
        """Advance ``n_steps``, recording StepStats per step (n_steps, B).

        Args:
          deltas: optional (B,) per-row window widths — the sweep mode
            (see ``init_sweep``); overrides ``cfg.delta`` row-wise.
          trial_base: global trial index of row 0 in the counter event
            stream.  A serial per-Δ loop that runs window ``w`` with
            ``trial_base=w*replicas`` consumes exactly the stream slice the
            batched sweep assigns to those rows, so the two are comparable
            bit-for-bit (tests/test_experiments.py).  A ``(B,)`` int vector
            instead assigns every row its *own* global trial index — the
            coalesced-batch mode of ``repro.service``, which packs rows
            from many requests (arbitrary, possibly duplicate, stream
            coordinates) into one pass; ``trial_base=c + arange(B)`` is
            bit-identical to the scalar ``trial_base=c``.
        """
        return self._dispatch(state, seed, n_steps, "record",
                              deltas=deltas, trial_base=trial_base)

    def run_mean(self, state: SimState, seed, n_steps: int, *,
                 deltas=None, trial_base=0):
        """Advance ``n_steps``; return only time-averaged StepStats (B,)."""
        return self._dispatch(state, seed, n_steps, "mean",
                              deltas=deltas, trial_base=trial_base)

    def burn_in(self, state: SimState, seed, n_steps: int, *,
                deltas=None, trial_base=0) -> SimState:
        """Advance without recording (reach the steady state)."""
        return self._dispatch(state, seed, n_steps, "burn",
                              deltas=deltas, trial_base=trial_base)[0]

    def _dispatch(self, state, seed, n_steps, mode, deltas=None, trial_base=0):
        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        seed = jnp.uint32(seed)
        if deltas is not None:
            deltas = jnp.asarray(deltas, state.tau.dtype)
            if deltas.shape != (state.tau.shape[0],):
                raise ValueError(
                    f"deltas must have shape ({state.tau.shape[0]},) — one "
                    f"window width per ensemble row — got {deltas.shape}")
        trial_base = jnp.asarray(trial_base, jnp.int32)
        if trial_base.ndim not in (0, 1) or (
                trial_base.ndim == 1
                and trial_base.shape != (state.tau.shape[0],)):
            raise ValueError(
                f"trial_base must be a scalar or have shape "
                f"({state.tau.shape[0]},) — one stream index per ensemble "
                f"row — got {trial_base.shape}")
        if self.ecfg.backend == "sharded":
            return self._run_sharded(state, seed, n_steps, mode,
                                     deltas=deltas, trial_base=trial_base)
        return _run_single(state, seed, self.cfg, self.ecfg, n_steps, mode,
                           deltas, trial_base, interpret=self.interpret)

    def _run_sharded(self, state, seed, n_steps, mode, deltas=None,
                     trial_base=0):
        from . import distributed as D
        K = self.dist.k_chunk
        if n_steps % K:
            raise ValueError(
                f"sharded backend advances whole chunks: n_steps={n_steps} "
                f"must be a multiple of k_chunk={K}")
        tau, off, comp, st = D.run_sharded_state(
            self.cfg, self.mesh, n_steps=n_steps, seed=seed,
            dist=self.dist, tau0=state.tau, off0=state.offset,
            comp0=state.offset_comp, step_base=state.step,
            deltas=deltas, trial_base=trial_base)
        out_state = SimState(tau, off, comp, state.step + n_steps)
        if mode == "burn":
            return out_state, None
        # ``gvt``/``mean_tau`` come back absolute (the runtime adds the
        # carried offset chunk-by-chunk, same schedule as _run_single).
        nan = jnp.full(st["u"].shape, jnp.nan, state.tau.dtype)
        stats = StepStats(
            utilization=st["u"], w2=st["w2"], wa=nan, gvt=st["gvt"],
            mean_tau=st["mean_tau"], max_dev=st["max_dev"],
            min_dev=st["min_dev"])
        if mode == "mean":
            stats = jax.tree.map(lambda a: jnp.mean(a, axis=0), stats)
        return out_state, stats
