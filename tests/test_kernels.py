"""Pallas kernel validation: shape/param sweeps vs the pure-jnp oracle."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import horizon
from repro.core.horizon import PDESConfig
from repro.kernels import ops, ref, tiling

KEY = jax.random.key(7)

SWEEP = [
    # (L, n_v, delta, rd_mode, B)
    (8, 1, math.inf, False, 3),
    (64, 1, math.inf, False, 12),
    (32, 10, 5.0, False, 8),
    (128, 3, 1.0, False, 4),
    (256, 1, 0.0, False, 2),
    (64, 100, 10.0, True, 8),
    (512, 7, 100.0, False, 1),
]


def _state_and_bits(cfg, B, steps=7):
    state = horizon.init_state(cfg, B)
    state = horizon.burn_in(state, KEY, cfg, steps)
    bits = horizon.event_bits(KEY, state.step, state.tau.shape)
    return state, bits


@pytest.mark.parametrize("L,n_v,delta,rd,B", SWEEP)
def test_pdes_step_matches_ref(L, n_v, delta, rd, B):
    cfg = PDESConfig(L=L, n_v=n_v, delta=delta, rd_mode=rd)
    state, bits = _state_and_bits(cfg, B)
    tau_h = ops.ring_halo(state.tau)
    gvt = jnp.min(state.tau, axis=-1, keepdims=True)
    t1, s1 = ops.pdes_step(tau_h, bits, gvt, n_v=n_v, delta=delta, rd_mode=rd,
                           interpret=True)
    t2, _, s2 = ref.pdes_step_ref(tau_h, bits, gvt, n_v=n_v, delta=delta,
                                  rd_mode=rd)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    for k in s1:
        np.testing.assert_allclose(np.asarray(s1[k]), np.asarray(s2[k]),
                                   rtol=1e-6)


@pytest.mark.parametrize("L,n_v,delta,rd,B", SWEEP)
def test_pdes_step_matches_core(L, n_v, delta, rd, B):
    """Kernel path == horizon.step_core (the system's own semantics)."""
    cfg = PDESConfig(L=L, n_v=n_v, delta=delta, rd_mode=rd)
    state, bits = _state_and_bits(cfg, B)
    t1, _ = ops.step_ring(state.tau, bits, cfg, interpret=True)
    is_l, is_r, eta = horizon.decode_events(bits, cfg)
    t2, _, _ = horizon.step_core(state.tau, is_l, is_r, eta, cfg)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


@pytest.mark.parametrize("L,n_v,delta,rd,B", SWEEP[:5])
@pytest.mark.parametrize("K", [1, 4, 6])
def test_pdes_multistep_matches_ref(L, n_v, delta, rd, B, K):
    cfg = PDESConfig(L=L, n_v=n_v, delta=delta, rd_mode=rd)
    state, _ = _state_and_bits(cfg, B)
    bits = jnp.stack([horizon.event_bits(KEY, state.step + i, state.tau.shape)
                      for i in range(K)])
    t1, s1 = ops.pdes_multistep(state.tau, bits, n_v=n_v, delta=delta,
                                rd_mode=rd, interpret=True)
    t2, s2 = ref.pdes_multistep_ref(state.tau, bits, n_v=n_v, delta=delta,
                                    rd_mode=rd)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    for k in s1:
        np.testing.assert_allclose(np.asarray(s1[k]), np.asarray(s2[k]),
                                   rtol=1e-6)


@pytest.mark.parametrize("L,n_v,delta,rd,B", SWEEP[:5])
def test_pdes_multistep_counter_matches_ref(L, n_v, delta, rd, B):
    """In-kernel event generation == host counter stream (bitwise)."""
    cfg = PDESConfig(L=L, n_v=n_v, delta=delta, rd_mode=rd)
    state, _ = _state_and_bits(cfg, B)
    ctr = jnp.array([[3, 5, 0, 0]], dtype=jnp.uint32)
    t1, s1 = ops.pdes_multistep_counter(state.tau, ctr, k_steps=6, n_v=n_v,
                                        delta=delta, rd_mode=rd,
                                        interpret=True)
    t2, s2 = ref.pdes_multistep_counter_ref(state.tau, ctr, k_steps=6,
                                            n_v=n_v, delta=delta, rd_mode=rd)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    for k in s1:
        np.testing.assert_allclose(np.asarray(s1[k]), np.asarray(s2[k]),
                                   rtol=1e-6)


# tiles must be multiples of 8 rows (or the whole batch), so the grids
# below split B = 64 rings into 1, 2 or 8 row blocks.
@pytest.mark.parametrize("n_blocks", [1, 2, 8])
def test_block_size_invariance(n_blocks):
    """Tiling must not change results."""
    cfg = PDESConfig(L=64, n_v=2, delta=4.0)
    state, bits = _state_and_bits(cfg, 64)
    ta, _ = ops.step_ring(state.tau, bits, cfg, block_b=64, interpret=True)
    tb, _ = ops.step_ring(state.tau, bits, cfg, block_b=64 // n_blocks,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))


@pytest.mark.parametrize("n_blocks", [1, 2, 8])
def test_counter_kernel_block_invariance(n_blocks):
    """The counter kernel derives trial indices from program_id * block_b —
    tiling must not shift the event stream."""
    cfg = PDESConfig(L=64, n_v=2, delta=4.0)
    state, _ = _state_and_bits(cfg, 64)
    ctr = jnp.array([[11, 0, 4, 0]], dtype=jnp.uint32)   # nonzero b0 too
    ta, _ = ops.pdes_multistep_counter(state.tau, ctr, k_steps=4, n_v=2,
                                       delta=4.0, block_b=64, interpret=True)
    tb, _ = ops.pdes_multistep_counter(state.tau, ctr, k_steps=4, n_v=2,
                                       delta=4.0, block_b=64 // n_blocks,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))


@pytest.mark.parametrize("n_steps,k_fuse", [(5, 8), (16, 8), (37, 8), (24, 6)])
def test_simulate_equals_run(n_steps, k_fuse):
    """Kernel-path driver reproduces horizon.run stats and state exactly."""
    cfg = PDESConfig(L=64, n_v=4, delta=8.0)
    st0 = horizon.init_state(cfg, 8)
    key = jax.random.key(3)
    st_a, stats_a = horizon.run(st0, key, cfg, n_steps)
    st_b, out_b = ops.simulate(st0, key, cfg, n_steps, k_fuse=k_fuse,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(stats_a.utilization),
                               np.asarray(out_b["u"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(stats_a.w2),
                               np.asarray(out_b["w2"]), rtol=1e-4, atol=1e-4)
    abs_a = np.asarray(st_a.tau) + np.asarray(st_a.offset)[:, None]
    abs_b = np.asarray(st_b.tau) + np.asarray(st_b.offset)[:, None]
    np.testing.assert_allclose(abs_a, abs_b, rtol=1e-5, atol=1e-4)


def test_vmem_budget_helper():
    L, B = 16384, 1024
    bb = tiling.pick_vmem_block(B, L)
    assert bb % 8 == 0 and B % bb == 0
    assert tiling.vmem_bytes(L, bb) <= tiling.SCOPED_VMEM_BYTES
    assert tiling.vmem_bytes(L, 2 * bb) > tiling.SCOPED_VMEM_BYTES


@pytest.mark.parametrize("B", [1, 3, 8, 12, 24, 40, 64, 100, 1280])
@pytest.mark.parametrize("hint", [1, 4, 8, 16, 40, 4096])
def test_tile_rule(B, hint):
    """Every chosen tile divides B and is a multiple of 8 or B itself."""
    bb = tiling.pick_divisor_block(B, hint)
    assert B % bb == 0 and (bb % 8 == 0 or bb == B)
    assert bb <= hint or bb == tiling.valid_tiles(B)[0]


def test_no_valid_tile_is_a_clear_error():
    with pytest.raises(ValueError, match=r"B=8 .*L=4194304.*budget"):
        tiling.pick_vmem_block(8, 1 << 22)
