"""Compile the main path's kernels for a TPU v5e that is described, not attached.

The chip's compiler (Mosaic) refuses what the Pallas interpreter accepts:
blocks off the (8, 128) tiling, casts and selects it cannot lower, and tiles
beyond the scoped VMEM.  These tests compile the kernels at the shapes the
served path runs, against a ``v5e:2x2`` topology description, so a refusal
shows here instead of on the chip.  Nothing runs; results are checked by the
interpret-mode tests.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every test file.
Keep every such compile in this one file.
"""
import math
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.engine import EngineConfig, _run_single  # noqa: E402
from repro.core.horizon import PDESConfig, SimState  # noqa: E402
from repro.kernels import tiling  # noqa: E402
from repro.kernels.pdes_multistep import pdes_multistep_counter  # noqa: E402
from repro.kernels.pdes_step import pdes_step  # noqa: E402

#: The paper-scale Δ-study the chip smoke serves (ROADMAP R1): 5 windows x
#: 256 replicas of rings with L = 10^4 PEs.
SMOKE_B, SMOKE_L, K = 1280, 10_000, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> a ShapeDtypeStruct on one described chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_multistep_counter_compiles_at_paper_scale(shape):
    bb = tiling.pick_vmem_block(SMOKE_B, SMOKE_L, in_kernel_bits=True)

    def fn(tau, ctr, delta_col, trial_col):
        return pdes_multistep_counter(
            tau, ctr, delta_col, trial_col, k_steps=K, n_v=10, delta=0.0,
            block_b=bb, interpret=False)

    text = _compiled_text(
        fn, shape((SMOKE_B, SMOKE_L)), shape((1, 4), jnp.uint32),
        shape((SMOKE_B, 1)), shape((SMOKE_B, 1), jnp.uint32))
    assert "tpu_custom_call" in text


def test_pdes_step_compiles_at_auto_tile(shape):
    B, L = 64, 1024
    bb = tiling.pick_vmem_block(B, L)

    def fn(tau_h, bits, gvt):
        return pdes_step(tau_h, bits, gvt, n_v=10, delta=10.0, block_b=bb,
                         interpret=False)

    text = _compiled_text(fn, shape((B, L + 2)), shape((B, L, 2), jnp.uint32),
                          shape((B, 1)))
    assert "tpu_custom_call" in text


def test_engine_pass_compiles_at_smoke_shape(shape):
    """One whole recorded ``pallas_multistep`` sweep pass, as served."""
    cfg = PDESConfig(L=SMOKE_L, n_v=10, delta=math.inf)
    ecfg = EngineConfig(backend="pallas_multistep", window="exact", k_fuse=K)
    B = SMOKE_B
    state = SimState(shape((B, SMOKE_L)), shape((B,)), shape((B,)),
                     shape((), jnp.int32))

    def fn(state, seed, deltas, trials):
        return _run_single(state, seed, cfg, ecfg, 4 * K, "record", deltas,
                           trials, interpret=False)

    text = _compiled_text(fn, state, shape((), jnp.uint32), shape((B,)),
                          shape((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_long_ring_tile_is_aligned_and_compiles(shape):
    """At L = 2^16 the old halving rule chose 6 rows of 96; the chip needs 8."""
    B, L = 96, 1 << 16
    bb = tiling.pick_vmem_block(B, L, in_kernel_bits=True)
    assert bb % 8 == 0 and B % bb == 0

    def fn(tau, ctr):
        return pdes_multistep_counter(tau, ctr, k_steps=K, n_v=10, delta=10.0,
                                      block_b=bb, interpret=False)

    text = _compiled_text(fn, shape((B, L)), shape((1, 4), jnp.uint32))
    assert "tpu_custom_call" in text
