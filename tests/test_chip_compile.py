"""Compile rehearsal for the chip: the analytic engine's device kernels
compile for one TPU v5e at the padded bucket shapes ``chip_smoke.py``
runs, with no chip attached.

The chip's compiler is installed here and compiles for a *described*
``v5e:2x2`` topology; nothing executes.  This catches what the CPU
backend accepts but the TPU compiler refuses (float64 lowering,
unsupported scatter or sort forms, programs that do not fit 16 GB)
before any chip time is spent.  ``_sim_batch`` in float64 compiles in
about three minutes for the described chip, too slow for tier-1; it,
the planner's jitted solve and the mixture fit are rehearsed by hand.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import maxmin
from repro.kernels.batched_maxmin import _solve_batch
from repro.kernels.stack_distance import _dist_batch, _fifo_batch

V5E_HBM_BYTES = 16 * 2**30

f64, f32, i64, i32 = jnp.float64, jnp.float32, jnp.int64, jnp.int32
b1 = jnp.bool_

# (program, x64, argument (shape, dtype) list) at the smoke's buckets:
# stack distances (B, N); FIFO (B, N, K); sweep pricing (B, F, L, width);
# the simulator's single waterfill (L, F, width).
CASES = {
    "dist_8x65536": (_dist_batch, True,
                     [((8, 65536), i64), ((8, 65536), f64)]),
    "dist_4x32768": (_dist_batch, True,
                     [((4, 32768), i64), ((4, 32768), f64)]),
    "fifo_16x65536x8192": (_fifo_batch, True,
                           [((16, 65536), i32), ((16, 65536), f64),
                            ((16, 65536), b1), ((16, 65536), b1),
                            ((16, 8192), f64), ((16,), f64)]),
    "solve_batch_16x8192x32x8": (_solve_batch, False,
                                 [((16, 32), f32), ((16, 8192, 8), i32),
                                  ((16, 8192), f32)]),
    "solve_2048x2048x4": (maxmin._solve, False,
                          [((2048,), f32), ((2048, 4), i32),
                           ((2048,), f32)]),
}


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs out of the temp directory
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, x64, args = CASES[case]
    with jax.enable_x64(x64):
        specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                 for shape, dtype in args]
        compiled = fn.lower(*specs).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, (case, mem)
