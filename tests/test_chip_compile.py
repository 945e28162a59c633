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

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import maxmin
from repro.kernels.batched_maxmin import _solve_batch
from repro.kernels.stack_distance import (_FLOOR_K, _FLOOR_N, _dist_batch,
                                          _fifo_batch, _sim_batch)

V5E_HBM_BYTES = 16 * 2**30

f64, f32, i64, i32 = jnp.float64, jnp.float32, jnp.int64, jnp.int32
b1 = jnp.bool_


def _fifo_args(B, N, K):
    """``_fifo_batch``'s (shape, dtype) list at bucket (B, N, K)."""
    return [((B, N), i32), ((B, N), f64), ((B, N), b1), ((B, N), b1),
            ((B, K), f64), ((B,), f64)]

# (program, x64, argument (shape, dtype) list) at the smoke's buckets:
# stack distances (B, N); FIFO (B, N, K); sweep pricing (B, F, L, width);
# the simulator's single waterfill (L, F, width).
CASES = {
    "dist_8x65536": (_dist_batch, True,
                     [((8, 65536), i64), ((8, 65536), f64)]),
    "dist_4x32768": (_dist_batch, True,
                     [((4, 32768), i64), ((4, 32768), f64)]),
    "fifo_16x65536x8192": (_fifo_batch, True, _fifo_args(16, 65536, 8192)),
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


def test_fifo_step_has_no_nested_loop_and_no_full_copy(one_chip,
                                                       no_persistent_cache):
    """At the day cell's bucket the FIFO scan is one ``while``: the
    frontier search is a count, not a nested binary-search loop, and no
    step relayouts the carried curve (a copy of B*N/16 elements or more
    inside the loop would be one)."""
    B, N, K = 64, 65536, 8192
    with jax.enable_x64():
        specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                 for shape, dtype in _fifo_args(B, N, K)]
        hlo = _fifo_batch.lower(*specs).compile().as_text()
    assert len(re.findall(r"\swhile\(", hlo)) == 1
    big = []
    # one computation per block of text that starts at column 0
    for comp in re.split(r"\n(?=\S)", hlo):
        if comp.startswith("ENTRY"):
            continue
        for dims in re.findall(r"= \w+\[([\d,]*)\]\S* copy\(", comp):
            if math.prod(int(d) for d in dims.split(",") if d) >= B * N // 16:
                big.append(dims)
    assert not big, big


# The benchmark reads each kernel's device seconds by its XLA module's
# name (``chipbench/metrics/dev_s.*.py``), which JAX takes from the
# function's name: (program, x64, arguments at the smallest bucket,
# module name prefix).
N, K = _FLOOR_N, _FLOOR_K
MODULE_NAMES = {
    "distances": (_dist_batch, True, [((1, N), i64), ((1, N), f64)],
                  "jit__distances"),
    "fifo": (_fifo_batch, True,
             [((1, N), i32), ((1, N), f64), ((1, N), b1), ((1, N), b1),
              ((1, K), f64), ((1,), f64)], "jit__fifo_replay"),
    "cache_sim": (_sim_batch, True,
                  [((1, N), i32), ((1, N), b1), ((1, N), b1), ((1, K), f64),
                   ((1,), f64), ((1,), b1)], "jit__simulate"),
    "waterfill": (_solve_batch, False,
                  [((1, 16), f32), ((1, 8, 4), i32), ((1, 8), f32)],
                  "jit_solve_waterfill"),
}


@pytest.mark.parametrize("kernel", sorted(MODULE_NAMES))
def test_kernel_module_names_are_the_ones_the_benchmark_reads(kernel):
    fn, x64, args, prefix = MODULE_NAMES[kernel]
    with jax.enable_x64(x64):
        lowered = fn.lower(*[jax.ShapeDtypeStruct(shape, dtype)
                             for shape, dtype in args])
    name = lowered.compiler_ir("stablehlo").operation.attributes["sym_name"]
    assert str(name).strip('"').startswith(prefix), name
