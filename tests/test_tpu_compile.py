"""The main path's kernels and step compile for a TPU v5e chip.

Compiles for a described ``v5e:2x2`` topology (no chip attached): the TPU
compiler refuses what interpret mode accepts — tile-misaligned blocks, vector
gathers, int32 matmuls, scalar-prefetch operands beyond SMEM — and programs
that do not fit the chip's memory.  Nothing runs here, so these tests say
nothing about results or times.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fixed_point import Q1_25
from repro.core.ppr import make_ppr_fixed_step
from repro.core.spmv import SortedDst
from repro.kernels.coo_spmv import MAX_LAUNCH_STEPS, ROW_BLOCK, coo_spmv_pallas
from repro.kernels.fused_ppr import fused_ppr_iteration

V_TILE, PACKET, KAPPA = 512, 256, 16
N_BLK = 64                            # dst blocks written by the one launch
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent cache off: entries written
    for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fused(spec, fmt):
    steps = MAX_LAUNCH_STEPS
    rows = -(-steps // ROW_BLOCK) * ROW_BLOCK
    v = N_BLK * V_TILE - 7                            # ragged last block
    pdt = jnp.float32 if fmt is None else jnp.uint32
    args = ([spec((steps,), jnp.int32)] * 5
            + [spec((rows, PACKET), jnp.int32)] * 2
            + [spec((rows, PACKET), pdt), spec((v,), jnp.bool_),
               spec((v, KAPPA), pdt), spec((v, KAPPA), pdt)])
    return fused_ppr_iteration.lower(
        *args, v_tile=V_TILE, packet=PACKET, n_blk=N_BLK,
        chunks=((0, steps, 0, N_BLK),), num_vertices=v, alpha=0.85, fmt=fmt,
        interpret=False)


def _coo(spec, fmt):
    steps = MAX_LAUNCH_STEPS
    rows = -(-steps // ROW_BLOCK) * ROW_BLOCK
    pdt = jnp.float32 if fmt is None else jnp.uint32
    args = ([spec((rows, PACKET), jnp.uint16)] * 2
            + [spec((rows, PACKET), pdt), spec((N_BLK * V_TILE, KAPPA), pdt)]
            + [spec((steps,), jnp.int32)] * 3)
    return coo_spmv_pallas.lower(
        *args, v_tile=V_TILE, packet=PACKET, chunks=((0, steps, 0, N_BLK),),
        frac_bits=None if fmt is None else fmt.frac_bits, interpret=False)


def _composed_paper_1m(spec, fmt):
    """``make_ppr_fixed_step`` at the paper's envelope: 2^20 vertices,
    2^24 edges, κ = 16."""
    v, e = 1 << 20, 16 << 20
    step = make_ppr_fixed_step(fmt, v, 0.85)
    return step.lower(spec((e,), jnp.int32), spec((e,), jnp.int32),
                      spec((e,), jnp.uint32), spec((v,), jnp.bool_),
                      spec((v, KAPPA), jnp.uint32), spec((v, KAPPA), jnp.uint32))


def _composed_row_prefix_1m(spec, fmt):
    """The same step on a dst-sorted stream: ``SortedDst`` makes its
    reduction a prefix sum read at the row pointers."""
    v, e = 1 << 20, 16 << 20
    step = make_ppr_fixed_step(fmt, v, 0.85)
    rows = SortedDst(spec((e,), jnp.int32), spec((v + 1,), jnp.int32))
    return step.lower(rows, spec((e,), jnp.int32),
                      spec((e,), jnp.uint32), spec((v,), jnp.bool_),
                      spec((v, KAPPA), jnp.uint32), spec((v, KAPPA), jnp.uint32))


@pytest.mark.parametrize("build,fmt,kernel", [
    (_fused, None, True),
    (_fused, Q1_25, True),
    (_coo, None, True),
    (_coo, Q1_25, True),
    (_composed_paper_1m, Q1_25, False),
    (_composed_row_prefix_1m, Q1_25, False),
], ids=["fused-f32", "fused-q1.25", "coo-f32", "coo-q1.25",
        "composed-q1.25-2^20", "composed-row-prefix-q1.25-2^20"])
def test_compiles_for_v5e(one_chip, build, fmt, kernel):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = build(spec, fmt).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES
