"""Compile-only checks of the main-path kernels for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, which ships with jax,
compiles for a ``v5e:2x2`` topology that is described but not attached.
That catches what interpret mode cannot — block shapes the chip's tiling
refuses, VMEM overruns, programs that cannot be partitioned — at no chip
time.  The topology is described inside a fixture, never at import, so
that under pytest-xdist only the worker given this file loads the TPU
library; keep every such compile in this one file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.backends.jax_backend import _group_contract
from repro.backends.pallas_backend import _batched_pallas_contract
from repro.core.distributed import distributed_gemm

# (G, steps, tile) step groups: four tasks of an 8192^3 GEMM at the
# paper's 1024 tile (what the chip smoke run dispatches), a lone task,
# single-step items at 512, and a tile that is no multiple of 128
GROUPS = [(4, 8, 1024), (1, 8, 1024), (8, 1, 512), (2, 3, 1000)]
DTYPES = ("float32", "bfloat16")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _group_operands(sharding, group, dtype):
    g, steps, tile = group
    shape = (g, steps, tile, tile)
    return (jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding),
            jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("group", GROUPS, ids=str)
def test_pallas_group_contract_compiles_to_mosaic(one_chip, group, dtype):
    g, steps, tile = group
    fn = _batched_pallas_contract(steps, tile, tile, tile, dtype, False)
    compiled = fn.lower(*_group_operands(one_chip, group, dtype)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("group", GROUPS, ids=str)
def test_jax_group_contract_compiles(one_chip, group, dtype):
    compiled = _group_contract().lower(
        *_group_operands(one_chip, group, dtype)).compile()
    assert compiled.output_shardings.device_set == one_chip.device_set
    # an f32 GEMM must not be one bf16 pass on the MXU
    assert "operand_precision={highest,highest}" in compiled.as_text()


def test_distributed_gemm_ring_compiles_for_2x2(topo):
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    n = 16384
    a = jax.ShapeDtypeStruct((n, n), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data", "model")))
    b = jax.ShapeDtypeStruct((n, n), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("model", None)))
    compiled = jax.jit(lambda x, y: distributed_gemm(
        x, y, mesh, mode="ring")).lower(a, b).compile()
    assert "collective-permute" in compiled.as_text()
    assert len(compiled.output_shardings.device_set) == 4
