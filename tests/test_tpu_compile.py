"""Compile every Pallas kernel for a described TPU v5e, without a chip.

The TPU compiler is installed with jax, and it compiles for a topology that
is described rather than attached.  This catches what interpret mode
cannot: layouts Mosaic refuses, tiles not aligned to the hardware, VMEM
overuse.  Nothing runs, so these tests say nothing about results or times.
The topology is described inside a fixture, never at import, so that only
the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import copy_stream, flash_attention, matmul, rmsnorm
from repro.kernels import sort_bitonic

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this jax build
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compilation_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,k,n,dtype", [
    (256, 256, 256, F32),           # the zoo's prefill projection
    (2048, 2048, 2048, BF16),
    (256, 2048, 8192, BF16),        # llama3.2-1b MLP up-projection slab
    (4096, 4096, 4096, BF16),       # paper-dag coarse payload
    (1024, 1024, 1024, F32),        # HIGHEST's bf16 split in the budget
])
def test_matmul_compiles(one_chip, m, k, n, dtype):
    _compile(matmul.matmul, [((m, k), dtype), ((k, n), dtype)], one_chip)


@pytest.mark.parametrize("shape,dtype", [((1024, 256), F32),
                                         ((4096, 2048), BF16)])
def test_copy_compiles(one_chip, shape, dtype):
    _compile(copy_stream.copy, [(shape, dtype)], one_chip)


@pytest.mark.parametrize("shape", [(1024, 256), (4096, 2048)])
def test_triad_compiles(one_chip, shape):
    _compile(copy_stream.triad, [((), F32), (shape, F32), (shape, F32)],
             one_chip)


@pytest.mark.parametrize("rows,d,dtype", [(256, 256, F32),
                                          (512, 2048, BF16)])
def test_rmsnorm_compiles(one_chip, rows, d, dtype):
    _compile(rmsnorm.rmsnorm, [((rows, d), dtype), ((d,), dtype)], one_chip)


@pytest.mark.parametrize("rows,n", [(32, 1024), (64, 1024), (64, 2048)])
def test_sort_rows_compiles(one_chip, rows, n):
    _compile(sort_bitonic.sort_rows, [((rows, n), F32)], one_chip)


@pytest.mark.parametrize("hq,hkv,s,d,dtype", [
    (4, 4, 256, 64, F32),           # the zoo's prefill slab
    (32, 8, 2048, 64, BF16),        # llama3.2-1b GQA at a 2k prompt
    (20, 4, 4096, 128, BF16),       # falcon-h1-34b, the shortest prompt
    (20, 4, 32768, 128, BF16),      # falcon-h1-34b, the longest prompt
], ids=["4-4-256-float32", "32-8-2048-bfloat16",   # as before head_dim
        "20-4-4096-128-bfloat16", "20-4-32768-128-bfloat16"])
def test_flash_attention_compiles(one_chip, hq, hkv, s, d, dtype):
    _compile(flash_attention.flash_attention,
             [((1, hq, s, d), dtype), ((1, hkv, s, d), dtype),
              ((1, hkv, s, d), dtype)], one_chip)
