"""Pallas TPU streaming kernels — the paper's *memory-bound* class.

``copy``   : out[i] = x[i]                (pure stream, paper's copy TAO)
``triad``  : out[i] = a * x[i] + y[i]     (STREAM-triad; 2 reads + 1 write)

Blocks stream HBM->VMEM->HBM with a 1-D grid; the block is (rows_block, cols)
so DMA transfers are long contiguous runs and the grid pipeline keeps the
memory controller saturated (the point of the paper's copy TAO: a single big
core nearly saturates HBM/DDR bandwidth, so extra width buys little).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _triad_kernel(a_ref, x_ref, y_ref, o_ref):
    o_ref[...] = a_ref[0] * x_ref[...] + y_ref[...]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def copy(x: jax.Array, *, block_rows: int = 256, interpret: bool = False):
    """Streaming copy of a 2-D array, row-blocked."""
    rows, cols = x.shape
    if rows % block_rows:
        raise ValueError(f"rows {rows} not divisible by block_rows {block_rows}")
    return pl.pallas_call(
        _copy_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def triad(a, x: jax.Array, y: jax.Array, *, block_rows: int = 256,
          interpret: bool = False):
    """STREAM triad ``a*x + y`` with the scalar prefetched to SMEM."""
    if x.shape != y.shape:
        raise ValueError(f"shape mismatch {x.shape} vs {y.shape}")
    rows, cols = x.shape
    if rows % block_rows:
        raise ValueError(f"rows {rows} not divisible by block_rows {block_rows}")
    a = jnp.asarray(a, x.dtype).reshape((1,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, cols), lambda i, a_ref: (i, 0)),
            pl.BlockSpec((block_rows, cols), lambda i, a_ref: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i, a_ref: (i, 0)),
    )
    return pl.pallas_call(
        _triad_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(a, x, y)
