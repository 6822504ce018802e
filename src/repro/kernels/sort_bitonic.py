"""Pallas TPU bitonic row sort — the paper's *data-reuse* kernel class.

The paper's sort TAO (quicksort + two mergesort levels) is a pointer-chasing
CPU algorithm; its TPU-native analogue is a **bitonic sorting network**: a
fixed O(n log^2 n) sequence of compare-exchange stages over vectors — branch
free, fully vectorizable on the VPU, and with the whole working set resident
in VMEM between stages (the data-reuse property the paper selects sort for).

Each grid step sorts ``block_rows`` independent rows of length ``n`` (a power
of two).  A stage at (k, j) compare-exchanges lanes at distance d = 2^j with
direction flipping every 2^(k+1) lanes.  The block keeps its 2-D
``(block_rows, n)`` layout: the partner lanes come from two lane rotations
and an iota mask picks min or max per lane, so a stage lowers to lane
rotates plus VPU min/max/select — no gathers and no reshape of the lane
axis (which Mosaic refuses).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bitonic_stage(x: jax.Array, k: int, j: int) -> jax.Array:
    """One compare-exchange stage on rows; x: (rows, n).

    Lane ``i`` pairs with lane ``i ^ d``: the lower lane of a pair reads
    its partner from ``roll(x, n - d)`` (lane ``i + d``), the upper lane
    from ``roll(x, d)`` (lane ``i - d``).  ``pltpu.roll`` shifts like
    ``jnp.roll``; the lower lane keeps the min where the pair sorts
    ascending, i.e. where bit (k+1) of the lane index is 0."""
    n = x.shape[1]
    d = 1 << j
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    upper = (lane & d) != 0
    partner = jnp.where(upper, pltpu.roll(x, d, 1), pltpu.roll(x, n - d, 1))
    ascending = ((lane >> (k + 1)) & 1) == 0
    return jnp.where(upper != ascending, jnp.minimum(x, partner),
                     jnp.maximum(x, partner))


def _sort_kernel(x_ref, o_ref, *, n: int):
    x = x_ref[...]
    stages = int(math.log2(n))
    for k in range(stages):
        for j in range(k, -1, -1):
            x = _bitonic_stage(x, k, j)
    o_ref[...] = x


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def sort_rows(x: jax.Array, *, block_rows: int = 8, interpret: bool = False):
    """Sort each row of a (rows, n) array ascending; n must be a power of 2."""
    rows, n = x.shape
    if n & (n - 1):
        raise ValueError(f"row length {n} must be a power of two")
    if rows % block_rows:
        raise ValueError(f"rows {rows} not divisible by block_rows {block_rows}")
    return pl.pallas_call(
        functools.partial(_sort_kernel, n=n),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x)
