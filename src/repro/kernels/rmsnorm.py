"""Pallas TPU fused RMSNorm: one HBM round-trip instead of XLA's several.

out = x * rsqrt(mean(x^2) + eps) * w, computed rowwise in fp32.  Row blocks
stream through VMEM; the weight block is broadcast to every grid step (index
map pins it to block 0), so it is loaded once and stays VMEM-resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """RMSNorm rows of (rows, d) by weight (d,)."""
    rows, d = x.shape
    if w.shape != (d,):
        raise ValueError(f"weight shape {w.shape} != ({d},)")
    if rows % block_rows:
        raise ValueError(f"rows {rows} not divisible by block_rows {block_rows}")
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x, w.reshape(1, d))
