"""Pallas TPU block matmul — the paper's *compute-bound* kernel class.

MXU-aligned tiling: (bm, bk) x (bk, bn) blocks accumulated in an fp32 VMEM
scratch across the k grid dimension.  Default 128-multiples so the MXU
(128x128 systolic array) sees hardware-aligned contractions; the working set

    (bm*bk + bk*bn) * in_bytes + bm*bn * (4 + out_bytes)

fits comfortably in VMEM (~16 MB on v5e).  Grid order (m, n, k) with k
innermost lets the pipeline prefetch the next k-block over HBM->VMEM DMA
while the MXU processes the current one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def f32_precision(dtype) -> jax.lax.Precision | None:
    """Dot precision that keeps float32 operands float32.

    Mosaic runs a float32 dot at default precision as one bf16 pass; on a
    TPU v5e that put a 256x256x256 product 0.19 off XLA's float32 result.
    ``HIGHEST`` gives float32 products.  Narrower operands multiply exactly
    at the default."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32,
        precision=f32_precision(x_ref.dtype),
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret")
)
def matmul(
    x: jax.Array,
    y: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """``x @ y`` via a Pallas grid; shapes must tile evenly by (bm, bn, bk)."""
    m, k = x.shape
    k2, n = y.shape
    if k != k2:
        raise ValueError(f"contracting dims mismatch: {x.shape} @ {y.shape}")
    if m % bm or n % bn or k % bk:
        raise ValueError(
            f"shape ({m},{k})x({k},{n}) not tiled by bm={bm}, bn={bn}, bk={bk}"
        )
    out_dtype = out_dtype or x.dtype
    k_steps = k // bk
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, y)
