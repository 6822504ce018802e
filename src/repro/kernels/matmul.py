"""Pallas TPU block matmul — the paper's *compute-bound* kernel class.

MXU-aligned tiling: (bm, bk) x (bk, bn) blocks accumulated in an fp32 VMEM
scratch across the k grid dimension.  Grid order (m, n, k) with k innermost
lets the pipeline prefetch the next k-block over HBM->VMEM DMA while the MXU
processes the current one.

Tiles left unset are chosen from the operands' shapes and dtypes
(:func:`choose_tiles`): per dimension, the largest of 1024, 512, 256 and 128
that divides it, such that the kernel's VMEM stays within ``VMEM_BUDGET``
(14 MiB, under v5e's 16 MiB default scoped VMEM).  That VMEM is the
double-buffered operand blocks, the f32 accumulator, the double-buffered
output block and the dot's own stack, which Mosaic sizes at about the
(bm, bk) block, four times it for float32 at ``HIGHEST`` (split into bf16
parts):

    (bm*bk + bk*bn) * in_bytes * 2 + bm*bn * (4 + 2 * out_bytes)
        + bm*bk * in_bytes * (4 if float32 else 1)

(read from compiles for a described v5e; within 0.5 MiB of what the
compiler allocates at every ladder tile).  Where the largest tiles do not
fit, output reuse (``bm*bn``) is kept before depth (``bk``).  Each grid
step costs a fixed ~0.35 us on a v5e, so a 4096^3 bf16 product at 128^3
tiles (32768 steps) spends most of its time on step overhead; at
(1024, 1024, 512) it takes 128 steps.
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


VMEM_BUDGET = 14 * 1024 * 1024
_TILE_LADDER = (1024, 512, 256, 128)


def vmem_bytes(bm: int, bn: int, bk: int, in_dtype, out_dtype=None) -> int:
    """The VMEM a (bm, bn, bk) tile needs, as the module docstring states."""
    in_bytes = jnp.dtype(in_dtype).itemsize
    out_bytes = jnp.dtype(out_dtype or in_dtype).itemsize
    dot_stack = bm * bk * in_bytes * (4 if f32_precision(in_dtype) else 1)
    return ((bm * bk + bk * bn) * in_bytes * 2
            + bm * bn * (4 + 2 * out_bytes) + dot_stack)


def choose_tiles(m: int, k: int, n: int, in_dtype,
                 out_dtype=None) -> tuple[int, int, int]:
    """(bm, bn, bk) for an (m, k) x (k, n) product: the largest ladder tiles
    that divide each dimension and keep :func:`vmem_bytes` within
    ``VMEM_BUDGET``, preferring large ``bm*bn`` over large ``bk``."""
    def fits(d):
        tiles = [t for t in _TILE_LADDER if d % t == 0]
        if not tiles:
            raise ValueError(f"dimension {d} of ({m},{k})x({k},{n}) is not "
                             f"a multiple of {_TILE_LADDER[-1]}")
        return tiles

    def used(t):
        return vmem_bytes(*t, in_dtype, out_dtype)

    fitting = [(bm, bn, bk) for bm in fits(m) for bn in fits(n)
               for bk in fits(k) if used((bm, bn, bk)) <= VMEM_BUDGET]
    return max(fitting, key=lambda t: (t[0] * t[1], t[2], -used(t)))


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
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """``x @ y`` via a Pallas grid; shapes must tile evenly by (bm, bn, bk).
    A tile left ``None`` is taken from :func:`choose_tiles`."""
    m, k = x.shape
    k2, n = y.shape
    if k != k2:
        raise ValueError(f"contracting dims mismatch: {x.shape} @ {y.shape}")
    if None in (bm, bn, bk):
        cm, cn, ck = choose_tiles(m, k, n, x.dtype, out_dtype)
        bm, bn, bk = bm or cm, bn or cn, bk or ck
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
