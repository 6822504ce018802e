"""Pallas TPU flash attention (forward) with GQA, causal and sliding-window.

Online-softmax blocked attention: grid (batch, q_heads, q_blocks, kv_blocks)
with the kv dimension innermost ("arbitrary"); running max/sum and the fp32
accumulator live in VMEM scratch across kv steps.  GQA is handled in the
index maps (kv head = q head // group), so no materialized head repeat.

Block sizes default to (bq, bk) = (256, 256): working set per step is
  q(bq,d) + k(bk,d) + v(bk,d) + acc(bq,d)fp32 + scores(bq,bk)fp32
~ 256*128*(2+2+2+4) + 256*256*4 B ~ 0.6 MB, leaving VMEM headroom for the
pipeline's double buffering.

Causal masking and sliding windows are applied per-element inside the block;
fully-masked kv blocks are *skipped* via ``pl.when`` (the compute guard), so
causal attention does ~half the FLOPs and a sliding window does O(S*W) — the
property that makes mixtral/hymba ``long_500k`` decode feasible.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .matmul import f32_precision


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  kv_steps: int, bq: int, bk: int, causal: bool,
                  window: int | None, sm_scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk

    # --- block-level skip: entire kv block out of the visible range? -------
    if causal or window is not None:
        # rows visible: [q_start, q_start+bq); cols in [k_start, k_start+bk)
        max_row = q_start + bq - 1
        visible = k_start <= max_row if causal else True
        if window is not None:
            # col >= row - window + 1 for some row in block
            visible = jnp.logical_and(
                visible, k_start + bk - 1 >= q_start - (window - 1))
        run = visible if isinstance(visible, jax.Array) else (
            jnp.asarray(visible))
    else:
        run = jnp.asarray(True)

    @pl.when(run)
    def _block():
        precision = f32_precision(q_ref.dtype)
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * sm_scale   # (bq, bk)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        if window is not None:
            mask = jnp.logical_and(mask, cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                           # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)               # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _flush():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bk", "sm_scale", "interpret"))
def flash_attention(
    q: jax.Array,  # (B, Hq, S, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    *,
    causal: bool = True,
    window: int | None = None,
    bq: int = 256,
    bk: int = 256,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, hq, s, d = q.shape
    _, hkv, sk, dk = k.shape
    if (d != dk) or (k.shape != v.shape):
        raise ValueError(f"bad kv shapes {k.shape} {v.shape} for q {q.shape}")
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if s % bq or sk % bk:
        raise ValueError(f"seq {s}/{sk} not tiled by bq={bq}/bk={bk}")
    group = hq // hkv
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    kv_steps = sk // bk
    kernel = functools.partial(
        _flash_kernel, kv_steps=kv_steps, bq=bq, bk=bk,
        causal=causal, window=window, sm_scale=sm_scale)
    return pl.pallas_call(
        kernel,
        name="flash_attention",      # the kernel's op name in a device trace
        grid=(b, hq, s // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, i, j: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, i, j, g=group: (bb, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, i, j, g=group: (bb, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bb, h, i, j: (bb, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
