"""Public jit'd kernel entry points.

Each op dispatches to the Pallas kernel on TPU and to the pure-jnp reference
(XLA) on other backends.  The test suite checks every kernel body against
the oracles in ``ref.py`` in interpret mode on CPU, and
``tests/test_tpu_compile.py`` compiles each kernel for a described TPU v5e;
``chip_smoke.py`` runs them on a chip against ``ref.py``.  Setting
``force='pallas'``/``force='ref'`` overrides dispatch; ``force='interpret'``
runs the Pallas kernel body in interpret mode (Python on CPU).
"""
from __future__ import annotations

import jax

from . import copy_stream as _copy_stream
from . import flash_attention as _flash
from . import matmul as _matmul
from . import ref
from . import rmsnorm as _rmsnorm
from . import sort_bitonic as _sort


def _use_pallas(force: str | None) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)."""
    if force == "pallas":
        return True, False
    if force == "interpret":
        return True, True
    if force == "ref":
        return False, False
    return jax.default_backend() == "tpu", False


def matmul(x, y, *, bm=None, bn=None, bk=None, out_dtype=None, force=None):
    pallas, interp = _use_pallas(force)
    if pallas:
        return _matmul.matmul(x, y, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                              interpret=interp)
    return ref.matmul(x, y, out_dtype=out_dtype)


def copy(x, *, block_rows=256, force=None):
    pallas, interp = _use_pallas(force)
    if pallas:
        return _copy_stream.copy(x, block_rows=block_rows, interpret=interp)
    return ref.copy(x)


def triad(a, x, y, *, block_rows=256, force=None):
    pallas, interp = _use_pallas(force)
    if pallas:
        return _copy_stream.triad(a, x, y, block_rows=block_rows,
                                  interpret=interp)
    return ref.triad(a, x, y)


def sort_rows(x, *, block_rows=8, force=None):
    pallas, interp = _use_pallas(force)
    if pallas:
        return _sort.sort_rows(x, block_rows=block_rows, interpret=interp)
    return ref.sort_rows(x)


def rmsnorm(x, w, *, eps=1e-6, block_rows=256, force=None):
    pallas, interp = _use_pallas(force)
    if pallas:
        return _rmsnorm.rmsnorm(x, w, eps=eps, block_rows=block_rows,
                                interpret=interp)
    return ref.rmsnorm(x, w, eps=eps)


def flash_attention(q, k, v, *, causal=True, window=None, bq=256, bk=256,
                    sm_scale=None, force=None):
    pallas, interp = _use_pallas(force)
    if pallas:
        return _flash.flash_attention(q, k, v, causal=causal, window=window,
                                      bq=bq, bk=bk, sm_scale=sm_scale,
                                      interpret=interp)
    return ref.attention(q, k, v, causal=causal, window=window,
                         sm_scale=sm_scale)

