"""Public jit'd kernel entry points.

Each op dispatches to the Pallas kernel on TPU and to the pure-jnp reference
(XLA) on other backends.  The test suite checks every kernel body against
the oracles in ``ref.py`` in interpret mode on CPU, and
``tests/test_tpu_compile.py`` compiles each kernel for a described TPU v5e;
``chip_smoke.py`` runs them on a chip against ``ref.py``.  Setting
``force='pallas'``/``force='ref'`` overrides dispatch; ``force='interpret'``
runs the Pallas kernel body in interpret mode (Python on CPU).

Implementation registry
-----------------------
:func:`available_impls` enumerates the library's interchangeable
implementations — name, availability predicate, and per-op callables — so
the scheduler's variant machinery (``TAO.impls``, the per-(class, impl,
width) PTT) and the serving zoo bind variants without hardcoding strings.
``force=`` remains as the thin back-compat shim over the same dispatch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

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


def matmul(x, y, *, bm=128, bn=128, bk=128, out_dtype=None, force=None):
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


# ---------------------------------------------------------------------------
# implementation registry
# ---------------------------------------------------------------------------
_OPS: dict[str, Callable] = {
    "matmul": matmul,
    "copy": copy,
    "triad": triad,
    "sort_rows": sort_rows,
    "rmsnorm": rmsnorm,
    "flash_attention": flash_attention,
}


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    """One interchangeable implementation of the kernel library.

    ``force`` is the value the back-compat shim understands; ``available``
    is the host predicate, evaluated at enumeration time: a TPU host sees
    ``ref`` and ``pallas``, a CPU host sees ``ref`` and, where the Pallas
    interpreter works there, ``interpret``.  Interpret mode is never a
    variant on a TPU, where it would be a slow copy of ``pallas``.
    """

    name: str
    force: str | None
    available: Callable[[], bool]

    def op(self, op_name: str) -> Callable:
        """The public op pinned to this implementation (a real callable —
        variant payloads close over it instead of a force string)."""
        return functools.partial(_OPS[op_name], force=self.force)


def _pallas_native() -> bool:
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=1)
def _interpret_works() -> bool:
    """Probe (once) whether the Pallas interpreter runs on this host: some
    jax builds ship TPU-only Pallas pieces whose interpret path raises."""
    import jax.numpy as jnp
    try:
        x = jnp.ones((128, 128), jnp.float32)
        jax.block_until_ready(matmul(x, x, force="interpret"))
        return True
    except Exception:
        return False


_IMPLS = (
    KernelImpl("ref", "ref", lambda: True),
    KernelImpl("pallas", "pallas", _pallas_native),
    KernelImpl("interpret", "interpret",
               lambda: not _pallas_native() and _interpret_works()),
)


def all_impls() -> tuple[KernelImpl, ...]:
    """Every registered implementation, available on this host or not."""
    return _IMPLS


def available_impls() -> tuple[KernelImpl, ...]:
    """Implementations whose availability predicate holds on this host, in
    registry order (``ref`` first — always available — then the Pallas
    flavors)."""
    return tuple(im for im in _IMPLS if im.available())


def get_impl(name: str) -> KernelImpl:
    for im in _IMPLS:
        if im.name == name:
            return im
    raise KeyError(f"unknown kernel impl {name!r}; "
                   f"known: {[im.name for im in _IMPLS]}")


def op_names() -> tuple[str, ...]:
    return tuple(_OPS)
