"""Mesh construction.

``jax.devices()`` is what the host has: one CPU device under the tests, one
chip (or the four of a v5e 2x2 host) on a TPU, and ``make_debug_mesh`` spans
those.  The scheduler's threaded runtime uses no mesh: every worker launches
onto the default device.
"""
from __future__ import annotations

import jax


def make_debug_mesh(n_devices: int | None = None,
                    model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (CPU tests)."""
    n = n_devices or len(jax.devices())
    if n % model:
        raise ValueError(f"{n} devices not divisible by model={model}")
    return jax.make_mesh((n // model, model), ("data", "model"))
