"""Mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant), so importing
this module never touches jax device state.  Its 256- and 512-chip meshes
exist only in the dry run, which sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax
initializes.  Everywhere else ``jax.devices()`` is what the host has: one
CPU device under the tests, one chip (or the four of a v5e 2x2 host) on a
TPU, and ``make_debug_mesh`` spans those.  The scheduler's threaded runtime
uses no mesh: every worker launches onto the default device.
"""
from __future__ import annotations

import jax

# the chip a production mesh stands for: a 16x16 v5e pod
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None,
                    model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (CPU tests)."""
    n = n_devices or len(jax.devices())
    if n % model:
        raise ValueError(f"{n} devices not divisible by model={model}")
    return jax.make_mesh((n // model, model), ("data", "model"))


def mesh_chip_count(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
