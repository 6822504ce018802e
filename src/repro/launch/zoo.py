"""Tenant zoo: real jitted payloads behind the serving orchestrator.

Each serving tenant runs one *flavor* — a smoke-sized model from the model
zoo (``transformer``/``ssm``/``hybrid``) served through its jitted
``prefill``/``decode_step``, or the raw ``kernel`` flavor that binds
``repro.kernels`` ops directly (flash-attention + matmul prefill slab,
copy-class decode).  A :class:`ZooTenant` compiles its payloads once
(``warm()``); every payload shape is fixed, so no request ever triggers a
recompile on a worker thread.

One prefill *chunk* stands for ``slab_tokens`` prompt tokens: a request's
prefill TAO carries ``ceil(prompt_len / slab_tokens)`` chunks, each chunk one
jitted slab call.  Chunk counts therefore scale with prompt length, which
gives the preemption controllers real yield points inside long prefills and
lets the PTT measure per-(class, width) costs from actual wall-clock
execution.  Decode bursts stay single-chunk (they are already the
continuous-batching granularity).

Use with the orchestrator's general threaded entry point::

    zoo = default_zoo()
    warm_zoo(zoo)
    stats = run_serving_workload_threaded(reqs, spec, policy, zoo_binder(zoo))
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

from ..core.dag import TAO
from ..core.runtime import ChunkedWork
from ..core.serve_orchestrator import ServeRequest

# flavor -> model-zoo architecture serving it (smoke-sized configs)
FLAVOR_ARCHS = {
    "transformer": "llama3.2-1b",
    "ssm": "mamba2-780m",
    "hybrid": "hymba-1.5b",
}
FLAVORS = ("kernel",) + tuple(FLAVOR_ARCHS)


class ZooTenant:
    """One tenant's compiled serving engine (a flavor + its jitted payloads).

    ``prefill_slab()`` and ``decode_burst()`` are the two kernel classes the
    scheduler sees: the slab is compute-bound (flash-attention/matmul class),
    the burst is memory-bound (copy class).  ``decode_steps`` repeats the
    decode call inside one burst to pad very fast smoke models up to a
    measurable TAO.
    """

    def __init__(self, name: str, flavor: str = "kernel",
                 slab_tokens: int = 1024, decode_steps: int = 1,
                 seed: int = 0):
        if flavor not in FLAVORS:
            raise ValueError(f"unknown flavor {flavor!r}; known: {FLAVORS}")
        self.name = name
        self.flavor = flavor
        self.slab_tokens = max(1, int(slab_tokens))
        self.decode_steps = max(1, int(decode_steps))
        if flavor == "kernel":
            self._build_kernel_payloads(seed)
        else:
            self._build_model_payloads(FLAVOR_ARCHS[flavor], seed)

    # -- payload construction -------------------------------------------
    def _build_kernel_payloads(self, seed: int) -> None:
        """repro.kernels ops, no model: the two classes in their pure form."""
        from ..kernels import ops

        B, H, S, D = 1, 4, 256, 64
        k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(k0, (B, H, S, D), jnp.float32)
        kv = jax.random.normal(k1, (B, H, S, D), jnp.float32)
        w = jax.random.normal(k2, (H * D, H * D), jnp.float32)
        # decode touches a KV-cache-sized slab: pure bandwidth
        cache_slab = jax.random.normal(k3, (4 * S, H * D), jnp.float32)
        x1 = jax.random.normal(k0, (1, H * D), jnp.float32)

        def prefill_slab() -> None:
            attn = ops.flash_attention(q, kv, kv)
            y = ops.matmul(attn.reshape(S, H * D), w)
            jax.block_until_ready(y)

        # the burst's GEMV is a single row, which no 128-multiple tile of
        # the Pallas matmul divides, so it is pinned to XLA (force="ref")
        # on every backend; the copy keeps auto dispatch
        def decode_burst() -> None:
            for _ in range(self.decode_steps):
                moved = ops.copy(cache_slab)
                y = ops.matmul(x1, w, force="ref")
                jax.block_until_ready((moved, y))

        self.prefill_slab = prefill_slab
        self.decode_burst = decode_burst

    def _build_model_payloads(self, arch: str, seed: int) -> None:
        from ..configs import get_smoke_config
        from ..models import get_model

        cfg = get_smoke_config(arch)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(seed))
        toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, 16), 0,
                                  cfg.vocab_size)
        prefill_j = jax.jit(model.prefill)
        decode_j = jax.jit(model.decode_step)
        # fixed decode state: one step's worth of cache, reused per burst
        # (serving-shape work, not a faithful token-by-token generation)
        _, cache0 = prefill_j(params, {"tokens": toks})
        last = toks[:, -1:]

        def prefill_slab() -> None:
            logits, _ = prefill_j(params, {"tokens": toks})
            jax.block_until_ready(logits)

        def decode_burst() -> None:
            for _ in range(self.decode_steps):
                logits, _ = decode_j(params, last, cache0)
                jax.block_until_ready(logits)

        self.prefill_slab = prefill_slab
        self.decode_burst = decode_burst

    # -- serving interface ----------------------------------------------
    def warm(self) -> None:
        """Compile all payloads now, off the worker threads."""
        self.prefill_slab()
        self.decode_burst()

    def prefill_chunks(self, r: ServeRequest) -> int:
        return max(1, math.ceil(r.prompt_len / self.slab_tokens))

    def kv_bytes_per_token(self) -> float:
        """Per-token KV-cache bytes this tenant's decode actually streams.

        Sized from the kernel flavor's decode slab (``4*S x H*D`` float32
        standing for ``slab_tokens`` tokens of cache), so threaded serving
        footprints track the bytes the payload really touches — model
        flavors share the same figure for comparable footprints."""
        H, S, D = 4, 256, 64
        slab_bytes = (4 * S) * (H * D) * 4
        return slab_bytes / float(self.slab_tokens)

    def bind(self, tao: TAO, r: ServeRequest) -> None:
        """Attach this tenant's ChunkedWork payload to one serving TAO."""
        n = self.prefill_chunks(r) if tao.type == "prefill" else 1
        fn = (self.prefill_slab if tao.type == "prefill"
              else self.decode_burst)
        tao.work = ChunkedWork(lambda i, fn=fn: fn(), n)


def default_zoo(flavors: dict | None = None, slab_tokens: int = 1024,
                decode_steps: int = 1, seed: int = 0) -> dict:
    """``tenant name -> ZooTenant``.  Default pairing mirrors the bursty
    trace: the latency-sensitive ``steady`` tenant serves a transformer,
    the ``burst`` tenant hammers the raw Pallas-class kernels."""
    flavors = flavors or {"steady": "transformer", "burst": "kernel"}
    return {name: ZooTenant(name, flavor=fl, slab_tokens=slab_tokens,
                            decode_steps=decode_steps, seed=seed + i)
            for i, (name, fl) in enumerate(flavors.items())}


def warm_zoo(zoo: dict) -> None:
    for tenant in zoo.values():
        tenant.warm()


def zoo_binder(zoo: dict) -> Callable[[TAO, ServeRequest], None]:
    """Binder for ``run_serving_workload_threaded``: dispatch each request's
    TAOs to its tenant's compiled payloads."""
    def binder(tao: TAO, r: ServeRequest) -> None:
        if r.tenant not in zoo:
            raise KeyError(f"request {r.id}: no tenant {r.tenant!r} in zoo "
                           f"(have {sorted(zoo)})")
        zoo[r.tenant].bind(tao, r)
    return binder
