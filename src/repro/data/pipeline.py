"""Deterministic synthetic data pipeline.

Real fleet training would read a tokenized corpus via per-host shards; this
substrate reproduces that structure (per-host iterator, global-batch
assembly, deterministic seeding by (seed, step, host)) with a synthetic
Zipf-ish token source so every example/benchmark is hermetic and offline.

The generators are numpy-based (host-side, like a real input pipeline) and
hand jax the final device arrays.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax.numpy as jnp


@dataclasses.dataclass
class SyntheticLM:
    """Zipf-distributed token stream -> (tokens, targets) batches."""

    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    host_index: int = 0
    host_count: int = 1

    def __post_init__(self):
        if self.global_batch % self.host_count:
            raise ValueError("global_batch must divide by host_count")
        self._host_batch = self.global_batch // self.host_count

    def _rng(self, step: int) -> np.random.Generator:
        return np.random.default_rng(
            (self.seed * 1_000_003 + step) * 97 + self.host_index)

    def host_batch(self, step: int) -> dict:
        """The shard of the global batch this host produces."""
        rng = self._rng(step)
        # Zipf-ish marginal over the vocab (heavy head like natural text)
        z = rng.zipf(1.3, size=(self._host_batch, self.seq_len + 1))
        tokens = np.minimum(z - 1, self.vocab_size - 1).astype(np.int32)
        return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}

    def batch(self, step: int) -> dict:
        """Single-host convenience: the full global batch as jax arrays."""
        out = [self.host_batch(step)] if self.host_count == 1 else [
            dataclasses.replace(self, host_index=h).host_batch(step)
            for h in range(self.host_count)]
        cat = {k: np.concatenate([o[k] for o in out]) for k in out[0]}
        return {k: jnp.asarray(v) for k, v in cat.items()}


@dataclasses.dataclass
class SyntheticFrames:
    """Precomputed frame/patch embeddings for [audio]/[vlm] stub frontends."""

    d_model: int
    seq_len: int
    global_batch: int
    n_classes: int = 504
    seed: int = 0

    def batch(self, step: int) -> dict:
        rng = np.random.default_rng(self.seed * 7 + step)
        feats = rng.standard_normal(
            (self.global_batch, self.seq_len, self.d_model)).astype(np.float32)
        labels = rng.integers(
            0, self.n_classes,
            size=(self.global_batch, self.seq_len)).astype(np.int32)
        return {"frames": jnp.asarray(feats, jnp.bfloat16),
                "targets": jnp.asarray(labels)}

