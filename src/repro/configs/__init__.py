"""repro.configs — the 11 architectures, the 4 input shapes, and
the (arch x shape) cell matrix with structural-skip logic.

Every architecture is selectable as ``--arch <id>`` in the launchers; each
also exposes a reduced ``smoke`` variant used by the CPU smoke tests (full
configs are exercised only abstractly, via the dry-run).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Iterable

import jax
import jax.numpy as jnp

from ..models import ModelConfig, get_model
from ..parallel.sharding import logical_sharding

ARCH_IDS = (
    "internvl2-2b",
    "mamba2-780m",
    "moonshot-v1-16b-a3b",
    "mixtral-8x22b",
    "hubert-xlarge",
    "minicpm-2b",
    "llama3.2-1b",
    "chatglm3-6b",
    "llama3-8b",
    "hymba-1.5b",
    "falcon-h1-34b",
)

_MODULES = {
    "internvl2-2b": "internvl2_2b",
    "mamba2-780m": "mamba2_780m",
    "moonshot-v1-16b-a3b": "moonshot_v1_16b_a3b",
    "mixtral-8x22b": "mixtral_8x22b",
    "hubert-xlarge": "hubert_xlarge",
    "minicpm-2b": "minicpm_2b",
    "llama3.2-1b": "llama3_2_1b",
    "chatglm3-6b": "chatglm3_6b",
    "llama3-8b": "llama3_8b",
    "hymba-1.5b": "hymba_1_5b",
    "falcon-h1-34b": "falcon_h1_34b",
}


def _module(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {list(_MODULES)}")
    return importlib.import_module(f".{_MODULES[arch]}", __package__)


def get_config(arch: str) -> ModelConfig:
    return _module(arch).config()


def get_smoke_config(arch: str) -> ModelConfig:
    return _module(arch).smoke_config()


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}
SHAPE_NAMES = tuple(SHAPES)


def cell_skip_reason(cfg: ModelConfig, shape: ShapeSpec) -> str | None:
    """None if the (arch, shape) cell runs; otherwise the structural reason."""
    if cfg.family == "encoder" and shape.kind == "decode":
        return "encoder-only: no decode step"
    if shape.name == "long_500k":
        sub_quadratic = (cfg.family in ("ssm", "hybrid")
                         or cfg.window is not None)
        if not sub_quadratic:
            return "full attention: 500k decode needs sub-quadratic attention"
    return None


def valid_cells(archs: Iterable[str] = ARCH_IDS,
                shapes: Iterable[str] = SHAPE_NAMES):
    """All runnable (arch, shape) pairs + the skip list."""
    run, skip = [], []
    for a in archs:
        cfg = get_config(a)
        for s in shapes:
            reason = cell_skip_reason(cfg, SHAPES[s])
            if reason is None:
                run.append((a, s))
            else:
                skip.append((a, s, reason))
    return run, skip


# ---------------------------------------------------------------------------
# input specs (the dry-run stand-ins; weak-type-correct, no allocation)
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    """ShapeDtypeStructs for every model input of this (arch, shape) cell.

    ``train``   -> the training batch
    ``prefill`` -> the request batch (full prompt)
    ``decode``  -> one-token batch + a KV/state cache of seq_len
    Shardings come from the active sharding context (batch over pod x data).
    """
    B, S = shape.global_batch, shape.seq_len

    def sds(shp, dtype, names):
        return jax.ShapeDtypeStruct(shp, dtype,
                                    sharding=logical_sharding(shp, names))

    if shape.kind == "train":
        if cfg.frontend == "frames":
            batch = {
                "frames": sds((B, S, cfg.d_model), jnp.bfloat16,
                              ("batch", "seq", None)),
                "targets": sds((B, S), jnp.int32, ("batch", "seq")),
            }
        else:
            batch = {
                "tokens": sds((B, S), jnp.int32, ("batch", "seq")),
                "targets": sds((B, S), jnp.int32, ("batch", "seq")),
            }
            if cfg.frontend == "patch":
                batch["patch_embeds"] = sds(
                    (B, cfg.n_patches, cfg.d_model), jnp.bfloat16,
                    ("batch", None, None))
        return {"batch": batch}

    if shape.kind == "prefill":
        if cfg.frontend == "frames":
            batch = {"frames": sds((B, S, cfg.d_model), jnp.bfloat16,
                                   ("batch", "seq", None))}
        else:
            batch = {"tokens": sds((B, S), jnp.int32, ("batch", "seq"))}
            if cfg.frontend == "patch":
                batch["patch_embeds"] = sds(
                    (B, cfg.n_patches, cfg.d_model), jnp.bfloat16,
                    ("batch", None, None))
        return {"batch": batch}

    if shape.kind == "decode":
        model = get_model(cfg)
        cache = model.init_cache(B, S, abstract=True)
        tokens = sds((B, 1), jnp.int32, ("batch", None))
        return {"tokens": tokens, "cache": cache}

    raise ValueError(f"unknown shape kind {shape.kind}")


__all__ = [
    "ARCH_IDS", "SHAPES", "SHAPE_NAMES", "ShapeSpec", "get_config",
    "get_smoke_config", "cell_skip_reason", "valid_cells", "input_specs",
]
