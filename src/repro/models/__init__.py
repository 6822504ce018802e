"""repro.models — model zoo + generic train/serve step builders."""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from .model_api import BaseModel, ModelConfig, ParamDef
from .transformer import DecoderLM
from .mamba2 import Mamba2LM
from .hybrid import HymbaLM
from .falcon_h1 import FalconH1LM


def get_model(cfg: ModelConfig) -> BaseModel:
    if cfg.family in ("decoder", "encoder"):
        return DecoderLM(cfg)
    if cfg.family == "ssm":
        return Mamba2LM(cfg)
    if cfg.family == "hybrid":
        return HymbaLM(cfg)
    if cfg.family == "falcon_h1":
        return FalconH1LM(cfg)
    raise ValueError(f"unknown family {cfg.family!r}")


# ---------------------------------------------------------------------------
# generic steps (shared across all architectures)
# ---------------------------------------------------------------------------
def make_train_step(model: BaseModel, lr_schedule: Callable | float = 3e-4,
                    max_grad_norm: float = 1.0):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""
    from ..optimizer import adamw_update, clip_by_global_norm

    sched = (lr_schedule if callable(lr_schedule)
             else (lambda step: jnp.asarray(lr_schedule, jnp.float32)))

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch)
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr = sched(opt_state.step + 1)
        params, opt_state = adamw_update(params, grads, opt_state, lr)
        metrics = dict(metrics)
        metrics.update({"grad_norm": gnorm, "lr": lr})
        return params, opt_state, metrics

    return train_step


def make_prefill_step(model: BaseModel):
    def prefill_step(params, batch):
        return model.prefill(params, batch)
    return prefill_step


def make_decode_step(model: BaseModel):
    def decode_step(params, tokens, cache):
        return model.decode_step(params, tokens, cache)
    return decode_step


def make_encode_step(model: BaseModel):
    """Encoder-only serve step: full forward -> per-frame logits."""
    def encode_step(params, batch):
        return model.forward(params, batch)
    return encode_step


__all__ = [
    "BaseModel", "ModelConfig", "ParamDef", "DecoderLM", "Mamba2LM",
    "HymbaLM", "FalconH1LM", "get_model", "make_train_step", "make_prefill_step",
    "make_decode_step", "make_encode_step",
]
