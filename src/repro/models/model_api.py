"""Model API: one config dataclass + param-definition machinery shared by all
families (dense/MoE decoder, SSM, hybrid, encoder, VLM backbone).

Every model exposes:
  * ``param_defs()``      — {name: ParamDef(shape, logical names, init)}
  * ``init(key)``         — concrete fp32 params
  * ``loss(params, batch)``              — scalar loss + metrics
  * ``prefill(params, batch)``           — logits + populated cache
  * ``decode_step(params, tokens, cache)`` — one-token serve step
  * ``init_cache(batch_size, max_len)``  — zeroed decode cache

Layer params are stacked along a leading "layers" dim and consumed by
``lax.scan`` — the HLO stays one-layer-sized, however deep the model.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..parallel.sharding import pad_to_multiple


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # decoder | ssm | hybrid | falcon_h1 |
                                 # encoder
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 -> d_model // n_heads
    # --- MoE ---
    n_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    # --- attention ---
    window: int | None = None            # sliding-window size (None = full)
    global_layers: tuple = ()            # layer idxs with full attention (hybrid)
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0           # chatglm "2d" RoPE rotates half dims
    qkv_bias: bool = False
    causal: bool = True                  # encoders set False
    # --- SSM ---
    ssm_state: int = 0
    ssm_expand: float = 2            # d_inner = round(ssm_expand * d_model)
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_groups: int = 1                  # B/C groups: head h reads group
                                         # h // (ssm_heads // ssm_groups)
    # --- muP multipliers (Falcon-H1's published ones; 1.0 = none) ---
    embedding_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0,) * 5  # on in_proj's [z, x, B, C, dt]
    mlp_multipliers: tuple = (1.0, 1.0)  # gate, down
    lm_head_multiplier: float = 1.0
    # --- misc ---
    norm_eps: float = 1e-5
    vocab_pad_multiple: int = 256
    tie_embeddings: bool = False
    frontend: str = "none"               # none | patch (vlm) | frames (audio)
    n_patches: int = 256                 # vlm stub patch count
    # --- execution (hillclimb knobs; defaults = paper-faithful baseline) ---
    remat: bool = True
    remat_policy: str = "nothing"        # nothing | dots
    attn_chunk: int = 1024               # q-chunked attention block
    dense_attn_max_seq: int = 1024       # S above this -> chunked attention
    logits_chunk: int = 0                # 0 = unchunked CE
    ce_onehot: bool = False              # TP-safe cross-entropy (no vocab
                                         # all-gather); see models/losses.py
    ssd_shard_acts: bool = False         # shard SSD intra-chunk activations
    swa_block_skip: bool = False         # static kv-slicing in chunked attn
                                         # (skip causal/SWA-masked blocks)
    swa_ring_buffer: bool = False        # SWA decode: slot=pos%W insert
                                         # instead of shift-concat (which
                                         # copies + reshards the whole cache
                                         # every step)
    decode_no_fsdp: bool = False         # decode cells: keep weights fully
                                         # sharded (ff over model+data)
                                         # instead of FSDP-gathering the full
                                         # weight per layer for a 1-token step

    # -- derived -------------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return pad_to_multiple(self.vocab_size, self.vocab_pad_multiple)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def d_inner(self) -> int:
        return round(self.ssm_expand * self.d_model)

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def d_inner_hybrid(self) -> int:
        """Hybrid (hymba) SSM branch width == attention branch width, so the
        normalized branch outputs fuse elementwise."""
        return self.n_heads * self.hd

    def moe_capacity(self, group_tokens: int) -> int:
        c = math.ceil(group_tokens * self.experts_per_token *
                      self.capacity_factor / self.n_experts)
        return max(8, pad_to_multiple(c, 8))


# ---------------------------------------------------------------------------
# Param definitions
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple
    names: tuple                 # logical axis names (see parallel.sharding)
    init: str = "normal"         # normal | zeros | ones
    scale: float = 0.02
    dtype: Any = jnp.float32


def init_param(key: jax.Array, d: ParamDef) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init == "normal":
        return (jax.random.normal(key, d.shape, jnp.float32) * d.scale
                ).astype(d.dtype)
    if d.init == "ssm_a":  # mamba A_log init: log of Uniform[1, 16]
        u = jax.random.uniform(key, d.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(d.dtype)
    if d.init == "ssm_dt":  # dt bias init: softplus^-1 of Uniform[1e-3, 1e-1]
        u = jax.random.uniform(key, d.shape, jnp.float32, 1e-3, 1e-1)
        return jnp.log(jnp.expm1(u)).astype(d.dtype)
    raise ValueError(f"unknown init {d.init!r}")


class BaseModel:
    """Shared init machinery."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # subclasses provide --------------------------------------------------
    def param_defs(self) -> dict:
        raise NotImplementedError

    def loss(self, params, batch):
        raise NotImplementedError

    # shared ----------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        defs = self.param_defs()
        params = {}
        for i, (name, d) in enumerate(sorted(defs.items())):
            params[name] = init_param(jax.random.fold_in(key, i), d)
        return params

    def param_count(self) -> int:
        import numpy as np
        return int(sum(np.prod(d.shape) for d in self.param_defs().values()))

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k of the experts)."""
        import numpy as np
        cfg = self.cfg
        total = 0
        for name, d in self.param_defs().items():
            n = int(np.prod(d.shape))
            if cfg.is_moe and ".experts." in name:
                n = n * cfg.experts_per_token // cfg.n_experts
            total += n
        return total
