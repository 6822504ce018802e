"""Falcon-H1 (tiiuae, 2025): every decoder layer runs a Mamba-2 mixer and
GQA attention side by side on one normalized input and adds both outputs to
the residual, then a SwiGLU MLP.  The muP multipliers are applied as
published (transformers' ``modeling_falcon_h1``):

    h = rmsnorm(x)
    x = x + mixer(h) * ssm_out + attn(h * attn_in) * attn_out
    x = x + mlp(rmsnorm(x))

The mixer is mamba2's (``mixer_full`` / ``mixer_step``) with
``cfg.ssm_groups`` B/C groups and a gated RMSNorm per group; keys are scaled
by ``key_multiplier`` before RoPE (``rotate_half`` convention).  Serving
keeps, per layer, a growing KV cache beside the fixed-size conv and SSD
state.  Weights are stored in bfloat16; layers are stacked and run by
``lax.scan``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..parallel.sharding import constrain, logical_sharding
from .layers import attention, decode_attention, rmsnorm
from .losses import lm_cross_entropy
from .mamba2 import mixer_full, mixer_param_defs, mixer_step, mixer_widths
from .model_api import BaseModel, ParamDef

BF16 = jnp.bfloat16
F32 = jnp.float32
ATTN_BLOCK = 256        # the Pallas flash kernel's (bq, bk): prompts pad to it


def rope(x, positions, theta: float):
    """RoPE on ``x`` (B, S, H, D) at ``positions`` (S,), rotating the two
    halves of each head (``rotate_half``), in float32."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None]           # (S, D/2)
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None]   # (1,S,1,D)
    xf = x.astype(F32)
    rot = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], axis=-1)
    return (xf * jnp.cos(ang) + rot * jnp.sin(ang)).astype(x.dtype)


def _scale(x, m: float):
    return x if m == 1.0 else x * m


def _mm(x, w):
    """``x @ w`` in ``x``'s dtype; a no-op cast for weights stored so."""
    return x @ w.astype(x.dtype)


class FalconH1LM(BaseModel):
    # ------------------------------------------------------------- params --
    def param_defs(self) -> dict:
        cfg = self.cfg
        L, M, V, F = cfg.n_layers, cfg.d_model, cfg.padded_vocab, cfg.d_ff
        Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        defs = {
            "embed.w": ParamDef((V, M), ("vocab", "embed"), dtype=BF16),
            "final_norm.w": ParamDef((M,), (None,), init="ones"),
            "head.w": ParamDef((M, V), ("embed", "vocab"), dtype=BF16),
        }
        lyr = {
            "norm.w": ParamDef((L, M), ("layers", None), init="ones"),
            "attn.wq": ParamDef((L, M, Hq * D), ("layers", "embed", "heads"),
                                dtype=BF16),
            "attn.wk": ParamDef((L, M, Hkv * D),
                                ("layers", "embed", "kv_heads"), dtype=BF16),
            "attn.wv": ParamDef((L, M, Hkv * D),
                                ("layers", "embed", "kv_heads"), dtype=BF16),
            "attn.wo": ParamDef((L, Hq * D, M), ("layers", "heads", "embed"),
                                dtype=BF16),
            "mlp_norm.w": ParamDef((L, M), ("layers", None), init="ones"),
            "mlp.gate": ParamDef((L, M, F), ("layers", "embed", "ff"),
                                 dtype=BF16),
            "mlp.up": ParamDef((L, M, F), ("layers", "embed", "ff"),
                               dtype=BF16),
            "mlp.down": ParamDef((L, F, M), ("layers", "ff", "embed"),
                                 dtype=BF16),
        }
        defs.update({f"layers.{k}": v for k, v in lyr.items()})
        defs.update(mixer_param_defs(cfg, dtype=BF16))
        return defs

    # -------------------------------------------------------------- parts --
    def _qkv(self, lp, h, positions):
        """h: (B, S, M) -> q (B, S, Hq, D), k and v (B, S, Hkv, D), RoPE'd."""
        cfg = self.cfg
        B, S, _ = h.shape
        h = _scale(h, cfg.attention_in_multiplier)
        q = _mm(h, lp["attn.wq"]).reshape(B, S, cfg.n_heads, cfg.hd)
        k = _mm(h, lp["attn.wk"]).reshape(B, S, cfg.n_kv_heads, cfg.hd)
        v = _mm(h, lp["attn.wv"]).reshape(B, S, cfg.n_kv_heads, cfg.hd)
        k = _scale(k, cfg.key_multiplier)
        return (rope(q, positions, cfg.rope_theta),
                rope(k, positions, cfg.rope_theta), v)

    def _attn_full(self, lp, h, kernel: bool):
        """Causal GQA over the whole sequence.  ``kernel`` (serving) runs
        ``ops.flash_attention`` (Pallas on a TPU), padded to its block;
        otherwise (``forward``, which training differentiates: the Pallas
        kernel has no gradient) ``layers.attention``.  Returns (out
        (B, S, M), (k, v) as (B, Hkv, S, D))."""
        cfg = self.cfg
        B, S, _ = h.shape
        positions = jnp.arange(S, dtype=jnp.int32)
        q, k, v = (t.transpose(0, 2, 1, 3)
                   for t in self._qkv(lp, h, positions))
        if kernel:
            pad = ((0, 0), (0, 0), (0, (-S) % ATTN_BLOCK), (0, 0))
            o = ops.flash_attention(*(jnp.pad(t, pad) for t in (q, k, v)),
                                    causal=True)[:, :, :S]
        else:
            o = attention(q, k, v, q_pos=positions, k_pos=positions,
                          causal=True, dense_max_seq=cfg.dense_attn_max_seq,
                          chunk=cfg.attn_chunk)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.hd)
        return _mm(o, lp["attn.wo"]), (k, v)

    def _mlp(self, lp, x):
        cfg = self.cfg
        h = rmsnorm(x, lp["mlp_norm.w"], cfg.norm_eps)
        gate = _scale(_mm(h, lp["mlp.gate"]).astype(F32),
                      cfg.mlp_multipliers[0])
        y = (jax.nn.silu(gate) * _mm(h, lp["mlp.up"])).astype(h.dtype)
        return _scale(_mm(y, lp["mlp.down"]), cfg.mlp_multipliers[1])

    def _mixers(self, x, ssm, attn):
        """Residual plus both mixers' outputs, each times its multiplier,
        summed in float32."""
        cfg = self.cfg
        return (x.astype(F32) + _scale(ssm.astype(F32), cfg.ssm_out_multiplier)
                + _scale(attn.astype(F32), cfg.attention_out_multiplier)
                ).astype(x.dtype)

    def _plus_mlp(self, lp, x):
        with jax.named_scope("mlp"):
            return (x.astype(F32) + self._mlp(lp, x).astype(F32)
                    ).astype(x.dtype)

    def _layer_full(self, lp, x, kernel: bool = False):
        """One layer over a whole sequence.  Returns (x, ((k, v),
        (conv_state, ssd_state)))."""
        cfg = self.cfg
        h = rmsnorm(x, lp["norm.w"], cfg.norm_eps)
        with jax.named_scope("ssm"):
            ssm, state = mixer_full(cfg, lp, h, want_state=True)
        with jax.named_scope("attn"):
            attn, kv = self._attn_full(lp, h, kernel)
        x = self._plus_mlp(lp, self._mixers(x, ssm, attn))
        return constrain(x, "batch", "seq", "act_embed"), (kv, state)

    def _embed(self, params, tokens):
        x = jnp.take(params["embed.w"], tokens, axis=0).astype(F32)
        return _scale(x, self.cfg.embedding_multiplier).astype(BF16)

    def _head(self, params, x):
        cfg = self.cfg
        with jax.named_scope("head"):
            x = rmsnorm(x, params["final_norm.w"], cfg.norm_eps)
            logits = jnp.dot(x, params["head.w"].astype(x.dtype),
                             preferred_element_type=F32)
            return _scale(logits, cfg.lm_head_multiplier)

    @staticmethod
    def _stacked(params) -> dict:
        return {k[len("layers."):]: v for k, v in params.items()
                if k.startswith("layers.")}

    # ------------------------------------------------------------ forward --
    def forward(self, params, batch):
        x = self._embed(params, batch["tokens"])
        x = constrain(x, "batch", "seq", "act_embed")
        layer = self._layer_full
        if self.cfg.remat:
            layer = jax.checkpoint(
                layer, policy=jax.checkpoint_policies.nothing_saveable)

        def body(carry, lp):
            out, _ = layer(lp, carry)
            return out, None

        x, _ = jax.lax.scan(body, x, self._stacked(params))
        return constrain(self._head(params, x), "batch", "seq", "vocab")

    def loss(self, params, batch):
        logits = self.forward(params, batch)
        loss = lm_cross_entropy(logits, batch["targets"],
                                onehot=self.cfg.ce_onehot)
        return loss, {"loss": loss}

    # -------------------------------------------------------------- serve --
    def init_cache(self, batch_size: int, max_len: int, abstract=False):
        cfg = self.cfg
        L, H, N, P = (cfg.n_layers, cfg.ssm_heads, cfg.ssm_state,
                      cfg.ssm_head_dim)
        conv_dim, _ = mixer_widths(cfg)
        kv = ((L, batch_size, cfg.n_kv_heads, max_len, cfg.hd),
              ("layers", "batch", "kv_heads", "kv_seq", None), BF16)
        shapes = {
            "k": kv, "v": kv,
            "conv": ((L, batch_size, cfg.ssm_conv - 1, conv_dim),
                     ("layers", "batch", None, "ff"), BF16),
            "ssd": ((L, batch_size, H, N, P),
                    ("layers", "batch", None, None, None), F32),
            "pos": ((), (), jnp.int32),
        }
        out = {}
        for name, (shape, names, dtype) in shapes.items():
            if abstract:
                sh = logical_sharding(shape, names) if shape else None
                out[name] = jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
            else:
                out[name] = jnp.zeros(shape, dtype)
        return out

    def prefill(self, params, batch, max_len: int | None = None):
        """Run the prompt; return the last position's logits and a cache
        whose KV holds ``max_len`` positions (the prompt's first)."""
        B, S = batch["tokens"].shape
        max_len = max_len or S
        pad = ((0, 0), (0, 0), (0, max_len - S), (0, 0))

        def body(carry, lp):
            x, ((k, v), (conv, ssd)) = self._layer_full(lp, carry,
                                                         kernel=True)
            return x, (jnp.pad(k, pad), jnp.pad(v, pad), conv, ssd)

        x, (k, v, conv, ssd) = jax.lax.scan(
            body, self._embed(params, batch["tokens"]), self._stacked(params))
        cache = {"k": k.astype(BF16), "v": v.astype(BF16), "conv": conv,
                 "ssd": ssd.astype(F32), "pos": jnp.full((), S, jnp.int32)}
        return self._head(params, x[:, -1:]), cache

    def decode_step(self, params, tokens, cache):
        """One token per sequence: writes its K and V at ``pos``, attends
        over positions <= ``pos`` and steps the SSM state."""
        cfg = self.cfg
        B = tokens.shape[0]
        pos = cache["pos"]
        visible = jnp.arange(cache["k"].shape[3]) <= pos
        x = self._embed(params, tokens[:, 0])                 # (B, M)

        def body(carry, xs):
            x, k_all, v_all = carry
            i, lp, conv_c, ssd_c = xs
            h = rmsnorm(x, lp["norm.w"], cfg.norm_eps)
            with jax.named_scope("ssm"):
                ssm, state = mixer_step(cfg, lp, h, conv_c, ssd_c)
            with jax.named_scope("attn"):
                q, k, v = self._qkv(lp, h[:, None], pos[None])
                at = (i, 0, 0, pos, 0)
                k_all = jax.lax.dynamic_update_slice(
                    k_all, k.transpose(0, 2, 1, 3)[None].astype(BF16), at)
                v_all = jax.lax.dynamic_update_slice(
                    v_all, v.transpose(0, 2, 1, 3)[None].astype(BF16), at)
                o = decode_attention(q.transpose(0, 2, 1, 3), k_all[i],
                                     v_all[i], valid_mask=visible)
                attn = _mm(o.reshape(B, cfg.n_heads * cfg.hd), lp["attn.wo"])
            x = self._plus_mlp(lp, self._mixers(x, ssm, attn))
            return (x, k_all, v_all), state

        (x, k, v), (conv, ssd) = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (jnp.arange(cfg.n_layers), self._stacked(params), cache["conv"],
             cache["ssd"]))
        return self._head(params, x)[:, None], {
            "k": k, "v": v, "conv": conv, "ssd": ssd, "pos": pos + 1}
