"""Decoder-only transformer LM covering the dense, MoE and VLM-backbone
families (llama3/3.2, minicpm, chatglm3, internvl2's InternLM2, mixtral,
moonshot) and the encoder family (hubert) via ``causal=False``.

Structure per layer (pre-norm):
    x += attn(rmsnorm(x))          # GQA + RoPE (+ optional SWA, qkv bias)
    x += ffn(rmsnorm(x))           # SwiGLU, or MoE top-k routed SwiGLU

Layer params are stacked on a leading L dim and executed with ``lax.scan``
(+ optional ``jax.checkpoint``), keeping HLO one-layer-sized.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..parallel.sharding import constrain
from .layers import (attention, apply_rope, decode_attention, moe_ffn,
                     rmsnorm, swiglu)
from .losses import lm_cross_entropy
from .model_api import BaseModel, ModelConfig, ParamDef


def _remat_policy(cfg: ModelConfig):
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return jax.checkpoint_policies.nothing_saveable


class DecoderLM(BaseModel):
    """Dense / MoE / VLM-backbone decoder (and bidirectional encoder)."""

    # ------------------------------------------------------------- params --
    def param_defs(self) -> dict:
        cfg = self.cfg
        L, M, V = cfg.n_layers, cfg.d_model, cfg.padded_vocab
        HD, Hq, Hkv, F = cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
        defs: dict[str, ParamDef] = {
            "embed.w": ParamDef((V, M), ("vocab", "embed")),
            "final_norm.w": ParamDef((M,), (None,), init="ones"),
        }
        if not cfg.tie_embeddings:
            defs["head.w"] = ParamDef((M, V), ("embed", "vocab"))
        lyr = {
            "attn_norm.w": ParamDef((L, M), ("layers", None), init="ones"),
            "attn.wq": ParamDef((L, M, Hq * HD), ("layers", "embed", "heads")),
            "attn.wk": ParamDef((L, M, Hkv * HD), ("layers", "embed", "kv_heads")),
            "attn.wv": ParamDef((L, M, Hkv * HD), ("layers", "embed", "kv_heads")),
            "attn.wo": ParamDef((L, Hq * HD, M), ("layers", "heads", "embed")),
            "mlp_norm.w": ParamDef((L, M), ("layers", None), init="ones"),
        }
        if cfg.qkv_bias:
            lyr["attn.bq"] = ParamDef((L, Hq * HD), ("layers", "heads"), init="zeros")
            lyr["attn.bk"] = ParamDef((L, Hkv * HD), ("layers", "kv_heads"), init="zeros")
            lyr["attn.bv"] = ParamDef((L, Hkv * HD), ("layers", "kv_heads"), init="zeros")
        if cfg.is_moe:
            E = cfg.n_experts
            lyr.update({
                "moe.router": ParamDef((L, M, E), ("layers", "embed", None)),
                "moe.experts.w1": ParamDef((L, E, M, F),
                                           ("layers", "expert", "embed", "ff")),
                "moe.experts.w3": ParamDef((L, E, M, F),
                                           ("layers", "expert", "embed", "ff")),
                "moe.experts.w2": ParamDef((L, E, F, M),
                                           ("layers", "expert", "ff", "embed")),
            })
        else:
            lyr.update({
                "mlp.w1": ParamDef((L, M, F), ("layers", "embed", "ff")),
                "mlp.w3": ParamDef((L, M, F), ("layers", "embed", "ff")),
                "mlp.w2": ParamDef((L, F, M), ("layers", "ff", "embed")),
            })
        defs.update({f"layers.{k}": v for k, v in lyr.items()})
        return defs

    # ------------------------------------------------------------ forward --
    def _layer(self, p: dict, x: jax.Array, *, positions, layer_window,
               want_kv: bool = False):
        """One decoder layer (full-sequence path).  Returns (x, kv) where kv
        is the (k, v) cache contribution when ``want_kv`` else None."""
        cfg = self.cfg
        B, S, M = x.shape
        Hq, Hkv, HD = cfg.n_heads, cfg.n_kv_heads, cfg.hd

        h = rmsnorm(x, p["attn_norm.w"], cfg.norm_eps)
        q = h @ p["attn.wq"].astype(h.dtype)
        k = h @ p["attn.wk"].astype(h.dtype)
        v = h @ p["attn.wv"].astype(h.dtype)
        if cfg.qkv_bias:
            q = q + p["attn.bq"].astype(h.dtype)
            k = k + p["attn.bk"].astype(h.dtype)
            v = v + p["attn.bv"].astype(h.dtype)
        q = q.reshape(B, S, Hq, HD)
        k = k.reshape(B, S, Hkv, HD)
        v = v.reshape(B, S, Hkv, HD)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
        q = constrain(q, "batch", "seq", "act_heads", None)
        k = constrain(k, "batch", "seq", "act_heads", None)

        qT = q.transpose(0, 2, 1, 3)
        kT = k.transpose(0, 2, 1, 3)
        vT = v.transpose(0, 2, 1, 3)

        pos = positions if positions.ndim == 1 else positions[0]
        o = attention(qT, kT, vT, q_pos=pos, k_pos=pos,
                      causal=cfg.causal, window=layer_window,
                      dense_max_seq=cfg.dense_attn_max_seq,
                      chunk=cfg.attn_chunk,
                      block_skip=cfg.swa_block_skip)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, Hq * HD)
        x = x + (o @ p["attn.wo"].astype(o.dtype))

        h = rmsnorm(x, p["mlp_norm.w"], cfg.norm_eps)
        if cfg.is_moe:
            y = moe_ffn(h, p["moe.router"], p["moe.experts.w1"],
                        p["moe.experts.w3"], p["moe.experts.w2"],
                        n_experts=cfg.n_experts,
                        top_k=cfg.experts_per_token,
                        capacity=cfg.moe_capacity(S))
        else:
            y = swiglu(h, p["mlp.w1"].astype(h.dtype),
                       p["mlp.w3"].astype(h.dtype),
                       p["mlp.w2"].astype(h.dtype))
        x = x + y
        x = constrain(x, "batch", "seq", "act_embed")
        return x, ((kT, vT) if want_kv else None)

    def _split_params(self, params: dict) -> tuple[dict, dict]:
        stacked = {k[len("layers."):]: v for k, v in params.items()
                   if k.startswith("layers.")}
        top = {k: v for k, v in params.items() if not k.startswith("layers.")}
        return top, stacked

    def _embed_inputs(self, params: dict, batch: dict) -> jax.Array:
        """Token embeddings, with the VLM/audio stub frontends spliced in."""
        cfg = self.cfg
        if cfg.frontend == "frames":
            return batch["frames"].astype(jnp.bfloat16)
        emb = params["embed.w"]
        x = jnp.take(emb, batch["tokens"], axis=0).astype(jnp.bfloat16)
        if cfg.frontend == "patch" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(jnp.bfloat16)
            x = jnp.concatenate([pe, x[:, pe.shape[1]:]], axis=1)
        return x

    def _hidden(self, params: dict, batch: dict):
        """Backbone -> (final-normed hidden (B,S,M), LM head (M,V))."""
        cfg = self.cfg
        top, stacked = self._split_params(params)
        x = self._embed_inputs(params, batch)
        x = constrain(x, "batch", "seq", "act_embed")
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)

        layer_fn = functools.partial(self._layer, positions=positions,
                                     layer_window=cfg.window)
        if cfg.remat:
            layer_fn = jax.checkpoint(layer_fn, policy=_remat_policy(cfg),
                                      static_argnums=())

        def body(carry, lp):
            out, _ = layer_fn(lp, carry)
            return out, None
        x, _ = jax.lax.scan(body, x, stacked)

        x = rmsnorm(x, top["final_norm.w"], cfg.norm_eps)
        head = (top["embed.w"].T if cfg.tie_embeddings else top["head.w"])
        return x, head

    def forward(self, params: dict, batch: dict) -> jax.Array:
        """Full-sequence forward -> logits (B, S, V)."""
        x, head = self._hidden(params, batch)
        logits = x @ head.astype(x.dtype)
        return constrain(logits, "batch", "seq", "vocab")

    # --------------------------------------------------------------- loss --
    def loss(self, params: dict, batch: dict):
        cfg = self.cfg
        targets = batch["targets"]
        mask = None
        if cfg.frontend == "patch":  # VLM: patch positions carry no LM loss
            mask = jnp.ones(targets.shape, jnp.float32
                            ).at[:, :cfg.n_patches].set(0.0)
        if cfg.logits_chunk > 1:
            loss = self._chunked_ce(params, batch, targets, mask)
        else:
            logits = self.forward(params, batch)
            loss = lm_cross_entropy(logits, targets, onehot=cfg.ce_onehot,
                                    mask=mask)
        return loss, {"loss": loss, "ppl_proxy": jnp.exp(
            jnp.clip(loss, max=20.0))}

    def _chunked_ce(self, params, batch, targets, mask):
        """Sequence-chunked cross-entropy: only one (B, S/K, V) logits chunk
        is ever live (the full fp32 (B,S,V) is the largest train temp).
        §Perf knob `logits_chunk`."""
        cfg = self.cfg
        x, head = self._hidden(params, batch)          # (B,S,M)
        B, S, M = x.shape
        K = cfg.logits_chunk
        if S % K:
            raise ValueError(f"seq {S} not divisible by logits_chunk {K}")
        cs = S // K
        xc = x.reshape(B, K, cs, M).transpose(1, 0, 2, 3)
        tc = targets.reshape(B, K, cs).transpose(1, 0, 2)
        mc = (mask.reshape(B, K, cs).transpose(1, 0, 2) if mask is not None
              else jnp.ones((K, B, cs), jnp.float32))
        headc = head.astype(x.dtype)

        def one(carry, inp):
            xi, ti, mi = inp
            logits = xi @ headc
            nll_sum = lm_cross_entropy(logits, ti, onehot=cfg.ce_onehot,
                                       mask=mi) * jnp.maximum(mi.sum(), 1.0)
            tot, cnt = carry
            return (tot + nll_sum, cnt + mi.sum()), None

        chunk_fn = one
        if cfg.remat:
            chunk_fn = jax.checkpoint(one)
        (tot, cnt), _ = jax.lax.scan(chunk_fn, (jnp.zeros((), jnp.float32),
                                                jnp.zeros((), jnp.float32)),
                                     (xc, tc, mc))
        return tot / jnp.maximum(cnt, 1.0)

    # -------------------------------------------------------------- serve --
    def prefill(self, params: dict, batch: dict, max_len: int | None = None):
        """Returns (last-token logits, populated KV cache).

        The cache is padded to ``max_len`` (default prompt + 64) so decode
        steps have insertion headroom; SWA archs whose prompt exceeds the
        window get a rolling window-sized buffer instead.
        """
        cfg = self.cfg
        top, stacked = self._split_params(params)
        x = self._embed_inputs(params, batch)
        S = x.shape[1]
        max_len = max_len or S + 64
        positions = jnp.arange(S, dtype=jnp.int32)
        rolling = cfg.window is not None and S >= cfg.window

        def body(carry, lp):
            out, kv = self._layer(lp, carry, positions=positions,
                                  layer_window=cfg.window, want_kv=True)
            k, v = kv
            if rolling:
                k = k[:, :, -cfg.window:]   # rolling SWA buffer
                v = v[:, :, -cfg.window:]
                if cfg.swa_ring_buffer:
                    # slot invariant: position p lives at slot p % W
                    shift = S % cfg.window
                    k = jnp.roll(k, shift, axis=2)
                    v = jnp.roll(v, shift, axis=2)
            elif max_len > S:               # insertion headroom
                pad = [(0, 0), (0, 0), (0, max_len - S), (0, 0)]
                k = jnp.pad(k, pad)
                v = jnp.pad(v, pad)
            return out, (k, v)

        x, (ks, vs) = jax.lax.scan(body, x, stacked)
        x = rmsnorm(x, top["final_norm.w"], cfg.norm_eps)
        head = (top["embed.w"].T if cfg.tie_embeddings else top["head.w"])
        logits = x[:, -1:] @ head.astype(x.dtype)
        cache = {"k": ks, "v": vs, "pos": jnp.full((), S, jnp.int32)}
        return logits, cache

    def init_cache(self, batch_size: int, max_len: int):
        cfg = self.cfg
        eff = min(max_len, cfg.window) if cfg.window else max_len
        shape = (cfg.n_layers, batch_size, cfg.n_kv_heads, eff, cfg.hd)
        return {"k": jnp.zeros(shape, jnp.bfloat16),
                "v": jnp.zeros(shape, jnp.bfloat16),
                "pos": jnp.zeros((), jnp.int32)}

    def decode_step(self, params: dict, tokens: jax.Array, cache: dict):
        """One-token decode.  tokens: (B, 1).  SWA archs use a rolling
        window buffer (shift-left insert); full-attention archs use a
        positional insert at ``pos``."""
        cfg = self.cfg
        top, stacked = self._split_params(params)
        B = tokens.shape[0]
        pos = cache["pos"]
        x = jnp.take(top["embed.w"], tokens, axis=0).astype(jnp.bfloat16)
        positions = jnp.broadcast_to(pos[None], (1,)).astype(jnp.int32)

        eff = cache["k"].shape[3]
        rolling = cfg.window is not None and eff == cfg.window

        def body(carry, lp_kv):
            lp, (k_c, v_c) = lp_kv
            h = rmsnorm(carry, lp["attn_norm.w"], cfg.norm_eps)
            q = h @ lp["attn.wq"].astype(h.dtype)
            k = h @ lp["attn.wk"].astype(h.dtype)
            v = h @ lp["attn.wv"].astype(h.dtype)
            if cfg.qkv_bias:
                q = q + lp["attn.bq"].astype(h.dtype)
                k = k + lp["attn.bk"].astype(h.dtype)
                v = v + lp["attn.bv"].astype(h.dtype)
            q = q.reshape(B, 1, cfg.n_heads, cfg.hd)
            k = k.reshape(B, 1, cfg.n_kv_heads, cfg.hd)
            v = v.reshape(B, 1, cfg.n_kv_heads, cfg.hd)
            q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
            kT = k.transpose(0, 2, 1, 3)
            vT = v.transpose(0, 2, 1, 3)
            if rolling and cfg.swa_ring_buffer:
                # ring buffer: write slot pos%W; the shift-concat variant
                # copies (and under kv_seq sharding RESHARDS) the whole
                # cache every token — see EXPERIMENTS.md §Perf (mixtral)
                slot = jnp.mod(pos, eff)
                k_c = jax.lax.dynamic_update_slice_in_dim(k_c, kT, slot,
                                                          axis=2)
                v_c = jax.lax.dynamic_update_slice_in_dim(v_c, vT, slot,
                                                          axis=2)
                slots = jnp.arange(eff)
                slot_pos = pos - jnp.mod(pos - slots, eff)
                valid = slot_pos >= 0    # in (pos-W, pos] by construction
            elif rolling:
                k_c = jnp.concatenate([k_c[:, :, 1:], kT], axis=2)
                v_c = jnp.concatenate([v_c[:, :, 1:], vT], axis=2)
                n_valid = jnp.minimum(pos + 1, eff)
                valid = jnp.arange(eff) >= (eff - n_valid)
            else:
                k_c = jax.lax.dynamic_update_slice_in_dim(k_c, kT, pos, axis=2)
                v_c = jax.lax.dynamic_update_slice_in_dim(v_c, vT, pos, axis=2)
                valid = jnp.arange(eff) <= pos
            o = decode_attention(q.transpose(0, 2, 1, 3), k_c, v_c,
                                 valid_mask=valid)
            o = o.transpose(0, 2, 1, 3).reshape(B, 1, cfg.n_heads * cfg.hd)
            carry = carry + o @ lp["attn.wo"].astype(o.dtype)
            h = rmsnorm(carry, lp["mlp_norm.w"], cfg.norm_eps)
            if cfg.decode_no_fsdp:
                # replicate the (tiny) token activations so the FFN runs
                # against fully-sharded weights with NO weight all-gather;
                # the batch axis conflict otherwise makes GSPMD gather the
                # FSDP factor of every expert weight per layer per token
                h = constrain(h, None, None, None)
            if cfg.is_moe:
                y = moe_ffn(h, lp["moe.router"], lp["moe.experts.w1"],
                            lp["moe.experts.w3"], lp["moe.experts.w2"],
                            n_experts=cfg.n_experts,
                            top_k=cfg.experts_per_token,
                            capacity=cfg.moe_capacity(1),
                            shard_acts=not cfg.decode_no_fsdp)
            else:
                y = swiglu(h, lp["mlp.w1"].astype(h.dtype),
                           lp["mlp.w3"].astype(h.dtype),
                           lp["mlp.w2"].astype(h.dtype),
                           shard_acts=not cfg.decode_no_fsdp)
            if cfg.decode_no_fsdp:
                y = constrain(y, "batch", None, None)
            return carry + y, (k_c, v_c)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (stacked, (cache["k"], cache["v"])))
        x = rmsnorm(x, top["final_norm.w"], cfg.norm_eps)
        head = (top["embed.w"].T if cfg.tie_embeddings else top["head.w"])
        logits = x @ head.astype(x.dtype)
        new_cache = {"k": new_k, "v": new_v, "pos": pos + 1}
        return logits, new_cache
