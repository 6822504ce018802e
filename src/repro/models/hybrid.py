"""Hymba-style hybrid-head model (arXiv:2411.13676): every layer runs
attention heads and Mamba/SSD heads **in parallel** on the same input and
fuses their (normalized) outputs.  Most layers use sliding-window attention;
``cfg.global_layers`` (3 of 32 in hymba-1.5b) keep full attention.

Adaptations noted in DESIGN.md: meta-tokens and cross-layer KV sharing are
omitted (orthogonal to the backbone compute shape); fusion uses learnable
per-dim scales beta_attn/beta_ssm on RMS-normalized branch outputs.

Layers are heterogeneous (global vs SWA cache shapes), so the stack is a
Python loop rather than ``lax.scan`` — at d_model=1600 the HLO stays small.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..parallel.sharding import constrain
from .layers import (apply_rope, attention, decode_attention, rmsnorm,
                     swiglu)
from .losses import lm_cross_entropy
from .mamba2 import ssd_chunked, ssd_decode_step
from .model_api import BaseModel, ModelConfig, ParamDef


class HymbaLM(BaseModel):
    # ------------------------------------------------------------- params --
    def param_defs(self) -> dict:
        cfg = self.cfg
        L, M, V = cfg.n_layers, cfg.d_model, cfg.padded_vocab
        HD, Hq, Hkv, F = cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
        DI, N = cfg.d_inner_hybrid, cfg.ssm_state
        conv_dim = DI + 2 * N
        H = DI // cfg.ssm_head_dim
        defs = {
            "embed.w": ParamDef((V, M), ("vocab", "embed")),
            "final_norm.w": ParamDef((M,), (None,), init="ones"),
            "head.w": ParamDef((M, V), ("embed", "vocab")),
        }
        lyr = {
            "norm.w": ParamDef((L, M), ("layers", None), init="ones"),
            # attention branch
            "attn.wq": ParamDef((L, M, Hq * HD), ("layers", "embed", "heads")),
            "attn.wk": ParamDef((L, M, Hkv * HD), ("layers", "embed", "kv_heads")),
            "attn.wv": ParamDef((L, M, Hkv * HD), ("layers", "embed", "kv_heads")),
            # ssm branch
            "ssm.in_proj": ParamDef((L, M, 2 * DI + 2 * N + H),
                                    ("layers", "embed", "ff")),
            "ssm.conv.w": ParamDef((L, cfg.ssm_conv, conv_dim),
                                   ("layers", None, "ff")),
            "ssm.conv.b": ParamDef((L, conv_dim), ("layers", "ff"),
                                   init="zeros"),
            "ssm.a_log": ParamDef((L, H), ("layers", None), init="ssm_a"),
            "ssm.d_skip": ParamDef((L, H), ("layers", None), init="ones"),
            "ssm.dt_bias": ParamDef((L, H), ("layers", None), init="ssm_dt"),
            # fusion + output
            "fuse.attn_norm": ParamDef((L, Hq * HD), ("layers", None), init="ones"),
            "fuse.ssm_norm": ParamDef((L, DI), ("layers", None), init="ones"),
            "fuse.beta_attn": ParamDef((L, Hq * HD), ("layers", None), init="ones"),
            "fuse.beta_ssm": ParamDef((L, DI), ("layers", None), init="ones"),
            "attn.wo": ParamDef((L, Hq * HD, M), ("layers", "heads", "embed")),
            # mlp
            "mlp_norm.w": ParamDef((L, M), ("layers", None), init="ones"),
            "mlp.w1": ParamDef((L, M, F), ("layers", "embed", "ff")),
            "mlp.w3": ParamDef((L, M, F), ("layers", "embed", "ff")),
            "mlp.w2": ParamDef((L, F, M), ("layers", "ff", "embed")),
        }
        defs.update({f"layers.{k}": v for k, v in lyr.items()})
        return defs

    def _lp(self, params: dict, i: int) -> dict:
        return {k[len("layers."):]: v[i] for k, v in params.items()
                if k.startswith("layers.")}

    def _window(self, layer_idx: int) -> int | None:
        return None if layer_idx in self.cfg.global_layers else self.cfg.window

    # -------------------------------------------------------------- layer --
    def _ssm_branch_full(self, lp, h):
        cfg = self.cfg
        B, S, _ = h.shape
        DI, N = cfg.d_inner_hybrid, cfg.ssm_state
        P = cfg.ssm_head_dim
        H = DI // P
        proj = h @ lp["ssm.in_proj"].astype(h.dtype)
        z = proj[..., :DI]
        xs = proj[..., DI:2 * DI]
        b = proj[..., 2 * DI:2 * DI + N]
        c = proj[..., 2 * DI + N:2 * DI + 2 * N]
        dt = proj[..., 2 * DI + 2 * N:]
        xbc = jnp.concatenate([xs, b, c], axis=-1)
        w = lp["ssm.conv.w"].astype(xbc.dtype)
        K = w.shape[0]
        pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + S] * w[i][None, None] for i in range(K))
        conv = jax.nn.silu(conv + lp["ssm.conv.b"].astype(conv.dtype))
        xs, b, c = conv[..., :DI], conv[..., DI:DI + N], conv[..., DI + N:]
        dt = jax.nn.softplus(dt.astype(jnp.float32) +
                             lp["ssm.dt_bias"].astype(jnp.float32))
        y, final = ssd_chunked(xs.reshape(B, S, H, P), dt, lp["ssm.a_log"],
                               b[:, :, None], c[:, :, None], lp["ssm.d_skip"],
                               chunk=min(cfg.ssm_chunk, S),
                               shard_acts=cfg.ssd_shard_acts)
        y = y.reshape(B, S, DI) * jax.nn.silu(
            z.astype(jnp.float32)).astype(y.dtype)
        conv_state = xbc[:, -(K - 1):].astype(jnp.bfloat16)
        return y, (conv_state, final)

    def _attn_branch_full(self, lp, h, positions, window):
        cfg = self.cfg
        B, S, _ = h.shape
        Hq, Hkv, HD = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        q = (h @ lp["attn.wq"].astype(h.dtype)).reshape(B, S, Hq, HD)
        k = (h @ lp["attn.wk"].astype(h.dtype)).reshape(B, S, Hkv, HD)
        v = (h @ lp["attn.wv"].astype(h.dtype)).reshape(B, S, Hkv, HD)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        qT, kT, vT = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        o = attention(qT, kT, vT, q_pos=positions, k_pos=positions,
                      causal=True, window=window,
                      dense_max_seq=cfg.dense_attn_max_seq,
                      chunk=cfg.attn_chunk,
                      block_skip=cfg.swa_block_skip)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, Hq * HD)
        return o, (kT, vT)

    def _fuse(self, lp, attn_out, ssm_out):
        cfg = self.cfg
        a = rmsnorm(attn_out, lp["fuse.attn_norm"], cfg.norm_eps)
        s = rmsnorm(ssm_out, lp["fuse.ssm_norm"], cfg.norm_eps)
        a = a * lp["fuse.beta_attn"].astype(a.dtype)
        s = s * lp["fuse.beta_ssm"].astype(s.dtype)
        return 0.5 * (a + s)

    def _layer_full(self, lp, x, positions, window, want_state=False):
        cfg = self.cfg
        h = rmsnorm(x, lp["norm.w"], cfg.norm_eps)
        attn_out, kv = self._attn_branch_full(lp, h, positions, window)
        ssm_out, state = self._ssm_branch_full(lp, h)
        fused = self._fuse(lp, attn_out, ssm_out)
        x = x + fused @ lp["attn.wo"].astype(fused.dtype)
        h2 = rmsnorm(x, lp["mlp_norm.w"], cfg.norm_eps)
        x = x + swiglu(h2, lp["mlp.w1"].astype(h2.dtype),
                       lp["mlp.w3"].astype(h2.dtype),
                       lp["mlp.w2"].astype(h2.dtype))
        x = constrain(x, "batch", "seq", "act_embed")
        return (x, (kv, state)) if want_state else (x, None)

    # ------------------------------------------------------------ forward --
    def forward(self, params, batch):
        cfg = self.cfg
        x = jnp.take(params["embed.w"], batch["tokens"], axis=0
                     ).astype(jnp.bfloat16)
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        for i in range(cfg.n_layers):
            lp = self._lp(params, i)
            layer = lambda p_, x_: self._layer_full(
                p_, x_, positions, self._window(i), want_state=True)
            if cfg.remat:
                layer = jax.checkpoint(
                    layer, policy=jax.checkpoint_policies.nothing_saveable)
            x, _ = layer(lp, x)
        x = rmsnorm(x, params["final_norm.w"], cfg.norm_eps)
        logits = x @ params["head.w"].astype(x.dtype)
        return constrain(logits, "batch", "seq", "vocab")

    def loss(self, params, batch):
        logits = self.forward(params, batch)
        loss = lm_cross_entropy(logits, batch["targets"],
                                onehot=self.cfg.ce_onehot)
        return loss, {"loss": loss}

    # --------------------------------------------------------------- serve --
    def init_cache(self, batch_size: int, max_len: int):
        cfg = self.cfg
        DI, N = cfg.d_inner_hybrid, cfg.ssm_state
        H = DI // cfg.ssm_head_dim
        conv_dim = DI + 2 * N
        P = cfg.ssm_head_dim

        ks, vs = [], []
        for i in range(cfg.n_layers):
            eff = max_len if self._window(i) is None else min(
                max_len, cfg.window)
            shape = (batch_size, cfg.n_kv_heads, eff, cfg.hd)
            ks.append(jnp.zeros(shape, jnp.bfloat16))
            vs.append(jnp.zeros(shape, jnp.bfloat16))
        return {
            "k": tuple(ks), "v": tuple(vs),
            "conv": jnp.zeros(
                (cfg.n_layers, batch_size, cfg.ssm_conv - 1, conv_dim),
                jnp.bfloat16),
            "ssd": jnp.zeros((cfg.n_layers, batch_size, H, N, P),
                             jnp.float32),
            "pos": jnp.zeros((), jnp.int32),
        }

    def prefill(self, params, batch, max_len: int | None = None):
        cfg = self.cfg
        B, S = batch["tokens"].shape
        max_len = max_len or S + 64
        x = jnp.take(params["embed.w"], batch["tokens"], axis=0
                     ).astype(jnp.bfloat16)
        positions = jnp.arange(S, dtype=jnp.int32)
        ks, vs, convs, ssds = [], [], [], []
        for i in range(cfg.n_layers):
            lp = self._lp(params, i)
            win = self._window(i)
            x, (kv, state) = self._layer_full(lp, x, positions, win,
                                              want_state=True)
            k, v = kv
            if win is not None and S >= win:
                k, v = k[:, :, -win:], v[:, :, -win:]
            elif max_len > S:
                pad = [(0, 0), (0, 0), (0, max_len - S), (0, 0)]
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            ks.append(k)
            vs.append(v)
            convs.append(state[0])
            ssds.append(state[1])
        x = rmsnorm(x, params["final_norm.w"], cfg.norm_eps)
        logits = x[:, -1:] @ params["head.w"].astype(x.dtype)
        cache = {"k": tuple(ks), "v": tuple(vs),
                 "conv": jnp.stack(convs),
                 "ssd": jnp.stack(ssds),
                 "pos": jnp.full((), S, jnp.int32)}
        return logits, cache

    def decode_step(self, params, tokens, cache):
        cfg = self.cfg
        B = tokens.shape[0]
        DI, N = cfg.d_inner_hybrid, cfg.ssm_state
        P = cfg.ssm_head_dim
        H = DI // P
        pos = cache["pos"]
        x = jnp.take(params["embed.w"], tokens, axis=0).astype(jnp.bfloat16)
        positions = jnp.broadcast_to(pos[None], (1,)).astype(jnp.int32)
        new_k, new_v, new_conv, new_ssd = [], [], [], []
        for i in range(cfg.n_layers):
            lp = self._lp(params, i)
            win = self._window(i)
            h = rmsnorm(x, lp["norm.w"], cfg.norm_eps)
            # ---- attention branch over the cache ----
            q = (h @ lp["attn.wq"].astype(h.dtype)).reshape(
                B, 1, cfg.n_heads, cfg.hd)
            k = (h @ lp["attn.wk"].astype(h.dtype)).reshape(
                B, 1, cfg.n_kv_heads, cfg.hd)
            v = (h @ lp["attn.wv"].astype(h.dtype)).reshape(
                B, 1, cfg.n_kv_heads, cfg.hd)
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            kT, vT = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            k_c, v_c = cache["k"][i], cache["v"][i]
            eff = k_c.shape[2]
            if win is not None and eff == win:
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
            attn_out = o.transpose(0, 2, 1, 3).reshape(B, 1, -1)
            # ---- ssm branch ----
            proj = h @ lp["ssm.in_proj"].astype(h.dtype)     # (B,1,dp)
            proj = proj[:, 0]
            z = proj[..., :DI]
            xs = proj[..., DI:2 * DI]
            b = proj[..., 2 * DI:2 * DI + N]
            c = proj[..., 2 * DI + N:2 * DI + 2 * N]
            dt = proj[..., 2 * DI + 2 * N:]
            xbc = jnp.concatenate([xs, b, c], axis=-1)
            hist = jnp.concatenate([cache["conv"][i], xbc[:, None]], axis=1)
            w = lp["ssm.conv.w"].astype(hist.dtype)
            conv = jnp.einsum("bkc,kc->bc", hist, w)
            conv = jax.nn.silu(conv + lp["ssm.conv.b"].astype(conv.dtype))
            xs_c, b_c, c_c = (conv[:, :DI], conv[:, DI:DI + N],
                              conv[:, DI + N:])
            dtp = jax.nn.softplus(dt.astype(jnp.float32) +
                                  lp["ssm.dt_bias"].astype(jnp.float32))
            y, ssd_next = ssd_decode_step(
                cache["ssd"][i], xs_c.reshape(B, H, P), dtp, lp["ssm.a_log"],
                b_c[:, None], c_c[:, None], lp["ssm.d_skip"])
            y = y.reshape(B, DI) * jax.nn.silu(
                z.astype(jnp.float32)).astype(y.dtype)
            ssm_out = y[:, None, :]
            fused = self._fuse(lp, attn_out, ssm_out)
            x = x + fused @ lp["attn.wo"].astype(fused.dtype)
            h2 = rmsnorm(x, lp["mlp_norm.w"], cfg.norm_eps)
            x = x + swiglu(h2, lp["mlp.w1"].astype(h2.dtype),
                           lp["mlp.w3"].astype(h2.dtype),
                           lp["mlp.w2"].astype(h2.dtype))
            new_k.append(k_c)
            new_v.append(v_c)
            new_conv.append(hist[:, 1:].astype(jnp.bfloat16))
            new_ssd.append(ssd_next)
        x = rmsnorm(x, params["final_norm.w"], cfg.norm_eps)
        logits = x @ params["head.w"].astype(x.dtype)
        cache = {"k": tuple(new_k), "v": tuple(new_v),
                 "conv": jnp.stack(new_conv),
                 "ssd": jnp.stack(new_ssd),
                 "pos": pos + 1}
        return logits, cache
