"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060) in pure JAX.

Chunked dual form: the sequence is split into chunks of Q tokens; within a
chunk the computation is a masked (decay-weighted) attention-like quadratic
— MXU-friendly matmuls — and across chunks a tiny recurrence over the
(H, N, P) states, computed with ``lax.associative_scan``.  Decode is the
O(1)-state recurrent step (why long_500k is runnable for this family).

Block layout follows the Mamba-2 paper: in_proj -> [z | x | B | C | dt],
depthwise conv over (x,B,C), SSD, gated RMSNorm, out_proj.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..parallel.sharding import constrain, logical_sharding
from .layers import rmsnorm
from .losses import lm_cross_entropy
from .model_api import BaseModel, ModelConfig, ParamDef


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------
def ssd_chunked(x, dt, a_log, b, c, d_skip, chunk: int,
                shard_acts: bool = False):
    """SSD in the chunked dual form, with G groups of B/C.

    x:  (B, L, H, P)   inputs per head
    dt: (B, L, H)      softplus'd step sizes
    a_log: (H,)        -A = exp(a_log) > 0
    b, c: (B, L, G, N) input/output projections; H % G == 0 and head h
                       reads group h // (H // G)
    d_skip: (H,)       skip connection
    Returns (y: (B, L, H, P), final_state: (B, H, N, P)).  One group runs
    :func:`_ssd_chunked_group` as it is; G groups run it once per group.
    """
    B, L, H, P = x.shape
    G, N = b.shape[-2:]
    if H % G:
        raise ValueError(f"{H} SSD heads do not split into {G} groups")
    if G == 1:
        return _ssd_chunked_group(x, dt, a_log, b.reshape(B, L, N),
                                  c.reshape(B, L, N), d_skip, chunk,
                                  shard_acts)
    R = H // G
    y, state = jax.vmap(
        functools.partial(_ssd_chunked_group, chunk=chunk,
                          shard_acts=shard_acts),
        in_axes=(2, 2, 0, 2, 2, 0), out_axes=(2, 1))(
            x.reshape(B, L, G, R, P), dt.reshape(B, L, G, R),
            a_log.reshape(G, R), b, c, d_skip.reshape(G, R))
    return y.reshape(B, L, H, P), state.reshape(B, H, N, P)


def _ssd_chunked_group(x, dt, a_log, b, c, d_skip, chunk: int,
                       shard_acts: bool = False):
    """SSD in the chunked dual form for heads that share one B/C group.

    x:  (B, L, H, P)   inputs per head
    dt: (B, L, H)      softplus'd step sizes
    a_log: (H,)        -A = exp(a_log) > 0
    b, c: (B, L, N)    input/output projections, shared over H
    d_skip: (H,)       skip connection
    ``shard_acts`` adds batch-sharding constraints on the big intra-chunk
    temporaries (the decay tensor is O(B*L*chunk*H) — without constraints
    GSPMD loses the batch sharding through the broadcast-subtract and
    replicates it; hillclimb knob `ssd_shard_acts`).
    Returns (y: (B, L, H, P), final_state: (B, H, N, P)).
    """
    B, L, H, P = x.shape
    N = b.shape[-1]
    L_orig = L
    if L % chunk:
        # zero-pad the tail: dt=0 makes decay exp(0)=1 and contribution 0,
        # so outputs (sliced back) and the terminal state are exact.
        pad = chunk - (L % chunk)
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
        L = L + pad
    nc, q = L // chunk, chunk

    A = -jnp.exp(a_log.astype(jnp.float32))            # (H,)
    dt = dt.astype(jnp.float32)
    dA = dt * A[None, None, :]                          # (B, L, H)  (<0)
    xr = x.reshape(B, nc, q, H, P)
    br = b.reshape(B, nc, q, N).astype(jnp.float32)
    cr = c.reshape(B, nc, q, N).astype(jnp.float32)
    dAr = dA.reshape(B, nc, q, H)
    dtr = dt.reshape(B, nc, q, H)

    # cumulative log-decay within each chunk
    La = jnp.cumsum(dAr, axis=2)                        # (B,nc,q,H)

    # ---- intra-chunk (quadratic, attention-like) --------------------------
    # decay(i<-j) = exp(La_i - La_j), j <= i
    diff = La[:, :, :, None, :] - La[:, :, None, :, :]  # (B,nc,q_i,q_j,H)
    ii = jnp.arange(q)
    causal = (ii[:, None] >= ii[None, :])[None, None, :, :, None]
    decay = jnp.where(causal, jnp.exp(diff), 0.0)
    if shard_acts:
        decay = constrain(decay, "batch", None, None, None, None)
    cb = jnp.einsum("bcin,bcjn->bcij", cr, br)          # (B,nc,q,q)
    w = cb[..., None] * decay * dtr[:, :, None, :, :]   # (B,nc,i,j,H)
    if shard_acts:
        w = constrain(w, "batch", None, None, None, None)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp",
                         w, xr.astype(jnp.float32))
    if shard_acts:
        y_intra = constrain(y_intra, "batch", None, None, None, None)

    # ---- chunk states ------------------------------------------------------
    # S_c = sum_j exp(La_last - La_j) dt_j B_j x_j^T   : (B,nc,H,N,P)
    last = La[:, :, -1:, :]                             # (B,nc,1,H)
    w_state = jnp.exp(last - La) * dtr                  # (B,nc,q,H)
    s_loc = jnp.einsum("bcjn,bcjh,bcjhp->bchnp",
                       br, w_state, xr.astype(jnp.float32))

    # ---- inter-chunk recurrence (associative scan over nc) ----------------
    # S_k = g_k * S_{k-1} + s_loc_k, g_k = exp(sum dA over chunk k)
    g = jnp.exp(last[:, :, 0, :])                       # (B,nc,H)

    def combine(l, r):
        gl, sl = l
        gr, sr = r
        return gl * gr, sr + gr * sl

    g_scan, s_scan = jax.lax.associative_scan(
        combine, (g[..., None, None], s_loc), axis=1)
    # state entering chunk k is S_{k-1}; s_scan[:, -1] is the terminal state
    s_prev = jnp.concatenate(
        [jnp.zeros_like(s_scan[:, :1]), s_scan[:, :-1]], axis=1)

    # ---- inter-chunk output -------------------------------------------------
    # y_inter_i = exp(La_i) * C_i . S_prev
    w_out = jnp.exp(La)                                 # (B,nc,q,H)
    y_inter = jnp.einsum("bcin,bcih,bchnp->bcihp", cr, w_out, s_prev)

    y = (y_intra + y_inter).reshape(B, L, H, P)
    y = y + d_skip[None, None, :, None] * x.astype(jnp.float32)
    return y[:, :L_orig].astype(x.dtype), s_scan[:, -1]   # (B,H,N,P)


def ssd_decode_step(state, x_t, dt_t, a_log, b_t, c_t, d_skip):
    """One recurrent step.  state: (B,H,N,P); x_t: (B,H,P); dt_t: (B,H);
    b_t/c_t: (B,G,N), head h reading group h // (H // G).  Returns (y_t,
    new_state)."""
    B, H, N, P = state.shape
    G = b_t.shape[1]
    if H % G:
        raise ValueError(f"{H} SSD heads do not split into {G} groups")
    if G == 1:
        return _ssd_step_group(state, x_t, dt_t, a_log, b_t.reshape(B, N),
                               c_t.reshape(B, N), d_skip)
    R = H // G
    y, new_state = jax.vmap(
        _ssd_step_group, in_axes=(1, 1, 1, 0, 1, 1, 0), out_axes=(1, 1))(
            state.reshape(B, G, R, N, P), x_t.reshape(B, G, R, P),
            dt_t.reshape(B, G, R), a_log.reshape(G, R), b_t, c_t,
            d_skip.reshape(G, R))
    return y.reshape(B, H, P), new_state.reshape(B, H, N, P)


def _ssd_step_group(state, x_t, dt_t, a_log, b_t, c_t, d_skip):
    """One recurrent step of heads that share one B/C group.  b_t/c_t:
    (B,N)."""
    A = -jnp.exp(a_log.astype(jnp.float32))
    dA = jnp.exp(dt_t.astype(jnp.float32) * A[None, :])       # (B,H)
    upd = jnp.einsum("bn,bh,bhp->bhnp", b_t.astype(jnp.float32),
                     dt_t.astype(jnp.float32), x_t.astype(jnp.float32))
    new_state = state * dA[..., None, None] + upd
    y = jnp.einsum("bn,bhnp->bhp", c_t.astype(jnp.float32), new_state)
    y = y + d_skip[None, :, None] * x_t.astype(jnp.float32)
    return y.astype(x_t.dtype), new_state


# ---------------------------------------------------------------------------
# the Mamba-2 mixer, shared by Mamba2LM and FalconH1LM
# ---------------------------------------------------------------------------
def mixer_widths(cfg: ModelConfig) -> tuple[int, int]:
    """-> (conv_dim, d_in_proj): in_proj emits [z | x | B | C | dt], the
    convolution runs over [x | B | C]."""
    gn = cfg.ssm_groups * cfg.ssm_state
    conv_dim = cfg.d_inner + 2 * gn
    return conv_dim, cfg.d_inner + conv_dim + cfg.ssm_heads


def mixer_param_defs(cfg: ModelConfig, dtype=jnp.float32) -> dict:
    """The mixer's stacked parameters, named under ``layers.``; ``dtype``
    is that of the projections and the convolution."""
    L, M = cfg.n_layers, cfg.d_model
    DI, H = cfg.d_inner, cfg.ssm_heads
    conv_dim, d_in_proj = mixer_widths(cfg)
    return {
        "layers.in_proj.w": ParamDef((L, M, d_in_proj),
                                     ("layers", "embed", "ff"), dtype=dtype),
        "layers.conv.w": ParamDef((L, cfg.ssm_conv, conv_dim),
                                  ("layers", None, "ff"), dtype=dtype),
        "layers.conv.b": ParamDef((L, conv_dim), ("layers", "ff"),
                                  init="zeros", dtype=dtype),
        "layers.a_log": ParamDef((L, H), ("layers", None), init="ssm_a"),
        "layers.d_skip": ParamDef((L, H), ("layers", None), init="ones"),
        "layers.dt_bias": ParamDef((L, H), ("layers", None),
                                   init="ssm_dt"),
        "layers.gate_norm.w": ParamDef((L, DI), ("layers", "ff"),
                                       init="ones"),
        "layers.out_proj.w": ParamDef((L, DI, M), ("layers", "ff", "embed"),
                                      dtype=dtype),
    }


def _mup_vector(cfg: ModelConfig) -> np.ndarray:
    """Per-channel multipliers of in_proj's output, ``ssm_multipliers`` on
    [z | x | B | C | dt] (Falcon-H1's ``compute_mup_vector``)."""
    gn = cfg.ssm_groups * cfg.ssm_state
    sizes = (cfg.d_inner, cfg.d_inner, gn, gn, cfg.ssm_heads)
    return np.concatenate([np.full(n, m, np.float32)
                           for n, m in zip(sizes, cfg.ssm_multipliers)])


def step_proj(h, w):
    """``h @ w`` in ``h``'s dtype for the one-token step.  A weight stored
    wider than ``h`` (float32 beside bfloat16 activations) is read as stored
    and rounded to ``h``'s precision inside the product, which sums in
    float32: the operands are the values ``w.astype(h.dtype)`` holds, but no
    separate pass writes a converted copy of the weight (XLA hoists such a
    cast of a whole layer stack out of the layer scan, and pays it every
    step).  A weight stored in ``h``'s dtype takes the plain product."""
    if jnp.dtype(w.dtype).itemsize <= jnp.dtype(h.dtype).itemsize:
        return h @ w.astype(h.dtype)
    fi = jnp.finfo(h.dtype)
    w = lax.reduce_precision(w, exponent_bits=fi.nexp, mantissa_bits=fi.nmant)
    out = lax.dot_general(h, w, (((h.ndim - 1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    return out.astype(h.dtype)


def _in_proj(cfg: ModelConfig, p, h, step: bool = False):
    """in_proj with Falcon-H1's input and per-channel multipliers; at unit
    multipliers (Mamba-2) no multiply is traced.  ``step``: the one-token
    step's product, :func:`step_proj`."""
    if cfg.ssm_in_multiplier != 1.0:
        h = h * cfg.ssm_in_multiplier
    w = p["in_proj.w"]
    proj = step_proj(h, w) if step else h @ w.astype(h.dtype)
    if any(m != 1.0 for m in cfg.ssm_multipliers):
        proj = (proj.astype(jnp.float32) * _mup_vector(cfg)
                ).astype(proj.dtype)
    return proj


def _split(cfg: ModelConfig, x):
    """in_proj output -> (z, x, B, C, dt), B and C ``G * N`` wide."""
    DI, gn = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    z = x[..., :DI]
    xs = x[..., DI:2 * DI]
    b = x[..., 2 * DI:2 * DI + gn]
    c = x[..., 2 * DI + gn:2 * DI + 2 * gn]
    dt = x[..., 2 * DI + 2 * gn:]
    return z, xs, b, c, dt


def gated_rmsnorm(y, z, w, eps: float, groups: int = 1):
    """RMSNorm of ``y * silu(z)`` over each of ``groups`` equal slices of
    the last axis (gate before norm: ``norm_before_gate`` false)."""
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    if groups == 1:
        return rmsnorm(y, w, eps)
    shape = y.shape
    y = y.reshape(*shape[:-1], groups, shape[-1] // groups)
    return rmsnorm(y, w.reshape(groups, -1), eps).reshape(shape)


def mixer_full(cfg: ModelConfig, p, h, want_state: bool = False):
    """The mixer over a whole sequence.  h: (B, S, M), already normalized.
    Returns (out: (B, S, M) before the residual add,
    (conv_state, ssd_state) | None)."""
    B, S, _ = h.shape
    DI, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    G = cfg.ssm_groups
    with jax.named_scope("in_proj"):
        proj = _in_proj(cfg, p, h)
        z, xs, b, c, dt = _split(cfg, proj)
    with jax.named_scope("conv"):
        # depthwise causal conv over (xs|b|c)
        xbc = jnp.concatenate([xs, b, c], axis=-1)   # (B,S,conv_dim)
        w = p["conv.w"].astype(xbc.dtype)            # (K, conv_dim)
        K = w.shape[0]
        pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + S] * w[i][None, None] for i in range(K))
        conv = jax.nn.silu(conv + p["conv.b"].astype(conv.dtype))
        xs, b, c = (conv[..., :DI], conv[..., DI:DI + G * N],
                    conv[..., DI + G * N:])
    with jax.named_scope("ssd"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) +
                             p["dt_bias"].astype(jnp.float32))
        y, final_state = ssd_chunked(
            xs.reshape(B, S, H, P), dt, p["a_log"], b.reshape(B, S, G, N),
            c.reshape(B, S, G, N), p["d_skip"], chunk=min(cfg.ssm_chunk, S),
            shard_acts=cfg.ssd_shard_acts)
    with jax.named_scope("out_proj"):      # gate, gate norm, projection
        y = gated_rmsnorm(y.reshape(B, S, DI), z, p["gate_norm.w"],
                          cfg.norm_eps, G)
        y = constrain(y, "batch", "seq", "act_ff")
        out = y @ p["out_proj.w"].astype(y.dtype)
    if not want_state:
        return out, None
    conv_state = xbc[:, -(cfg.ssm_conv - 1):]
    return out, (conv_state.astype(jnp.bfloat16), final_state)


def mixer_step(cfg: ModelConfig, p, h, conv_c, ssd_c):
    """The mixer for one token.  h: (B, M), already normalized; conv_c:
    (B, K - 1, conv_dim); ssd_c: (B, H, N, P).  Returns (out: (B, M) before
    the residual add, (new conv_c, new ssd_c))."""
    B = h.shape[0]
    DI, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    G = cfg.ssm_groups
    with jax.named_scope("in_proj"):
        proj = _in_proj(cfg, p, h, step=True)           # (B, d_in_proj)
        z, xs, b, c, dt = _split(cfg, proj)
    with jax.named_scope("conv"):
        xbc = jnp.concatenate([xs, b, c], axis=-1)      # (B, conv_dim)
        hist = jnp.concatenate([conv_c, xbc[:, None]], axis=1)  # (B, K, cd)
        w = p["conv.w"].astype(hist.dtype)              # (K, cd)
        conv = jnp.einsum("bkc,kc->bc", hist, w)
        conv = jax.nn.silu(conv + p["conv.b"].astype(conv.dtype))
        xs_c, b_c, c_c = (conv[:, :DI], conv[:, DI:DI + G * N],
                          conv[:, DI + G * N:])
    with jax.named_scope("ssd"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) +
                             p["dt_bias"].astype(jnp.float32))
        y, new_ssd = ssd_decode_step(
            ssd_c, xs_c.reshape(B, H, P), dt, p["a_log"],
            b_c.reshape(B, G, N), c_c.reshape(B, G, N), p["d_skip"])
    with jax.named_scope("out_proj"):      # gate, gate norm, projection
        y = gated_rmsnorm(y.reshape(B, DI), z, p["gate_norm.w"],
                          cfg.norm_eps, G)
        out = step_proj(y, p["out_proj.w"])
    return out, (hist[:, 1:].astype(jnp.bfloat16), new_ssd)


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------
class Mamba2LM(BaseModel):
    def param_defs(self) -> dict:
        cfg = self.cfg
        L, M, V = cfg.n_layers, cfg.d_model, cfg.padded_vocab
        defs = {
            "embed.w": ParamDef((V, M), ("vocab", "embed")),
            "final_norm.w": ParamDef((M,), (None,), init="ones"),
            "head.w": ParamDef((M, V), ("embed", "vocab")),
            "layers.norm.w": ParamDef((L, M), ("layers", None), init="ones"),
        }
        defs.update(mixer_param_defs(cfg))
        return defs

    # --------------------------------------------------------------- layer --
    def _layer_full(self, p, x, want_state: bool = False):
        """Full-sequence SSD layer.  x: (B, L_seq, M).  Returns
        (out, (conv_state, ssd_state)|None)."""
        cfg = self.cfg
        with jax.named_scope("norm"):
            h = rmsnorm(x, p["norm.w"], cfg.norm_eps)
        y, state = mixer_full(cfg, p, h, want_state)
        return x + y, state

    # ------------------------------------------------------------- forward --
    def forward(self, params, batch):
        cfg = self.cfg
        stacked = {k[len("layers."):]: v for k, v in params.items()
                   if k.startswith("layers.")}
        x = jnp.take(params["embed.w"], batch["tokens"], axis=0
                     ).astype(jnp.bfloat16)
        x = constrain(x, "batch", "seq", "act_embed")
        layer = self._layer_full
        if cfg.remat:
            layer = jax.checkpoint(
                layer, policy=jax.checkpoint_policies.nothing_saveable)

        def body(carry, lp):
            out, _ = layer(lp, carry)
            return out, None

        x, _ = jax.lax.scan(body, x, stacked)
        with jax.named_scope("head"):
            x = rmsnorm(x, params["final_norm.w"], cfg.norm_eps)
            logits = x @ params["head.w"].astype(x.dtype)
        return constrain(logits, "batch", "seq", "vocab")

    def loss(self, params, batch):
        logits = self.forward(params, batch)
        loss = lm_cross_entropy(logits, batch["targets"],
                                onehot=self.cfg.ce_onehot)
        return loss, {"loss": loss}

    # --------------------------------------------------------------- serve --
    def init_cache(self, batch_size: int, max_len: int, abstract=False):
        cfg = self.cfg
        N, H, P = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
        conv_dim, _ = mixer_widths(cfg)
        shapes = {
            "conv": ((cfg.n_layers, batch_size, cfg.ssm_conv - 1, conv_dim),
                     ("layers", "batch", None, "ff"), jnp.bfloat16),
            "ssd": ((cfg.n_layers, batch_size, H, N, P),
                    ("layers", "batch", None, None, None), jnp.float32),
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

    def prefill(self, params, batch):
        """Encode the prompt; emit the final SSD/conv state as the cache."""
        cfg = self.cfg
        # Full-state prefill: run forward and rebuild final states per layer.
        # For the serving path we reuse the chunked kernel but also need the
        # terminal state; recompute it with a scan over layers.
        stacked = {k[len("layers."):]: v for k, v in params.items()
                   if k.startswith("layers.")}
        B, S = batch["tokens"].shape
        x = jnp.take(params["embed.w"], batch["tokens"], axis=0
                     ).astype(jnp.bfloat16)

        def body(carry, lp):
            out, state = self._layer_full(lp, carry, want_state=True)
            return out, state

        x, (conv_states, ssd_states) = jax.lax.scan(body, x, stacked)
        with jax.named_scope("head"):
            x = rmsnorm(x, params["final_norm.w"], cfg.norm_eps)
            logits = x[:, -1:] @ params["head.w"].astype(x.dtype)
        cache = {"conv": conv_states.astype(jnp.bfloat16),
                 "ssd": ssd_states.astype(jnp.float32),
                 "pos": jnp.full((), S, jnp.int32)}
        return logits, cache

    def decode_step(self, params, tokens, cache):
        cfg = self.cfg
        stacked = {k[len("layers."):]: v for k, v in params.items()
                   if k.startswith("layers.")}
        x = jnp.take(params["embed.w"], tokens[:, 0], axis=0
                     ).astype(jnp.bfloat16)          # (B, M)

        def body(carry, lp_cache):
            lp, (conv_c, ssd_c) = lp_cache
            with jax.named_scope("norm"):
                h = rmsnorm(carry, lp["norm.w"], cfg.norm_eps)
            y, state = mixer_step(cfg, lp, h, conv_c, ssd_c)
            return carry + y, state

        x, (new_conv, new_ssd) = jax.lax.scan(
            body, x, (stacked, (cache["conv"], cache["ssd"])))
        with jax.named_scope("head"):
            x = rmsnorm(x, params["final_norm.w"], cfg.norm_eps)
            logits = step_proj(x, params["head.w"])[:, None, :]
        return logits, {"conv": new_conv, "ssd": new_ssd,
                        "pos": cache["pos"] + 1}
