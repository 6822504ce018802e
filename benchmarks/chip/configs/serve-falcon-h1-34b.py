"""One-tenant serving of Falcon-H1-34B at its published widths, cut to one
pipeline stage (4 of 72 layers, an eighth of the vocabulary), through the
program's scheduler: ``build_serving_workload`` ->
``run_serving_workload_threaded`` (Workload -> SchedulerCore ->
ThreadedRuntime) on ``hikey960()`` under the configuration's policy.

Binding: the serving cell of ``serve-mamba2-780m.py``, whose ``Cell`` this
one extends: each request is a prefill TAO and a chain of decode TAOs of at
most ``DECODE_UNIT`` tokens.  The prefill TAO runs the request's own prompt
through ``FalconH1LM.prefill`` with a KV buffer of prompt + ``output_max``
positions and keeps the request's KV cache, conv and SSD state and its
greedy first token; each decode TAO runs exactly the tokens it accounts for
through ``decode_step``, greedily, from that state.  Programs compile once
per prompt length.  Here come the programs, the weights, the reference and
what the readers read.

Plain reference: :func:`reference_logits`, a float32 forward pass written
from transformers' ``modeling_falcon_h1`` (``FalconH1DecoderLayer``,
``FalconH1Mixer.torch_forward``, ``FalconH1RMSNormGated``,
``FalconH1MLP``, ``compute_mup_vector``): sequential SSM recurrence, exact
softmax attention in blocks of queries, the MLP in blocks of rows,
``highest`` matmul precision, no kernels, no cache.  It departs from the
published model in two ways: the logits cover the configuration's slice of
the vocabulary, and the weights are random from the seed (``assumed`` in
the configuration).  It imports nothing of the program and regenerates
the weights from the seed itself.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import falcon_h1_work as work
from yardstick import requests as traffic_gen


def _serving_cell():
    """``serve-mamba2-780m.py``, loaded under a name of this module's own."""
    path = pathlib.Path(__file__).with_name("serve-mamba2-780m.py")
    spec = importlib.util.spec_from_file_location(__name__ + "_serving",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


SERVING = _serving_cell()
make_prompts, served_gaps = SERVING.make_prompts, SERVING.served_gaps

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# -- weights -------------------------------------------------------------------
def fan_in(cfg: dict) -> dict:
    """Weight name -> (shape, fan-in) of every matrix, named as the
    program's ``FalconH1LM`` names them."""
    d = work.dims(cfg)
    L, M, F = d["layers"], d["d_model"], d["d_ff"]
    Q, KV, DI = d["q_width"], d["kv_width"], d["d_ssm"]
    return {
        "layers.attn.wq": ((L, M, Q), M),
        "layers.attn.wk": ((L, M, KV), M),
        "layers.attn.wv": ((L, M, KV), M),
        "layers.attn.wo": ((L, Q, M), Q),
        "layers.in_proj.w": ((L, M, d["d_in_proj"]), M),
        "layers.out_proj.w": ((L, DI, M), DI),
        "layers.mlp.gate": ((L, M, F), M),
        "layers.mlp.up": ((L, M, F), M),
        "layers.mlp.down": ((L, F, M), F),
        "head.w": ((M, d["vocab"]), M),
    }


def make_weights(cfg: dict, seed: int) -> dict:
    """Every parameter, on the device in one jitted call.  A matrix is
    N(0, (gain / sqrt(fan_in))^2) with its gain from ``cfg["init"]``, then
    rounded to the configuration's ``param_dtype``; the embedding is
    N(0, embedding_std^2).  The SSM follows mamba_ssm's init: convolution
    weight and bias U(-0.5, 0.5), A_log = log U[1, 16], dt bias the inverse
    softplus of log-uniform [1e-3, 1e-1]; norm weights 1; the skip weight
    D is ``init["d_skip"]``.  The SSM's per-head scalars stay float32."""
    d = work.dims(cfg)
    init = cfg["init"]
    L, M, V = d["layers"], d["d_model"], d["vocab"]
    H, K, CD = d["ssm_heads"], d["d_conv"], d["conv_dim"]
    dtype = cfg["param_dtype"]

    def make(key):
        ks = iter(jax.random.split(key, 24))

        def normal(shape, std):
            return jax.random.normal(next(ks), shape, F32) * std

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(ks), shape, F32, lo, hi)

        w = {name: normal(shape, init["gain"][name] / np.sqrt(n))
             for name, (shape, n) in fan_in(cfg).items()}
        w["embed.w"] = normal((V, M), init["embedding_std"])
        w["layers.conv.w"] = uniform((L, K, CD), -0.5, 0.5)
        w["layers.conv.b"] = uniform((L, CD), -0.5, 0.5)
        for name, n in (("final_norm.w", (M,)), ("layers.norm.w", (L, M)),
                        ("layers.mlp_norm.w", (L, M)),
                        ("layers.gate_norm.w", (L, d["d_ssm"]))):
            w[name] = jnp.ones(n, F32)
        w = {k: v.astype(dtype) for k, v in w.items()}
        dt = jnp.exp(uniform((L, H), np.log(1e-3), np.log(1e-1)))
        w["layers.a_log"] = jnp.log(uniform((L, H), 1.0, 16.0))
        w["layers.dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
        w["layers.d_skip"] = jnp.full((L, H), init["d_skip"], F32)
        return w

    return jax.jit(make)(jax.random.key(seed % 2**32, impl="rbg"))


# -- the plain reference ----------------------------------------------------
def _fp8(x):
    """Round to fp8 e4m3 (4 exponent, 3 mantissa bits): the control's
    matmul operands.  ``reduce_precision``, because XLA may drop a pair of
    converts to float8 and back as a no-op."""
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _dot(x, w, fp8: bool):
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    """``FalconH1RMSNorm``."""
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps))


def _blocks(fn, x, block: int):
    """``fn`` over rows of ``x`` in blocks of ``block`` rows, one block at a
    time (the tail padded and cut off again), so that its temporaries fit."""
    S = x.shape[0]
    pad = (-S) % block
    xb = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    xb = xb.reshape(-1, block, *x.shape[1:])
    out = jax.lax.map(fn, (jnp.arange(xb.shape[0]) * block, xb))
    return out.reshape(-1, *out.shape[2:])[:S]


def _rope(x, theta: float):
    """``apply_rotary_pos_emb`` on ``x`` (S, heads, D) at positions 0..S-1:
    ``x * cos + rotate_half(x) * sin``."""
    S, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    freqs = jnp.arange(S, dtype=F32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _mixer(lp, h, d, cfg, fp8):
    """``FalconH1Mixer.torch_forward`` without a cache, the SSM as its
    sequential recurrence.  h: (S, M), the normalized input."""
    S = h.shape[0]
    DI, H, P, N = d["d_ssm"], d["ssm_heads"], d["ssm_head_dim"], d["d_state"]
    G, K = cfg["mamba_n_groups"], d["d_conv"]
    mult = cfg["ssm_multipliers"]
    gn = G * N
    mup = jnp.concatenate([jnp.full((n,), m, F32) for n, m in zip(
        (DI, DI, gn, gn, H), mult)])
    proj = _dot(h * cfg["ssm_in_multiplier"], lp["in_proj.w"], fp8) * mup
    gate, xbc, dt = (proj[:, :DI], proj[:, DI:DI + d["conv_dim"]],
                     proj[:, DI + d["conv_dim"]:])
    # depthwise causal convolution, kernel K, with bias, then SiLU
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], 0)
    xbc = jax.nn.silu(lp["conv.b"] + sum(padded[k:k + S] * lp["conv.w"][k]
                                         for k in range(K)))
    xs = xbc[:, :DI].reshape(S, H, P)
    rep = H // G                                   # heads per B/C group
    b = jnp.repeat(xbc[:, DI:DI + gn].reshape(S, G, N), rep, axis=1)
    c = jnp.repeat(xbc[:, DI + gn:].reshape(S, G, N), rep, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                   # (S, H)
    a = -jnp.exp(lp["a_log"])                                  # (H,)

    def step(state, inp):                                      # (H, P, N)
        x_t, dt_t, b_t, c_t = inp
        state = (state * jnp.exp(dt_t * a)[:, None, None]
                 + dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (xs, dt, b, c))
    y = (y + lp["d_skip"][None, :, None] * xs).reshape(S, DI)
    # FalconH1RMSNormGated, norm_before_gate false: gate, then norm per group
    y = (y * jax.nn.silu(gate)).reshape(S, G, DI // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    y = lp["gate_norm.w"] * y.reshape(S, DI)
    return _dot(y, lp["out_proj.w"], fp8)


def _attention(lp, h, d, cfg, fp8, block):
    """``FalconH1Attention`` with exact causal softmax, ``block`` queries
    at a time."""
    S = h.shape[0]
    D = d["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h = h * cfg["attention_in_multiplier"]
    q = _dot(h, lp["attn.wq"], fp8).reshape(S, hq, D)
    k = _dot(h, lp["attn.wk"], fp8).reshape(S, hkv, D) * cfg["key_multiplier"]
    v = _dot(h, lp["attn.wv"], fp8).reshape(S, hkv, D)
    theta = float(cfg["rope_theta"])
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, hq // hkv, axis=1)                       # repeat_kv
    v = jnp.repeat(v, hq // hkv, axis=1)

    def rows(inp):
        start, qb = inp                                        # (blk, hq, D)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / np.sqrt(D)
        qpos = start + jnp.arange(qb.shape[0])
        s = jnp.where(jnp.arange(S)[None, None] <= qpos[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    o = _blocks(rows, q, block).reshape(S, hq * D)
    return _dot(o, lp["attn.wo"], fp8)


@functools.partial(jax.jit, static_argnames=("dims", "cfg_items", "fp8"))
def _ref_layer(w, i, x, *, dims, cfg_items, fp8):
    """``FalconH1DecoderLayer`` ``i`` over a whole sequence ``x`` (S, M),
    float32."""
    d, cfg = dict(dims), dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    lp = {k[len("layers."):]: v[i].astype(F32) for k, v in w.items()
          if k.startswith("layers.")}
    h = _rmsnorm(x, lp["norm.w"], eps)
    x = (x + _mixer(lp, h, d, cfg, fp8) * cfg["ssm_out_multiplier"]
         + _attention(lp, h, d, cfg, fp8, block=512)
         * cfg["attention_out_multiplier"])
    gate_m, down_m = cfg["mlp_multipliers"]

    def mlp(inp):                                              # FalconH1MLP
        _, xb = inp
        hb = _rmsnorm(xb, lp["mlp_norm.w"], eps)
        y = _dot(hb, lp["mlp.up"], fp8) * jax.nn.silu(
            _dot(hb, lp["mlp.gate"], fp8) * gate_m)
        return _dot(y, lp["mlp.down"], fp8) * down_m

    return x + _blocks(mlp, x, 4096)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _ref_logits(w, x, rows, *, cfg_items, fp8):
    cfg = dict(cfg_items)
    h = _rmsnorm(x[rows], w["final_norm.w"].astype(F32), cfg["rms_norm_eps"])
    return _dot(h, w["head.w"].astype(F32), fp8) * cfg["lm_head_multiplier"]


def _hashable(cfg: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, list))
                        and not isinstance(v, bool)))


def reference_logits(w: dict, cfg: dict, tokens: np.ndarray, rows,
                     fp8: bool = False) -> np.ndarray:
    """Float32 logits at positions ``rows`` of the sequence ``tokens``,
    layer by layer.  ``fp8`` rounds every matmul's operands to fp8 e4m3:
    the control."""
    d = work.dims(cfg)
    dims, items = tuple(sorted(d.items())), _hashable(cfg)
    x = jnp.take(w["embed.w"], jnp.asarray(tokens, jnp.int32), axis=0
                 ).astype(F32) * cfg["embedding_multiplier"]
    for i in range(d["layers"]):
        x = _ref_layer(w, i, x, dims=dims, cfg_items=items, fp8=fp8)
    return np.asarray(_ref_logits(w, x, jnp.asarray(rows, jnp.int32),
                                  cfg_items=items, fp8=fp8))


# -- the cell -------------------------------------------------------------------
def program_config(cfg: dict):
    """The program's ``ModelConfig`` for the configuration's keys."""
    from repro.models import ModelConfig
    if cfg["attn_layer_indices"] is not None or cfg["rope_scaling"]:
        raise ValueError("the program's FalconH1LM runs attention in every "
                         "layer, with plain RoPE")
    return ModelConfig(
        name="falcon-h1-34b", family="falcon_h1",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        vocab_pad_multiple=1, rope_theta=float(cfg["rope_theta"]),
        ssm_state=cfg["mamba_d_state"],
        ssm_expand=cfg["mamba_d_ssm"] / cfg["hidden_size"],
        ssm_head_dim=cfg["mamba_d_head"], ssm_conv=cfg["mamba_d_conv"],
        ssm_chunk=cfg["mamba_chunk_size"], ssm_groups=cfg["mamba_n_groups"],
        norm_eps=cfg["rms_norm_eps"],
        embedding_multiplier=cfg["embedding_multiplier"],
        attention_in_multiplier=cfg["attention_in_multiplier"],
        attention_out_multiplier=cfg["attention_out_multiplier"],
        key_multiplier=cfg["key_multiplier"],
        ssm_in_multiplier=cfg["ssm_in_multiplier"],
        ssm_out_multiplier=cfg["ssm_out_multiplier"],
        ssm_multipliers=tuple(cfg["ssm_multipliers"]),
        mlp_multipliers=tuple(cfg["mlp_multipliers"]),
        lm_head_multiplier=cfg["lm_head_multiplier"])


class Cell(SERVING.Cell):
    """mamba2-780m's serving cell with Falcon-H1's programs, weights and
    reference; the binder, the window, the end-to-end metrics, the sample
    and the checks are that cell's."""

    # -- programs ---------------------------------------------------------------
    def _programs(self):
        from repro.models import get_model
        model = get_model(program_config(self.cfg))
        fault, extra = self.ctx.fault, int(self.mix["output_max"])

        def greedy(logits):
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            return tok.astype(jnp.int32)

        def falcon_h1_prefill(params, tokens):
            logits, cache = model.prefill(params, {"tokens": tokens},
                                          max_len=tokens.shape[1] + extra)
            if fault == "dropped_kv":      # lost between prefill and decode
                cache = dict(cache, k=jnp.zeros_like(cache["k"]),
                             v=jnp.zeros_like(cache["v"]))
            return greedy(logits), cache

        def falcon_h1_decode(params, tok, cache):
            logits, new = model.decode_step(params, tok, cache)
            out = greedy(logits)
            if fault == "alter_token":
                out = (out + 1) % logits.shape[-1]
            if fault == "unchanged_state":
                new = cache
            return out, new

        return (jax.jit(falcon_h1_prefill),
                jax.jit(falcon_h1_decode, donate_argnums=(2,)))

    # -- set-up -------------------------------------------------------------------
    def setup(self) -> None:
        from repro.core import ThreadedRuntime, hikey960, make_policy
        if self.cfg["workers"] != "hikey960":
            raise ValueError(f"unknown worker pool {self.cfg['workers']!r}")
        seconds = (float(self.mix["trace_seconds"]) if self.ctx.trace
                   else self.ctx.seconds)
        self.reqs = traffic_gen.generate(self.mix, seconds)
        self.by_id = {r.id: r for r in self.reqs}
        self.params = make_weights(self.cfg, self.ctx.seed)
        self.prefill, self.decode = self._programs()
        self.policy = make_policy(self.cfg["policy"])
        self.rt = ThreadedRuntime(hikey960(), self.policy, seed=self.ctx.seed)
        # warm-up: one request of each prompt length through the same path,
        # which compiles (or loads) every program the window runs
        lengths = sorted(set(self.mix["prompt_lengths"]))
        warm = [traffic_gen.Request(-1 - i, L, 2, 0.0)
                for i, L in enumerate(lengths)]
        self.prompts = make_prompts(warm + self.reqs, self.ctx.seed,
                                    int(self.cfg["vocab_size"]))
        self._serve(warm)
        self.served.clear()
        self.prefill_lengths.clear()

    # -- what per-layer readers read ----------------------------------------
    def prefill_work(self) -> work.Work:
        """Declared work of every prefill in the window."""
        return work.Work(flops=sum(work.prefill(self.cfg, L).flops
                                   for L in self.prefill_lengths), bytes=0.0)

    def decode_bytes(self) -> list:
        """Bytes each decode step in the window must move: a request that
        served n tokens fed positions prompt_len .. prompt_len + n - 2."""
        return [work.decode(self.cfg, p).bytes
                for rid, s in self.served.items()
                for p in range(self.by_id[rid].prompt_len,
                               self.by_id[rid].prompt_len + len(s.tokens) - 1)]

    def attention_calls(self) -> list:
        """The flash kernel's declared work, one entry per call (one call a
        layer of each prefill in the window)."""
        layers = work.dims(self.cfg)["layers"]
        return [work.attention(self.cfg, L) for L in self.prefill_lengths
                for _ in range(layers)]

    # -- correctness ------------------------------------------------------------
    def compare(self, control: bool = False) -> dict:
        """Widest gap of a served token below the reference's best, over the
        sample (``program``), and the number of tokens compared.  With
        ``control``, also the widest gap of the token that the fp8
        reference puts first at the same positions (``control``)."""
        w = make_weights(self.cfg, self.ctx.seed)
        prompts = make_prompts(self.reqs, self.ctx.seed,
                               int(self.cfg["vocab_size"]))
        out = {"program": 0.0, "control": 0.0, "tokens": 0}
        for r in self.sample():
            toks = self.served_tokens(r)
            # padded at the end to one length per prompt length, so that
            # the reference compiles once per length; it is causal, so the
            # padding changes no row that is read
            pad = int(self.mix["output_max"]) - r.output_len
            seq = np.concatenate([np.asarray(prompts[r.id]).reshape(-1),
                                  toks[:-1], np.zeros(pad, np.int64)])
            rows = np.arange(r.prompt_len - 1, r.prompt_len + r.output_len)
            ref = reference_logits(w, self.cfg, seq, rows)
            out["program"] = max(out["program"],
                                 float(served_gaps(ref, toks).max()))
            out["tokens"] += len(toks)
            if control:
                low = reference_logits(w, self.cfg, seq, rows, fp8=True)
                out["control"] = max(out["control"], float(
                    served_gaps(ref, low.argmax(-1)).max()))
        return out
