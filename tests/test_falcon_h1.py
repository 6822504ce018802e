"""Falcon-H1 on the CPU: the grouped SSD against its sequential recurrence,
one group bit for bit as before groups existed, the program's prefill and
decode against the benchmark's plain float32 reference, and that reference
against transformers' own ``FalconH1ForCausalLM`` on a tiny config."""
import hashlib
import importlib.util
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import FalconH1LM, get_model
from repro.models.mamba2 import ssd_chunked, ssd_decode_step

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


def _binding():
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    path = BENCH / "configs" / "serve-falcon-h1-34b.py"
    spec = importlib.util.spec_from_file_location("falcon_h1_binding", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


SERVE = _binding()

# the published keys at a tiny size: 2 layers, GQA 4:2, 4 SSM heads in
# 2 B/C groups, the published multipliers
TINY = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 97, "mamba_d_ssm": 32, "mamba_n_heads": 4,
    "mamba_d_head": 8, "mamba_d_state": 8, "mamba_n_groups": 2,
    "mamba_d_conv": 4, "mamba_chunk_size": 8, "rms_norm_eps": 1e-5,
    "rope_theta": 100000000000, "attn_layer_indices": None,
    "rope_scaling": None, "param_dtype": "bfloat16",
    "embedding_multiplier": 5.656854249492381,
    "attention_in_multiplier": 1.0, "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "lm_head_multiplier": 0.0078125,
}


def _tiny(**over):
    cfg = json.loads((BENCH / "configs" / "serve-falcon-h1-34b.json")
                     .read_text())
    return {**cfg, **TINY, **over}


# -- the grouped SSD ---------------------------------------------------------
def _ssd_inputs(G, B=2, L=40, H=4, P=8, N=16, seed=0):
    k = iter(jax.random.split(jax.random.key(seed), 8))
    x = jax.random.normal(next(k), (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(next(k), (B, L, H)))
    a_log = jnp.log(jax.random.uniform(next(k), (H,), minval=1.0,
                                       maxval=16.0))
    b = jax.random.normal(next(k), (B, L, G, N))
    c = jax.random.normal(next(k), (B, L, G, N))
    d = jax.random.normal(next(k), (H,))
    return x, dt, a_log, b, c, d


def _recurrence(x, dt, a_log, b, c, d, state=None):
    """h_t = exp(dt A) h_{t-1} + dt B_t x_t; y_t = C_t h_t + D x_t, head h
    reading group h // (H // G); float64."""
    x, dt, a_log, b, c, d = (np.asarray(t, np.float64)
                             for t in (x, dt, a_log, b, c, d))
    B, L, H, P = x.shape
    G, N = b.shape[-2:]
    grp = np.arange(H) // (H // G)
    a = -np.exp(a_log)
    h = np.zeros((B, H, N, P)) if state is None else np.asarray(state,
                                                                np.float64)
    ys = []
    for t in range(L):
        bt, ct = b[:, t][:, grp], c[:, t][:, grp]                # (B, H, N)
        h = (h * np.exp(dt[:, t] * a)[..., None, None]
             + dt[:, t][..., None, None] * bt[..., None] * x[:, t][:, :, None])
        ys.append(np.einsum("bhn,bhnp->bhp", ct, h) + d[:, None] * x[:, t])
    return np.stack(ys, 1), h


@pytest.mark.parametrize("groups", [1, 2])
def test_ssd_chunked_matches_recurrence(groups):
    x, dt, a_log, b, c, d = _ssd_inputs(groups)
    y, state = jax.jit(ssd_chunked, static_argnames="chunk")(
        x, dt, a_log, b, c, d, chunk=16)       # 40 tokens: a padded chunk
    want_y, want_state = _recurrence(x, dt, a_log, b, c, d)
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state, want_state, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("groups", [1, 2])
def test_ssd_decode_step_matches_recurrence(groups):
    x, dt, a_log, b, c, d = _ssd_inputs(groups, L=1)
    state = jax.random.normal(jax.random.key(9), (2, 4, 16, 8))
    y, new = jax.jit(ssd_decode_step)(state, x[:, 0], dt[:, 0], a_log,
                                      b[:, 0], c[:, 0], d)
    want_y, want_state = _recurrence(x, dt, a_log, b, c, d, state)
    np.testing.assert_allclose(y, want_y[:, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new, want_state, rtol=1e-5, atol=1e-5)


def test_one_group_is_bit_identical_to_before_groups():
    """Digests of the outputs that ``ssd_chunked`` and ``ssd_decode_step``
    gave on this input when they took one (B, L, N) group."""
    k = iter(jax.random.split(jax.random.key(20260419), 8))
    B, L, H, P, N = 2, 40, 4, 8, 16
    x = jax.random.normal(next(k), (B, L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(next(k), (B, L, H)))
    a_log = jnp.log(jax.random.uniform(next(k), (H,), minval=1.0,
                                       maxval=16.0))
    b = jax.random.normal(next(k), (B, L, N))
    c = jax.random.normal(next(k), (B, L, N))
    d = jax.random.normal(next(k), (H,))
    state = jax.random.normal(next(k), (B, H, N, P))
    y, s = jax.jit(ssd_chunked, static_argnames="chunk")(
        x, dt, a_log, b[:, :, None], c[:, :, None], d, chunk=16)
    y1, s1 = jax.jit(ssd_decode_step)(state, x[:, 0], dt[:, 0], a_log,
                                      b[:, 0, None], c[:, 0, None], d)
    got = [hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]
           for a in (y, s, y1, s1)]
    assert got == ["3e58582f01315850", "4b1d7af7a6fafa12",
                   "c1b61a4c4f1892e8", "22d40798471d6312"]


def test_groups_must_divide_heads():
    x, dt, a_log, b, c, d = _ssd_inputs(3, H=4)
    with pytest.raises(ValueError):
        ssd_chunked(x, dt, a_log, b, c, d, chunk=16)


# -- the model ---------------------------------------------------------------
def test_published_config_builds_at_published_widths():
    cfg = get_config("falcon-h1-34b")
    model = get_model(cfg)
    assert isinstance(model, FalconH1LM)
    defs = model.param_defs()
    assert defs["layers.in_proj.w"].shape == (72, 5120, 2 * 4096 + 2 * 2 * 256
                                              + 32)
    assert defs["layers.attn.wq"].shape == (72, 5120, 20 * 128)
    assert defs["layers.attn.wk"].shape == (72, 5120, 4 * 128)
    assert defs["layers.mlp.gate"].shape == (72, 5120, 21504)
    assert defs["layers.gate_norm.w"].shape == (72, 4096)
    assert defs["head.w"].shape == (5120, 261120)
    assert (cfg.ssm_heads, cfg.ssm_groups, cfg.d_inner) == (32, 2, 4096)
    assert defs["layers.mlp.down"].dtype == jnp.bfloat16


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny()
    model = get_model(SERVE.program_config(cfg))
    w = SERVE.make_weights(cfg, seed=2**31 + 11)
    toks = np.asarray(jax.random.randint(jax.random.key(4), (40,), 0, 97))
    return cfg, model, w, toks


def _rel(got, want):
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(want, axis=-1)).max()


def test_prefill_then_decode_matches_reference(tiny_model):
    """The program keeps the residual, activations and the KV cache in
    bfloat16 (relative rounding 2^-9 a value, some twenty roundings a
    layer); at this width and depth its logits differ from the float32
    reference by 2.7% of their norm at worst, so the tolerance is 6%.  A
    lost branch differs by more than 100% (``test_reference_sees_a_lost_
    branch``)."""
    cfg, model, w, toks = tiny_model
    logits, cache = jax.jit(model.prefill, static_argnames="max_len")(
        w, {"tokens": jnp.asarray(toks[:24])[None]}, max_len=40)
    assert cache["k"].shape == (2, 1, 2, 40, 16)
    rows = [np.asarray(logits[0, -1], np.float32)]
    step = jax.jit(model.decode_step)
    for t in toks[24:-1]:
        logits, cache = step(w, jnp.asarray([[t]]), cache)
        rows.append(np.asarray(logits[0, -1], np.float32))
    assert int(cache["pos"]) == 39
    want = SERVE.reference_logits(w, cfg, toks, np.arange(23, 39))
    assert _rel(np.stack(rows), want) < 0.06


def test_reference_sees_a_lost_branch(tiny_model):
    """Each branch moves the reference's logits by far more than the
    program's rounding: the init's gains are what make the check see a
    fault in any of them."""
    cfg, _, w, toks = tiny_model
    want = SERVE.reference_logits(w, cfg, toks, np.arange(40))
    for name in ("layers.attn.wo", "layers.out_proj.w", "layers.mlp.down"):
        off = dict(w, **{name: jnp.zeros_like(w[name])})
        assert _rel(SERVE.reference_logits(off, cfg, toks, np.arange(40)),
                    want) > 0.5, name


def test_reference_matches_transformers():
    """The plain reference is the published layer: transformers'
    ``FalconH1ForCausalLM`` on a tiny config (2 B/C groups, non-unit
    multipliers, float32) given the seeded weights gives the same logits."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    cfg = _tiny(param_dtype="float32", attention_in_multiplier=0.9)
    cfg["init"] = dict(cfg["init"], d_skip=0.5)        # the skip term too
    w = {k: np.asarray(v, np.float32)
         for k, v in SERVE.make_weights(cfg, seed=5).items()}
    hf = transformers.FalconH1Config(
        vocab_size=97, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
        mamba_n_groups=2, mamba_d_state=8, mamba_d_conv=4,
        mamba_chunk_size=8, mamba_conv_bias=True, mamba_proj_bias=False,
        mamba_norm_before_gate=False, mamba_rms_norm=True,
        projectors_bias=False, rope_theta=1e11, rms_norm_eps=1e-5,
        max_position_embeddings=64, attn_layer_indices=None,
        tie_word_embeddings=False,
        **{k: cfg[k] for k in (
            "embedding_multiplier", "attention_in_multiplier",
            "attention_out_multiplier", "key_multiplier",
            "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
            "mlp_multipliers", "lm_head_multiplier")})
    hf._attn_implementation = "eager"
    model = transformers.FalconH1ForCausalLM(hf).eval()
    t = lambda a: torch.tensor(np.ascontiguousarray(a))
    with torch.no_grad():
        m = model.model
        m.embed_tokens.weight.copy_(t(w["embed.w"]))
        m.final_layernorm.weight.copy_(t(w["final_norm.w"]))
        model.lm_head.weight.copy_(t(w["head.w"].T))
        for i, layer in enumerate(m.layers):
            lw = {k[len("layers."):]: v[i] for k, v in w.items()
                  if k.startswith("layers.")}
            at, mx, ff = layer.self_attn, layer.mamba, layer.feed_forward
            layer.input_layernorm.weight.copy_(t(lw["norm.w"]))
            layer.pre_ff_layernorm.weight.copy_(t(lw["mlp_norm.w"]))
            for lin, name in ((at.q_proj, "attn.wq"), (at.k_proj, "attn.wk"),
                              (at.v_proj, "attn.wv"), (at.o_proj, "attn.wo"),
                              (mx.in_proj, "in_proj.w"),
                              (mx.out_proj, "out_proj.w"),
                              (ff.gate_proj, "mlp.gate"),
                              (ff.up_proj, "mlp.up"),
                              (ff.down_proj, "mlp.down")):
                lin.weight.copy_(t(lw[name].T))
            mx.conv1d.weight.copy_(t(lw["conv.w"].T[:, None, :]))
            mx.conv1d.bias.copy_(t(lw["conv.b"]))
            mx.A_log.copy_(t(lw["a_log"]))
            mx.D.copy_(t(lw["d_skip"]))
            mx.dt_bias.copy_(t(lw["dt_bias"]))
            mx.norm.weight.copy_(t(lw["gate_norm.w"]))
        toks = np.asarray(jax.random.randint(jax.random.key(8), (30,), 0, 97))
        got = model(torch.tensor(toks)[None], logits_to_keep=0).logits[0]
    want = SERVE.reference_logits({k: jnp.asarray(v) for k, v in w.items()},
                                  cfg, toks, np.arange(30))
    np.testing.assert_allclose(got.numpy(), want, rtol=2e-4, atol=2e-4)
