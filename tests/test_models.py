"""Per-architecture smoke tests (reduced configs, 1 real step on CPU, shape
+ finiteness assertions) and cross-path consistency checks."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import get_model, make_train_step
from repro.optimizer import adamw_init


def _batch_for(cfg, B=2, S=32, seed=0):
    key = jax.random.PRNGKey(seed)
    if cfg.frontend == "frames":
        return {
            "frames": jax.random.normal(key, (B, S, cfg.d_model),
                                        jnp.bfloat16),
            "targets": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        }
    toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    if cfg.frontend == "patch":
        batch["patch_embeds"] = jax.random.normal(
            key, (B, cfg.n_patches, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch):
    """One forward + backward + optimizer step; finite outputs."""
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    batch = _batch_for(cfg)
    step = jax.jit(make_train_step(model))
    params2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(opt2.step) == 1
    # params actually moved
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved
    # one more step must also be finite (optimizer state sane)
    _, _, m3 = step(params2, opt2, batch)
    assert np.isfinite(float(m3["loss"]))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_output_shapes(arch):
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg)
    logits = jax.jit(model.forward)(params, batch)
    B = batch["targets"].shape[0]
    S = batch["targets"].shape[1]
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if a != "hubert-xlarge"])
def test_arch_prefill_decode_consistency(arch):
    """Teacher-forcing check: logits from (prefill(t_0..t_{n-1}) then decode
    t_n) must match forward over the full sequence at position n."""
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 17
    key = jax.random.PRNGKey(3)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch_full = {"tokens": toks, "targets": toks}
    if cfg.frontend == "patch":
        pe = jax.random.normal(key, (B, cfg.n_patches, cfg.d_model),
                               jnp.bfloat16)
        batch_full["patch_embeds"] = pe
    full_logits = model.forward(params, batch_full)

    batch_pre = {"tokens": toks[:, :-1]}
    if cfg.frontend == "patch":
        batch_pre["patch_embeds"] = pe
    pre_logits, cache = model.prefill(params, batch_pre)
    # prefill last-position logits == forward at position S-2
    np.testing.assert_allclose(
        np.asarray(pre_logits[:, 0], np.float32),
        np.asarray(full_logits[:, S - 2], np.float32), rtol=3e-2, atol=3e-2)
    # decode of token S-1 == forward at position S-1
    dec_logits, cache2 = model.decode_step(params, toks[:, -1:], cache)
    np.testing.assert_allclose(
        np.asarray(dec_logits[:, 0], np.float32),
        np.asarray(full_logits[:, S - 1], np.float32), rtol=3e-2, atol=3e-2)
    assert int(cache2["pos"]) == S


def test_decode_rolling_window_matches_full_history():
    """SWA rolling buffer: decoding with a window-sized cache must equal
    full attention once the context is shorter than the window."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("mixtral-8x22b")   # window=32
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 12                              # S < window -> identical
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0,
                              cfg.vocab_size)
    full = model.forward(params, {"tokens": toks})
    pre, cache = model.prefill(params, {"tokens": toks[:, :-1]})
    dec, _ = model.decode_step(params, toks[:, -1:], cache)
    dec32 = np.asarray(dec[:, 0], np.float32)
    full32 = np.asarray(full[:, -1], np.float32)
    # The two paths accumulate attention/MLP sums in different orders, and
    # activations are bf16 (eps = 2^-8), so per-element error grows like
    # eps * sqrt(n_reductions) — roughly 4 major reductions per layer (attn
    # scores/values, two MLP matmuls) plus embed/unembed.  The 4x headroom
    # covers constant factors without masking real cache bugs; the old flat
    # rtol/atol=0.03 flaked whenever a single reduction reassociated.
    eps_bf16 = 2.0 ** -8
    depth = 4 * cfg.n_layers + 2
    atol = 4 * eps_bf16 * math.sqrt(depth)
    np.testing.assert_allclose(dec32, full32, rtol=3e-2, atol=atol)


def test_moe_capacity_drops_are_bounded():
    """With capacity_factor >= 1 and k=top_k, most tokens route."""
    cfg = get_smoke_config("moonshot-v1-16b-a3b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, B=2, S=64)
    loss1, _ = model.loss(params, batch)
    assert np.isfinite(float(loss1))


def test_loss_decreases_over_steps():
    """~100 steps on a tiny model must reduce loss on a fixed batch."""
    cfg = get_smoke_config("llama3.2-1b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    batch = _batch_for(cfg, B=4, S=32)
    step = jax.jit(make_train_step(model, lr_schedule=1e-3))
    first = None
    for i in range(60):
        params, opt, metrics = step(params, opt, batch)
        if first is None:
            first = float(metrics["loss"])
    last = float(metrics["loss"])
    assert last < first * 0.7, f"loss {first} -> {last}: not learning"



def _mamba2_smoke_decode():
    cfg = get_smoke_config("mamba2-780m")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 17), 0,
                              cfg.vocab_size)
    return model, params, toks, jax.jit(model.decode_step)


def _step_rounds_inside():
    """The step's float32 weights against the same weights rounded to
    bfloat16 values and stored as float32: bit for bit the same logits and
    state."""
    model, params, toks, decode = _mamba2_smoke_decode()
    rounded = dict(params)
    for name in ("layers.in_proj.w", "layers.out_proj.w", "head.w"):
        rounded[name] = params[name].astype(jnp.bfloat16).astype(jnp.float32)
        assert not np.array_equal(rounded[name], params[name])
    _, cache = model.prefill(params, {"tokens": toks[:, :-1]})
    got = jax.tree.leaves(decode(params, toks[:, -1:], cache))
    want = jax.tree.leaves(decode(rounded, toks[:, -1:], cache))
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def _step_matches_prefill_and_forward():
    """Two decode steps after a prefill follow the forward pass, within
    test_arch_prefill_decode_consistency's tolerances."""
    model, params, toks, decode = _mamba2_smoke_decode()
    S = toks.shape[1]
    full = np.asarray(model.forward(params, {"tokens": toks}), np.float32)
    pre, cache = model.prefill(params, {"tokens": toks[:, :-2]})
    np.testing.assert_allclose(np.asarray(pre[:, 0], np.float32),
                               full[:, S - 3], rtol=3e-2, atol=3e-2)
    for i in (S - 2, S - 1):
        logits, cache = decode(params, toks[:, i:i + 1], cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                                   full[:, i], rtol=3e-2, atol=3e-2)
    assert int(cache["pos"]) == S


def _step_plain_dot_for_bf16():
    """Weights stored as bfloat16 (Falcon-H1's) take the plain product;
    float32 weights are rounded inside it."""
    from repro.models.mamba2 import step_proj
    h = jnp.ones((1, 8), jnp.bfloat16)

    def prims(dtype):
        jaxpr = jax.make_jaxpr(step_proj)(h, jnp.ones((8, 4), dtype))
        return [e.primitive.name for e in jaxpr.eqns]

    assert prims(jnp.bfloat16) == ["dot_general"]
    assert "reduce_precision" in prims(jnp.float32)


_STEP_CASES = {"rounds_inside": _step_rounds_inside,
               "matches_prefill_and_forward": _step_matches_prefill_and_forward,
               "plain_dot_for_bf16": _step_plain_dot_for_bf16}


@pytest.mark.parametrize("case", list(_STEP_CASES))
def test_mamba2_step_projection(case):
    """The one-token step's projections (``mamba2.step_proj``) round float32
    weights to bfloat16 inside the product and leave bfloat16 weights on
    the plain product."""
    _STEP_CASES[case]()
