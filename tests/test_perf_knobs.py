"""Numerical-equivalence regression tests for every §Perf knob: optimized
paths must compute the same values as the baseline paths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.models.layers import attention
from repro.models.losses import lm_cross_entropy


def test_ce_onehot_equals_gather():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((2, 8, 64)), jnp.float32)
    tgt = jnp.asarray(rng.integers(0, 64, (2, 8)), jnp.int32)
    a = lm_cross_entropy(logits, tgt, onehot=False)
    b = lm_cross_entropy(logits, tgt, onehot=True)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)
    # with a mask
    mask = jnp.asarray(rng.integers(0, 2, (2, 8)), jnp.float32)
    am = lm_cross_entropy(logits, tgt, onehot=False, mask=mask)
    bm = lm_cross_entropy(logits, tgt, onehot=True, mask=mask)
    np.testing.assert_allclose(float(am), float(bm), rtol=1e-6)


@pytest.mark.parametrize("window", [None, 100, 128])
def test_block_skip_attention_equals_masked(window):
    rng = np.random.default_rng(2)
    B, Hq, Hkv, S, D = 1, 4, 2, 512, 32
    q = jnp.asarray(rng.standard_normal((B, Hq, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    pos = jnp.arange(S)
    base = attention(q, k, v, q_pos=pos, k_pos=pos, causal=True,
                     window=window, dense_max_seq=1, chunk=128,
                     block_skip=False)
    skip = attention(q, k, v, q_pos=pos, k_pos=pos, causal=True,
                     window=window, dense_max_seq=1, chunk=128,
                     block_skip=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(skip),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("knobs", [
    {"swa_ring_buffer": True},
    {"swa_ring_buffer": True, "decode_no_fsdp": True},
    {"decode_no_fsdp": True},
])
def test_swa_decode_knobs_match_forward(knobs):
    """Ring buffer / decode layouts: teacher-forced decode past the window
    must match the full forward exactly (modulo bf16 noise)."""
    base = get_smoke_config("mixtral-8x22b")   # window=32
    cfg = dataclasses.replace(base, **knobs)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S_prompt, n_gen = 1, 40, 8              # prompt > window
    toks = jax.random.randint(jax.random.PRNGKey(5),
                              (B, S_prompt + n_gen), 0, cfg.vocab_size)
    full = model.forward(params, {"tokens": toks})
    _, cache = model.prefill(params, {"tokens": toks[:, :S_prompt]})
    outs = []
    for i in range(n_gen):
        lg, cache = model.decode_step(
            params, toks[:, S_prompt + i:S_prompt + i + 1], cache)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    ref = full[:, S_prompt:S_prompt + n_gen]
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_ssm_chunk_invariance():
    """SSD output must not depend on the chunk size (block-size knob)."""
    cfg64 = dataclasses.replace(get_smoke_config("mamba2-780m"), ssm_chunk=8)
    cfg16 = dataclasses.replace(get_smoke_config("mamba2-780m"), ssm_chunk=32)
    m64, m16 = get_model(cfg64), get_model(cfg16)
    params = m64.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                              cfg64.vocab_size)
    batch = {"tokens": toks}
    a = m64.forward(params, batch)
    b = m16.forward(params, batch)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_optimized_train_flags_still_learn():
    cfg = dataclasses.replace(get_smoke_config("llama3.2-1b"),
                              ce_onehot=True, swa_block_skip=True,
                              remat_policy="dots")
    model = get_model(cfg)
    from repro.models import make_train_step
    from repro.optimizer import adamw_init
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    step = jax.jit(make_train_step(model, lr_schedule=1e-3))
    first = None
    for _ in range(40):
        params, opt, metrics = step(params, opt, batch)
        first = first if first is not None else float(metrics["loss"])
    assert float(metrics["loss"]) < first * 0.8
