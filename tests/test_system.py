"""End-to-end behaviour tests for the full system."""
import subprocess
import sys

import jax
import numpy as np
import pytest


def test_quickstart_example_runs():
    out = subprocess.run(
        [sys.executable, "examples/quickstart.py"],
        capture_output=True, text=True, timeout=560,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "HOME": "/tmp"},
        cwd=".",
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "molding:weight" in out.stdout


def test_end_to_end_train_small(tmp_path):
    """Full pipeline: data -> model -> optimizer -> checkpoint, loss falls."""
    from repro.data import SyntheticLM
    from repro.models import ModelConfig, get_model, make_train_step
    from repro.optimizer import adamw_init, cosine_schedule
    from repro.checkpointing import CheckpointManager

    cfg = ModelConfig(name="e2e", family="decoder", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=512)
    model = get_model(cfg)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    step_fn = jax.jit(make_train_step(
        model, lr_schedule=cosine_schedule(1e-3, 2, 30)))
    params, opt = model.init(jax.random.PRNGKey(0)), None
    from repro.optimizer import adamw_init as _init
    opt = _init(params)
    losses = []
    for s in range(30):
        params, opt, m = step_fn(params, opt, data.batch(s))
        losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    mgr = CheckpointManager(tmp_path)
    mgr.save(30, {"params": params})
    assert mgr.latest() == 30


def test_data_pipeline_determinism_and_sharding():
    from repro.data import SyntheticLM
    a = SyntheticLM(vocab_size=1000, seq_len=16, global_batch=8).batch(3)
    b = SyntheticLM(vocab_size=1000, seq_len=16, global_batch=8).batch(3)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    # next-token alignment
    full_a = SyntheticLM(vocab_size=1000, seq_len=16, global_batch=8)
    ba = full_a.batch(0)
    np.testing.assert_array_equal(np.asarray(ba["tokens"][:, 1:]),
                                  np.asarray(ba["targets"][:, :-1]))
    # host sharding partitions the global batch deterministically
    hosts = [SyntheticLM(vocab_size=1000, seq_len=16, global_batch=8,
                         host_index=h, host_count=2) for h in range(2)]
    parts = [h.host_batch(5)["tokens"] for h in hosts]
    assert parts[0].shape == (4, 16)
    assert not np.array_equal(parts[0], parts[1])
    # different steps differ
    assert not np.array_equal(np.asarray(a["tokens"]),
                              np.asarray(full_a.batch(1)["tokens"]))
