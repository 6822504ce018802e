"""Entry-point plumbing: the compile cache, the peaks table, the kernel
registry's variants per backend, and what ``serve.main`` reports."""
import pathlib

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import ops
from repro.launch import compile_cache, serve
from repro.launch.device_peaks import device_peaks
from repro.models import get_model

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    """Restore the global cache-directory setting after a test moves it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defers_to_env(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_ignored_dir(monkeypatch,
                                                     cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.enable_compile_cache() == got     # fixed, not fresh
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("kind,known", [("TPU v5 lite", True),
                                        ("TPU v4", False), ("cpu", False)])
def test_device_peaks_known_kinds_only(kind, known):
    if known:
        peaks = device_peaks(kind)
        assert (peaks.flops_bf16, peaks.hbm_bw) == (197e12, 819e9)
        assert "v5e" in peaks.source
    else:
        with pytest.raises(KeyError, match="no published peaks"):
            device_peaks(kind)


@pytest.mark.parametrize("backend,want", [("tpu", ("ref", "pallas")),
                                          ("cpu", ("ref", "interpret"))])
def test_available_impls_never_offer_interpret_on_tpu(monkeypatch, backend,
                                                      want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(ops, "_interpret_works", lambda: True)
    assert tuple(im.name for im in ops.available_impls()) == want


def test_serve_main_reports_what_it_served(monkeypatch):
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    report = serve.main(["--arch", "llama3.2-1b", "--smoke", "--batch", "2",
                         "--prompt-len", "16", "--gen", "3",
                         "--orchestrate"])
    assert report.tokens.shape == (2, 3)
    toks = np.asarray(report.tokens)
    assert 0 <= toks.min() and toks.max() < report.vocab_size
    assert np.isfinite(np.asarray(report.logits)).all()
    assert len(report.requests) == 8
    assert report.n_taos > 0
    assert report.stats.result.completed == report.n_taos
    # what chip_smoke.py checks at full width: the cached decode's last
    # step equals the full forward pass over prompt + generated ids
    model = get_model(get_smoke_config("llama3.2-1b"))
    seq = np.concatenate([report.prompt, report.tokens], axis=1)
    want = model.forward(report.params, {"tokens": seq})[:, -1]
    np.testing.assert_allclose(np.asarray(report.logits[:, 0], np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)
