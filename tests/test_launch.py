"""Entry-point plumbing: the compile cache and what ``serve.main`` reports,
batched and through the tenant zoo."""
import pathlib

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.serve_orchestrator import build_serving_dag
from repro.launch import compile_cache, serve
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


def test_serve_zoo_serves_both_tenants_and_conserves(monkeypatch):
    """The bursty two-tenant trace through the tenant zoo on real threads,
    with the token-bucket gate and critical-boost preemption: every
    admitted request finishes, and TAOs and tokens are accounted per
    request (what chip_smoke.py checks on the chip)."""
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    report = serve.main(["--arch", "llama3.2-1b", "--zoo"])
    res = report.stats.result
    by_name = {f"req{r.id}": r for r in report.requests}
    assert len(res.per_dag) == len(by_name)
    admitted = []
    for st in res.per_dag.values():
        if not st.rejected:
            assert st.done, f"admitted {st.name} did not finish"
            admitted.append(by_name[st.name])
    delivered = res.tokens_by_tenant()
    assert {t for t, n in delivered.items() if n > 0} == {"steady", "burst"}
    assert res.completed == sum(len(build_serving_dag([r]).nodes)
                                for r in admitted)
    want: dict[str, float] = {t: 0.0 for t in delivered}
    for r in admitted:
        want[r.tenant] += r.tokens
    assert delivered == want
