"""The plain float32 Mamba-2 reference against the program's own forward
pass, at a smoke size on the CPU, on weights made from a seed.

The reference follows the published description (sequential recurrence,
float32, ``highest`` precision).  The program departs from it in ways the
tolerances below absorb: it keeps the residual stream and the matmul
inputs in bfloat16 (published: residual in float32), and it computes the
SSD in the chunked dual form.  A bfloat16 value carries a relative
rounding of up to 2^-9; over two layers and a 64-wide model the logits
then differ from float32 by about 1% of their norm, while a wrong
recurrence, a missing gate or a shifted convolution differs by order 1.
"""
from chip_bench_setup import BENCH_DIR  # first: puts the benchmark on sys.path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import load_module


SERVE = load_module(BENCH_DIR / "configs" / "serve-mamba2-780m.py")

SMOKE = {
    "d_model": 64, "d_intermediate": 0, "n_layer": 2, "vocab_size": 503,
    "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4, "expand": 2,
                "headdim": 16, "ngroups": 1, "chunk_size": 16},
    "attn_layer_idx": [], "norm_epsilon": 1e-5,
    "pad_vocab_size_multiple": 16, "param_dtype": "float32",
    "init": {"d_skip": 0.0},        # the recurrence carries the output
}


@pytest.fixture(scope="module")
def setup():
    from repro.models import get_model
    model = get_model(SERVE.program_config(SMOKE))
    w = SERVE.make_weights(SMOKE, seed=2**31 + 7)
    # a shorter memory than at the published dt (1e-3..1e-1), so that the
    # state changes within the 40 tokens compared
    w = dict(w, **{"layers.dt_bias": w["layers.dt_bias"] + 3.0})
    toks = np.asarray(jax.random.randint(jax.random.key(3), (40,), 0, 503))
    return model, w, toks


def test_reference_matches_program_forward(setup):
    model, w, toks = setup
    got = np.asarray(model.forward(w, {"tokens": jnp.asarray(toks)[None]})[0],
                     np.float32)
    want = SERVE.reference_logits(w, SMOKE, toks, np.arange(len(toks)))
    assert got.shape == want.shape == (40, 512)
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert rel.max() < 0.03, rel.max()


def test_reference_matches_program_decode(setup):
    """Prefill then decode through the program's state, against the
    reference's full pass over the same tokens."""
    model, w, toks = setup
    logits, cache = model.prefill(w, {"tokens": jnp.asarray(toks[:24])[None]})
    rows = [np.asarray(logits[0, -1], np.float32)]
    for t in toks[24:]:
        logits, cache = model.decode_step(w, jnp.asarray([[t]]), cache)
        rows.append(np.asarray(logits[0, -1], np.float32))
    got = np.stack(rows)
    want = SERVE.reference_logits(w, SMOKE, toks, np.arange(23, 40))
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert rel.max() < 0.03, rel.max()


def test_reference_sees_a_broken_recurrence(setup):
    """A reference run on other weights (a layer's A doubled) is off by
    far more than the program is."""
    model, w, toks = setup
    want = SERVE.reference_logits(w, SMOKE, toks, np.arange(40))
    bad = dict(w, **{"layers.a_log": w["layers.a_log"] + np.log(2.0)})
    other = SERVE.reference_logits(bad, SMOKE, toks, np.arange(40))
    rel = np.linalg.norm(other - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert rel.max() > 0.1


def test_served_gaps():
    ref = np.array([[1.0, 3.0, 2.0], [0.5, 0.0, 0.25]])
    assert list(SERVE.served_gaps(ref, [1, 2])) == [0.0, 0.25]
