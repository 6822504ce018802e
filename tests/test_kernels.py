"""Pallas kernel validation: shape/dtype sweeps, interpret-mode kernel body
vs the pure-jnp oracle in ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # dev-only dep (requirements-dev.txt): skip, not error
from hypothesis import given, settings, strategies as st

from repro.kernels import matmul, ops, ref

RNG = np.random.default_rng(42)


def _arr(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


# ---------------------------------------------------------------- matmul --
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (256, 384, 256, 128, 128, 128),
    (256, 256, 512, 128, 256, 64),
    (512, 128, 128, 256, 128, 128),
])
def test_matmul_sweep(m, k, n, bm, bn, bk, dtype, tol):
    x, y = _arr((m, k), dtype), _arr((k, n), dtype)
    got = ops.matmul(x, y, bm=bm, bn=bn, bk=bk, force="interpret")
    want = ref.matmul(x, y)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


def test_matmul_rejects_untiled():
    with pytest.raises(ValueError):
        ops.matmul(_arr((100, 128), jnp.float32), _arr((128, 128), jnp.float32),
                   force="interpret")


@pytest.mark.parametrize("m,k,n,dtype,want", [
    # paper-dag coarse; 1024^3 would need 18 MiB, so bk halves, not bm*bn
    (4096, 4096, 4096, jnp.bfloat16, (1024, 1024, 512)),
    (256, 256, 256, jnp.float32, (256, 256, 256)),        # paper-dag fine
    (256, 2048, 8192, jnp.bfloat16, (256, 1024, 1024)),   # zoo prefill slab
    # the budget keeps bm*bn at half the ladder's top and bk at a quarter
    (4096, 4096, 4096, jnp.float32, (512, 1024, 256)),
    (384, 128, 640, jnp.float32, (128, 128, 128)),
])
def test_choose_tiles(m, k, n, dtype, want):
    bm, bn, bk = matmul.choose_tiles(m, k, n, dtype)
    assert (bm, bn, bk) == want
    for d, t in ((m, bm), (n, bn), (k, bk)):
        assert t % 128 == 0 and d % t == 0
    assert matmul.vmem_bytes(bm, bn, bk, dtype) <= matmul.VMEM_BUDGET


@pytest.mark.parametrize("shape", [(100, 128, 128), (128, 100, 128),
                                   (128, 128, 100)])
def test_choose_tiles_rejects_untiled(shape):
    with pytest.raises(ValueError):
        matmul.choose_tiles(*shape, jnp.float32)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_matmul_chosen_tiles_match_ref(dtype, tol):
    m, k, n = 512, 1024, 512
    assert matmul.choose_tiles(m, k, n, dtype) != (128, 128, 128)
    x, y = _arr((m, k), dtype), _arr((k, n), dtype)
    got = ops.matmul(x, y, force="interpret")
    want = ref.matmul(x, y)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


# ------------------------------------------------------------ copy/triad --
@pytest.mark.parametrize("shape,block", [((256, 128), 256), ((512, 64), 128),
                                         ((1024, 256), 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_copy_sweep(shape, block, dtype):
    x = (_arr(shape, dtype) if dtype != jnp.int32
         else jnp.asarray(RNG.integers(0, 100, shape), jnp.int32))
    got = ops.copy(x, block_rows=block, force="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))


@pytest.mark.parametrize("a", [0.0, 1.0, -2.5])
def test_triad(a):
    x, y = _arr((256, 128), jnp.float32), _arr((256, 128), jnp.float32)
    got = ops.triad(a, x, y, block_rows=128, force="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.triad(a, x, y)),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ sort --
@pytest.mark.parametrize("rows,n,block", [(8, 64, 8), (16, 256, 8),
                                          (32, 1024, 4), (8, 128, 2)])
def test_sort_sweep(rows, n, block):
    x = _arr((rows, n), jnp.float32)
    got = ops.sort_rows(x, block_rows=block, force="interpret")
    np.testing.assert_array_equal(np.asarray(got),
                                  np.sort(np.asarray(x), axis=-1))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_sort_property_is_sorted_permutation(seed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((4, 128)), jnp.float32)
    got = np.asarray(ops.sort_rows(x, block_rows=4, force="interpret"))
    assert np.all(np.diff(got, axis=-1) >= 0)          # sorted
    np.testing.assert_allclose(np.sort(got, axis=-1),
                               np.sort(np.asarray(x), axis=-1))  # permutation


def test_sort_rejects_non_power_of_two():
    with pytest.raises(ValueError):
        ops.sort_rows(_arr((8, 100), jnp.float32), force="interpret")


# --------------------------------------------------------------- rmsnorm --
@pytest.mark.parametrize("rows,d,block", [(256, 128, 256), (512, 512, 128),
                                          (256, 64, 64)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_rmsnorm_sweep(rows, d, block, dtype, tol):
    x, w = _arr((rows, d), dtype), _arr((d,), dtype)
    got = ops.rmsnorm(x, w, block_rows=block, force="interpret")
    want = ref.rmsnorm(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# --------------------------------------------------------- flash attention --
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 100), (True, 256)])
def test_flash_attention_modes(causal, window):
    B, Hq, Hkv, S, D = 2, 4, 2, 256, 64
    q, k, v = (_arr((B, Hq, S, D), jnp.float32),
               _arr((B, Hkv, S, D), jnp.float32),
               _arr((B, Hkv, S, D), jnp.float32))
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              bq=128, bk=128, force="interpret")
    want = ref.attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 4), (8, 1)])
def test_flash_attention_gqa_ratios(hq, hkv):
    B, S, D = 1, 256, 32
    q = _arr((B, hq, S, D), jnp.float32)
    k = _arr((B, hkv, S, D), jnp.float32)
    v = _arr((B, hkv, S, D), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, bq=128, bk=128,
                              force="interpret")
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    B, Hq, Hkv, S, D = 1, 2, 1, 256, 64
    q = _arr((B, Hq, S, D), jnp.bfloat16)
    k = _arr((B, Hkv, S, D), jnp.bfloat16)
    v = _arr((B, Hkv, S, D), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, causal=True, bq=128, bk=128,
                              force="interpret")
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


# ------------------------------------------- jnp chunked-flash (layers.py) --
def test_chunked_attention_matches_dense():
    """The model-side q-chunked flash path vs the dense path."""
    from repro.models.layers import attention
    B, Hq, Hkv, S, D = 2, 4, 2, 512, 32
    q = _arr((B, Hq, S, D), jnp.float32)
    k = _arr((B, Hkv, S, D), jnp.float32)
    v = _arr((B, Hkv, S, D), jnp.float32)
    pos = jnp.arange(S)
    dense = attention(q, k, v, q_pos=pos, k_pos=pos, causal=True,
                      dense_max_seq=10_000)
    chunked = attention(q, k, v, q_pos=pos, k_pos=pos, causal=True,
                        dense_max_seq=1, chunk=128)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(chunked),
                               rtol=2e-4, atol=2e-4)
