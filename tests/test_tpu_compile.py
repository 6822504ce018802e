"""Compile every Pallas kernel for a described TPU v5e, without a chip.

The TPU compiler is installed with jax, and it compiles for a topology that
is described rather than attached.  This catches what interpret mode
cannot: layouts Mosaic refuses, tiles not aligned to the hardware, VMEM
overuse.  Two tests read the compiled text of a model's decode step, to pin
where its weights are converted.  Nothing runs, so these tests say nothing
about results or times.
The topology is described inside a fixture, never at import, so that only
the worker that runs this file loads the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import copy_stream, flash_attention, matmul, rmsnorm
from repro.kernels import sort_bitonic

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this jax build
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compilation_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,k,n,dtype", [
    (256, 256, 256, F32),           # the zoo's prefill projection
    (2048, 2048, 2048, BF16),
    (256, 2048, 8192, BF16),        # llama3.2-1b MLP up-projection slab
    (4096, 4096, 4096, BF16),       # paper-dag coarse payload
    (1024, 1024, 1024, F32),        # HIGHEST's bf16 split in the budget
])
def test_matmul_compiles(one_chip, m, k, n, dtype):
    _compile(matmul.matmul, [((m, k), dtype), ((k, n), dtype)], one_chip)


@pytest.mark.parametrize("shape,dtype", [((1024, 256), F32),
                                         ((4096, 2048), BF16)])
def test_copy_compiles(one_chip, shape, dtype):
    _compile(copy_stream.copy, [(shape, dtype)], one_chip)


@pytest.mark.parametrize("shape", [(1024, 256), (4096, 2048)])
def test_triad_compiles(one_chip, shape):
    _compile(copy_stream.triad, [((), F32), (shape, F32), (shape, F32)],
             one_chip)


@pytest.mark.parametrize("rows,d,dtype", [(256, 256, F32),
                                          (512, 2048, BF16)])
def test_rmsnorm_compiles(one_chip, rows, d, dtype):
    _compile(rmsnorm.rmsnorm, [((rows, d), dtype), ((d,), dtype)], one_chip)


@pytest.mark.parametrize("rows,n", [(32, 1024), (64, 1024), (64, 2048)])
def test_sort_rows_compiles(one_chip, rows, n):
    _compile(sort_bitonic.sort_rows, [((rows, n), F32)], one_chip)


@pytest.mark.parametrize("hq,hkv,s,d,dtype", [
    (4, 4, 256, 64, F32),           # the zoo's prefill slab
    (32, 8, 2048, 64, BF16),        # llama3.2-1b GQA at a 2k prompt
    (20, 4, 4096, 128, BF16),       # falcon-h1-34b, the shortest prompt
    (20, 4, 32768, 128, BF16),      # falcon-h1-34b, the longest prompt
], ids=["4-4-256-float32", "32-8-2048-bfloat16",   # as before head_dim
        "20-4-4096-128-bfloat16", "20-4-32768-128-bfloat16"])
def test_flash_attention_compiles(one_chip, hq, hkv, s, d, dtype):
    _compile(flash_attention.flash_attention,
             [((1, hq, s, d), dtype), ((1, hkv, s, d), dtype),
              ((1, hkv, s, d), dtype)], one_chip)


def _decode_step_hlo(model, one_chip, max_len=64):
    """Compiled text of ``model.decode_step`` at batch 1 for one chip."""
    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = {k: jax.ShapeDtypeStruct(d.shape, d.dtype, sharding=one_chip)
              for k, d in model.param_defs().items()}
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init_cache(1, max_len)))
    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
    return jax.jit(model.decode_step).lower(params, tok, cache).compile(
        ).as_text()


def _fusions(text):
    """Fused computations of a compiled HLO text: name -> body."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"%(\S*fus\S*) \(", line)
        if head and line.endswith("{"):
            name, out[head.group(1)] = head.group(1), []
        elif line == "}":
            name = None
        elif name:
            out[name].append(line)
    return out


def test_mamba2_decode_rounds_weights_inside_the_matvec(one_chip):
    """mamba2-780m's widths, 2 layers: no pass writes a bfloat16 copy of
    ``in_proj.w``, ``out_proj.w`` or ``head.w``; each is rounded by a
    ``reduce-precision`` in the fusion that reads it."""
    from repro.configs.mamba2_780m import config
    from repro.models import get_model
    model = get_model(dataclasses.replace(config(), n_layers=2))
    defs = model.param_defs()
    weights = {tuple(defs[k].shape[-2:])
               for k in ("layers.in_proj.w", "layers.out_proj.w", "head.w")}
    text = _decode_step_hlo(model, one_chip)
    shape = r"\[([\d,]+)\]"
    converted = {tuple(int(n) for n in m.split(","))[-2:]
                 for m in re.findall(r"= bf16" + shape + r"\S* convert\(",
                                     text)}
    assert not converted & weights
    rounded = {tuple(int(n) for n in m.split(","))[-2:]
               for body in _fusions(text).values() for line in body
               for m in re.findall(r"= f32" + shape + r"\S* reduce-precision",
                                   line)}
    assert weights <= rounded


def test_falcon_h1_decode_has_no_rounding(one_chip):
    """Falcon-H1 stores its weights in bfloat16: its decode step keeps the
    plain product, with no ``reduce-precision``."""
    from repro.configs.falcon_h1_34b import config
    from repro.models import get_model
    model = get_model(dataclasses.replace(config(), n_layers=1,
                                          vocab_size=32640))
    assert "reduce-precision" not in _decode_step_hlo(model, one_chip)
