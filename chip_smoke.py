#!/usr/bin/env python3
"""Smoke run of the scheduler's main path on one TPU chip.

Run from the root of a checkout, on a host with a TPU:

    python3 chip_smoke.py

One process, one chip.  It refuses to run without a TPU, then runs five
phases and stops at the first that fails:

1. device      — platform, device_kind and count;
2. kernels     — every op of ``repro.kernels.ops`` on auto dispatch (the
                 Pallas kernels) against ``force="ref"`` (XLA), at the
                 serving zoo's shapes plus one llama3.2-1b-width case each;
3. mixed-dag   — the paper's mixed-mode DAG (300 matmul/sort/copy TAOs,
                 molding:weight) on the threaded runtime, Pallas payloads;
4. serve       — ``repro.launch.serve --arch llama3.2-1b --orchestrate`` at
                 full width (random weights), requests through
                 Workload -> SchedulerCore -> ThreadedRuntime; the last
                 decode step is checked against a full forward pass;
5. zoo         — ``repro.launch.serve --zoo``: the bursty two-tenant trace
                 with the token-bucket gate and critical-boost preemption.

Each phase prints one line with its wall time, the time JAX spent
compiling in it (trace, lowering and backend compile, or persistent-cache
reads when the cache is warm) and the process's peak HBM so far.  The last
line of a run in which every phase passed is one JSON object naming the
device.  These are smoke timings, not benchmark results.
"""
from __future__ import annotations

import json
import pathlib
import sys
import threading
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (ChunkedWork, ThreadedRuntime, hikey960,  # noqa: E402
                        make_policy, random_dag)
from repro.core.serve_orchestrator import ptt_profiles  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import get_model  # noqa: E402

ARCH = "llama3.2-1b"
DECODE_REL_ERR = 0.25


class CompileClock:
    """Sums JAX's compile-duration events (from any thread) and counts
    persistent-cache hits."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.secs = 0.0
        self.cache_hits = 0

    def on_duration(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.secs += secs

    def on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


def run_phase(name: str, fn, clock: CompileClock, dev) -> None:
    c0, h0 = clock.secs, clock.cache_hits
    t0 = time.perf_counter()
    detail = fn()
    wall = time.perf_counter() - t0
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"[{name}] ok: {wall:.3f} s wall, {clock.secs - c0:.3f} s compile "
          f"({clock.cache_hits - h0} cache hits), peak HBM "
          f"{peak / 2**30:.3f} GiB; {detail}", flush=True)


def lowers_to_pallas(fn, *args) -> bool:
    """Whether ``fn(*args)`` lowers to a Mosaic kernel on this backend."""
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


# -- phase 2: kernels --------------------------------------------------------
def _roll_kernel(x_ref, o_ref):
    o_ref[...] = pltpu.roll(x_ref[...], 1, 1)


def check_roll_direction() -> None:
    """``kernels/sort_bitonic.py`` relies on ``pltpu.roll`` shifting like
    ``jnp.roll``; check it on the chip rather than assume it."""
    x = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    got = pl.pallas_call(
        _roll_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
    if not np.array_equal(np.asarray(got), np.asarray(jnp.roll(x, 1, 1))):
        raise AssertionError(
            f"pltpu.roll(x, 1, 1) differs from jnp.roll: row 0 starts "
            f"{np.asarray(got)[0, :4]}, jnp.roll gives "
            f"{np.asarray(jnp.roll(x, 1, 1))[0, :4]}")


def _normal(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def kernel_cases(key):
    """(label, op name, args, rtol, atol); tolerances are those of
    ``tests/test_kernels.py``, exact (None) for copy and sort."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    ks = iter(jax.random.split(key, 32))

    def arr(shape, dtype=f32):
        return _normal(next(ks), shape, dtype)

    # flash attention: zoo slab (B, H, S, D) and llama3.2-1b GQA (32q / 8kv)
    zq, zkv = arr((1, 4, 256, 64)), arr((1, 4, 256, 64))
    lq = arr((1, 32, 2048, 64), bf16)
    lk, lv = arr((1, 8, 2048, 64), bf16), arr((1, 8, 2048, 64), bf16)
    return [
        ("matmul zoo 256x256x256 f32", "matmul",
         (arr((256, 256)), arr((256, 256))), 5e-5, 5e-4),
        ("matmul llama 256x2048x8192 bf16", "matmul",
         (arr((256, 2048), bf16), arr((2048, 8192), bf16)), 2e-2, 2e-1),
        ("copy zoo 1024x256 f32", "copy", (arr((1024, 256)),), None, None),
        ("copy llama 4096x2048 bf16", "copy", (arr((4096, 2048), bf16),),
         None, None),
        ("triad 1024x256 f32", "triad",
         (-2.5, arr((1024, 256)), arr((1024, 256))), 1e-5, 1e-6),
        ("triad llama 4096x2048 f32", "triad",
         (-2.5, arr((4096, 2048)), arr((4096, 2048))), 1e-5, 1e-6),
        ("sort_rows dag 32x1024 f32", "sort_rows", (arr((32, 1024)),),
         None, None),
        ("sort_rows llama 64x2048 f32", "sort_rows", (arr((64, 2048)),),
         None, None),
        ("rmsnorm 256x256 f32", "rmsnorm", (arr((256, 256)), arr((256,))),
         2e-5, 2e-5),
        ("rmsnorm llama 2048x2048 bf16", "rmsnorm",
         (arr((2048, 2048), bf16), arr((2048,), bf16)), 2e-2, 2e-2),
        ("flash zoo (1,4,256,64) f32", "flash_attention", (zq, zkv, zkv),
         2e-4, 2e-4),
        ("flash llama GQA (1,32|8,2048,64) bf16", "flash_attention",
         (lq, lk, lv), 5e-2, 5e-2),
    ]


def phase_kernels() -> str:
    check_roll_direction()
    failures = []
    cases = kernel_cases(jax.random.PRNGKey(0))
    for label, name, args, rtol, atol in cases:
        op = getattr(ops, name)
        if not lowers_to_pallas(op, *args):
            failures.append(f"{label}: auto dispatch is not a Pallas kernel")
            continue
        got = np.asarray(jax.block_until_ready(op(*args)), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(op(*args, force="ref"), np.float32)
        err = float(np.max(np.abs(got - want)))
        if rtol is None:
            ok = np.array_equal(got, want)
        else:
            ok = np.allclose(got, want, rtol=rtol, atol=atol)
        print(f"  {label}: max |pallas - ref| = {err:.3g} "
              f"({'exact' if rtol is None else f'rtol {rtol} atol {atol}'})"
              f"{'' if ok else '  MISMATCH'}", flush=True)
        if not ok:
            failures.append(f"{label}: max abs err {err:.3g}")
    if failures:
        raise AssertionError("kernels differ from ref.py: "
                             + "; ".join(failures))
    return f"pltpu.roll shifts like jnp.roll; {len(cases)} cases match ref.py"


# -- phase 3: the paper's mixed-mode DAG -------------------------------------
def phase_mixed_dag(n_tasks: int = 300) -> str:
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(1), 3)
    mat = _normal(k0, (256, 256), jnp.float32)
    rows = _normal(k1, (32, 1024), jnp.float32)
    stream = _normal(k2, (2048, 256), jnp.float32)
    payloads = {
        "matmul": (lambda x: ops.matmul(x, x), mat),
        "sort": (ops.sort_rows, rows),
        "copy": (ops.copy, stream),
    }
    for typ, (fn, x) in payloads.items():
        if not lowers_to_pallas(fn, x):
            raise AssertionError(f"{typ} TAO payload is not a Pallas kernel")
        jax.block_until_ready(fn(x))          # compile off the workers

    dag = random_dag(n_tasks=n_tasks, target_degree=3.0, seed=1)
    for node in dag.nodes:
        fn, x = payloads[node.type]
        node.work = ChunkedWork(
            lambda i, fn=fn, x=x: jax.block_until_ready(fn(x)), n_chunks=4)
    rt = ThreadedRuntime(hikey960(), make_policy("molding:weight"), seed=0)
    out = rt.run(dag, timeout_s=600)
    if out["completed"] != len(dag.nodes):
        raise AssertionError(f"{out['completed']} of {len(dag.nodes)} TAOs "
                             f"completed")
    cells = ptt_profiles(rt.core)
    unmeasured = [t for t in payloads if not cells.get(t)]
    if unmeasured:
        raise AssertionError(f"PTT recorded no measured cell for "
                             f"{unmeasured}")
    return (f"{out['completed']}/{len(dag.nodes)} TAOs, "
            f"{out['throughput_taos_per_s']:.1f} TAOs/s, PTT cells "
            f"{ {t: len(cells[t]) for t in payloads} }")


# -- phase 4: full-width serving through the scheduler -----------------------
def phase_serve(argv) -> str:
    report = serve.main(argv)
    if not bool(jnp.all(jnp.isfinite(report.logits))):
        raise AssertionError("non-finite logits")
    toks = np.asarray(report.tokens)
    if toks.min() < 0 or toks.max() >= report.vocab_size:
        raise AssertionError(f"token ids outside [0, {report.vocab_size})")
    # the KV-cache decode's last step against one full forward pass over
    # prompt + generated ids.  Activations are bf16 (unit roundoff 2^-8) and
    # 16 layers compound the two paths' rounding to a few percent of the
    # logits' norm, past the elementwise tolerance tests/test_models.py
    # sets for 2-layer models; a cache read at a wrong position or layout
    # is off by the order of the logits themselves (relative error near 1).
    model = get_model(get_config(ARCH))
    seq = jnp.concatenate([report.prompt, report.tokens], axis=1)
    want = np.asarray(
        jax.jit(model.forward)(report.params, {"tokens": seq})[:, -1],
        np.float32)
    got = np.asarray(report.logits[:, 0], np.float32)
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    if not np.all(rel <= DECODE_REL_ERR):
        raise AssertionError(f"last decode step vs forward pass: relative "
                             f"L2 error per row {rel} > {DECODE_REL_ERR}")
    done = report.stats.result.completed
    if done != report.n_taos:
        raise AssertionError(f"{done} of {report.n_taos} TAOs completed")
    return (f"{done}/{report.n_taos} TAOs, tokens {toks.shape} in vocab "
            f"{report.vocab_size}, last decode step vs forward pass: "
            f"relative L2 error <= {rel.max():.4g}, "
            f"{report.stats.tokens_per_s:.0f} tok/s")


# -- phase 5: the tenant zoo -------------------------------------------------
def phase_zoo(argv) -> str:
    report = serve.main(argv)
    res = report.stats.result
    offered: dict[str, float] = {}
    for r in report.requests:
        offered[r.tenant] = offered.get(r.tenant, 0.0) + r.tokens
    refused: dict[str, float] = {}
    admitted_taos = 0
    for st in res.per_dag.values():
        if st.rejected:
            refused[st.tenant] = refused.get(st.tenant, 0.0) + st.tokens
        elif st.done:
            admitted_taos += st.n_taos
        else:
            raise AssertionError(f"admitted {st.name} did not finish")
    delivered = res.tokens_by_tenant()
    accounted = {t: delivered.get(t, 0.0) + refused.get(t, 0.0)
                 for t in {*delivered, *refused}}
    if accounted != offered:
        raise AssertionError(f"tokens offered {offered}, delivered "
                             f"{delivered} + refused {refused}")
    if res.completed != admitted_taos:
        raise AssertionError(f"{res.completed} TAOs completed, admitted "
                             f"DAGs hold {admitted_taos}")
    return (f"tokens conserved per tenant: delivered {delivered}, refused "
            f"{refused}; {res.completed} TAOs, {res.n_rejected} refused")


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock.on_duration)
    jax.monitoring.register_event_listener(clock.on_event)
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} compile-cache={cache_dir}",
          flush=True)
    run_phase("kernels", phase_kernels, clock, dev)
    run_phase("mixed-dag", phase_mixed_dag, clock, dev)
    run_phase("serve", lambda: phase_serve(["--arch", ARCH,
                                            "--orchestrate"]), clock, dev)
    run_phase("zoo", lambda: phase_zoo(["--arch", ARCH, "--zoo"]), clock,
              dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
