"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Batched prefill+decode serving of a model (full width, or smoke-sized with
``--smoke``), scheduled either directly or through the XiTAO runtime
(``--orchestrate``), where the PTT + weight-based policy learn
prefill->big / decode->LITTLE placement online.  ``main`` returns a
:class:`ServeReport` so an in-process caller (``chip_smoke.py``) can check
what was served.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..core.serve_orchestrator import ServeStats
from ..models import get_model
from .compile_cache import enable_compile_cache


@dataclasses.dataclass
class ServeReport:
    """What one ``main`` call served.

    Batch mode fills ``vocab_size``, ``params``, ``prompt`` (``(batch,
    prompt_len)`` ids), ``tokens`` (``(batch, gen)`` generated ids) and
    ``logits`` (the last decode step's), enough to recompute the last step
    with the model's full forward pass.  ``--orchestrate`` and ``--zoo``
    add the orchestrated run's ``stats`` and its ``requests``;
    ``--orchestrate`` also ``n_taos``, the TAOs its requests build."""

    vocab_size: int = 0
    params: dict | None = None
    prompt: jax.Array | None = None
    tokens: jax.Array | None = None
    logits: jax.Array | None = None
    requests: list = dataclasses.field(default_factory=list)
    n_taos: int = 0
    stats: ServeStats | None = None


def main(argv=None) -> ServeReport:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--orchestrate", action="store_true")
    ap.add_argument("--zoo", action="store_true",
                    help="orchestrate a bursty two-tenant trace through the "
                         "tenant zoo instead of a single-model batch")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.zoo:
        return _run_zoo(args)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "encoder":
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (args.batch, args.prompt_len), 0,
                              cfg.vocab_size)

    prefill_j = jax.jit(lambda p, b: model.prefill(
        p, b, max_len=args.prompt_len + args.gen + 1))
    decode_j = jax.jit(model.decode_step)

    t0 = time.time()
    logits, cache = prefill_j(params, {"tokens": toks})
    logits.block_until_ready()
    t_prefill = time.time() - t0

    out_tokens = []
    next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    t0 = time.time()
    for i in range(args.gen):
        out_tokens.append(next_tok)
        logits, cache = decode_j(params, next_tok, cache)
        next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = time.time() - t0

    total_tokens = args.batch * args.gen
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"prefill: {t_prefill:.3f}s "
          f"({args.batch * args.prompt_len / t_prefill:.0f} tok/s)")
    print(f"decode:  {t_decode:.3f}s ({total_tokens / t_decode:.0f} tok/s)")
    report = ServeReport(vocab_size=cfg.vocab_size, params=params,
                         prompt=toks,
                         tokens=jnp.concatenate(out_tokens, axis=1),
                         logits=logits)

    if args.orchestrate:
        from ..core import hikey960, make_policy
        from ..core.serve_orchestrator import (ServeRequest,
                                               build_serving_dag,
                                               run_serving_threaded)
        reqs = [ServeRequest(i, args.prompt_len, args.gen)
                for i in range(args.batch * 4)]
        report.requests = reqs
        report.n_taos = len(build_serving_dag(reqs).nodes)
        report.stats = stats = run_serving_threaded(
            reqs, hikey960(), make_policy("molding:weight"),
            prefill_fn=lambda r: jax.block_until_ready(
                prefill_j(params, {"tokens": toks})[0]),
            decode_fn=lambda r, i: jax.block_until_ready(
                decode_j(params, next_tok, cache)[0]))
        print(f"orchestrated: {stats.result.completed} TAOs, "
              f"{stats.tokens_per_s:.0f} tok/s, "
              f"mean sojourn {stats.mean_latency * 1e3:.1f} ms, "
              f"p99 {stats.p99_latency * 1e3:.1f} ms")
    return report


def _run_zoo(args) -> ServeReport:
    """Bursty two-tenant trace through the tenant zoo on real threads."""
    from ..core import hikey960, make_policy
    from ..core.admission import make_gate
    from ..core.preemption import make_preemption
    from ..core.serve_orchestrator import (bursty_serving_trace,
                                           run_serving_workload_threaded)
    from .zoo import default_zoo, warm_zoo, zoo_binder

    zoo = default_zoo()
    print(f"warming zoo: { {n: t.flavor for n, t in zoo.items()} }")
    warm_zoo(zoo)
    reqs = bursty_serving_trace(n_steady=12, n_burst=12, burst_at=0.2,
                                steady_prompts=(512, 1024), steady_gens=(64,),
                                burst_prompts=(2048, 4096), burst_gens=(64,))
    stats = run_serving_workload_threaded(
        reqs, hikey960(), make_policy("molding:weight"), zoo_binder(zoo),
        admission=make_gate("token-bucket", rate=40.0, burst=8,
                            max_delay=2.0),
        preemption=make_preemption("critical-boost"))
    print(f"zoo: {stats.result.completed} TAOs, "
          f"{stats.tokens_per_s:.0f} tok/s, p99 sojourn "
          f"{stats.p99_latency:.3f}s")
    for tenant, p99 in sorted(stats.p99_by_tenant().items()):
        tps = stats.tokens_per_s_by_tenant.get(tenant, 0.0)
        print(f"  {tenant:8s} p99={p99:.3f}s tok/s={tps:.0f}")
    for typ, cells in sorted(stats.ptt_profiles.items()):
        if cells:
            fastest = min(cells.values())
            print(f"  PTT[{typ}]: {len(cells)} measured cells, "
                  f"fastest {fastest * 1e3:.2f} ms")
    return ServeReport(requests=reqs, stats=stats)


if __name__ == "__main__":
    main()
