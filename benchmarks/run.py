"""Benchmark harness — one function per paper table/figure + framework
benches.  Prints ``name,us_per_call,derived`` CSV rows (derived = the
figure's own metric, e.g. TAOs/s for Fig 6).

  fig4   — kernel profiling (paper Fig 4): throughput vs (chains x width x
           core class) on the calibrated simulator, plus real kernel
           wall-times on the auto-dispatched ops (Pallas on a TPU, XLA
           elsewhere), each row labelled with the device it ran on.
  fig6   — randomized DAGs (paper Fig 6): 3 parallelism degrees x all
           scheduling policies, width hints 1 and 4.
  tab1/2 — task-molding impact (paper Tables 1 and 2).
  multi-dag — concurrent workload stream; `--vehicle {sim,threaded}` picks
           the executor, `--admission {none,token-bucket,slo-adaptive}`
           swaps the policy sweep for the bursty-tenant admission A/B, and
           `--preemption {none,backlog,critical-boost}` (composing with
           `--admission`) A/Bs chunk-granularity preemption of running
           TAOs on the same bursty stream.
  serve  — serving on the multi-tenant engine: policy sweep + bursty
           two-tenant admission x preemption A/B on both vehicles (sim with
           calibrated models, threaded with real zoo kernels); writes the
           JSON report to `--out` (default benchmarks/BENCH_serve.json).
  impl   — implementation-variant A/B (joint (impl, width, leader)
           placement): byte-identity pin gate, then static single-impl legs
           vs the joint decision on both vehicles (sim on cluster-divergent
           per-(type, impl) cost curves, threaded with every host-available
           kernel impl bound as TAO variants); writes
           `--out` (default benchmarks/BENCH_impl.json).
  chaos  — fleet-scale fault injection: byte-identity pin gate, then the
           bursty two-tenant stream under a mid-burst group kill plus
           straggler onset, legs {no-chaos, chaos, chaos+gate+preemption}
           with chunk-conservation asserts on both vehicles; writes
           `--out` (default benchmarks/BENCH_chaos.json).
  train  — training-DAG orchestrator at fleet scale.
  roofline — per (arch x shape) roofline terms from the dry-run artifacts
             (see EXPERIMENTS.md §Roofline; requires experiments/dryrun/).
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

ROWS: list[tuple[str, float, str]] = []


def emit(name: str, us_per_call: float, derived: str) -> None:
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.2f},{derived}", flush=True)


# ---------------------------------------------------------------------------
# Fig 4: kernel profiling
# ---------------------------------------------------------------------------
def fig4_kernel_profile() -> None:
    from repro.core import (BIG, LITTLE, Simulator, TaoDag, chain, hikey960,
                            make_policy)

    spec = hikey960()

    def profile(kernel: str, n_chains: int, width: int, cluster: str):
        sim = Simulator(spec, make_policy("homogeneous"), seed=0)
        dead = spec.little_workers if cluster == BIG else spec.big_workers
        for w in dead:
            sim.fail_worker(w)
        dag = TaoDag()
        for _ in range(n_chains):
            chain(dag, kernel, 40, width_hint=width)
        res = sim.run(dag)
        emit(f"fig4.{kernel}.{n_chains}x{width}.{cluster}",
             res.makespan / res.completed * 1e6,
             f"{res.throughput:.1f}")

    for kernel in ("matmul", "sort", "copy"):
        for n_chains, width in ((1, 1), (1, 2), (1, 4), (2, 1), (4, 1),
                                (2, 2)):
            for cluster in (BIG, LITTLE):
                profile(kernel, n_chains, width, cluster)


def fig4_real_kernels() -> None:
    """Real kernel wall-times of the auto-dispatched ops (Pallas on a TPU,
    the XLA reference elsewhere); ``derived`` names the device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    r = np.random.default_rng(0)
    dev = jax.devices()[0]
    device = f"{dev.platform}:{dev.device_kind}"

    def bench(name, fn, *args, iters=20):
        fn(*args).block_until_ready()          # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        out.block_until_ready()
        us = (time.perf_counter() - t0) / iters * 1e6
        emit(f"fig4.real.{name}", us, device)

    a = jnp.asarray(r.standard_normal((512, 512)), jnp.float32)
    b = jnp.asarray(r.standard_normal((512, 512)), jnp.float32)
    bench("matmul_512", ops.matmul, a, b)
    big = jnp.asarray(r.standard_normal((4096, 512)), jnp.float32)
    bench("copy_8MB", ops.copy, big)
    s = jnp.asarray(r.standard_normal((64, 1024)), jnp.float32)
    bench("sort_64x1024", ops.sort_rows, s)


# ---------------------------------------------------------------------------
# Fig 6: randomized DAGs
# ---------------------------------------------------------------------------
FIG6_POLICIES = ("homogeneous", "crit-aware", "crit-ptt", "weight",
                 "molding:crit-ptt", "molding:weight")


def fig6_random_dags(n_tasks: int = 3000) -> None:
    from repro.core import Simulator, hikey960, make_policy, random_dag

    spec = hikey960()
    for degree in (1.62, 3.03, 8.06):
        for hint in (1, 4):
            for policy in FIG6_POLICIES:
                dag = random_dag(n_tasks, target_degree=degree,
                                 seed=int(degree * 100), width_hint=hint)
                sim = Simulator(spec, make_policy(policy), seed=1)
                res = sim.run(dag)
                emit(f"fig6.deg{degree}.hint{hint}.{policy}",
                     res.makespan / res.completed * 1e6,
                     f"{res.throughput:.1f}")


# ---------------------------------------------------------------------------
# Tables 1-2: molding impact
# ---------------------------------------------------------------------------
def tables_molding(n_tasks: int = 3000) -> None:
    from repro.core import Simulator, hikey960, make_policy, random_dag

    spec = hikey960()
    # paper: hints = best-for-base-case (4 for low degrees, 1 for 8.06)
    cases = ((1.62, 4), (3.03, 4), (8.06, 1))
    for tab, base_pol in (("tab1", "weight"), ("tab2", "crit-ptt")):
        for degree, hint in cases:
            for molding in (False, True):
                pol = f"molding:{base_pol}" if molding else base_pol
                dag = random_dag(n_tasks, target_degree=degree,
                                 seed=int(degree * 100), width_hint=hint)
                res = Simulator(spec, make_policy(pol), seed=2).run(dag)
                tag = "with_molding" if molding else "without_molding"
                emit(f"{tab}.deg{degree}.hint{hint}.{tag}",
                     res.makespan / res.completed * 1e6,
                     f"{res.throughput:.1f}")


# ---------------------------------------------------------------------------
# beyond-paper: concurrent multi-DAG workload stream (online arrivals)
# ---------------------------------------------------------------------------
def multi_dag_bench(n_dags: int = 16, n_tasks: int = 150,
                    rate: float = 4.0, vehicle: str = "sim",
                    shards: int | None = None) -> None:
    """Rank every policy on an online-arrival stream.

    ``n_dags`` mixed-degree random DAGs arrive as a Poisson process; the
    metric is per-DAG sojourn (completion - arrival), reported as mean
    (us_per_call column) plus p50/p99 in the derived column.

    ``vehicle='sim'`` replays the stream on the discrete-event simulator
    over a fleet(48, 16) pool; ``vehicle='threaded'`` runs the *same
    Workload abstraction* on real worker threads (hikey960-shaped 8-thread
    pool, scaled-down stream so arrivals are real wall-clock sleeps) —
    making the two execution vehicles directly comparable on one stream.
    ``shards`` (``--shards N``) routes both vehicles through the
    :class:`ShardedScheduler`; the derived column then also reports the
    inter-shard work-exchange count.
    """
    from repro.core import (ALL_POLICY_NAMES, Simulator, ThreadedRuntime,
                            fleet, hikey960, make_policy, random_workload)

    if vehicle == "threaded":
        # real wall-clock execution: compress the stream so the whole
        # policy sweep stays a few seconds
        spec, tag = hikey960(), "threaded8"
        n_dags, n_tasks, rate = min(n_dags, 6), min(n_tasks, 40), 40.0
    else:
        spec, tag = fleet(48, 16), "fleet64"   # 48 big + 16 LITTLE groups
    if shards is not None:
        tag = f"{tag}.s{shards}"
    ranking = []
    for policy in ALL_POLICY_NAMES:
        wl = random_workload(n_dags=n_dags, rate=rate, n_tasks=n_tasks,
                             seed=0)
        if vehicle == "threaded":
            rt = ThreadedRuntime(spec, make_policy(policy), seed=1,
                                 n_shards=shards)
            res = rt.run_workload(wl, timeout_s=120.0)
        else:
            sim = Simulator(spec, make_policy(policy), seed=1,
                            n_shards=shards)
            res = sim.run_workload(wl)
        assert res.completed == wl.total_taos()
        p50, p99 = res.sojourn_p50(), res.sojourn_p99()
        ex = ";exchanges=%d" % (res.exchanges or {}).get("total", 0) \
            if shards is not None else ""
        emit(f"multidag.{tag}.{policy}",
             res.mean_sojourn() * 1e6,
             f"p50={p50:.4f}s;p99={p99:.4f}s;"
             f"makespan={res.makespan:.4f}s;util={res.utilization:.3f}{ex}")
        ranking.append((p50, p99, policy))
    for i, (p50, p99, policy) in enumerate(sorted(ranking), 1):
        print(f"# multidag rank {i}: {policy} "
              f"(p50={p50:.4f}s, p99={p99:.4f}s)", flush=True)


# ---------------------------------------------------------------------------
# beyond-paper: the shared bursty two-tenant A/B harness
# ---------------------------------------------------------------------------
def _bursty_setup(vehicle: str, gate: str, n_chunks: int = 1):
    """Per-vehicle scaffolding the admission and preemption A/B benches
    share: pool/SLO/gate-knob tables plus the stream and executor.

    Returns ``(tag, slo, gate_kw, execute)`` where
    ``execute(gate_obj, ctrl_obj)`` runs one configuration of the bursty
    two-tenant stream under ``molding:adaptive``.  ``n_chunks`` sets the
    chunk boundaries per TAO (1 = monolithic, the admission bench's
    historical payload; the threaded payload always totals ~1 ms of
    GIL-releasing sleep split across the chunks)."""
    import time as _time
    from repro.core import (ChunkedWork, Simulator, ThreadedRuntime,
                            bursty_workload, fleet, hikey960, make_policy)

    if vehicle == "threaded":
        spec, tag = hikey960(), "threaded8"
        slo = {"steady": 0.12, "burst": 0.6}
        gate_kw = {
            "none": {},
            # headroom sized for the 8-worker pool: the backlog limit must
            # exceed one steady DAG (25 TAOs) but not two burst DAGs (200)
            "slo-adaptive": dict(slo=0.12, slo_per_tenant={"burst": 0.6},
                                 headroom=16.0),
            "token-bucket": dict(rate=30.0, burst=3, max_delay=0.5),
        }[gate]
        sleep_s = 0.001 / n_chunks

        def stream():
            wl = bursty_workload(n_steady=6, steady_rate=15.0,
                                 steady_tasks=25, n_burst=12, burst_at=0.05,
                                 burst_rate=200.0, burst_tasks=100, seed=2)
            for arr in wl:
                for node in arr.dag.nodes:
                    node.work = ChunkedWork(lambda i: _time.sleep(sleep_s),
                                            n_chunks)
            return wl

        def execute(gate_obj, ctrl_obj=None):
            rt = ThreadedRuntime(spec, make_policy("molding:adaptive"),
                                 seed=1)
            return rt.run_workload(stream(), timeout_s=120.0,
                                   admission=gate_obj, preemption=ctrl_obj)
    else:
        spec, tag = fleet(48, 16), "fleet64"
        slo = {"steady": 0.5, "burst": 3.0}
        gate_kw = {
            "none": {},
            "slo-adaptive": dict(slo=0.5, slo_per_tenant={"burst": 3.0}),
            "token-bucket": dict(rate=4.0, burst=3, max_delay=2.0),
        }[gate]

        def stream():
            return bursty_workload(seed=1, n_chunks=n_chunks)

        def execute(gate_obj, ctrl_obj=None):
            sim = Simulator(spec, make_policy("molding:adaptive"), seed=1)
            return sim.run_workload(stream(), admission=gate_obj,
                                    preemption=ctrl_obj)

    return tag, slo, gate_kw, execute


def _tenant_p99(res, tenant):
    from repro.core import percentile
    return percentile([s.sojourn for s in res.per_tenant().get(tenant, [])
                       if s.done], 99)


def _median_run(make_run, vehicle: str):
    """The simulator is deterministic; the threaded vehicle is real wall
    clock on a possibly-noisy host, so take the median-steady-p99 run
    of 3 there."""
    runs = [make_run() for _ in range(3 if vehicle == "threaded" else 1)]
    runs.sort(key=lambda r: _tenant_p99(r, "steady"))
    return runs[len(runs) // 2]


# ---------------------------------------------------------------------------
# beyond-paper: SLO-aware admission control on a bursty two-tenant stream
# ---------------------------------------------------------------------------
def admission_bench(vehicle: str = "sim",
                    gate: str = "slo-adaptive") -> None:
    """A/B the selected admission gate against ``none`` on a bursty stream.

    ``repro.core.bursty_workload`` builds two tenants: ``steady`` (small
    latency-sensitive DAGs on a gentle Poisson process) and ``burst`` (a
    batch spike of large DAGs).  Both configurations run the *same* stream
    under ``molding:adaptive``; rows report per-tenant sojourn p50/p99 and
    admission outcomes, plus total goodput — completed DAGs meeting their
    per-tenant SLO (strict for ``steady``, lax for ``burst``).  The gate
    should cut the steady tenant's p99 without regressing goodput.

    The threaded variant attaches ~1 ms sleeping payloads (sleeps release
    the GIL, so the 8-thread pool genuinely saturates) and scales the
    stream down to keep the bench a few seconds of wall-clock.
    """
    from repro.core import make_gate, percentile

    tag, slo, gate_kw, execute = _bursty_setup(vehicle, gate)
    tenant_p99 = _tenant_p99

    results = {}
    for name in ("none", gate):
        res = _median_run(
            lambda: execute(make_gate(name,
                                      **(gate_kw if name == gate else {}))),
            vehicle)
        results[name] = res
        for tenant, stats in res.per_tenant().items():
            so = [s.sojourn for s in stats if s.done]
            emit(f"admission.{tag}.{name}.{tenant}",
                 percentile(so, 99) * 1e6,
                 f"p50={percentile(so, 50):.4f}s;"
                 f"p99={percentile(so, 99):.4f}s;"
                 f"admitted={sum(1 for s in stats if s.was_admitted)}"
                 f"/{len(stats)};"
                 f"rejected={sum(1 for s in stats if s.rejected)}")
        emit(f"admission.{tag}.{name}.total",
             res.mean_admission_delay() * 1e6,
             f"goodput={res.goodput(slo)};completed={res.completed};"
             f"makespan={res.makespan:.4f}s")
    base, gated = results["none"], results[gate]
    print(f"# admission {gate} vs none [{tag}]: steady p99 "
          f"{tenant_p99(base, 'steady'):.4f}s -> "
          f"{tenant_p99(gated, 'steady'):.4f}s; goodput "
          f"{base.goodput(slo)} -> {gated.goodput(slo)}", flush=True)


# ---------------------------------------------------------------------------
# beyond-paper: chunk-granularity preemption on the bursty two-tenant stream
# ---------------------------------------------------------------------------
def preemption_bench(vehicle: str = "sim", gate: str = "slo-adaptive",
                     controller: str = "backlog") -> None:
    """A/B the selected preemption controller against no preemption.

    Both configurations run the *same* chunked bursty two-tenant stream
    (``bursty_workload(n_chunks=4)`` — 4 yield points per TAO) under
    ``molding:adaptive`` and the selected admission gate (``none`` for an
    ungated A/B), so the delta isolates what displacing *running* work
    adds on top of gating *arrivals*.  Rows report per-tenant sojourn
    p50/p99 and displacement counts (the fairness surface: the steady
    tenant must never be the victim), plus goodput.  The composed
    ``--admission slo-adaptive --preemption backlog`` run is the
    acceptance A/B: steady-tenant p99 must improve over the gate alone
    with goodput non-regressing.
    """
    from repro.core import make_gate, make_preemption, percentile

    # 4 yield points per TAO: the preemptible variant of the same stream
    # the admission bench runs monolithic
    tag, slo, gate_kw, execute = _bursty_setup(vehicle, gate, n_chunks=4)
    tenant_p99 = _tenant_p99

    results = {}
    for name in ("none", controller):
        res = _median_run(
            lambda: execute(
                make_gate(gate, **gate_kw) if gate != "none" else None,
                None if name == "none" else make_preemption(name)),
            vehicle)
        results[name] = res
        displaced = res.preemptions_by_tenant()
        for tenant, stats in res.per_tenant().items():
            so = [s.sojourn for s in stats if s.done]
            emit(f"preempt.{tag}.{gate}+{name}.{tenant}",
                 percentile(so, 99) * 1e6,
                 f"p50={percentile(so, 50):.4f}s;"
                 f"p99={percentile(so, 99):.4f}s;"
                 f"displaced={displaced.get(tenant, 0)};"
                 f"admitted={sum(1 for s in stats if s.was_admitted)}"
                 f"/{len(stats)}")
        emit(f"preempt.{tag}.{gate}+{name}.total",
             (res.mean_preemption_delay() if res.n_preemptions else 0.0)
             * 1e6,
             f"goodput={res.goodput(slo)};completed={res.completed};"
             f"preemptions={res.n_preemptions};"
             f"makespan={res.makespan:.4f}s")
    base, treat = results["none"], results[controller]
    print(f"# preemption {controller} vs none [{tag}, admission={gate}]: "
          f"steady p99 {tenant_p99(base, 'steady'):.4f}s -> "
          f"{tenant_p99(treat, 'steady'):.4f}s; goodput "
          f"{base.goodput(slo)} -> {treat.goodput(slo)}; "
          f"victims by tenant {treat.preemptions_by_tenant()}", flush=True)


# ---------------------------------------------------------------------------
# beyond-paper: serving + training orchestrators
# ---------------------------------------------------------------------------
SERVE_SLO = {"steady": 0.25, "burst": 1.5}   # per-tenant sojourn targets (s)


def _serve_stats_row(st, slo) -> dict:
    """One A/B cell of the serving report (both vehicles share this shape)."""
    res = st.result
    return {
        "makespan_s": round(st.makespan, 6),
        "completed_requests": len(st.latencies),
        "rejected_requests": res.n_rejected,
        "tokens_per_s": round(st.tokens_per_s, 1),
        "tokens_per_s_by_tenant": {t: round(v, 1) for t, v in
                                   sorted(st.tokens_per_s_by_tenant.items())},
        "mean_sojourn_s": round(st.mean_latency, 6),
        "p99_sojourn_s": round(st.p99_latency, 6),
        "p99_sojourn_by_tenant": {t: round(v, 6) for t, v in
                                  sorted(st.p99_by_tenant().items())},
        "goodput": res.goodput(slo),
        "preemptions_by_tenant": {t: int(v) for t, v in
                                  sorted(res.preemptions_by_tenant().items())},
        "ptt_profiles": {typ: {"cells": len(cells),
                               "min_ms": round(min(cells.values()) * 1e3, 4),
                               "max_ms": round(max(cells.values()) * 1e3, 4)}
                         for typ, cells in sorted(st.ptt_profiles.items())
                         if cells},
    }


def serve_bench(vehicle: str = "both", admission: str = "token-bucket",
                preemption: str = "critical-boost",
                out: str = "benchmarks/BENCH_serve.json") -> None:
    """Serving on the multi-tenant engine: policy sweep + the bursty
    two-tenant admission x preemption A/B, on both execution vehicles.

    The simulator leg replays a bursty request trace
    (``bursty_serving_trace``) against the calibrated serve-phase kernel
    models; the threaded leg runs a scaled-down trace with *real jitted
    kernels* from the tenant zoo (transformer flavor for the steady tenant,
    raw Pallas-class kernels for the burst tenant), so its PTT columns are
    measured wall-clock times.  Four configurations each — {no gate, gate} x
    {no preemption, controller} — land in ``out`` (BENCH_serve.json) with
    per-tenant p99 sojourn, token throughput and goodput.
    """
    from repro.core import hikey960, make_gate, make_policy, make_preemption
    from repro.core.serve_orchestrator import (bursty_serving_trace,
                                               simulate_serving)

    spec = hikey960()
    slo = SERVE_SLO
    combos = [("none", "none"), (admission, "none"), ("none", preemption),
              (admission, preemption)]
    report = {
        "spec": "hikey960 (4 big + 4 LITTLE)",
        "slo_s": slo,
        "combos": [f"{g}+{c}" for g, c in combos],
        "policy_sweep": {},
        "ab": {"sim": {}, "threaded": {}},
    }

    # -- policy sweep (sim): does the learned placement still pay off? -----
    sweep_reqs = bursty_serving_trace(seed=0)
    for pol in ("homogeneous", "weight", "molding:weight"):
        st = simulate_serving(sweep_reqs, spec, make_policy(pol), seed=0)
        emit(f"serve.policy.{pol}", st.mean_latency * 1e6,
             f"{st.tokens_per_s:.0f}tok/s;p99={st.p99_latency:.3f}s")
        report["policy_sweep"][pol] = _serve_stats_row(st, slo)

    def gate_for(name, threaded):
        if name == "none":
            return None
        kw = {
            "token-bucket": dict(rate=40.0 if threaded else 60.0, burst=6,
                                 max_delay=0.5),
            "slo-adaptive": dict(slo=slo["steady"],
                                 slo_per_tenant={"burst": slo["burst"]},
                                 headroom=8.0),
        }.get(name, {})
        return make_gate(name, **kw)

    # -- A/B, simulator leg (calibrated kernel models, chunked prefill) ----
    if vehicle in ("sim", "both"):
        for gate_name, ctrl_name in combos:
            reqs = bursty_serving_trace(seed=1)
            st = simulate_serving(
                reqs, spec, make_policy("molding:weight"), seed=1,
                n_chunks=4,
                admission=gate_for(gate_name, threaded=False),
                preemption=(make_preemption(ctrl_name)
                            if ctrl_name != "none" else None))
            row = _serve_stats_row(st, slo)
            report["ab"]["sim"][f"{gate_name}+{ctrl_name}"] = row
            for tenant, p99 in sorted(st.p99_by_tenant().items()):
                emit(f"serve.ab.sim.{gate_name}+{ctrl_name}.{tenant}",
                     p99 * 1e6,
                     f"p99={p99:.4f}s;"
                     f"tok/s={st.tokens_per_s_by_tenant.get(tenant, 0):.0f};"
                     f"goodput={row['goodput']}")

    # -- A/B, threaded leg (real jitted kernels from the tenant zoo) -------
    if vehicle in ("threaded", "both"):
        from repro.core.serve_orchestrator import run_serving_workload_threaded
        from repro.launch.zoo import default_zoo, warm_zoo, zoo_binder

        zoo = default_zoo(slab_tokens=1024)
        warm_zoo(zoo)     # compile off the worker threads
        for gate_name, ctrl_name in combos:
            # scaled-down trace: real wall-clock arrivals + kernel times
            reqs = bursty_serving_trace(
                n_steady=10, steady_rate=30.0, n_burst=14, burst_at=0.15,
                burst_rate=300.0, steady_prompts=(512, 1024),
                steady_gens=(64,), burst_prompts=(2048, 4096),
                burst_gens=(64, 128), seed=1)
            st = run_serving_workload_threaded(
                reqs, spec, make_policy("molding:weight"), zoo_binder(zoo),
                seed=1, timeout_s=120.0,
                admission=gate_for(gate_name, threaded=True),
                preemption=(make_preemption(ctrl_name)
                            if ctrl_name != "none" else None))
            row = _serve_stats_row(st, slo)
            report["ab"]["threaded"][f"{gate_name}+{ctrl_name}"] = row
            for tenant, p99 in sorted(st.p99_by_tenant().items()):
                emit(f"serve.ab.threaded.{gate_name}+{ctrl_name}.{tenant}",
                     p99 * 1e6,
                     f"p99={p99:.4f}s;"
                     f"tok/s={st.tokens_per_s_by_tenant.get(tenant, 0):.0f};"
                     f"goodput={row['goodput']}")

    if out:
        path = pathlib.Path(out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
        print(f"# serve report -> {path}", flush=True)


def _impl_sim_models() -> dict:
    """Per-(type, impl) cost curves for the implementation A/B.

    Calibrated so the best variant differs per *cluster class* (the
    arXiv:2108.13871 shape the joint decision exists for): ``interpret``
    models a vectorizer-friendly variant that pays off on the wide big cores
    but loses to ``ref`` on LITTLE for matmul, and the reverse for sort;
    copy stays single-variant to show both kinds coexist in one DAG.  The
    bare-type entries keep the paper's Fig-4 curves as the fallback/static
    baseline.
    """
    from repro.core import BIG, LITTLE, KernelModel, paper_kernel_models

    models = paper_kernel_models()
    eff_mm = {1: 1.0, 2: 0.98, 4: 0.96, 8: 0.94}
    eff_sort = {1: 1.0, 2: 0.80, 4: 0.55, 8: 0.35}
    models[("matmul", "ref")] = KernelModel(
        t_ref=0.010, speed={BIG: 2.4, LITTLE: 1.0}, efficiency=eff_mm)
    models[("matmul", "interpret")] = KernelModel(
        t_ref=0.010, speed={BIG: 3.4, LITTLE: 0.7}, efficiency=eff_mm)
    models[("sort", "ref")] = KernelModel(
        t_ref=0.010, speed={BIG: 1.15, LITTLE: 1.0}, efficiency=eff_sort,
        cache_penalty=0.12)
    models[("sort", "interpret")] = KernelModel(
        t_ref=0.010, speed={BIG: 0.9, LITTLE: 1.4}, efficiency=eff_sort,
        cache_penalty=0.12)
    return models


def _measured_cells(ptt, spec) -> dict:
    """``{type: {impl: {"w<width>@<leader>": ms}}}`` over tried cells."""
    out: dict = {}
    for typ in ptt.types():
        table = ptt.table(typ)
        per_impl: dict = {}
        for impl in table.impls():
            snap = table.snapshot(impl=impl)
            cells = {f"w{width}@{leader}": round(float(snap[leader, wi]) * 1e3,
                                                 4)
                     for wi, width in enumerate(spec.widths)
                     for leader in range(spec.n_workers)
                     if snap[leader, wi] > 0.0}
            if cells:
                per_impl[impl] = cells
        out[typ] = per_impl
    return out


def _impl_choice_by_cluster(ptt, spec, names, width: int = 2) -> dict:
    """Which variant the joint decision now picks per cluster class."""
    out: dict = {}
    for typ in ptt.types():
        table = ptt.table(typ)
        row = {}
        for cls, workers in (("big", spec.big_workers),
                             ("little", spec.little_workers)):
            leader = next((w for w in workers if w % width == 0), None)
            if leader is None:
                continue
            impl, t = table.best_impl(leader, width, names)
            if t > 0.0:     # tried cells only: a 0.0 would be exploration
                row[cls] = {"impl": impl, "ewma_ms": round(t * 1e3, 4)}
        if row:
            out[typ] = row
    return out


def impl_bench(vehicle: str = "both",
               out: str = "benchmarks/BENCH_impl.json") -> None:
    """Implementation-variant A/B: static single-impl legs vs the joint
    (impl, width, leader) placement, on both vehicles.

    Gate first: the byte-identity pins (single-variant TAOs must schedule
    exactly as the pre-variant stack) are recomputed and any mismatch aborts
    the bench with a non-zero exit — that check is deterministic virtual-time
    scheduling, so CI failing on it is never a timing flake.  The simulator
    leg then A/Bs static-ref / static-interpret / joint on cluster-divergent
    per-(type, impl) cost curves (one shared Simulator, reset_learning()
    between legs so no profile leaks); the threaded leg serves the bursty
    two-tenant trace with the kernel tenant's zoo payloads bound once per
    host-available implementation (``multi_impl``), recording *measured*
    per-(class, impl, width) PTT cells.
    """
    from repro.core import (ImplVariant, Simulator, hikey960, make_policy,
                            percentile, random_workload)
    from repro.core.identity import PINNED_SIGNATURES, check_pins

    # -- byte-identity gate (deterministic: a failure is a refactor bug) ---
    violations = check_pins()
    for v in violations:
        print(f"# BYTE-IDENTITY VIOLATION: {v}", flush=True)
    if violations:
        sys.exit("impl bench aborted: single-variant schedules diverged "
                 "from the pinned pre-variant signatures")
    n_pins = len(PINNED_SIGNATURES)
    emit("impl.identity.pins", 0.0,
         f"{n_pins}/{n_pins} pinned signatures reproduced")

    spec = hikey960()
    report: dict = {
        "spec": "hikey960 (4 big + 4 LITTLE)",
        "identity": {"pinned": n_pins, "violations": violations},
        "sim": {}, "threaded": {},
    }

    # -- simulator leg: static vs joint on cluster-divergent curves --------
    if vehicle in ("sim", "both"):
        models = _impl_sim_models()
        names = ("ref", "interpret")

        def leg_workload(leg):
            # copy stays single-variant (no per-impl curve) in every leg:
            # the joint machinery must coexist with legacy TAOs in one DAG
            chosen = [leg] if leg in names else list(names)
            impls = {kt: [ImplVariant(n) for n in chosen]
                     for kt in ("matmul", "sort")}
            return random_workload(n_dags=6, rate=4.0, n_tasks=120, seed=2,
                                   width_hint=2, impls=impls)

        sim = Simulator(spec, make_policy("molding:adaptive"), seed=7,
                        kernel_models=models)
        for leg in ("ref", "interpret", "joint"):
            sim.reset_learning()     # legs must not leak learned profiles
            res = sim.run(leg_workload(leg))
            sojourns = [st.sojourn for st in res.per_dag.values() if st.done]
            row = {
                "makespan_s": round(res.makespan, 6),
                "completed": res.completed,
                "p99_sojourn_s": round(percentile(sojourns, 99), 6),
            }
            if leg == "joint":
                row["measured_cells"] = _measured_cells(sim.core.ptt, spec)
                row["impl_choice_by_cluster"] = _impl_choice_by_cluster(
                    sim.core.ptt, spec, names)
            report["sim"][leg] = row
            emit(f"impl.sim.{leg}", res.makespan / max(res.completed, 1) * 1e6,
                 f"makespan={res.makespan:.4f}s;"
                 f"p99={row['p99_sojourn_s']:.4f}s")
        best_static = min(report["sim"]["ref"]["makespan_s"],
                          report["sim"]["interpret"]["makespan_s"])
        report["sim"]["joint_vs_best_static"] = round(
            report["sim"]["joint"]["makespan_s"] / best_static, 4)

    # -- threaded leg: real kernels, measured per-(class, impl, width) -----
    if vehicle in ("threaded", "both"):
        from repro.core.serve_orchestrator import (
            bursty_serving_trace, run_serving_workload_threaded)
        from repro.kernels import ops
        from repro.launch.zoo import default_zoo, warm_zoo, zoo_binder

        avail = [im.name for im in ops.available_impls()]
        report["threaded"]["host_impls"] = avail
        for leg, multi in (("static", False), ("joint", True)):
            zoo = default_zoo(slab_tokens=1024, multi_impl=multi)
            warm_zoo(zoo)
            reqs = bursty_serving_trace(
                n_steady=8, steady_rate=30.0, n_burst=10, burst_at=0.15,
                burst_rate=300.0, steady_prompts=(512, 1024),
                steady_gens=(64,), burst_prompts=(2048, 4096),
                burst_gens=(64, 128), seed=1)
            st = run_serving_workload_threaded(
                reqs, spec, make_policy("molding:weight"), zoo_binder(zoo),
                seed=1, timeout_s=120.0)
            # group the measured cells per impl ((worker, width) keys carry
            # the default impl; (worker, width, impl) the variants)
            cells_by_impl: dict = {}
            for typ, cells in st.ptt_profiles.items():
                per: dict = {}
                for key, v in cells.items():
                    w, wd = key[0], key[1]
                    impl = key[2] if len(key) == 3 else "default"
                    per.setdefault(impl, {})[f"w{wd}@{w}"] = round(v * 1e3, 4)
                cells_by_impl[typ] = per
            fastest = {typ: {im: round(min(c.values()), 4)
                             for im, c in per.items()}
                       for typ, per in cells_by_impl.items() if per}
            report["threaded"][leg] = {
                "completed_requests": len(st.latencies),
                "tokens_per_s": round(st.tokens_per_s, 1),
                "p99_sojourn_s": round(st.p99_latency, 6),
                "measured_cells": cells_by_impl,
                "fastest_ms_by_impl": fastest,
            }
            emit(f"impl.threaded.{leg}", st.mean_latency * 1e6,
                 f"tok/s={st.tokens_per_s:.0f};p99={st.p99_latency:.4f}s;"
                 f"impls={'+'.join(avail) if multi else 'auto'}")

    if out:
        path = pathlib.Path(out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
        print(f"# impl report -> {path}", flush=True)


def _slo_attainment(res, slo: dict) -> dict:
    """Per-tenant fraction of completed DAGs whose sojourn met the SLO."""
    out = {}
    for tenant, stats in res.per_tenant().items():
        done = [s for s in stats if s.done]
        if not done:
            out[tenant] = 0.0
            continue
        out[tenant] = round(
            sum(1 for s in done if s.sojourn <= slo[tenant]) / len(done), 4)
    return out


def _assert_conservation(res, workload, where: str) -> None:
    """Every admitted chunk completes exactly once: all admitted DAGs are
    done, the completion counter matches the admitted TAO total, and no
    TAO's ChunkCursor holds unclaimed chunks.  A violation is a scheduler
    bug (lost or stranded work), never a timing flake — abort hard."""
    admitted = [s for s in res.per_dag.values() if s.was_admitted]
    expect = sum(s.n_taos for s in admitted)
    not_done = [s.dag_id for s in admitted if not s.done]
    leftover = sum(
        1 for a in workload.arrivals() for t in a.dag.nodes
        if t.cursor is not None and t.cursor.unclaimed > 0)
    if res.completed != expect or not_done or leftover:
        sys.exit(f"CHUNK CONSERVATION VIOLATION ({where}): "
                 f"completed={res.completed} expected={expect} "
                 f"unfinished_dags={not_done[:8]} "
                 f"taos_with_unclaimed_chunks={leftover}")


def chaos_bench(vehicle: str = "both",
                out: str = "benchmarks/BENCH_chaos.json") -> None:
    """Fleet-scale chaos A/B: the bursty two-tenant stream under a
    mid-burst group kill plus straggler onset, legs {no-chaos, chaos,
    chaos+gate+preemption} on both vehicles.

    Gate first: the byte-identity pins are recomputed — chaos disabled
    must schedule exactly as the pre-chaos stack, and a mismatch aborts
    before any timing runs.  The simulator leg is fully deterministic
    (virtual-time fault injection); the threaded leg is a wall-clock smoke
    whose *assertions* are timing-free (chunk conservation: every payload
    chunk executed exactly once, every admitted TAO committed) while its
    latency numbers are informational only.
    """
    import threading
    import time as _time

    from repro.core import (ChunkedWork, Simulator, ThreadedRuntime,
                            bursty_workload, fleet, hikey960, make_gate,
                            make_policy, make_preemption)
    from repro.core.chaos import ChaosPlanBuilder
    from repro.core.identity import PINNED_SIGNATURES, check_pins

    # -- byte-identity gate (deterministic: a failure is a refactor bug) ---
    violations = check_pins()
    for v in violations:
        print(f"# BYTE-IDENTITY VIOLATION: {v}", flush=True)
    if violations:
        sys.exit("chaos bench aborted: chaos-disabled schedules diverged "
                 "from the pinned pre-chaos signatures")
    n_pins = len(PINNED_SIGNATURES)
    emit("chaos.identity.pins", 0.0,
         f"{n_pins}/{n_pins} pinned signatures reproduced")

    report: dict = {
        "identity": {"pinned": n_pins, "violations": violations},
        "sim": {}, "threaded": {},
    }

    # -- simulator leg: deterministic virtual-time fault injection ---------
    if vehicle in ("sim", "both"):
        spec = fleet(48, 16)
        slo = {"steady": 0.5, "burst": 3.0}
        # mid-burst (burst lands at t=0.5 on this stream): kill four BIG
        # groups of 8 outright, degrade the remaining two to 0.25x
        # (straggler onset) — the whole BIG fleet impaired until repair
        plan = (ChaosPlanBuilder()
                .kill(0.55, range(0, 32))
                .degrade(0.55, range(32, 48), 0.25)
                .recover(4.5, range(0, 48))
                .build())
        report["sim"]["plan"] = [
            {"at": e.at, "action": e.action, "workers": list(e.workers),
             "speed": e.speed} for e in plan.events]

        def sim_leg(chaos, gate, ctrl):
            # heavier burst than the admission bench's historical stream:
            # the fault window must overlap genuine contention, or 64-way
            # water-filling silently absorbs the lost capacity
            wl = bursty_workload(n_steady=10, steady_rate=2.0,
                                 steady_tasks=60, n_burst=30, burst_at=0.5,
                                 burst_rate=100.0, burst_tasks=250, seed=1,
                                 n_chunks=4)
            sim = Simulator(spec, make_policy("molding:adaptive"), seed=1)
            res = sim.run_workload(wl, admission=gate, preemption=ctrl,
                                   chaos=chaos)
            return res, wl

        legs = (
            ("no-chaos", None, None, None),
            ("chaos", plan, None, None),
            ("chaos+gate+preemption", plan,
             make_gate("slo-adaptive", slo=0.5,
                       slo_per_tenant={"burst": 3.0}),
             make_preemption("backlog")),
        )
        for name, chaos, gate, ctrl in legs:
            res, wl = sim_leg(chaos, gate, ctrl)
            _assert_conservation(res, wl, f"sim/{name}")
            attain = _slo_attainment(res, slo)
            row = {
                "makespan_s": round(res.makespan, 6),
                "completed": res.completed,
                "admitted_dags": sum(1 for s in res.per_dag.values()
                                     if s.was_admitted),
                "total_dags": len(res.per_dag),
                "slo_attainment": attain,
                "failure_requeues": res.failure_requeues_by_tenant(),
            }
            report["sim"][name] = row
            emit(f"chaos.sim.{name.replace('+', '_')}",
                 res.makespan / max(res.completed, 1) * 1e6,
                 f"makespan={res.makespan:.4f}s;"
                 f"attain={';'.join(f'{t}={v:.2f}' for t, v in sorted(attain.items()))};"
                 f"requeues={sum(row['failure_requeues'].values())}")

    # -- threaded leg: wall-clock smoke, timing-free conservation asserts --
    if vehicle in ("threaded", "both"):
        spec = hikey960()
        slo = {"steady": 0.12, "burst": 0.6}
        n_chunks = 4
        # wall-clock offsets sized so the kill lands inside the burst on a
        # typical host; if the host is fast/slow enough to miss it the
        # conservation asserts still hold (they are timing-independent)
        plan = (ChaosPlanBuilder()
                .kill(0.08, [4, 5])
                .degrade(0.08, [6], 0.3)
                .recover(0.6, [4, 5, 6])
                .build())
        report["threaded"]["plan"] = [
            {"at": e.at, "action": e.action, "workers": list(e.workers),
             "speed": e.speed} for e in plan.events]

        def threaded_leg(chaos, gate, ctrl):
            counts: dict = {}
            lock = threading.Lock()
            wl = bursty_workload(n_steady=6, steady_rate=15.0,
                                 steady_tasks=25, n_burst=8, burst_at=0.05,
                                 burst_rate=200.0, burst_tasks=60, seed=2,
                                 n_chunks=n_chunks)
            for arr in wl:
                for node in arr.dag.nodes:
                    def fn(i, key=(arr.dag_id, node.id)):
                        with lock:
                            counts[(key, i)] = counts.get((key, i), 0) + 1
                        _time.sleep(0.001 / n_chunks)
                    node.work = ChunkedWork(fn, n_chunks)
            rt = ThreadedRuntime(spec, make_policy("molding:adaptive"),
                                 seed=1)
            res = rt.run_workload(wl, timeout_s=120.0, admission=gate,
                                  preemption=ctrl, chaos=chaos)
            return res, wl, counts

        legs = (
            ("no-chaos", None, None, None),
            ("chaos", plan, None, None),
            ("chaos+gate+preemption", plan,
             make_gate("slo-adaptive", slo=0.12,
                       slo_per_tenant={"burst": 0.6}, headroom=16.0),
             make_preemption("backlog")),
        )
        for name, chaos, gate, ctrl in legs:
            res, wl, counts = threaded_leg(chaos, gate, ctrl)
            _assert_conservation(res, wl, f"threaded/{name}")
            # the strongest claim only the threaded vehicle can make: each
            # (tao, chunk) payload ran exactly once — nothing lost to the
            # kill, nothing replayed by the re-admission
            dup = [k for k, c in counts.items() if c != 1]
            admitted = [s for s in res.per_dag.values() if s.was_admitted]
            expect_chunks = sum(s.n_taos for s in admitted) * n_chunks
            if dup or len(counts) != expect_chunks:
                sys.exit(f"CHUNK CONSERVATION VIOLATION (threaded/{name}): "
                         f"{len(dup)} duplicated chunks, "
                         f"{len(counts)}/{expect_chunks} executed")
            attain = _slo_attainment(res, slo)
            row = {
                "makespan_s": round(res.makespan, 6),
                "completed": res.completed,
                "chunks_executed_once": len(counts),
                "slo_attainment": attain,
                "failure_requeues": res.failure_requeues_by_tenant(),
            }
            report["threaded"][name] = row
            emit(f"chaos.threaded.{name.replace('+', '_')}",
                 res.makespan / max(res.completed, 1) * 1e6,
                 f"chunks={len(counts)}/1x;"
                 f"attain={';'.join(f'{t}={v:.2f}' for t, v in sorted(attain.items()))};"
                 f"requeues={sum(row['failure_requeues'].values())}")

    if out:
        path = pathlib.Path(out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
        print(f"# chaos report -> {path}", flush=True)


# KV bytes per token for the simulator locality leg: footprints land in the
# 32-256MB range on the bursty trace, so a modeled move at the default 8GiB/s
# costs 4-31ms — the same order as the serve-phase t_refs (the regime where
# affinity-aware placement actually matters)
SIM_KV_BYTES_PER_TOKEN = 65536.0


def _locality_row(st, loc) -> dict:
    """One A/B cell of the locality report (shared by both vehicles)."""
    res = st.result
    hit_rate = res.cache_hit_rate()
    return {
        "makespan_s": round(st.makespan, 6),
        "completed_requests": len(st.latencies),
        "locality_hits": res.locality_hits(),
        "locality_misses": res.locality_misses(),
        "cache_hit_rate": (round(hit_rate, 4)
                           if hit_rate == hit_rate else None),
        "moved_mb": round(res.moved_bytes() / 1e6, 3),
        "moved_mb_by_tenant": {t: round(v / 1e6, 3) for t, v in
                               sorted(res.moved_bytes_by_tenant().items())},
        "p99_sojourn_s": round(st.p99_latency, 6),
        "p99_sojourn_by_tenant": {t: round(v, 6) for t, v in
                                  sorted(st.p99_by_tenant().items())},
        "movement_table_cells": len(loc.movement_table()),
    }


def _assert_moved_bytes(res, spec, kv_per_token: float, where: str) -> None:
    """Moved-bytes conservation: the bytes the tracker accounted must equal
    an independent replay of the residency automaton over the executed
    trace (off-resident placements x footprint bytes).  Deterministic on
    both vehicles — a mismatch is a double-count or a lost placement,
    never a timing flake — abort hard."""
    from repro.core.locality import replay_moved_bytes

    fps = {did: (st.tokens * kv_per_token, True)
           for did, st in res.per_dag.items()}
    replayed = replay_moved_bytes(res.trace, spec, fps)
    accounted = res.moved_bytes()
    if abs(replayed - accounted) > max(1.0, 1e-9 * accounted):
        sys.exit(f"MOVED-BYTES CONSERVATION VIOLATION ({where}): "
                 f"accounted={accounted} replayed={replayed}")


def locality_bench(vehicle: str = "both",
                   out: str = "benchmarks/BENCH_locality.json") -> None:
    """Data-aware placement A/B: KV-cache affinity {on, off} on the bursty
    two-tenant serving trace, both vehicles.

    Gate first: the byte-identity pins are recomputed — zero-footprint TAOs
    (and the explicit ``serve.locality-off`` leg) must schedule exactly as
    the pre-locality stack, and a mismatch aborts before any timing runs.
    Both legs carry real KV-cache footprints and both pay for cache moves
    (modeled transfer time on the simulator, a measured host byte-copy on
    the threaded vehicle); the A/B knob is whether *placement* charges
    ``move_cost`` (``LocalityTracker.charge``).  Each leg asserts
    moved-bytes conservation against an independent trace replay — a
    deterministic check on both vehicles, never a timing flake.
    """
    from repro.core import Simulator, hikey960, make_policy
    from repro.core.identity import PINNED_SIGNATURES, check_pins
    from repro.core.serve_orchestrator import (_stats_from,
                                               build_serving_workload,
                                               bursty_serving_trace,
                                               serving_kernel_models)

    # -- byte-identity gate (deterministic: a failure is a refactor bug) ---
    violations = check_pins()
    for v in violations:
        print(f"# BYTE-IDENTITY VIOLATION: {v}", flush=True)
    if violations:
        sys.exit("locality bench aborted: footprint-free schedules diverged "
                 "from the pinned pre-locality signatures")
    n_pins = len(PINNED_SIGNATURES)
    emit("locality.identity.pins", 0.0,
         f"{n_pins}/{n_pins} pinned signatures reproduced")

    spec = hikey960()
    report: dict = {
        "spec": "hikey960 (4 big + 4 LITTLE)",
        "identity": {"pinned": n_pins, "violations": violations},
        "sim": {}, "threaded": {},
    }

    # -- simulator leg: deterministic modeled transfer costs ---------------
    if vehicle in ("sim", "both"):
        report["sim"]["kv_bytes_per_token"] = SIM_KV_BYTES_PER_TOKEN
        for leg, charge in (("affinity-on", True), ("affinity-off", False)):
            reqs = bursty_serving_trace(seed=1)
            wl, by_dag = build_serving_workload(
                reqs, n_chunks=4,
                kv_bytes_per_token=SIM_KV_BYTES_PER_TOKEN)
            sim = Simulator(spec, make_policy("molding:weight"),
                            kernel_models=serving_kernel_models(), seed=1)
            sim.core.locality.charge = charge
            res = sim.run_workload(wl)
            _assert_moved_bytes(res, spec, SIM_KV_BYTES_PER_TOKEN,
                                f"sim/{leg}")
            st = _stats_from(res, by_dag, sim.core)
            row = _locality_row(st, sim.core.locality)
            report["sim"][leg] = row
            emit(f"locality.sim.{leg}", st.mean_latency * 1e6,
                 f"hit_rate={row['cache_hit_rate']};"
                 f"moved={row['moved_mb']:.0f}MB;"
                 f"steady_p99="
                 f"{row['p99_sojourn_by_tenant'].get('steady', 0):.4f}s")

    # -- threaded leg: measured host byte-copies on cache misses -----------
    if vehicle in ("threaded", "both"):
        from repro.core import ThreadedRuntime
        from repro.launch.zoo import default_zoo, warm_zoo, zoo_binder

        zoo = default_zoo(slab_tokens=1024)
        warm_zoo(zoo)     # compile off the worker threads
        # per-token bytes from the zoo's real cache slab, scaled up to the
        # footprint a production-sized model would carry for the same token
        # counts (the smoke models are ~64x under-sized stand-ins) — this
        # puts cache moves in the same order as the measured kernel times,
        # the regime the sim leg models and the one where affinity matters
        kv_per_token = next(iter(zoo.values())).kv_bytes_per_token() * 64.0
        report["threaded"]["kv_bytes_per_token"] = kv_per_token
        for leg, charge in (("affinity-on", True), ("affinity-off", False)):
            def make_run(charge=charge, leg=leg):
                reqs = bursty_serving_trace(
                    n_steady=10, steady_rate=30.0, n_burst=14, burst_at=0.15,
                    burst_rate=300.0, steady_prompts=(512, 1024),
                    steady_gens=(64,), burst_prompts=(2048, 4096),
                    burst_gens=(64, 128), seed=1)
                wl, by_dag = build_serving_workload(
                    reqs, bind=zoo_binder(zoo),
                    kv_bytes_per_token=kv_per_token)
                rt = ThreadedRuntime(spec, make_policy("molding:weight"),
                                     seed=1)
                rt.core.locality.charge = charge
                res = rt.run_workload(wl, timeout_s=120.0)
                # conservation holds on EVERY run, not just the reported one
                _assert_moved_bytes(res, spec, kv_per_token,
                                    f"threaded/{leg}")
                return res, by_dag, rt.core
            # real wall clock on a possibly-noisy host: report the
            # median-steady-p99 run of 3 (same discipline as _median_run)
            runs = [make_run() for _ in range(3)]
            runs.sort(key=lambda r: _tenant_p99(r[0], "steady"))
            res, by_dag, core = runs[len(runs) // 2]
            st = _stats_from(res, by_dag, core)
            row = _locality_row(st, core.locality)
            report["threaded"][leg] = row
            emit(f"locality.threaded.{leg}", st.mean_latency * 1e6,
                 f"hit_rate={row['cache_hit_rate']};"
                 f"moved={row['moved_mb']:.0f}MB;"
                 f"steady_p99="
                 f"{row['p99_sojourn_by_tenant'].get('steady', 0):.4f}s")

    if out:
        path = pathlib.Path(out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
        print(f"# locality report -> {path}", flush=True)


def train_bench() -> None:
    from repro.core import fleet, make_policy
    from repro.core.train_orchestrator import simulate_training

    for n_groups, mb in ((64, 32), (512, 256), (1024, 512)):
        spec = fleet(n_groups * 3 // 4, n_groups // 4)
        for pol in ("homogeneous", "molding:crit-ptt"):
            res = simulate_training(n_steps=5, n_microbatches=mb, spec=spec,
                                    policy=make_policy(pol), seed=0)
            emit(f"train.groups{n_groups}.mb{mb}.{pol}",
                 res.makespan / 5 * 1e6,
                 f"{res.throughput:.0f}taos/s;util={res.utilization:.2f}")


# ---------------------------------------------------------------------------
# roofline (from dry-run artifacts)
# ---------------------------------------------------------------------------
def roofline(dryrun_dir: str = "experiments/dryrun/single_pod") -> None:
    """Roofline terms of the dry-run cells against the peaks of the chip
    the production mesh stands for."""
    from repro.launch.device_peaks import device_peaks
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND

    peaks = device_peaks(PRODUCTION_DEVICE_KIND)
    d = pathlib.Path(dryrun_dir)
    if not d.exists():
        print(f"# roofline: {d} missing (run repro.launch.dryrun first)",
              flush=True)
        return
    for path in sorted(d.glob("*.json")):
        rec = json.loads(path.read_text())
        if rec.get("status") != "ok":
            continue
        # per-device quantities (cost analysis of the SPMD module)
        t_comp = rec["flops"] / peaks.flops_bf16
        t_mem = rec["bytes_accessed"] / peaks.hbm_bw
        coll = rec.get("collectives", {})
        coll_bytes = sum(v for k, v in coll.items() if k != "count")
        t_coll = coll_bytes / peaks.ici_link_bw
        dom = max((t_comp, "compute"), (t_mem, "memory"),
                  (t_coll, "collective"))[1]
        emit(f"roofline.{rec['arch']}.{rec['shape']}",
             max(t_comp, t_mem, t_coll) * 1e6,
             f"comp={t_comp:.4f}s;mem={t_mem:.4f}s;coll={t_coll:.4f}s;"
             f"bound={dom}")


# ---------------------------------------------------------------------------
SECTIONS = ("all", "fig4", "fig6", "tab", "multi-dag", "multidag", "serve",
            "impl", "chaos", "locality", "train", "roofline")


VEHICLES = ("sim", "threaded")


def main() -> None:
    # Selectors: positional section names and/or `--workload <name>`
    # (`run.py --workload multi-dag` is the documented stream-bench entry);
    # all selected sections run, unknown names abort with the valid list.
    # `--vehicle {sim,threaded}` picks the multi-dag execution vehicle;
    # `--shards N` routes the multi-dag stream through the sharded
    # scheduler (both vehicles);
    # `--admission {none,token-bucket,slo-adaptive}` replaces the multi-dag
    # policy sweep with the bursty-tenant admission A/B bench;
    # `--preemption {none,backlog,critical-boost}` composes with it and
    # runs the running-work displacement A/B instead.
    from repro.core import ALL_GATE_NAMES, ALL_PREEMPTION_NAMES
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = sys.argv[1:]
    selected: list[str] = []
    vehicle = "sim"
    vehicle_set = False       # serve defaults to both vehicles unless set
    admission = "none"
    preemption = "none"
    shards: int | None = None
    out = None                # --out: serve report path override
    i = 0
    while i < len(args):
        if args[i] == "--workload":
            i += 1
            if i >= len(args):
                sys.exit("--workload needs a value (e.g. --workload multi-dag)")
            selected.append(args[i])
        elif args[i].startswith("--workload="):
            selected.append(args[i].split("=", 1)[1])
        elif args[i] == "--vehicle":
            i += 1
            if i >= len(args):
                sys.exit("--vehicle needs a value (sim or threaded)")
            vehicle = args[i]
            vehicle_set = True
        elif args[i].startswith("--vehicle="):
            vehicle = args[i].split("=", 1)[1]
            vehicle_set = True
        elif args[i] == "--out":
            i += 1
            if i >= len(args):
                sys.exit("--out needs a path (e.g. --out /tmp/serve.json)")
            out = args[i]
        elif args[i].startswith("--out="):
            out = args[i].split("=", 1)[1]
        elif args[i] == "--admission":
            i += 1
            if i >= len(args):
                sys.exit("--admission needs a value "
                         "(e.g. --admission slo-adaptive)")
            admission = args[i]
        elif args[i].startswith("--admission="):
            admission = args[i].split("=", 1)[1]
        elif args[i] == "--preemption":
            i += 1
            if i >= len(args):
                sys.exit("--preemption needs a value "
                         "(e.g. --preemption backlog)")
            preemption = args[i]
        elif args[i].startswith("--preemption="):
            preemption = args[i].split("=", 1)[1]
        elif args[i] == "--shards":
            i += 1
            if i >= len(args):
                sys.exit("--shards needs a count (e.g. --shards 4)")
            shards = int(args[i])
        elif args[i].startswith("--shards="):
            shards = int(args[i].split("=", 1)[1])
        else:
            selected.append(args[i])
        i += 1
    if vehicle not in VEHICLES:
        sys.exit(f"unknown vehicle: {vehicle} "
                 f"(choose from: {', '.join(VEHICLES)})")
    if admission not in ALL_GATE_NAMES:
        sys.exit(f"unknown admission gate: {admission} "
                 f"(choose from: {', '.join(ALL_GATE_NAMES)})")
    if preemption not in ALL_PREEMPTION_NAMES:
        sys.exit(f"unknown preemption controller: {preemption} "
                 f"(choose from: {', '.join(ALL_PREEMPTION_NAMES)})")
    if shards is not None and shards < 1:
        sys.exit("--shards must be >= 1")
    unknown = [s for s in selected if s not in SECTIONS]
    if unknown:
        sys.exit(f"unknown section(s): {', '.join(unknown)} "
                 f"(choose from: {', '.join(SECTIONS)})")
    which = set(selected) or {"all"}

    def sel(*names: str) -> bool:
        return bool(which & ({"all"} | set(names)))

    print("name,us_per_call,derived")
    t0 = time.time()
    if sel("fig4"):
        fig4_kernel_profile()
        fig4_real_kernels()
    if sel("fig6"):
        fig6_random_dags()
    if sel("tab"):
        tables_molding()
    if sel("multi-dag", "multidag"):
        if preemption != "none":
            preemption_bench(vehicle=vehicle, gate=admission,
                             controller=preemption)
        elif admission == "none":
            multi_dag_bench(vehicle=vehicle, shards=shards)
        else:
            admission_bench(vehicle=vehicle, gate=admission)
    if sel("serve"):
        # serve A/Bs both vehicles unless --vehicle narrows it; the gate /
        # controller default to the acceptance pair when not overridden
        serve_bench(vehicle=vehicle if vehicle_set else "both",
                    admission=(admission if admission != "none"
                               else "token-bucket"),
                    preemption=(preemption if preemption != "none"
                                else "critical-boost"),
                    out=out or "benchmarks/BENCH_serve.json")
    if sel("impl"):
        # implementation-variant A/B: byte-identity gate + static-vs-joint
        # placement on both vehicles (--vehicle narrows, --out overrides)
        impl_bench(vehicle=vehicle if vehicle_set else "both",
                   out=out or "benchmarks/BENCH_impl.json")
    if sel("chaos"):
        # chaos A/B: byte-identity gate + {no-chaos, chaos, chaos+gate+
        # preemption} with chunk-conservation asserts (--vehicle narrows)
        chaos_bench(vehicle=vehicle if vehicle_set else "both",
                    out=out or "benchmarks/BENCH_chaos.json")
    if sel("locality"):
        # data-aware placement A/B: byte-identity gate + KV-cache affinity
        # {on, off} with moved-bytes conservation asserts (--vehicle narrows)
        locality_bench(vehicle=vehicle if vehicle_set else "both",
                       out=out or "benchmarks/BENCH_locality.json")
    if sel("train"):
        train_bench()
    if sel("roofline"):
        roofline()
    print(f"# total {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
