"""Chunk-granularity preemption: the unified yield-point execution core.

The tentpole contracts pinned here:

* **Chunk conservation** — under arbitrary displacement, every chunk of
  every TAO runs exactly once on the threaded vehicle (counting chunk
  callables) and the simulator's cursors partition ``[0, n_chunks)``
  across trace segments (preempted segments + exactly one completing
  segment per TAO).
* **`preemption=none` byte-identity** — passing no controller, the
  ``none`` controller, or nothing at all produces byte-identical
  simulator schedules (the same standard as the PR-3 fast/slow gate).
* **Decision parity** — controllers are stateless deterministic
  functions: fed the same observation trace, two instances (the "sim"
  and "threaded" consults) pick the same victims.
* **Seeded determinism** — a preempting simulator run replays
  byte-identically for a fixed seed.
* **Fairness** — under ``backlog`` on the bursty two-tenant stream the
  steady tenant is never the displacement victim
  (``WorkloadResult.preemptions_by_tenant``).
"""
import dataclasses
import threading
import time

import pytest

from repro.core import (BacklogPreemption, ChunkCursor, ChunkedWork,
                        CriticalBoostPreemption, LoadSignals, MoldingPolicy,
                        RunningView, Simulator, TAO, TaoDag, ThreadedRuntime,
                        Workload, bursty_workload, chunk_count, fleet,
                        hikey960, make_gate, make_policy, make_preemption,
                        percentile, random_dag)
from repro.core.preemption import NoPreemption, ensure_cursor


def _trace_key(res):
    return [dataclasses.astuple(t) for t in res.trace]


def _chunked_bursty(seed=1, chunks=4, **kw):
    return bursty_workload(seed=seed, n_chunks=chunks, **kw)


def _slo_gate():
    return make_gate("slo-adaptive", slo=0.5, slo_per_tenant={"burst": 3.0})


# --------------------------------------------------------------- ChunkCursor
def test_chunk_cursor_claims_yield_and_continuation():
    c = ChunkCursor(4)
    assert c.claim() == 0 and c.claim() == 1
    c.request_yield()
    assert c.yield_requested and c.claim() is None
    assert c.unclaimed == 2 and c.remaining_fraction == 0.5
    c.rearm()                       # continuation reopens where it stopped
    assert c.preemptions == 1 and not c.yield_requested
    assert c.claim() == 2 and c.claim() == 3 and c.claim() is None
    assert c.unclaimed == 0


def test_chunk_cursor_advance_clamps_and_clear_yield():
    c = ChunkCursor(3)
    c.advance(2)
    assert c.next_chunk == 2
    c.advance(5)
    assert c.next_chunk == 3 and c.unclaimed == 0
    c.request_yield()
    c.clear_yield()                 # raced with natural completion: no count
    assert not c.yield_requested and c.preemptions == 0


def test_chunk_count_prefers_payload_over_field():
    t = TAO(type="matmul")
    assert chunk_count(t) == 1
    t.n_chunks = 6
    assert chunk_count(t) == 6
    t.work = ChunkedWork(lambda i: None, 3)     # payload wins
    assert chunk_count(t) == 3
    cur = ensure_cursor(t)
    assert cur.n_chunks == 3 and ensure_cursor(t) is cur


# ---------------------------------------------------- threaded conservation
def test_threaded_chunk_conservation_under_preemption():
    """Every chunk of every TAO runs exactly once even while the backlog
    controller displaces the burst tenant's running TAOs."""
    wl = bursty_workload(n_steady=4, steady_rate=15.0, steady_tasks=20,
                         n_burst=6, burst_at=0.05, burst_rate=200.0,
                         burst_tasks=60, seed=2)
    counts, lock = {}, threading.Lock()
    for arr in wl:
        for node in arr.dag.nodes:
            def chunk(i, key=(arr.dag_id, node.id)):
                with lock:
                    counts[(key, i)] = counts.get((key, i), 0) + 1
                time.sleep(0.0003)
            node.work = ChunkedWork(chunk, 4)
    rt = ThreadedRuntime(hikey960(), make_policy("molding:adaptive"), seed=1)
    res = rt.run_workload(wl, timeout_s=120.0, admission=_slo_gate(),
                          preemption=make_preemption("backlog"))
    total = wl.total_taos()
    assert res.completed == total
    assert len(counts) == total * 4                 # none lost
    assert all(v == 1 for v in counts.values())     # none ran twice
    # per-DAG completion is intact despite displacement
    for st in res.per_dag.values():
        assert st.done and st.completed == st.n_taos
    # preempted trace segments carry the flag; completions appear once each
    finals = [r for r in res.trace if not r.preempted]
    assert len(finals) == total
    assert {(r.dag_id, r.tao_id) for r in finals} == \
        {(a.dag_id, n.id) for a in wl for n in a.dag.nodes}


def test_threaded_preemption_none_matches_no_controller():
    """The `none` controller completes the same stream with zero
    displacements and untouched accounting (real wall-clock runs are not
    bit-reproducible, so the threaded byte-identity claim is pinned on
    the simulator; here we pin the no-op contract)."""
    def run(ctrl):
        wl = _chunked_bursty(seed=3, n_steady=3, steady_tasks=15, n_burst=3,
                             burst_tasks=30)
        rt = ThreadedRuntime(hikey960(), make_policy("molding:adaptive"),
                             seed=2)
        return wl, rt.run_workload(wl, timeout_s=60.0, preemption=ctrl)

    for ctrl in (None, make_preemption("none")):
        wl, res = run(ctrl)
        assert res.completed == wl.total_taos()
        assert res.n_preemptions == 0
        assert res.preemptions_by_tenant() == {"steady": 0, "burst": 0}
        assert all(not r.preempted for r in res.trace)
        assert all(s.preemption_delay == 0.0 for s in res.per_dag.values())


# --------------------------------------------------------- sim conservation
def test_sim_chunk_conservation_under_preemption():
    sim = Simulator(fleet(48, 16), make_policy("molding:adaptive"), seed=1)
    wl = _chunked_bursty(seed=1)
    res = sim.run_workload(wl, admission=_slo_gate(),
                           preemption=make_preemption("backlog"))
    total = wl.total_taos()
    assert res.completed == sum(len(a.dag) for a in wl
                                if res.per_dag[a.dag_id].was_admitted)
    assert res.n_preemptions > 0
    # each TAO: zero or more preempted segments then exactly one completion,
    # in non-overlapping time order, and its cursor is fully consumed
    segs = {}
    for r in res.trace:
        segs.setdefault((r.dag_id, r.tao_id), []).append(r)
    n_final = 0
    for (dag_id, tao_id), recs in segs.items():
        assert [r for r in recs if not r.preempted][-1] is recs[-1]
        assert sum(1 for r in recs if not r.preempted) == 1
        n_final += 1
        for a, b in zip(recs, recs[1:]):
            assert a.end <= b.start + 1e-9
    assert n_final == res.completed
    for a in wl:
        for node in a.dag.nodes:
            if node.cursor is not None:
                assert node.cursor.unclaimed == 0
    # displaced DAGs carry the ledger; delays are non-negative
    assert sum(s.preempted_count for s in res.per_dag.values()) == \
        res.n_preemptions
    assert all(s.preemption_delay >= 0.0 for s in res.per_dag.values())


def test_sim_preemption_none_byte_identical_to_baseline():
    """PR-3-gate standard: with `none` (or no controller at all) the
    simulator schedule is byte-identical to the pre-preemption baseline —
    ungated and through the slo-adaptive gate alike."""
    def run(gate, ctrl):
        sim = Simulator(fleet(24, 8), make_policy("molding:adaptive"), seed=5)
        wl = _chunked_bursty(seed=4, n_steady=5, steady_tasks=25, n_burst=5,
                             burst_tasks=60)
        return sim.run_workload(wl, admission=gate, preemption=ctrl)

    for gated in (False, True):
        base = run(_slo_gate() if gated else None, None)
        for ctrl in (make_preemption("none"), NoPreemption()):
            res = run(_slo_gate() if gated else None, ctrl)
            assert _trace_key(res) == _trace_key(base)
            assert res.makespan == base.makespan
            assert {i: s.sojourn for i, s in res.per_dag.items()} == \
                   {i: s.sojourn for i, s in base.per_dag.items()}
            assert res.n_preemptions == 0


def test_sim_seeded_determinism_with_preemption():
    def run():
        sim = Simulator(fleet(48, 16), make_policy("molding:adaptive"),
                        seed=1)
        return sim.run_workload(_chunked_bursty(seed=1),
                                admission=_slo_gate(),
                                preemption=make_preemption("backlog"))

    r1, r2 = run(), run()
    assert _trace_key(r1) == _trace_key(r2)
    assert r1.makespan == r2.makespan
    assert r1.n_preemptions == r2.n_preemptions > 0
    assert r1.preemptions_by_tenant() == r2.preemptions_by_tenant()


# ------------------------------------------------------------ decision parity
def _views(spec_n=8):
    """A synthetic running set: burst holds most slots, steady one TAO."""
    def tao(i, dag, crit):
        return TAO(type="matmul", id=i, criticality=crit, dag_id=dag)
    return [
        RunningView.of(tao(1, 2, 5), "burst", leader=0, width=4,
                       preemptible=True),
        RunningView.of(tao(2, 2, 1), "burst", leader=4, width=2,
                       preemptible=True),
        RunningView.of(tao(3, 2, 9), "burst", leader=6, width=1,
                       preemptible=False),
        RunningView.of(tao(4, 3, 7), "steady", leader=7, width=1,
                       preemptible=True),
    ]


def test_backlog_controller_decision_parity_and_tiebreaks():
    """The same observation trace produces the same victims on two fresh
    instances (the sim consult and the threaded consult are the same pure
    function), least-critical-first with (dag_id, tao_id) tie-breaks."""
    signals = LoadSignals(in_flight=12, active_namespaces=2, n_workers=8,
                          completed=3)
    backlog = {"burst": 120, "steady": 10}
    ready = TAO(type="sort", id=9, criticality=3, dag_id=3, width_hint=2)
    picks = []
    for _ in range(2):   # "sim" and "threaded" instances
        ctrl = BacklogPreemption()
        ctrl.prepare(hikey960())
        got = ctrl.on_ready(ready, "steady", _views(), signals, backlog)
        picks.append([(v.dag_id, v.tao_id) for v in got])
    assert picks[0] == picks[1]
    # least-critical burst TAO first (crit 1 before crit 5) — its width (2)
    # already covers the arrival's hint, so one victim suffices; the
    # non-preemptible crit-9 TAO is never chosen
    assert picks[0] == [(2, 2)]
    # a wider arrival needs more slots: the next-least-critical follows
    wide = TAO(type="sort", id=10, criticality=3, dag_id=3, width_hint=4)
    ctrl = BacklogPreemption()
    ctrl.prepare(hikey960())
    got = ctrl.on_ready(wide, "steady", _views(), signals, backlog)
    assert [(v.dag_id, v.tao_id) for v in got] == [(2, 2), (2, 1)]
    # gate feedback displaces the dominant tenant itself, one slot's worth
    fb = BacklogPreemption()
    fb.prepare(hikey960())
    got = fb.on_gate_feedback("burst", _views(), signals, backlog)
    assert [(v.dag_id, v.tao_id) for v in got] == [(2, 2)]


def test_backlog_throttled_filter_and_dominant_flag():
    """On gated runs the dominant tenant must also be gate-throttled for
    dominance: the drain phase (protected tenant briefly holds most of
    the residual backlog) must not displace it.  The gate marks its
    dominance-driven verdicts with ``AdmissionDecision.dominant``."""
    signals = LoadSignals(in_flight=12, active_namespaces=2, n_workers=8,
                          completed=3)
    backlog = {"burst": 120, "steady": 10}
    ready = TAO(type="sort", id=9, criticality=3, dag_id=3, width_hint=2)
    ctrl = BacklogPreemption()
    ctrl.prepare(hikey960())
    # dominant tenant held at the gate: displaced
    got = ctrl.on_ready(ready, "steady", _views(), signals, backlog,
                        frozenset({"burst"}))
    assert [(v.dag_id, v.tao_id) for v in got] == [(2, 2)]
    # dominant but NOT gate-throttled (drain phase): untouchable
    assert ctrl.on_ready(ready, "steady", _views(), signals, backlog,
                         frozenset()) == []
    # ungated run (throttled=None): raw dominance applies
    assert ctrl.on_ready(ready, "steady", _views(), signals, backlog,
                         None) != []
    # gate feedback with no other tenant waiting: self-preemption refused
    assert ctrl.on_gate_feedback("burst", _views(), signals,
                                 {"burst": 120}) == []
    # the slo-adaptive gate stamps dominance-driven delays
    from repro.core import AdmissionRequest, SloAdaptiveGate
    gate = SloAdaptiveGate(slo=0.5, headroom=0.01)
    req = AdmissionRequest(dag_id=1, tenant="burst", n_taos=200, arrival=0.0)
    gate.on_admit(req, 0.0)          # huge backlog, all one tenant
    v = gate.decide(AdmissionRequest(dag_id=2, tenant="burst", n_taos=200,
                                     arrival=0.1), 0.1, signals)
    assert v.action == "delay" and v.dominant
    # a verdict driven by the tenant's own degraded p99 is NOT dominant
    gate2 = SloAdaptiveGate(slo=0.01, min_samples=1)
    gate2.on_dag_done("steady", 5.0, 1.0)
    v2 = gate2.decide(AdmissionRequest(dag_id=3, tenant="steady", n_taos=2,
                                       arrival=1.0), 1.0, signals)
    assert v2.action == "delay" and not v2.dominant


def test_backlog_controller_guards():
    signals_idle = LoadSignals(in_flight=2, active_namespaces=2, n_workers=64,
                               completed=0)
    ready = TAO(type="sort", id=9, criticality=3, dag_id=3)
    ctrl = BacklogPreemption()
    ctrl.prepare(hikey960())
    backlog = {"burst": 120, "steady": 10}
    # free capacity: never displace
    assert ctrl.on_ready(ready, "steady", _views(), signals_idle, backlog) == []
    busy = LoadSignals(in_flight=12, active_namespaces=2, n_workers=8,
                       completed=3)
    # the dominant tenant's own arrivals never displace anyone
    assert ctrl.on_ready(ready, "burst", _views(), busy, backlog) == []
    # no dominance (even split) -> no victims; no backlog at all -> none
    assert ctrl.on_ready(ready, "steady", _views(), busy,
                         {"burst": 10, "steady": 11}) == []
    assert ctrl.on_ready(ready, "steady", _views(), busy, None) == []
    # gate feedback for a non-dominant tenant is a no-op
    assert ctrl.on_gate_feedback("steady", _views(), busy, backlog) == []


def test_critical_boost_controller_decisions():
    spec = hikey960()                    # workers 4..7 are big
    signals = LoadSignals(in_flight=9, active_namespaces=2, n_workers=8,
                          completed=0)

    def tao(i, dag, crit):
        return TAO(type="matmul", id=i, criticality=crit, dag_id=dag)

    views = [
        RunningView.of(tao(1, 2, 2), "b", leader=4, width=2, preemptible=True),
        RunningView.of(tao(2, 2, 4), "b", leader=6, width=2, preemptible=True),
        RunningView.of(tao(3, 3, 1), "s", leader=0, width=4, preemptible=True),
    ]
    critical = tao(9, 3, 8)             # critical in namespace 3
    picks = []
    for _ in range(2):
        ctrl = CriticalBoostPreemption()
        ctrl.prepare(spec)
        got = ctrl.on_ready(critical, "s", views, signals)
        picks.append([(v.dag_id, v.tao_id) for v in got])
    assert picks[0] == picks[1] == [(2, 1)]   # lowest-crit big occupant
    # a non-critical arrival displaces nobody
    ctrl = CriticalBoostPreemption()
    ctrl.prepare(spec)
    assert ctrl.on_ready(tao(10, 3, 0), "s", views + [
        RunningView.of(tao(11, 3, 6), "s", leader=1, width=1,
                       preemptible=True)], signals) == []
    # big cluster with a free worker: no displacement either
    free_views = views[:1]              # only workers 4-5 busy, 6-7 free
    ctrl = CriticalBoostPreemption()
    ctrl.prepare(spec)
    assert ctrl.on_ready(critical, "s", free_views, signals) == []


# ------------------------------------------------------------------ fairness
def test_backlog_steady_tenant_never_displaced():
    """The fairness surface: on the bursty stream the steady tenant's DAGs
    are never the displacement victim, on either vehicle."""
    sim = Simulator(fleet(48, 16), make_policy("molding:adaptive"), seed=1)
    res = sim.run_workload(_chunked_bursty(seed=1), admission=_slo_gate(),
                           preemption=make_preemption("backlog"))
    by_tenant = res.preemptions_by_tenant()
    assert by_tenant["steady"] == 0
    assert by_tenant["burst"] == res.n_preemptions > 0
    for st in res.per_dag.values():
        if st.tenant == "steady":
            assert st.preempted_count == 0 and st.preemption_delay == 0.0


def test_sim_backlog_improves_steady_p99_over_gate_alone():
    """The acceptance A/B (deterministic on the simulator): composing the
    backlog controller with the slo-adaptive gate cuts the steady
    tenant's p99 vs the gate alone, without losing goodput."""
    def run(ctrl):
        sim = Simulator(fleet(48, 16), make_policy("molding:adaptive"),
                        seed=1)
        return sim.run_workload(_chunked_bursty(seed=1),
                                admission=_slo_gate(), preemption=ctrl)

    def steady_p99(res):
        return percentile([s.sojourn for s in res.per_tenant()["steady"]
                           if s.done], 99)

    slo = {"steady": 0.5, "burst": 3.0}
    base, treat = run(None), run(make_preemption("backlog"))
    assert steady_p99(treat) < steady_p99(base)
    assert treat.goodput(slo) >= base.goodput(slo)
    assert treat.completed == base.completed


# ----------------------------------------------------- molding continuation
def test_molding_caps_continuation_width_at_unclaimed_chunks():
    class _Ctx:
        spec = fleet(12, 4)

        def __init__(self):
            import random as _r
            self.rng = _r.Random(0)
            from repro.core import PTTRegistry
            self.ptt = PTTRegistry(self.spec)

        def system_load(self, namespace=None):
            return 0                     # idle pool: molding widens fully

        def active_namespaces(self):
            return 1

        def running_max_criticality(self, namespace=0):
            return 0

    ctx = _Ctx()
    pol = MoldingPolicy(make_policy("homogeneous"))
    fresh = TAO(type="matmul", width_hint=1, n_chunks=8)
    wide = pol.place(fresh, ctx, waker=0).width
    assert wide > 2                      # idle pool: molded wide
    cont = TAO(type="matmul", width_hint=1, n_chunks=8)
    ensure_cursor(cont).advance(6)       # continuation: 2 chunks left
    assert pol.place(cont, ctx, waker=0).width <= 2
    # a fresh cursor (nothing claimed) must not change molding at all
    untouched = TAO(type="matmul", width_hint=1, n_chunks=8)
    ensure_cursor(untouched)
    assert pol.place(untouched, ctx, waker=0).width == wide


# ------------------------------------------------------------- aggregates
def test_workload_result_preemption_aggregates():
    from repro.core import DagStats, WorkloadResult
    a = DagStats.for_arrival(1, "a", 0.0, 5, tenant="t1")
    b = DagStats.for_arrival(2, "b", 0.0, 5, tenant="t2")
    a.record_preemption()
    a.record_preemption()
    a.preemption_delay = 0.3
    res = WorkloadResult(makespan=1.0, throughput=10.0, completed=10,
                         utilization=0.5, trace=[], per_dag={1: a, 2: b})
    assert res.n_preemptions == 2
    assert res.preemptions_by_tenant() == {"t1": 2, "t2": 0}
    assert res.mean_preemption_delay() == pytest.approx(0.15)
    assert "preemptions=2" in repr(res)
    empty = WorkloadResult(makespan=1.0, throughput=0.0, completed=0,
                           utilization=0.0, trace=[], per_dag={2: b})
    assert empty.n_preemptions == 0
    import math
    assert math.isnan(empty.mean_preemption_delay())
    assert "preemptions" not in repr(empty)


def test_make_preemption_registry():
    from repro.core import ALL_PREEMPTION_NAMES
    assert ALL_PREEMPTION_NAMES == ("none", "backlog", "critical-boost")
    for name in ALL_PREEMPTION_NAMES:
        assert make_preemption(name).name == name
    with pytest.raises(ValueError, match="unknown preemption"):
        make_preemption("nope")
    with pytest.raises(ValueError):
        BacklogPreemption(share=0.0)
    with pytest.raises(ValueError):
        CriticalBoostPreemption(max_victims=0)


def test_release_balances_admit_accounting():
    """SchedulerCore.release undoes admit exactly: counters return to the
    pre-admit state and a later re-admit + commit drains the namespace."""
    from repro.core import SchedulerCore
    core = SchedulerCore(hikey960(), make_policy("homogeneous"), seed=0)
    dag = TaoDag()
    t = dag.add_task("matmul")
    core.prepare(dag, dag_id=7)
    core.admit(t, waker=0)
    assert core.system_load(7) == 1 and core.active_namespaces() == 1
    core.release(t)
    assert core.system_load(7) == 0 and core.active_namespaces() == 0
    assert t.assigned_leader == -1
    assert core.completed == 0
    core.admit(t, waker=0)
    core.commit_and_wakeup(t)
    assert core.completed == 1 and core.system_load(7) == 0
