"""Implementation-variant machinery: per-(class, impl, width) PTT cells,
the joint (impl, width, leader) decisions, per-impl simulator cost curves,
preemption-aware damping, and A/B-leg independence via reset_learning()."""
import math
import random

import pytest

from repro.core import (DEFAULT_IMPL, BIG, LITTLE, PTT, ImplVariant,
                        KernelModel, PTTRegistry, Simulator, TaoDag, fleet,
                        hikey960, make_policy, random_dag, trace_signature)
from repro.core.policies import (DAMP_DISPLACEMENTS, HomogeneousPolicy,
                                 MoldingPolicy, _choose_impl, _variant_names)


# ------------------------------------------------------ PTT impl dimension --
def test_impl_cells_are_independent():
    t = PTT(hikey960())
    t.record(0, 1, 10.0, impl="a")
    assert t.time(0, 1, impl="a") == 10.0
    assert t.time(0, 1) == 0.0                 # DEFAULT_IMPL untouched
    assert t.samples(0, 1, impl="a") == 1
    assert t.samples(0, 1, impl="b") == 0      # unmaterialised: zeros
    assert t.untried(0, 1, impl="b")
    # EWMA evolves per impl
    t.record(0, 1, 20.0, impl="a")
    t.record(0, 1, 2.0, impl="b")
    assert t.time(0, 1, impl="a") == pytest.approx((4 * 10.0 + 20.0) / 5)
    assert t.time(0, 1, impl="b") == 2.0


def test_read_only_queries_do_not_materialise_blocks():
    t = PTT(hikey960())
    t.time(0, 1, impl="ghost")
    t.samples(0, 1, impl="ghost")
    t.snapshot(impl="ghost")
    assert t.impls() == (DEFAULT_IMPL,)


def test_best_impl_untried_first_in_declared_order():
    t = PTT(hikey960())
    names = ("a", "b", "c")
    assert t.best_impl(0, 1, names) == ("a", 0.0)
    t.record(0, 1, 5.0, impl="a")
    assert t.best_impl(0, 1, names) == ("b", 0.0)
    t.record(0, 1, 3.0, impl="b")
    assert t.best_impl(0, 1, names) == ("c", 0.0)
    t.record(0, 1, 4.0, impl="c")
    assert t.best_impl(0, 1, names) == ("b", 3.0)


def test_best_impl_tie_breaks_first_wins():
    t = PTT(hikey960())
    t.record(0, 1, 5.0, impl="a")
    t.record(0, 1, 5.0, impl="b")
    # strict < over declared order: the earlier variant keeps a tie
    assert t.best_impl(0, 1, ("a", "b")) == ("a", 5.0)
    assert t.best_impl(0, 1, ("b", "a")) == ("b", 5.0)


def test_best_cell_explores_impl_major():
    spec = hikey960()
    t = PTT(spec)
    names = ("a", "b")
    # fill impl "a" completely at width 1; "b" untried everywhere
    for w in range(spec.n_workers):
        t.record(w, 1, 10.0 - w, impl="a")
    impl, leader, tm = t.best_cell(1, names)
    assert (impl, tm) == ("b", 0.0)            # impl-major exploration
    assert leader == 0                         # b's first untried leader
    # fill "b" too: the joint minimum wins
    for w in range(spec.n_workers):
        t.record(w, 1, 20.0 + w, impl="b")
    assert t.best_cell(1, names) == ("a", 7, pytest.approx(3.0))


def test_best_cell_joint_min_across_impls():
    t = PTT(hikey960())
    for w in range(8):
        t.record(w, 1, 5.0, impl="a")
        t.record(w, 1, 5.0 if w != 3 else 1.0, impl="b")
    assert t.best_cell(1, ("a", "b")) == ("b", 3, 1.0)


def test_untried_cursor_and_best_cache_per_impl_fast_equals_slow():
    spec = fleet(5, 3)
    fast, slow = PTT(spec), PTT(spec, fast_query=False)
    rng = random.Random(11)
    impls = (DEFAULT_IMPL, "x", "y")
    for _ in range(200):
        im = rng.choice(impls)
        worker = rng.randrange(spec.n_workers)
        width = rng.choice(spec.widths)
        el = rng.uniform(0.0, 50.0)
        fast.record(worker, width, el, impl=im)
        slow.record(worker, width, el, impl=im)
        probe = rng.choice(impls)
        for w in spec.widths:
            assert fast.best_leader(w, impl=probe) == \
                slow.best_leader(w, impl=probe)
            for group in (spec.big_workers, spec.little_workers):
                assert fast.cluster_time(group, w, impl=probe) == \
                    slow.cluster_time(group, w, impl=probe)


def test_best_width_reads_the_impl_row():
    t = PTT(hikey960())
    for w in (1, 2, 4, 8):
        t.record(0, w, 1.0, impl="a")           # a: width 1 most efficient
    assert t.best_width(0, impl="a") == (1, 1.0)
    assert t.best_width(0, impl="b") == (1, 0.0)   # all untried: explore


def test_ptt_reset_restores_zero_init_all_impls():
    t = PTT(hikey960())
    t.record(0, 1, 5.0)
    t.record(2, 2, 5.0, impl="z")
    t.reset()
    assert t.impls() == (DEFAULT_IMPL,)
    assert t.time(0, 1) == 0.0 and t.time(2, 2, impl="z") == 0.0
    assert t.best_leader(1) == (0, 0.0)        # cursor back to exploration


def test_registry_reset_keeps_held_references_valid():
    reg = PTTRegistry(hikey960())
    tbl = reg.table("matmul")
    tbl.record(0, 1, 5.0, impl="a")
    reg.table("copy").record(1, 1, 2.0)
    reg.reset()
    assert reg.table("matmul") is tbl          # same object, zeroed
    assert tbl.time(0, 1, impl="a") == 0.0
    assert reg.table("copy").time(1, 1) == 0.0
    assert set(reg.types()) == {"matmul", "copy"}


# ------------------------------------------------------- decision helpers --
class _StubCtx:
    """Minimal SchedulerContext for unit-testing policy decisions."""

    def __init__(self, spec, displaced=0, load=10 ** 6):
        self.spec = spec
        self.ptt = PTTRegistry(spec)
        self.rng = random.Random(0)
        self._displaced = displaced
        self._load = load

    def system_load(self, namespace=None):
        return self._load

    def active_namespaces(self):
        return 1

    def running_max_criticality(self, namespace=0):
        return 0

    def displacements(self, namespace=0):
        return self._displaced


def _variant_tao(dag=None, impls=("a", "b"), width_hint=8, type="matmul"):
    dag = dag or TaoDag()
    return dag.add_task(type, width_hint=width_hint,
                        impls=[ImplVariant(n) for n in impls])


def test_choose_impl_damped_ignores_untried_cells():
    t = PTT(hikey960())
    t.record(0, 1, 7.0, impl="a")
    # exploring would pick untried "b"; damped picks the best *tried* cell
    assert _choose_impl(t, 0, 1, ("a", "b"), explore=True) == "b"
    assert _choose_impl(t, 0, 1, ("a", "b"), explore=False) == "a"
    # nothing tried at all: damped falls back to the declared first
    assert _choose_impl(t, 4, 1, ("a", "b"), explore=False) == "a"


def test_continuation_is_pinned_to_its_impl():
    class _Cursor:
        next_chunk = 3
        unclaimed = 2

    tao = _variant_tao()
    assert _variant_names(tao) == ("a", "b")
    tao.assigned_impl = "b"
    tao.cursor = _Cursor()
    assert _variant_names(tao) == ("b",)


def test_molding_damps_width_with_displacement_history():
    spec = hikey960()
    pol = MoldingPolicy(HomogeneousPolicy())
    tao = _variant_tao(width_hint=8)
    undamped = pol.place(tao, _StubCtx(spec, displaced=0), waker=0)
    assert undamped.width == 8                 # loaded system: hint kept
    two_levels = pol.place(
        tao, _StubCtx(spec, displaced=2 * DAMP_DISPLACEMENTS), waker=0)
    assert two_levels.width == 2               # 8 -> 4 -> 2
    # below the damping threshold: byte-identical to undamped
    assert pol.place(tao, _StubCtx(spec, displaced=DAMP_DISPLACEMENTS - 1),
                     waker=0).width == 8


def test_molding_respects_variant_width_bounds():
    spec = hikey960()
    pol = MoldingPolicy(HomogeneousPolicy())
    dag = TaoDag()
    tao = dag.add_task("matmul", width_hint=8,
                       impls=[ImplVariant("narrow", max_width=2)])
    p = pol.place(tao, _StubCtx(spec), waker=0)
    assert p.impl == "narrow" and p.width <= 2
    tao2 = dag.add_task("matmul", width_hint=1,
                        impls=[ImplVariant("wide", min_width=4)])
    p2 = pol.place(tao2, _StubCtx(spec), waker=0)
    assert p2.width >= 4


# ------------------------------------------------ simulator joint placement --
def _impl_models():
    """matmul with two variants whose best cluster differs: 'bigfriend' is
    fastest on BIG cores, 'littlefriend' on LITTLE — the shape that makes the
    joint decision pick different impls on different cluster classes."""
    base = KernelModel(t_ref=0.010, speed={BIG: 2.4, LITTLE: 1.0},
                       efficiency={1: 1.0, 2: 0.98, 4: 0.96, 8: 0.94})
    return {
        "matmul": base,
        ("matmul", "bigfriend"): KernelModel(
            t_ref=0.010, speed={BIG: 4.0, LITTLE: 0.5},
            efficiency={1: 1.0, 2: 0.98, 4: 0.96, 8: 0.94}),
        ("matmul", "littlefriend"): KernelModel(
            t_ref=0.010, speed={BIG: 1.2, LITTLE: 2.0},
            efficiency={1: 1.0, 2: 0.98, 4: 0.96, 8: 0.94}),
    }


def _variant_dag(n=160):
    return random_dag(n, target_degree=4.0, kernel_types=("matmul",),
                      seed=5, width_hint=2,
                      impls=[ImplVariant("bigfriend"),
                             ImplVariant("littlefriend")])


def test_simulator_dispatches_per_impl_cost_curves():
    sim = Simulator(hikey960(), make_policy("crit-ptt"), seed=1,
                    kernel_models=_impl_models())
    res = sim.run(_variant_dag())
    impls_seen = {t.impl for t in res.trace}
    assert impls_seen <= {"bigfriend", "littlefriend"}
    assert len(impls_seen) == 2                # both variants explored


def test_joint_placement_picks_different_impls_per_cluster():
    """After a run, the learned per-(class, impl, width) cells must make the
    joint decision pick a *different* variant per cluster class.  (Judged at
    the decision layer, not by trace majorities: the simulator's random work
    stealing legitimately executes a TAO away from the leader its impl was
    chosen for.)"""
    spec = hikey960()
    sim = Simulator(spec, make_policy("crit-ptt"), seed=1,
                    kernel_models=_impl_models())
    res = sim.run(_variant_dag(400))
    assert {t.impl for t in res.trace} == {"bigfriend", "littlefriend"}
    table = sim.core.ptt.table("matmul")
    names = ("bigfriend", "littlefriend")
    w = 2  # the hinted (clamped) width every placement addressed
    big_leader = next(l for l in spec.big_workers if l % w == 0)
    little_leader = next(l for l in spec.little_workers if l % w == 0)
    assert table.best_impl(big_leader, w, names)[0] == "bigfriend"
    assert table.best_impl(little_leader, w, names)[0] == "littlefriend"
    # and both cells are measured, not exploration artifacts
    assert table.time(big_leader, w, impl="bigfriend") > 0.0
    assert table.time(little_leader, w, impl="littlefriend") > 0.0


@pytest.mark.parametrize("policy", ["homogeneous", "crit-aware", "crit-ptt",
                                    "weight", "adaptive", "molding:adaptive",
                                    "molding:weight"])
def test_every_policy_completes_multi_variant_dags(policy):
    sim = Simulator(hikey960(), make_policy(policy), seed=2,
                    kernel_models=_impl_models())
    res = sim.run(_variant_dag(120))
    assert res.completed == 120
    assert all(t.impl in ("bigfriend", "littlefriend") for t in res.trace)


def test_joint_no_worse_than_best_static_choice():
    """The acceptance bar: the learned joint placement's makespan must not
    lose to the best single static variant (same DAG, same policy)."""
    spans = {}
    for leg in ("bigfriend", "littlefriend", "joint"):
        sim = Simulator(hikey960(), make_policy("molding:adaptive"), seed=3,
                        kernel_models=_impl_models())
        if leg == "joint":
            dag = _variant_dag(400)
        else:
            dag = random_dag(400, target_degree=4.0,
                             kernel_types=("matmul",), seed=5, width_hint=2,
                             impls=[ImplVariant(leg)])
        spans[leg] = sim.run(dag).makespan
    best_static = min(spans["bigfriend"], spans["littlefriend"])
    assert spans["joint"] <= best_static * 1.05


# ----------------------------------------------------- A/B leg independence --
def test_reset_learning_makes_legs_byte_identical():
    """The A/B leg reset: leg B after reset_learning() must
    reproduce a fresh Simulator's leg B byte for byte — no PTT profile,
    threshold or RNG state may leak across legs."""
    models = _impl_models()
    dag_a = lambda: _variant_dag(120)
    dag_b = lambda: random_dag(100, target_degree=3.0,
                               kernel_types=("matmul",), seed=9,
                               impls=[ImplVariant("bigfriend"),
                                      ImplVariant("littlefriend")])
    sim = Simulator(hikey960(), make_policy("molding:adaptive"), seed=4,
                    kernel_models=models)
    sim.run(dag_a())
    sim.reset_learning()
    reused = trace_signature(sim.run(dag_b()).trace)
    fresh_sim = Simulator(hikey960(), make_policy("molding:adaptive"), seed=4,
                          kernel_models=models)
    fresh = trace_signature(fresh_sim.run(dag_b()).trace)
    assert reused == fresh
    # sanity: without the reset the legs do leak (learned profiles differ)
    sim2 = Simulator(hikey960(), make_policy("molding:adaptive"), seed=4,
                     kernel_models=models)
    sim2.run(dag_a())
    assert trace_signature(sim2.run(dag_b()).trace) != fresh
