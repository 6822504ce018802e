"""Fast-path equivalence tests (constant-time scheduling hot path).

The perf refactor's contract is *byte-identical schedules*: every O(1)
structure (PTT incremental aggregates, bitmask dispatch sets, interference
counters, prefix-sum water-filling) must compute exactly what the
O(n_workers) scan baselines compute — the speed-up comes from the data
structure, never from a semantic shortcut.  These tests pin that contract,
up to fleets of 256 workers.
"""
import random
import time

import pytest

from repro.core import (ClusterSpec, PTT, Simulator, ThreadedRuntime,
                        Workload, fleet, hikey960, homogeneous, make_policy,
                        random_dag, random_workload)


# ------------------------------------------------------------ PTT queries --
def _trace_key(res):
    import dataclasses
    return [dataclasses.astuple(t) for t in res.trace]


def test_fast_ptt_matches_scan_on_fixed_history():
    spec = hikey960()
    fast, slow = PTT(spec), PTT(spec, fast_query=False)
    history = [(0, 1, 5.0), (3, 1, 2.0), (4, 2, 1.5), (0, 4, 9.0),
               (4, 4, 3.0), (3, 1, 8.0), (0, 1, 5.0), (6, 2, 1.5)]
    for worker, width, elapsed in history:
        fast.record(worker, width, elapsed)
        slow.record(worker, width, elapsed)
        for w in spec.widths:
            assert fast.best_leader(w) == slow.best_leader(w)
            assert fast.cluster_time(spec.big_workers, w) == \
                slow.cluster_time(spec.big_workers, w)
            assert fast.cluster_time(spec.little_workers, w) == \
                slow.cluster_time(spec.little_workers, w)


def test_fast_ptt_property_equals_from_scratch():
    """Hypothesis: after ANY record sequence (with queries interleaved, so
    the untried cursor and best-leader cache churn), the incremental
    aggregates equal a from-scratch recompute exactly."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    specs = (hikey960(), fleet(5, 3), homogeneous(4))

    @settings(max_examples=30, deadline=None)
    @given(data=st.data())
    def prop(data):
        spec = data.draw(st.sampled_from(specs))
        fast, slow = PTT(spec), PTT(spec, fast_query=False)
        n_ops = data.draw(st.integers(1, 40))
        for _ in range(n_ops):
            worker = data.draw(st.integers(0, spec.n_workers - 1))
            width = data.draw(st.sampled_from(spec.widths))
            elapsed = data.draw(st.floats(0.0, 1e6, allow_nan=False))
            fast.record(worker, width, elapsed)
            slow.record(worker, width, elapsed)
            assert fast.samples(worker, width) == slow.samples(worker, width)
            assert fast.untried(worker, width) == slow.untried(worker, width)
            for w in spec.widths:
                # exact equality, not approx: the aggregates are maintained
                # in exact integer arithmetic precisely so that fast==slow
                assert fast.best_leader(w) == slow.best_leader(w)
                for group in (spec.big_workers, spec.little_workers):
                    assert fast.cluster_time(group, w) == \
                        slow.cluster_time(group, w)

    prop()


def test_fast_ptt_property_equals_from_scratch_per_impl():
    """The same property with the implementation dimension in play: records
    and queries scattered over (impl, worker, width) cells must keep every
    impl's incremental aggregates, untried cursor and best-leader cache
    exactly equal to the scan recompute — each impl block owns its own
    fast-query state, and cross-impl traffic must never perturb it."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from repro.core import DEFAULT_IMPL

    specs = (hikey960(), fleet(5, 3), homogeneous(4))
    impls = (DEFAULT_IMPL, "ref", "pallas")

    @settings(max_examples=30, deadline=None)
    @given(data=st.data())
    def prop(data):
        spec = data.draw(st.sampled_from(specs))
        fast, slow = PTT(spec), PTT(spec, fast_query=False)
        n_ops = data.draw(st.integers(1, 40))
        for _ in range(n_ops):
            impl = data.draw(st.sampled_from(impls))
            worker = data.draw(st.integers(0, spec.n_workers - 1))
            width = data.draw(st.sampled_from(spec.widths))
            elapsed = data.draw(st.floats(0.0, 1e6, allow_nan=False))
            fast.record(worker, width, elapsed, impl=impl)
            slow.record(worker, width, elapsed, impl=impl)
            probe = data.draw(st.sampled_from(impls))
            assert fast.samples(worker, width, impl=impl) == \
                slow.samples(worker, width, impl=impl)
            assert fast.untried(worker, width, impl=probe) == \
                slow.untried(worker, width, impl=probe)
            for w in spec.widths:
                # exact equality per impl, plus the joint queries built on it
                assert fast.best_leader(w, impl=probe) == \
                    slow.best_leader(w, impl=probe)
                assert fast.best_cell(w, impls) == slow.best_cell(w, impls)
                for group in (spec.big_workers, spec.little_workers):
                    assert fast.cluster_time(group, w, impl=probe) == \
                        slow.cluster_time(group, w, impl=probe)

    prop()


def test_fast_ptt_cluster_time_arbitrary_subset_falls_back():
    spec = hikey960()
    t = PTT(spec)
    t.record(4, 1, 2.0)
    t.record(5, 1, 4.0)
    # a non-class-group iterable takes the scan path but the same math
    assert t.cluster_time([4, 5], 1) == t.cluster_time(spec.big_workers, 1) \
        == pytest.approx(3.0)
    assert t.cluster_time([0, 1], 1) == 0.0


def test_best_leader_explicit_candidates_still_scan():
    spec = hikey960()
    t = PTT(spec)
    for w in range(8):
        t.record(w, 1, 10.0 - w)
    leader, tm = t.best_leader(1, candidates=[2, 3])
    assert leader == 3 and tm == pytest.approx(7.0)


# ------------------------------------------------------- dispatch bit-set --
def test_bitset_choice_matches_seed_sorted_choice():
    """_BitSet.choice must pick exactly the element the seed path's
    ``rng.choice(sorted(set))`` picks for the same RNG state — that identity
    is what makes fast_dispatch trace-equal to the scan baseline."""
    from repro.core.simulator import _BitSet

    rng_fast, rng_slow = random.Random(7), random.Random(7)
    ops = random.Random(3)
    bs, ref = _BitSet(), set()
    for _ in range(600):
        v = ops.randrange(130)          # spans >64 bits: exercises chunking
        if ops.random() < 0.55:
            bs.add(v)
            ref.add(v)
        else:
            bs.discard(v)
            ref.discard(v)
        assert len(bs) == len(ref)
        if ref:
            assert bs.choice(rng_fast) == rng_slow.choice(sorted(ref))
    for v in range(130):
        assert (v in bs) == (v in ref)


# --------------------------------------------------- interference counters --
def test_interference_tracker_matches_rescan():
    from repro.core.simulator import _InterferenceTracker

    rng = random.Random(11)
    classes = ("big", "little", "mid")
    tracker = _InterferenceTracker()
    live = []
    for _ in range(500):
        if live and rng.random() < 0.45:
            t, cl = live.pop(rng.randrange(len(live)))
            tracker.finish(t, cl)
        else:
            t = rng.choice(("matmul", "copy"))
            cl = frozenset(rng.sample(classes, rng.randint(1, 3)))
            live.append((t, cl))
            tracker.start(t, cl)
        q_type = rng.choice(("matmul", "copy"))
        q_cl = frozenset(rng.sample(classes, rng.randint(1, 3)))
        brute = sum(1 for t2, cl2 in live if t2 == q_type and cl2 & q_cl)
        assert tracker.query(q_type, q_cl) == brute
    assert tracker.query("matmul", frozenset(classes)) == \
        sum(1 for t2, _ in live if t2 == "matmul")


# --------------------------------------------------- end-to-end equality --
@pytest.mark.parametrize("n_big,n_little", [(12, 4), (48, 16), (192, 64)],
                         ids=["16w", "64w", "256w"])
@pytest.mark.parametrize("policy", ["molding:adaptive", "adaptive",
                                    "molding:weight", "crit-ptt"])
def test_sim_fast_and_slow_paths_schedule_identically(policy, n_big,
                                                      n_little):
    """The acceptance gate: on a multi-DAG stream the fast paths
    (fast_dispatch + fast_query) must produce the byte-identical trace of
    the O(n_workers) scan baselines for the same seed, at fleet sizes where
    the scans and the O(1) structures diverge most in cost."""
    def run(fast):
        wl = random_workload(n_dags=5, rate=4.0, n_tasks=50, seed=2)
        sim = Simulator(fleet(n_big, n_little), make_policy(policy), seed=9,
                        fast_dispatch=fast, fast_query=fast)
        return sim.run_workload(wl)

    r_fast, r_slow = run(True), run(False)
    assert _trace_key(r_fast) == _trace_key(r_slow)
    assert r_fast.makespan == r_slow.makespan
    assert {i: s.sojourn for i, s in r_fast.per_dag.items()} == \
           {i: s.sojourn for i, s in r_slow.per_dag.items()}


def test_sim_fast_slow_identical_with_faults():
    """Fault injection exercises the water-filling fallback and failed-
    worker filtering; equality must survive it."""
    def run(fast):
        sim = Simulator(hikey960(), make_policy("molding:adaptive"), seed=4,
                        fast_dispatch=fast, fast_query=fast)
        sim.fail_worker(2)
        sim.set_speed_multiplier(6, 0.3)
        return sim.run(random_dag(80, target_degree=3.0, seed=5,
                                  width_hint=2))

    r_fast, r_slow = run(True), run(False)
    assert _trace_key(r_fast) == _trace_key(r_slow)
    assert all(2 not in t.participants for t in r_fast.trace)


# ------------------------------------------------------------ fault reset --
def test_reset_faults_restores_pristine_pool():
    sim = Simulator(hikey960(), make_policy("homogeneous"), seed=0)
    sim.fail_worker(3)
    sim.set_speed_multiplier(5, 0.25)
    r1 = sim.run(random_dag(60, target_degree=3.0, seed=0))
    assert all(3 not in t.participants for t in r1.trace)
    # reset_counters (run per execute) deliberately keeps fault state ...
    assert 3 in sim.failed and sim.speed_mult[5] == 0.25
    # ... and reset_faults clears it
    sim.reset_faults()
    assert not sim.failed and sim.speed_mult == [1.0] * 8
    r2 = sim.run(random_dag(60, target_degree=3.0, seed=1))
    assert any(3 in t.participants for t in r2.trace)


def test_reset_learning_keeps_faults_reset_faults_restores_identity():
    """A/B-leg contract: ``reset_learning()`` (the between-legs reset)
    models the same *hardware* across legs, so injected faults survive it;
    ``reset_faults()`` models repaired metal, after which the schedule must
    be byte-identical to a simulator that was never faulted at all."""
    def dag(seed):
        return random_dag(60, target_degree=3.0, seed=seed, width_hint=2)

    sim = Simulator(hikey960(), make_policy("molding:adaptive"), seed=4)
    sim.fail_worker(2)
    sim.set_speed_multiplier(6, 0.3)
    sim.run(dag(0))
    # leg boundary: learning reset, hardware state kept
    sim.reset_learning()
    assert 2 in sim.failed and sim.speed_mult[6] == 0.3
    r_faulty = sim.run(dag(1))
    assert all(2 not in t.participants for t in r_faulty.trace)
    # repaired metal + fresh learning == a pristine simulator, byte for byte
    sim.reset_faults()
    sim.reset_learning()
    r_repaired = sim.run(dag(2))
    pristine = Simulator(hikey960(), make_policy("molding:adaptive"), seed=4)
    pristine.reset_learning()   # same number of reseeds as the faulted sim
    pristine.reset_learning()
    r_pristine = pristine.run(dag(2))
    assert _trace_key(r_repaired) == _trace_key(r_pristine)
    assert r_repaired.makespan == r_pristine.makespan


# --------------------------------------------------- threaded idle parking --
def test_threaded_single_worker_pool_completes():
    """n=1 has no other worker to steal from: the self-steal fix must skip
    the steal draw entirely rather than spin on itself."""
    spec = ClusterSpec(classes=("big",))
    rt = ThreadedRuntime(spec, make_policy("homogeneous"), seed=0)
    out = rt.run(random_dag(12, target_degree=2.0, seed=1), timeout_s=30)
    assert out["completed"] == 12


@pytest.mark.perf
def test_threaded_idle_workers_park_without_cpu_burn():
    """Acceptance: parked idle workers consume ~0 CPU.  The whole pool sits
    idle for ~0.6s before the first DAG arrives; the old sleep-poll loop
    burned CPU across all 8 workers for that window, parked workers only
    pay ~20 guard wake-ups/s."""
    wl = Workload()
    wl.add(random_dag(10, target_degree=2.0, seed=0), at=0.6)
    rt = ThreadedRuntime(hikey960(), make_policy("homogeneous"), seed=0)
    cpu0 = time.process_time()
    res = rt.run_workload(wl, timeout_s=30.0)
    cpu = time.process_time() - cpu0
    assert res.completed == 10
    assert cpu < 1.2, f"idle pool burned {cpu:.2f}s CPU (sleep-poll regression?)"
