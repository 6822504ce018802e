"""Data-aware placement (PR 9): the locality layer's cross-vehicle
invariants.

Three families:

* tracker/unit semantics — residency materialisation, sticky vs movable
  moves, penalty vectors, movement-table EWMA, reset hooks;
* cross-vehicle invariants — moved-bytes conservation against an
  independent trace replay, hit+miss totals matching the executed trace,
  and the byte-identity guarantee that zero-footprint workloads reproduce
  the pinned pre-locality signatures exactly;
* decision-layer agreement — both vehicles drive the same
  ``SchedulerCore.admit``; property tests check that identical cores fed
  identical footprint sequences make identical (impl, width, leader)
  decisions, and that the PTT's penalised fast path equals the slow scan.

The hypothesis properties follow the repo convention: ``importorskip``
inside the test (dev-only dep), invariant tests stay ungated.
"""
import pytest

from conftest import footprint_map, serving_footprint_run

from repro.core import (DataFootprint, ImplVariant, LocalityTracker,
                        SchedulerCore, TaoDag, fleet, hikey960, make_policy,
                        replay_moved_bytes)
from repro.core.locality import DEFAULT_BANDWIDTH
from repro.core.places import leader_of


# -------------------------------------------------- tracker unit semantics
def test_tracker_place_semantics_sticky():
    lt = LocalityTracker(hikey960())
    fp = DataFootprint(nbytes=1e6)          # sticky by default
    # first touch materialises residency on the executing cluster: a hit
    hit, moved, cost = lt.place("decode", fp, 0)
    assert (hit, moved, cost) == (True, 0.0, 0.0)
    assert fp.resident == lt.cluster_of(0)
    # same-cluster re-dispatch: hit
    assert lt.place("decode", fp, 2)[0] is True
    # off-cluster: miss, full footprint streamed, residency stays (sticky)
    hit, moved, cost = lt.place("decode", fp, 5)
    assert hit is False and moved == 1e6 and cost > 0.0
    assert fp.resident == lt.cluster_of(0)
    assert (lt.hits, lt.misses, lt.moved_bytes) == (2, 1, 1e6)


def test_tracker_place_semantics_movable():
    lt = LocalityTracker(hikey960())
    fp = DataFootprint(nbytes=2e6, sticky=False)
    lt.place("matmul", fp, 0)
    assert lt.resident_bytes[lt.cluster_of(0)] == 2e6
    # movable data migrates residency to the new cluster on a miss
    hit, moved, _ = lt.place("matmul", fp, 5)
    assert hit is False and moved == 2e6
    assert fp.resident == lt.cluster_of(5)
    assert lt.resident_bytes[lt.cluster_of(0)] == 0.0
    assert lt.resident_bytes[lt.cluster_of(5)] == 2e6
    # and the next dispatch there is a hit
    assert lt.place("matmul", fp, 6)[0] is True


def test_penalties_none_is_the_legacy_signal():
    lt = LocalityTracker(hikey960())
    fp = DataFootprint(nbytes=1e6)
    assert lt.penalties("decode", None) is None          # no footprint
    assert lt.penalties("decode", fp) is None            # unmaterialised
    lt.place("decode", fp, 0)
    pen = lt.penalties("decode", fp)
    assert pen is not None
    assert pen[lt.cluster_of(0)] == 0.0                  # resident: free
    assert pen[lt.cluster_of(5)] == 1e6 / DEFAULT_BANDWIDTH
    lt.charge = False                                     # affinity-off knob
    assert lt.penalties("decode", fp) is None


def test_movement_table_ewma_and_fallback():
    lt = LocalityTracker(hikey960(), bandwidth=1e9)
    assert lt.seconds_per_byte("decode", 0, 0) == 0.0
    assert lt.seconds_per_byte("decode", 0, 1) == 1.0 / 1e9   # modeled
    lt.record_transfer("decode", 0, 1, nbytes=1e6, elapsed=0.01)
    assert lt.seconds_per_byte("decode", 0, 1) == 0.01 / 1e6  # measured
    # PTT-style 4:1 blend on the second observation
    lt.record_transfer("decode", 0, 1, nbytes=1e6, elapsed=0.02)
    want = (4 * (0.01 / 1e6) + 0.02 / 1e6) / 5
    assert lt.seconds_per_byte("decode", 0, 1) == pytest.approx(want)
    # zero-byte and same-cluster observations are ignored
    lt.record_transfer("decode", 0, 1, nbytes=0.0, elapsed=1.0)
    lt.record_transfer("decode", 1, 1, nbytes=1e6, elapsed=1.0)
    assert set(lt.movement_table()) == {("decode", 0, 1)}


def test_footprint_home_survives_reset():
    from repro.parallel.sharding import operand_footprint

    fp = operand_footprint(4e6, shard_index=3, n_clusters=2)
    assert fp.home == 1 and fp.resident == 1 and fp.sticky is False
    lt = LocalityTracker(hikey960())
    lt.place("matmul", fp, 0)       # migrates (movable) to cluster 0
    assert fp.resident == 0
    fp.reset()                      # reset_execution_state calls this
    assert fp.resident == 1         # back home, not unmaterialised
    # serving footprints have no home: reset rewinds to unmaterialised
    kv = DataFootprint(nbytes=1e6)
    kv.resident = 1
    kv.reset()
    assert kv.resident == -1


def test_scheduler_reset_hooks():
    core = SchedulerCore(hikey960(), make_policy("weight"), seed=0)
    loc = core.locality
    fp = DataFootprint(nbytes=1e6)
    loc.place("decode", fp, 0)
    loc.place("decode", fp, 5)
    loc.record_transfer("decode", 0, 1, 1e6, 0.01)
    core.reset_counters()
    # per-run accounting zeroed, learned movement table survives (like PTT)
    assert (loc.hits, loc.misses, loc.moved_bytes) == (0, 0, 0.0)
    assert loc.movement_table()
    core.reset_learning()
    assert loc.movement_table() == {}


# ------------------------------------------- cross-vehicle invariants
KV = 65536.0


@pytest.mark.parametrize("vehicle,charge", [
    ("sim", True), ("sim", False), ("threaded", True), ("threaded", False)])
def test_moved_bytes_conservation(vehicle, charge):
    """Bytes the tracker accounted live == an independent replay of the
    residency automaton over the executed trace (off-resident placements
    x footprint bytes).  Timing-free on both vehicles, and independent of
    the charging knob (accounting runs even when placement is legacy)."""
    res, spec, core = serving_footprint_run(vehicle, KV, charge=charge)
    assert res.locality_hits() > 0        # footprints were exercised
    if vehicle == "sim" and not charge:   # deterministic: legacy moves data
        assert res.locality_misses() > 0
    replayed = replay_moved_bytes(res.trace, spec, footprint_map(res, KV))
    assert replayed == pytest.approx(res.moved_bytes())
    # every dispatch of a footprint TAO was accounted exactly once
    assert res.locality_hits() + res.locality_misses() == len(res.trace)
    # DagStats totals agree with the tracker's own counters
    assert (core.locality.hits, core.locality.misses) == \
        (res.locality_hits(), res.locality_misses())
    assert core.locality.moved_bytes == pytest.approx(res.moved_bytes())


def test_affinity_charging_reduces_movement_sim():
    """Deterministic A/B on the simulator: charging move costs in placement
    must raise the KV-cache hit rate and cut moved bytes.  Footprints are
    sized so the move penalty dominates the compute gap — at that scale the
    charged leg MUST follow residency while the legacy leg keeps hopping
    (the marginal-penalty regime is not a unit test's business)."""
    kv_heavy = 1e7
    res_on, _, _ = serving_footprint_run("sim", kv_heavy, charge=True)
    res_off, _, _ = serving_footprint_run("sim", kv_heavy, charge=False)
    assert res_on.cache_hit_rate() > res_off.cache_hit_rate()
    assert res_on.moved_bytes() < res_off.moved_bytes()


def test_zero_footprint_reproduces_pinned_signature():
    """kv_bytes_per_token=0 builds no footprints: the locality-era stack
    must schedule the serve pin config byte-for-byte like the pre-locality
    stack (extends the repro.core.identity pins to the locality-off path)."""
    from repro.core.identity import (PINNED_SIGNATURES,
                                     locality_off_pin_trace,
                                     trace_signature)

    sig = trace_signature(locality_off_pin_trace())
    assert sig == PINNED_SIGNATURES["serve.locality-off"]
    assert sig == PINNED_SIGNATURES["serve.molding:weight"]


def test_zero_footprint_stats_stay_legacy():
    res, _, core = serving_footprint_run("sim", 0.0)
    assert res.locality_hits() == res.locality_misses() == 0
    assert res.moved_bytes() == 0.0
    assert res.cache_hit_rate() != res.cache_hit_rate()   # NaN: no samples
    assert core.locality.movement_table() == {}


# --------------------------------- decision layer: both vehicles share it
def _drive_core(core, spec, chains, kv_bytes):
    """Admit/execute footprint chains against a bare SchedulerCore exactly
    as the vehicles do (admit -> place accounting -> record -> commit),
    with deterministic elapsed times.  Returns the decision log."""
    log = []
    for ci, n_links in enumerate(chains):
        dag = TaoDag()
        fp = DataFootprint(nbytes=kv_bytes) if kv_bytes > 0 else None
        prev = None
        for li in range(n_links):
            t = dag.add_task("decode" if li else "prefill", width_hint=1,
                             deps=[prev] if prev else ())
            t.footprint = fp
            prev = t
        ready = list(core.prepare(dag, dag_id=ci))
        while ready:
            tao = ready.pop(0)
            p = core.admit(tao, waker=0)
            leader = leader_of(p.target, p.width)
            if tao.footprint is not None:
                core.locality.place(tao.type, tao.footprint, leader)
            log.append((tao.type, p.target, p.width, p.impl))
            core.record_time(tao, leader, p.width,
                             0.001 * (1 + leader % 3))
            ready.extend(core.commit_and_wakeup(tao))
    return log


def test_admit_decisions_deterministic_across_cores():
    """Hypothesis: two independent cores (same seed) fed the same footprint
    workload agree on every (target, width, impl) decision — the placement
    layer both vehicles share is deterministic, footprints included."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    specs = (hikey960(), fleet(6, 2))
    policies = ("molding:weight", "weight", "crit-ptt", "molding:adaptive")

    @settings(max_examples=20, deadline=None)
    @given(data=st.data())
    def prop(data):
        spec = data.draw(st.sampled_from(specs))
        pol = data.draw(st.sampled_from(policies))
        seed = data.draw(st.integers(0, 5))
        kv = data.draw(st.sampled_from([0.0, 1e5, 5e7]))
        chains = data.draw(st.lists(st.integers(1, 4), min_size=1,
                                    max_size=5))
        logs = []
        for _ in range(2):
            core = SchedulerCore(spec, make_policy(pol), seed=seed)
            logs.append(_drive_core(core, spec, chains, kv))
        assert logs[0] == logs[1]

    prop()


def test_charged_placement_follows_residency():
    """Hypothesis: once a sticky footprint is resident and large enough,
    a charged decision never pays a move the policy could see coming —
    the accounting the two vehicles share counts it as a hit."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 10), n_links=st.integers(2, 6))
    def prop(seed, n_links):
        spec = hikey960()
        core = SchedulerCore(spec, make_policy("molding:weight"), seed=seed)
        # warm the PTT so decisions are measured, not exploratory
        for w in range(spec.n_workers):
            core.ptt.table("prefill").record(w, 1, 0.002)
            core.ptt.table("decode").record(w, 1, 0.002)
        # a footprint so large the move penalty dominates any compute gap
        _drive_core(core, spec, [n_links], kv_bytes=1e12)
        # first touch is the materialising hit; everything after follows it
        assert core.locality.misses == 0
        assert core.locality.hits == n_links

    prop()


def test_penalized_fast_path_equals_slow_scan():
    """Hypothesis: the PTT's per-cluster penalised fast query returns the
    same (leader, time) as the O(n_workers) scan after any record history
    and any penalty vector — the fast/slow byte-identity gate extended to
    the locality queries."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from repro.core.ptt import PTT

    specs = (hikey960(), fleet(5, 3))

    @settings(max_examples=30, deadline=None)
    @given(data=st.data())
    def prop(data):
        spec = data.draw(st.sampled_from(specs))
        fast, slow = PTT(spec), PTT(spec, fast_query=False)
        nc = len(spec.clusters())
        n_ops = data.draw(st.integers(0, 25))
        for _ in range(n_ops):
            worker = data.draw(st.integers(0, spec.n_workers - 1))
            width = data.draw(st.sampled_from(spec.widths))
            elapsed = data.draw(st.floats(1e-9, 1e3, allow_nan=False))
            fast.record(worker, width, elapsed)
            slow.record(worker, width, elapsed)
            penalty = tuple(
                data.draw(st.floats(0.0, 1e3, allow_nan=False))
                for _ in range(nc))
            for w in spec.widths:
                assert fast.best_leader_penalized(w, penalty) == \
                    slow.best_leader_penalized(w, penalty)
        # zero penalties must degenerate to the plain best_leader choice
        zero = (0.0,) * nc
        for w in spec.widths:
            assert fast.best_leader_penalized(w, zero)[0] == \
                fast.best_leader(w)[0]

    prop()


def test_replay_conservation_property():
    """Hypothesis: conservation holds for ANY footprint sizing on the
    (deterministic) simulator, sticky and movable alike."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from repro.core import Simulator
    from repro.core.serve_orchestrator import (build_serving_workload,
                                               bursty_serving_trace,
                                               serving_kernel_models)

    @settings(max_examples=10, deadline=None)
    @given(kv=st.sampled_from([1.0, 1e3, 1e6, 1e8]),
           seed=st.integers(0, 3), charge=st.booleans())
    def prop(kv, seed, charge):
        spec = hikey960()
        reqs = bursty_serving_trace(n_steady=4, n_burst=5, seed=seed)
        wl, _ = build_serving_workload(reqs, kv_bytes_per_token=kv)
        sim = Simulator(spec, make_policy("molding:weight"),
                        kernel_models=serving_kernel_models(), seed=seed)
        sim.core.locality.charge = charge
        res = sim.run_workload(wl)
        replayed = replay_moved_bytes(res.trace, spec,
                                      footprint_map(res, kv))
        assert replayed == pytest.approx(res.moved_bytes())

    prop()


# ------------------------- continuation pinning x failure requeue (PR 9)
def test_failure_requeue_keeps_impl_reopens_leader():
    """Regression: a failure-requeued multi-impl TAO (``rearm`` +
    ``release`` with ``count_displacement=False``) must re-admit as a
    continuation that KEEPS its implementation (chunk state is
    impl-specific) while the leader reverts to the undistributed sentinel
    so placement may re-pick it — and the chaos path must spend neither
    the TAO's preemption budget nor the tenant's displacement history."""
    from repro.core.preemption import ensure_cursor

    spec = hikey960()
    core = SchedulerCore(spec, make_policy("molding:weight"), seed=3)
    dag = TaoDag()
    tao = dag.add_task("matmul", width_hint=1, work=1.0)
    tao.n_chunks = 4
    tao.impls = (ImplVariant("ref"), ImplVariant("interpret"))
    core.prepare(dag, dag_id=7)

    core.admit(tao, waker=0)
    impl0 = tao.assigned_impl
    assert impl0 in ("ref", "interpret")
    tao.assigned_leader = 2
    cur = ensure_cursor(tao)
    assert cur.claim() == 0 and cur.claim() == 1   # two chunks ran

    # the workers died under it: failure requeue (threaded _requeue_failed)
    cur.rearm(count_displacement=False)
    core.release(tao, count_displacement=False)
    assert tao.assigned_leader == -1               # leader re-pickable
    assert cur.preemptions == 0                    # budget untouched
    assert core.displacements(7) == 0              # no damping feedback

    p2 = core.admit(tao, waker=5)
    assert tao.assigned_impl == impl0              # continuation pins impl
    assert p2.impl == impl0
    # stealing moves the continuation: rebind at ANY leader keeps the impl
    for leader in (0, 4, 6):
        assert core.rebind_impl(tao, leader) == impl0
    # remaining chunks resume where the dead segment stopped
    assert cur.claim() == 2

    # contrast: a POLICY displacement does spend budget and feed damping
    cur.rearm()
    core.release(tao)
    assert cur.preemptions == 1
    assert core.displacements(7) == 1
    p3 = core.admit(tao, waker=1)
    assert p3.impl == impl0                        # still impl-pinned
