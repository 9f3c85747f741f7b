"""Tests for ``repro.core.planner`` and the bugfixes that ride with it.

Covers:
* memoized / warm / async planner decisions are *exactly* equal
  (segments and time) to fresh ``schedule`` / ``dp_forward`` /
  ``dp_backward`` solves on randomized costs,
* the DP incumbent/prefix-sum warm-start path of ``dp_forward`` /
  ``dp_backward``,
* the scheduler-restore bugfix (cross-mode / cross-strategy restores
  raise instead of silently rebuilding garbage),
* the ``PlanStepCache`` HLO retention bound + eviction counter,
* the injectable scheduler clock (fixed clock ⇒ bit-identical
  scheduling-seconds streams),
* async-planned vs synchronous-planned training runs are bit-identical.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (AsyncPlanner, LayerCosts, Planner, TopologyCosts,
                        backward_time, consensus_decision, dp_backward,
                        dp_forward, forward_time, schedule,
                        schedule_topology)
from repro.core.scheduler import (STRATEGIES, DynaCommScheduler,
                                  TopologyScheduler)


def _mk(pt, fc, bc, gt, dt, dt_bwd=None):
    return LayerCosts(pt=np.array(pt), fc=np.array(fc), bc=np.array(bc),
                      gt=np.array(gt), dt=dt, dt_bwd=dt_bwd)


def _rand_costs(rng, L=None):
    L = L or rng.integers(2, 9)
    return _mk(rng.uniform(0, 10, L), rng.uniform(0, 10, L),
               rng.uniform(0, 10, L), rng.uniform(0, 10, L),
               float(rng.uniform(0, 5)))


vec = lambda L: st.lists(st.floats(0.0, 100.0), min_size=L, max_size=L)
inst = st.integers(2, 8).flatmap(
    lambda L: st.tuples(vec(L), vec(L), vec(L), vec(L), st.floats(0.0, 10.0)))


# ---------------------------------------------------------------------------
# memoized planning == fresh solves
# ---------------------------------------------------------------------------


class TestPlannerExactness:
    @settings(max_examples=60, deadline=None)
    @given(inst)
    def test_memoized_equals_fresh_schedule(self, tup):
        """decide() == schedule() for every strategy, and the repeat
        lookup is a pure cache hit returning the identical decision."""
        pt, fc, bc, gt, dt = tup
        c = _mk(pt, fc, bc, gt, dt)
        planner = Planner()
        for strat in sorted(STRATEGIES):
            fresh = schedule(c, strat)
            assert planner.decide(c, strat) == fresh
            solves_before = planner.stats.solves + planner.stats.warm_solves
            assert planner.decide(c, strat) == fresh       # hit path
            assert planner.stats.solves + planner.stats.warm_solves == \
                solves_before
        assert planner.stats.hits == len(STRATEGIES)

    @settings(max_examples=60, deadline=None)
    @given(inst, st.floats(0.1, 8.0), st.floats(0.0, 10.0))
    def test_warm_solve_equals_fresh_dp(self, tup, comm_scale, new_dt):
        """Only the communication side moves between two cost points
        (same fc/bc): the second solve warm-starts off the first, and its
        segments + time exactly match a fresh ``dp_forward``/``dp_backward``."""
        pt, fc, bc, gt, dt = tup
        c1 = _mk(pt, fc, bc, gt, dt)
        c2 = _mk([p * comm_scale for p in pt], fc, bc,
                 [g * comm_scale for g in gt], new_dt)
        # a cost point that did not move (scale 1 or all-zero comm, same
        # dt) is the cold sibling itself: a cache hit, not a warm solve
        unmoved = (new_dt == dt and np.array_equal(c1.pt, c2.pt)
                   and np.array_equal(c1.gt, c2.gt))
        planner = Planner()
        planner.decide(c1, "dynacomm")                  # cold sibling
        warm_decision = planner.decide(c2, "dynacomm")  # warm path
        assert planner.stats.warm_solves == (0 if unmoved else 1)
        assert planner.stats.hits == (1 if unmoved else 0)
        f, b = dp_forward(c2), dp_backward(c2)
        assert warm_decision == (f.segments, b.segments)
        # the O(L) evaluation and the DP's prefix-sum arithmetic agree
        # to summation-order noise (the plans themselves are identical)
        assert forward_time(c2, warm_decision[0]) == pytest.approx(
            f.time, rel=1e-12)
        assert backward_time(c2, warm_decision[1]) == pytest.approx(
            b.time, rel=1e-12)

    @settings(max_examples=60, deadline=None)
    @given(inst, inst)
    def test_dp_incumbent_prune_is_exact(self, tup, bound_tup):
        """dp_forward/dp_backward with any *valid* incumbent upper bound
        (the time of a feasible segmentation) return exactly the full
        solve's segments and time."""
        pt, fc, bc, gt, dt = tup
        c = _mk(pt, fc, bc, gt, dt)
        full_f, full_b = dp_forward(c), dp_backward(c)
        # the all-in-one-segment plan is always feasible -> valid bound
        L = c.num_layers
        one_f, one_b = ((1, L),), ((1, L),)
        pruned_f = dp_forward(c, incumbent=forward_time(c, one_f))
        pruned_b = dp_backward(c, incumbent=backward_time(c, one_b))
        assert (pruned_f.segments, pruned_f.time) == \
            (full_f.segments, full_f.time)
        assert (pruned_b.segments, pruned_b.time) == \
            (full_b.segments, full_b.time)
        # prefix-sum reuse is equally exact
        fc_pref = np.concatenate([[0.0], np.cumsum(c.fc)])
        bc_pref = np.concatenate([[0.0], np.cumsum(c.bc[::-1])])
        warm_f = dp_forward(c, incumbent=full_f.time, fc_pref=fc_pref)
        warm_b = dp_backward(c, incumbent=full_b.time, bc_pref=bc_pref)
        assert (warm_f.segments, warm_f.time) == \
            (full_f.segments, full_f.time)
        assert (warm_b.segments, warm_b.time) == \
            (full_b.segments, full_b.time)

    def test_homogeneous_fleet_collapses_to_one_solve(self):
        """W identical workers cost one DP + W-1 dictionary hits."""
        rng = np.random.default_rng(7)
        c = _rand_costs(rng, L=6)
        topo = TopologyCosts(workers=tuple(c for _ in range(16)))
        planner = Planner()
        decisions = planner.decide_topology(topo, "dynacomm")
        assert decisions == schedule_topology(topo, "dynacomm")
        assert planner.stats.solves == 1
        assert planner.stats.hits == 15

    def test_consensus_matches_uncached_and_caches_topology(self):
        rng = np.random.default_rng(11)
        workers = tuple(_rand_costs(rng, L=5) for _ in range(4))
        topo = TopologyCosts(workers=workers)
        planner = Planner()
        got = planner.consensus(topo, "dynacomm")
        want = consensus_decision(topo, "dynacomm")
        assert got == want
        # revisit: whole-topology dictionary hit, no new solves
        solves = planner.stats.solves + planner.stats.warm_solves
        hits = planner.stats.hits
        assert planner.consensus(topo, "dynacomm") == want
        assert planner.stats.solves + planner.stats.warm_solves == solves
        assert planner.stats.hits == hits + 1

    def test_lru_eviction_counter_and_bound(self):
        rng = np.random.default_rng(3)
        planner = Planner(cache_size=2)
        for _ in range(5):
            planner.decide(_rand_costs(rng, L=4), "sequential")
        assert len(planner) <= 2
        assert planner.stats.evictions == 3

    def test_validation(self):
        with pytest.raises(ValueError, match="cache_size"):
            Planner(cache_size=0)
        with pytest.raises(ValueError, match="strategy"):
            Planner().decide(_rand_costs(np.random.default_rng(0)), "magic")

    def test_clear_drops_entries_but_keeps_counters(self):
        rng = np.random.default_rng(5)
        planner = Planner()
        c = _rand_costs(rng, L=4)
        planner.decide(c, "dynacomm")
        planner.clear()
        assert len(planner) == 0
        assert planner.stats.solves == 1
        planner.decide(c, "dynacomm")      # re-solve, not a hit
        assert planner.stats.solves == 2


# ---------------------------------------------------------------------------
# async two-phase protocol
# ---------------------------------------------------------------------------


class TestAsyncPlanner:
    def test_submit_collect_is_bit_identical_to_sync(self):
        rng = np.random.default_rng(21)
        costs = [_rand_costs(rng, L=6) for _ in range(8)]
        sync = Planner()
        want = [sync.decide(c, "dynacomm") for c in costs]
        ap = AsyncPlanner()
        try:
            for c in costs:
                assert ap.submit(c, "dynacomm") is True
            ap.drain()
            got = [ap.decide(c, "dynacomm") for c in costs]
        finally:
            ap.close()
        assert got == want
        assert ap.stats.async_submitted == len(costs)
        assert ap.stats.sync_fallbacks == 0
        # drained jobs land in the decision cache: collects are hits
        assert ap.stats.hits == len(costs)

    def test_duplicate_submit_is_refused(self):
        rng = np.random.default_rng(23)
        c = _rand_costs(rng, L=5)
        ap = AsyncPlanner()
        try:
            assert ap.submit(c, "dynacomm") is True
            assert ap.submit(c, "dynacomm") is False   # in flight or cached
            ap.drain()
            assert ap.submit(c, "dynacomm") is False   # cached
        finally:
            ap.close()
        assert ap.stats.async_submitted == 1

    def test_sync_fallback_without_submit(self):
        rng = np.random.default_rng(29)
        c = _rand_costs(rng, L=5)
        ap = AsyncPlanner()
        try:
            got = ap.decide(c, "dynacomm")
        finally:
            ap.close()
        assert got == schedule(c, "dynacomm")
        assert ap.stats.sync_fallbacks == 1
        assert ap.stats.async_submitted == 0

    def test_submit_topology_counts_new_jobs(self):
        rng = np.random.default_rng(31)
        c = _rand_costs(rng, L=5)
        topo = TopologyCosts(workers=(c, c, c, _rand_costs(rng, L=5)))
        ap = AsyncPlanner()
        try:
            # three identical workers -> one job; fourth distinct -> one
            assert ap.submit_topology(topo, "dynacomm") == 2
            ap.drain()
        finally:
            ap.close()

    def test_close_is_idempotent(self):
        ap = AsyncPlanner()
        ap.close()
        ap.close()


# ---------------------------------------------------------------------------
# scheduler-restore bugfix
# ---------------------------------------------------------------------------


class TestSchedulerRestore:
    def test_topology_cross_mode_restore_raises(self):
        a = TopologyScheduler(strategy="dynacomm", mode="per-worker")
        b = TopologyScheduler(strategy="dynacomm", mode="consensus")
        with pytest.raises(ValueError, match="mode"):
            b.load_state_dict(a.state_dict())

    def test_topology_cross_strategy_restore_raises(self):
        a = TopologyScheduler(strategy="lbl")
        b = TopologyScheduler(strategy="dynacomm")
        with pytest.raises(ValueError, match="strategy"):
            b.load_state_dict(a.state_dict())

    def test_dynacomm_cross_strategy_restore_raises(self):
        a = DynaCommScheduler(strategy="ibatch")
        b = DynaCommScheduler(strategy="dynacomm")
        with pytest.raises(ValueError, match="strategy"):
            b.load_state_dict(a.state_dict())

    def test_same_mode_roundtrip_restores_decision(self):
        rng = np.random.default_rng(13)
        topo = TopologyCosts(workers=tuple(_rand_costs(rng, L=4)
                                           for _ in range(3)))
        a = TopologyScheduler(strategy="dynacomm", mode="per-worker",
                              reschedule_every=4)
        a.decision_for_iteration(topo)
        b = TopologyScheduler(strategy="dynacomm", mode="per-worker",
                              reschedule_every=4)
        b.load_state_dict(a.state_dict())
        assert b.state_dict() == a.state_dict()

    def test_legacy_state_without_mode_loads(self):
        """Pre-fix checkpoints carry no mode/strategy keys — they load
        into a matching scheduler (nothing to validate against)."""
        a = TopologyScheduler(strategy="dynacomm", mode="consensus")
        state = a.state_dict()
        del state["mode"], state["strategy"]
        b = TopologyScheduler(strategy="dynacomm", mode="consensus")
        b.load_state_dict(state)            # no raise
        assert b._iter_seen == 0


# ---------------------------------------------------------------------------
# PlanStepCache HLO retention bugfix
# ---------------------------------------------------------------------------


class TestHloRetention:
    def _cache_with_compiles(self, retention, num_plans):
        import jax.numpy as jnp
        from repro.core.buckets import plan_from_decision
        from repro.runtime.replan import PlanStepCache
        cache = PlanStepCache(hlo_retention=retention)
        state, batch = jnp.zeros((4,)), jnp.ones((4,))
        plans = []
        for n in range(1, num_plans + 1):
            # merge the first n layers into one bucket -> distinct plans
            fwd = ((1, n),) + tuple((i, i) for i in range(n + 1, 5))
            plan = plan_from_decision(fwd, ((1, 4),), 4)
            plans.append(plan)
            cache.step_for(plan, lambda: (lambda s, b: s + b),
                           state, batch, count_hit=True)
        return cache, plans

    def test_retention_bound_and_eviction_counter(self):
        cache, plans = self._cache_with_compiles(retention=2, num_plans=4)
        assert cache.hlo_evictions == 2
        assert len(cache._hlo_text) == 2
        # newest two retained, oldest two evicted
        cache.hlo_text(plans[-1])
        cache.hlo_text(plans[-2])
        with pytest.raises(KeyError, match="evicted"):
            cache.hlo_text(plans[0])
        # compiled steps and collective counts are NOT evicted
        assert len(cache.plans) == 4
        assert cache.hlo_counts(plans[0]) is not None

    def test_retention_validation(self):
        from repro.runtime.replan import PlanStepCache
        with pytest.raises(ValueError, match="hlo_retention"):
            PlanStepCache(hlo_retention=0)


# ---------------------------------------------------------------------------
# injectable clock (DET-WALL-CLOCK bugfix)
# ---------------------------------------------------------------------------


class TestInjectableClock:
    def _ticker(self):
        t = [0.0]

        def clock():
            t[0] += 0.5
            return t[0]
        return clock

    def test_fixed_clock_streams_are_bit_identical(self):
        rng = np.random.default_rng(17)
        knots = [_rand_costs(rng, L=5) for _ in range(4)]

        def run():
            sched = DynaCommScheduler(strategy="dynacomm",
                                      reschedule_every=1,
                                      clock=self._ticker())
            out = []
            for c in knots:
                sched.decision_for_iteration(c)
                out.append(sched.last_scheduling_seconds)
            return out
        a, b = run(), run()
        assert a == b == [0.5] * 4        # exactly one tick per re-plan

    def test_topology_scheduler_accepts_clock(self):
        rng = np.random.default_rng(19)
        topo = TopologyCosts(workers=tuple(_rand_costs(rng, L=4)
                                           for _ in range(2)))
        sched = TopologyScheduler(strategy="dynacomm", reschedule_every=1,
                                  clock=self._ticker())
        sched.decision_for_iteration(topo)
        assert sched.last_scheduling_seconds == 0.5


# ---------------------------------------------------------------------------
# async-planned runs are bit-identical to synchronous-planned runs
# ---------------------------------------------------------------------------


class TestAsyncPlanningBitIdentity:
    def test_fleet_async_equals_sync(self):
        """Same losses, same plans, same replan events — only the
        planner's thread placement differs; plus the homogeneous-fleet
        cache collapse shows up as a nonzero hit rate."""
        import jax.numpy as jnp

        from repro.fleet import FleetSchedule, FleetTrainer
        from repro.optim import sgd

        rng = np.random.default_rng(0)
        layers = [{"w": jnp.asarray(rng.standard_normal(8), jnp.float32)}
                  for _ in range(3)]

        def loss_fn(layer_list, batch):
            return sum(jnp.sum((l["w"] - batch["t"]) ** 2)
                       for l in layer_list) / len(layer_list)

        def batch_fn(w, i):
            del w, i
            return {"t": jnp.zeros((8,), jnp.float32)}

        schedule = FleetSchedule.synthesize(range(8), churn=2.0,
                                            horizon=2.0, seed=5)

        def run(async_planning):
            tr = FleetTrainer(init_layers=layers, loss_fn=loss_fn,
                              optimizer=sgd(1e-2, 0.0), workers=8,
                              schedule=schedule, throttle="wait",
                              async_planning=async_planning)
            log = tr.run(48, batch_fn)
            key = [(e.worker, e.sim_time, e.version, e.loss)
                   for e in log.events]
            replans = [(e.reason, e.num_workers, e.plan_changed)
                       for e in tr.replan_events]
            return key, replans, tr.planner_stats

        sync_key, sync_replans, _ = run(False)
        async_key, async_replans, stats = run(True)
        assert async_key == sync_key
        assert async_replans == sync_replans
        assert stats["hit_rate"] > 0       # homogeneous collapse
