"""Unit + property tests for the DynaComm core scheduling library."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    LayerCosts, backward_time, bruteforce_backward, bruteforce_forward,
    check_partial_orders, dp_backward, dp_forward, evaluate, forward_time,
    ibatch_backward, ibatch_forward, iteration_time, lbl_backward, lbl_forward,
    plan_from_decision, random_costs, schedule, sequential_backward,
    sequential_forward, simulate_iteration,
)
from repro.core.costmodel import (
    backward_segments_from_g, forward_segments_from_p, g_from_backward_segments,
    p_from_forward_segments, validate_backward_segments,
    validate_forward_segments,
)


def make_costs(pt, fc, bc, gt, dt):
    return LayerCosts(pt=np.array(pt, float), fc=np.array(fc, float),
                      bc=np.array(bc, float), gt=np.array(gt, float), dt=dt)


# ---------------------------------------------------------------------------
# decision representations
# ---------------------------------------------------------------------------

class TestDecisions:
    def test_p_roundtrip(self):
        p = (1, 0, 1, 1, 0)
        segs = forward_segments_from_p(p)
        assert segs == ((1, 1), (2, 3), (4, 4), (5, 6))
        assert p_from_forward_segments(segs) == p

    def test_g_roundtrip(self):
        # L = 6, g[l-1] cuts after layer L+1-l going downward
        g = (1, 0, 1, 0, 0)
        segs = backward_segments_from_g(g)
        validate_backward_segments(segs, 6)
        assert segs[0][1] == 6 and segs[-1][0] == 1
        assert g_from_backward_segments(segs) == g

    def test_sequential_lbl_shapes(self):
        assert sequential_forward(5) == ((1, 5),)
        assert lbl_forward(3) == ((1, 1), (2, 2), (3, 3))
        assert lbl_backward(3) == ((3, 3), (2, 2), (1, 1))
        validate_forward_segments(lbl_forward(7), 7)
        validate_backward_segments(lbl_backward(7), 7)

    def test_invalid_segments_raise(self):
        with pytest.raises(ValueError):
            validate_forward_segments(((1, 2), (4, 5)), 5)  # gap
        with pytest.raises(ValueError):
            validate_backward_segments(((1, 3), (4, 5)), 5)  # wrong order


# ---------------------------------------------------------------------------
# f_m cost model — hand-checked examples
# ---------------------------------------------------------------------------

class TestCostModel:
    def test_sequential_closed_form(self):
        c = make_costs([1, 2], [3, 4], [5, 6], [7, 8], dt=0.5)
        # forward: dt + sum(pt) then sum(fc)
        assert forward_time(c, sequential_forward(2)) == pytest.approx(0.5 + 3 + 7)
        # backward: sum(bc) then dt + sum(gt)
        assert backward_time(c, sequential_backward(2)) == pytest.approx(11 + 0.5 + 15)

    def test_lbl_overlap_example(self):
        # pt=[1,1], fc=[10,10]: layer 2's pull fully hides under layer 1's fc
        c = make_costs([1, 1], [10, 10], [1, 1], [1, 1], dt=0.0)
        assert forward_time(c, lbl_forward(2)) == pytest.approx(1 + 10 + 10)
        # sequential pays both pulls up front: 2 + 20
        assert forward_time(c, sequential_forward(2)) == pytest.approx(22)

    def test_dt_penalises_decomposition(self):
        # compute tiny: decomposition only adds dt
        c = make_costs([1, 1, 1], [0, 0, 0], [0, 0, 0], [1, 1, 1], dt=5.0)
        t_seq = forward_time(c, sequential_forward(3))
        t_lbl = forward_time(c, lbl_forward(3))
        assert t_seq == pytest.approx(5 + 3)
        assert t_lbl == pytest.approx(3 * 5 + 3)
        assert t_seq < t_lbl

    def test_backward_pipelining(self):
        # big bc hides gt of earlier segments
        c = make_costs([0, 0], [0, 0], [10, 10], [1, 1], dt=0.0)
        t = backward_time(c, lbl_backward(2))
        # bc2 ends at 10, gt2 ends 11; bc1 ends 20 > 11, gt1 ends 21
        assert t == pytest.approx(21)


# ---------------------------------------------------------------------------
# DP vs brute force — the optimality claim (Section IV-B3)
# ---------------------------------------------------------------------------

costs_strategy = st.integers(min_value=1, max_value=9).flatmap(
    lambda L: st.tuples(
        st.lists(st.floats(0.0, 50.0), min_size=L, max_size=L),
        st.lists(st.floats(0.0, 50.0), min_size=L, max_size=L),
        st.lists(st.floats(0.0, 50.0), min_size=L, max_size=L),
        st.lists(st.floats(0.0, 50.0), min_size=L, max_size=L),
        st.floats(0.0, 20.0),
    )
)


class TestDPOptimality:
    @settings(max_examples=200, deadline=None)
    @given(costs_strategy)
    def test_forward_dp_matches_bruteforce(self, tup):
        pt, fc, bc, gt, dt = tup
        c = make_costs(pt, fc, bc, gt, dt)
        res = dp_forward(c)
        _, best = bruteforce_forward(c)
        assert res.time == pytest.approx(best, rel=1e-9, abs=1e-9)

    @settings(max_examples=200, deadline=None)
    @given(costs_strategy)
    def test_backward_dp_matches_bruteforce(self, tup):
        pt, fc, bc, gt, dt = tup
        c = make_costs(pt, fc, bc, gt, dt)
        res = dp_backward(c)
        _, best = bruteforce_backward(c)
        assert res.time == pytest.approx(best, rel=1e-9, abs=1e-9)

    @settings(max_examples=100, deadline=None)
    @given(costs_strategy)
    def test_dp_never_worse_than_any_baseline(self, tup):
        pt, fc, bc, gt, dt = tup
        c = make_costs(pt, fc, bc, gt, dt)
        L = c.num_layers
        fopt = dp_forward(c).time
        bopt = dp_backward(c).time
        eps = 1e-9
        for segs in (sequential_forward(L), lbl_forward(L), ibatch_forward(c)[0]):
            assert fopt <= forward_time(c, segs) + eps
        for segs in (sequential_backward(L), lbl_backward(L), ibatch_backward(c)[0]):
            assert bopt <= backward_time(c, segs) + eps

    def test_dp_larger_instances_beat_heuristics(self):
        for seed in range(5):
            c = random_costs(40, seed=seed, dt=2e-3)
            fopt = dp_forward(c).time
            assert fopt <= forward_time(c, lbl_forward(40)) + 1e-12
            assert fopt <= forward_time(c, ibatch_forward(c)[0]) + 1e-12

    def test_dp_segments_are_valid_and_match_time(self):
        c = random_costs(25, seed=3, dt=1e-3)
        f = dp_forward(c)
        b = dp_backward(c)
        validate_forward_segments(f.segments, 25)
        validate_backward_segments(b.segments, 25)
        assert forward_time(c, f.segments) == pytest.approx(f.time)
        assert backward_time(c, b.segments) == pytest.approx(b.time)


# ---------------------------------------------------------------------------
# iBatch reproduces its documented pathology
# ---------------------------------------------------------------------------

class TestIBatch:
    def test_valid_decisions(self):
        for seed in range(8):
            c = random_costs(30, seed=seed, dt=5e-3)
            fsegs, _ = ibatch_forward(c)
            bsegs, _ = ibatch_backward(c)
            validate_forward_segments(fsegs, 30)
            validate_backward_segments(bsegs, 30)

    def test_sometimes_worse_than_lbl(self):
        """Paper Fig. 5(c): the greedy can lose to plain layer-by-layer."""
        hits = 0
        for seed in range(60):
            c = random_costs(24, seed=seed, dt=5e-4)
            if ibatch_forward(c)[1] > forward_time(c, lbl_forward(24)) + 1e-12:
                hits += 1
        assert hits > 0, "expected at least one instance where iBatch < LBL"

    def test_single_layer(self):
        c = make_costs([1.0], [1.0], [1.0], [1.0], dt=0.1)
        assert ibatch_forward(c)[0] == ((1, 1),)
        assert ibatch_backward(c)[0] == ((1, 1),)


# ---------------------------------------------------------------------------
# simulator agrees with f_m and satisfies the partial orders
# ---------------------------------------------------------------------------

class TestSimulator:
    @settings(max_examples=60, deadline=None)
    @given(costs_strategy, st.randoms(use_true_random=False))
    def test_simulator_matches_fm(self, tup, rnd):
        pt, fc, bc, gt, dt = tup
        c = make_costs(pt, fc, bc, gt, dt)
        L = c.num_layers
        # random decision
        p = tuple(rnd.randint(0, 1) for _ in range(L - 1))
        g = tuple(rnd.randint(0, 1) for _ in range(L - 1))
        fsegs = forward_segments_from_p(p)
        bsegs = backward_segments_from_g(g)
        tl = simulate_iteration(c, fsegs, bsegs)
        assert tl.forward_time == pytest.approx(forward_time(c, fsegs), abs=1e-9)
        assert tl.backward_time == pytest.approx(backward_time(c, bsegs), abs=1e-9)
        assert tl.total == pytest.approx(iteration_time(c, fsegs, bsegs), abs=1e-9)
        check_partial_orders(tl, L)

    def test_breakdown_accounts_total(self):
        c = random_costs(12, seed=1, dt=1e-3)
        fsegs = dp_forward(c).segments
        bsegs = dp_backward(c).segments
        tl = simulate_iteration(c, fsegs, bsegs)
        for phase in ("forward", "backward"):
            br = tl.breakdown(phase)
            assert br.total == pytest.approx(
                br.comm_only + br.comp_only + br.overlap + br.idle, abs=1e-9)
            assert br.overlap >= -1e-12


# ---------------------------------------------------------------------------
# a looped stack: one application's costs per layer, the passes between
# ---------------------------------------------------------------------------

class TestLoopedStack:
    """The planner takes one application's fc/bc per block and head; a
    looped stack's further passes only shift every push's ready time."""

    @staticmethod
    def _costs(seed, loops):
        from repro.core import EdgeNetworkModel, LayerProfile, \
            costs_from_profiles
        rng = np.random.default_rng(seed)
        L = 10
        profiles = [LayerProfile(name=f"l{i}",
                                 param_bytes=float(rng.uniform(1e5, 5e7)),
                                 flops_fwd=float(rng.uniform(1e9, 1e11)),
                                 applications=1 if i == 0 else loops)
                    for i in range(L)]
        return costs_from_profiles(profiles, net=EdgeNetworkModel(),
                                   compute_flops_per_s=1e12)

    @pytest.mark.parametrize("seed,loops", [(0, 2), (1, 4), (2, 4), (3, 7)])
    def test_segments_invariant_time_carries_the_passes(self, seed, loops):
        once, looped = self._costs(seed, 1), self._costs(seed, loops)
        for name in ("pt", "fc", "bc", "gt"):
            np.testing.assert_array_equal(getattr(once, name),
                                          getattr(looped, name))
        f, b = dp_forward(looped), dp_backward(looped)
        assert f.segments == dp_forward(once).segments
        assert b.segments == dp_backward(once).segments
        assert len(f.segments) > 1 or len(b.segments) > 1   # not trivial
        # the first layer (the embedding) runs once
        extra = (loops - 1) * (np.sum(looped.fc[1:]) + np.sum(looped.bc[1:]))
        decision = (f.segments, b.segments)
        assert evaluate(looped, decision)["total"] - \
            evaluate(once, decision)["total"] == pytest.approx(extra,
                                                               rel=1e-12)
        assert simulate_iteration(looped, *decision).total == \
            pytest.approx(evaluate(looped, decision)["total"], rel=1e-12)

    def test_looped_profiles(self):
        import dataclasses
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.models.profiles import layer_profiles
        cfg = dataclasses.replace(get_config("ouro-2.6b"), num_layers=4)
        shape = InputShape("train_512", 512, 8, "train")
        profs = layer_profiles(cfg, shape)
        assert [p.applications for p in profs] == [1, 4, 4, 4, 4, 4]
        once = layer_profiles(dataclasses.replace(cfg, loop_steps=1), shape)
        assert all(p.applications == 1 for p in once)
        # the exit gate adds one output column to the head
        assert profs[-1].flops_fwd - once[-1].flops_fwd == 2.0 * 8 * 512 * 2048


# ---------------------------------------------------------------------------
# strategy registry + bucket plans
# ---------------------------------------------------------------------------

class TestSchedulerAPI:
    def test_registry_and_ordering(self):
        c = random_costs(16, seed=2, dt=1e-3)
        times = {name: evaluate(c, schedule(c, name))["total"]
                 for name in ("sequential", "lbl", "ibatch", "dynacomm")}
        assert times["dynacomm"] <= min(times.values()) + 1e-12

    def test_unknown_strategy(self):
        c = random_costs(4, seed=0)
        with pytest.raises(ValueError):
            schedule(c, "nope")

    def test_bucket_plan(self):
        c = random_costs(6, seed=0, dt=1e-3)
        f, b = schedule(c, "dynacomm")
        plan = plan_from_decision(f, b, 6)
        # forward buckets cover 0..5 in order
        assert [l for grp in plan.forward for l in grp] == list(range(6))
        # backward buckets cover 5..0 in reverse order
        assert [l for grp in plan.backward for l in grp] == list(range(5, -1, -1))

    def test_epoch_caching(self):
        from repro.core import DynaCommScheduler
        c = random_costs(10, seed=0, dt=1e-3)
        sched = DynaCommScheduler(strategy="dynacomm", reschedule_every=5)
        d0 = sched.decision_for_iteration(c)
        d1 = sched.decision_for_iteration(c)
        assert d0 == d1
        assert sched.last_scheduling_seconds >= 0.0
