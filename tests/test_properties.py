"""System-invariant property tests (hypothesis)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (LayerCosts, TopologyCosts, backward_time,
                        bruteforce_backward, bruteforce_forward, dp_backward,
                        dp_forward, forward_time, ibatch_backward,
                        iteration_time, simulate_ps_iteration)
from repro.core.baselines import lbl_backward, lbl_forward
from repro.core.costmodel import (backward_segments_from_g,
                                  forward_segments_from_p,
                                  g_from_backward_segments,
                                  p_from_forward_segments,
                                  validate_backward_segments)


def _mk(pt, fc, bc, gt, dt, dt_bwd=None):
    return LayerCosts(pt=np.array(pt), fc=np.array(fc), bc=np.array(bc),
                      gt=np.array(gt), dt=dt, dt_bwd=dt_bwd)


vec = lambda L: st.lists(st.floats(0.0, 100.0), min_size=L, max_size=L)
inst = st.integers(2, 8).flatmap(
    lambda L: st.tuples(vec(L), vec(L), vec(L), vec(L), st.floats(0.0, 10.0)))
# instance + a possibly-asymmetric push overhead: (tup, dt_bwd, asymmetric?)
inst_asym = st.tuples(inst, st.floats(0.0, 10.0), st.booleans())


class TestSchedulingInvariants:
    @settings(max_examples=100, deadline=None)
    @given(inst, st.floats(0.1, 10.0))
    def test_optimum_scales_linearly(self, tup, lam):
        """T*(λ·costs) == λ·T*(costs) — the objective is 1-homogeneous."""
        pt, fc, bc, gt, dt = tup
        c1 = _mk(pt, fc, bc, gt, dt)
        c2 = c1.scaled(compute=lam, comm=lam, dt=lam * dt)
        t1 = dp_forward(c1).time
        t2 = dp_forward(c2).time
        assert t2 == pytest.approx(lam * t1, rel=1e-9, abs=1e-9)

    @settings(max_examples=100, deadline=None)
    @given(inst)
    def test_zero_dt_makes_lbl_optimal(self, tup):
        """With Δt = 0, splitting a segment never hurts ⇒ LBL is optimal."""
        pt, fc, bc, gt, _ = tup
        c = _mk(pt, fc, bc, gt, 0.0)
        L = c.num_layers
        assert forward_time(c, lbl_forward(L)) == pytest.approx(
            dp_forward(c).time, rel=1e-9, abs=1e-9)
        assert backward_time(c, lbl_backward(L)) == pytest.approx(
            dp_backward(c).time, rel=1e-9, abs=1e-9)

    @settings(max_examples=100, deadline=None)
    @given(inst)
    def test_forward_backward_duality(self, tup):
        """The backward problem is the forward problem under time reversal:
        reversing a backward schedule turns the push of the last segment
        into the first pull, so T*_bwd(bc, gt) == T*_fwd(pt=gt, fc=bc)
        (indices unreversed — layer 1's push, executed last, maps to
        layer 1's pull, executed first)."""
        pt, fc, bc, gt, dt = tup
        c = _mk(pt, fc, bc, gt, dt)
        dual = _mk(gt, bc, bc, gt, dt)
        assert dp_backward(c).time == pytest.approx(
            dp_forward(dual).time, rel=1e-9, abs=1e-9)

    @settings(max_examples=100, deadline=None)
    @given(inst, st.floats(0.0, 5.0))
    def test_dt_monotone(self, tup, extra):
        """Raising Δt can never reduce the optimal time."""
        pt, fc, bc, gt, dt = tup
        c1 = _mk(pt, fc, bc, gt, dt)
        c2 = _mk(pt, fc, bc, gt, dt + extra)
        assert dp_forward(c2).time >= dp_forward(c1).time - 1e-9

    @settings(max_examples=100, deadline=None)
    @given(inst)
    def test_lower_bounds(self, tup):
        """T*_fwd ≥ max(total compute, Δt + total comm) — either stream is
        a lower bound."""
        pt, fc, bc, gt, dt = tup
        c = _mk(pt, fc, bc, gt, dt)
        t = dp_forward(c).time
        assert t >= float(np.sum(c.fc)) - 1e-9
        assert t >= dt + float(np.sum(c.pt)) - 1e-9


class TestOptimalityOracle:
    """The DP against the exhaustive 2^(L-1) search (ISSUE 4 satellite)."""

    @settings(max_examples=100, deadline=None)
    @given(inst)
    def test_dp_forward_matches_bruteforce(self, tup):
        pt, fc, bc, gt, dt = tup
        c = _mk(pt, fc, bc, gt, dt)
        segs, t = bruteforce_forward(c)
        res = dp_forward(c)
        assert res.time == pytest.approx(t, rel=1e-9, abs=1e-9)
        # and the DP's reported time is the f_m of its own segments
        assert res.time == pytest.approx(forward_time(c, res.segments),
                                         rel=1e-9, abs=1e-9)

    @settings(max_examples=100, deadline=None)
    @given(inst_asym)
    def test_dp_backward_matches_bruteforce(self, tup):
        """Including asymmetric Δt_bwd: the backward DP's objective must
        stay exact when a push pays a different per-transmission overhead
        than a pull (the PS uplink regime)."""
        (pt, fc, bc, gt, dt), dt_bwd, asym = tup
        c = _mk(pt, fc, bc, gt, dt, dt_bwd=dt_bwd if asym else None)
        segs, t = bruteforce_backward(c)
        res = dp_backward(c)
        assert res.time == pytest.approx(t, rel=1e-9, abs=1e-9)
        assert res.time == pytest.approx(backward_time(c, res.segments),
                                         rel=1e-9, abs=1e-9)

    @settings(max_examples=100, deadline=None)
    @given(inst_asym)
    def test_ibatch_backward_is_valid_and_lower_bounded(self, tup):
        """iBatch's greedy is *documented* to land in local optima
        (``core.greedy``: the greedy choice property does not hold, paper
        Fig. 5(c)) — so the oracle property is a sandwich, not equality:
        its decision is always valid, its reported time is the true f_m
        of that decision, and the exhaustive optimum lower-bounds it."""
        (pt, fc, bc, gt, dt), dt_bwd, asym = tup
        c = _mk(pt, fc, bc, gt, dt, dt_bwd=dt_bwd if asym else None)
        segs, t = ibatch_backward(c)
        validate_backward_segments(segs, c.num_layers)
        assert t == pytest.approx(backward_time(c, segs), rel=1e-9,
                                  abs=1e-9)
        _, opt = bruteforce_backward(c)
        assert t >= opt - 1e-9


class TestBandwidthMonotonicity:
    """More bandwidth can never hurt (ISSUE 4 satellite): comm costs scale
    as 1/bandwidth, so scaling pt/gt by s <= 1 must not increase any
    makespan — per fixed decision, at the optimum, and in the PS
    discrete-event simulator."""

    @settings(max_examples=100, deadline=None)
    @given(inst, st.floats(0.0, 1.0))
    def test_fixed_decision_times_monotone(self, tup, s):
        pt, fc, bc, gt, dt = tup
        c = _mk(pt, fc, bc, gt, dt)
        faster = c.scaled(comm=s)
        L = c.num_layers
        for segs in (((1, L),), lbl_forward(L)):
            assert forward_time(faster, segs) <= forward_time(c, segs) + 1e-9
        for segs in (((1, L),), lbl_backward(L)):
            assert backward_time(faster, segs) <= \
                backward_time(c, segs) + 1e-9

    @settings(max_examples=100, deadline=None)
    @given(inst, st.floats(0.0, 1.0))
    def test_optimum_monotone(self, tup, s):
        pt, fc, bc, gt, dt = tup
        c = _mk(pt, fc, bc, gt, dt)
        faster = c.scaled(comm=s)
        assert dp_forward(faster).time <= dp_forward(c).time + 1e-9
        assert dp_backward(faster).time <= dp_backward(c).time + 1e-9

    @settings(max_examples=50, deadline=None)
    @given(st.integers(2, 6).flatmap(lambda L: st.tuples(
        st.tuples(vec(L), vec(L), vec(L), vec(L), st.floats(0.0, 10.0)),
        st.tuples(vec(L), vec(L), vec(L), vec(L), st.floats(0.0, 10.0)),
        st.floats(0.0, 1.0))))
    def test_simulated_ps_makespan_monotone(self, tup):
        """The discrete-event PS makespan of a fixed shared decision is
        non-increasing when every link gets faster."""
        (t1, t2, s) = tup
        w1, w2 = _mk(*t1), _mk(*t2)       # same L: drawn from one flatmap
        L = w1.num_layers
        topo = TopologyCosts(workers=(w1, w2))
        d = (lbl_forward(L), lbl_backward(L))
        base = simulate_ps_iteration(topo, d).makespan
        fast = simulate_ps_iteration(topo.scaled(comm=s), d).makespan
        assert fast <= base + 1e-9
        # the simulator agrees with the closed-form straggler makespan
        assert base == pytest.approx(
            max(iteration_time(c, *d) for c in topo.workers))


class TestDecisionEncodings:
    @settings(max_examples=100, deadline=None)
    @given(st.integers(1, 12).flatmap(
        lambda L: st.lists(st.integers(0, 1), min_size=L - 1, max_size=L - 1)))
    def test_p_roundtrip(self, p):
        p = tuple(p)
        assert p_from_forward_segments(forward_segments_from_p(p)) == p

    @settings(max_examples=100, deadline=None)
    @given(st.integers(1, 12).flatmap(
        lambda L: st.lists(st.integers(0, 1), min_size=L - 1, max_size=L - 1)))
    def test_g_roundtrip(self, g):
        g = tuple(g)
        assert g_from_backward_segments(backward_segments_from_g(g)) == g
