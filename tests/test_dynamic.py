"""Dynamic re-scheduling: time-varying networks, the per-layer timing hook,
and the re-planning loop of the zero and ps runtimes.

Quick tests run single-device at the cost-model level; the multi-device
trainer claims (plan swap, step-cache hit counts, bit-identical losses,
HLO collective counts) run in a 4-forged-device subprocess.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core import (EdgeNetworkModel, LayerTimingHook, NetworkSchedule,
                        TPUSystemModel, as_schedule, bandwidth_shift,
                        costs_from_profiles, schedule)
from repro.models.profiles import layer_profiles

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestNetworkSchedule:
    def test_piecewise_selection(self):
        hi, lo = EdgeNetworkModel(bandwidth_bps=10e9), \
            EdgeNetworkModel(bandwidth_bps=1e9)
        sched = NetworkSchedule(knots=((0, hi), (3, lo)))
        assert sched.model_at(0) is hi
        assert sched.model_at(2) is hi
        assert sched.model_at(3) is lo
        assert sched.model_at(100) is lo

    def test_validation(self):
        m = EdgeNetworkModel()
        with pytest.raises(ValueError):
            NetworkSchedule(knots=())
        with pytest.raises(ValueError):
            NetworkSchedule(knots=((1, m),))          # must start at 0
        with pytest.raises(ValueError):
            NetworkSchedule(knots=((0, m), (0, m)))   # strictly increasing
        with pytest.raises(ValueError):
            NetworkSchedule(knots=((0, m),)).model_at(-1)

    def test_as_schedule_idempotent(self):
        m = TPUSystemModel()
        s = as_schedule(m)
        assert s.model_at(7) is m
        assert as_schedule(s) is s

    def test_bandwidth_shift(self):
        s = bandwidth_shift(10e9, 1e9, at_epoch=2)
        assert s.model_at(1).bandwidth_bps == 10e9
        assert s.model_at(2).bandwidth_bps == 1e9
        # RTT (and hence Δt) unchanged across the shift
        assert s.model_at(0).dt == s.model_at(2).dt
        with pytest.raises(ValueError):
            bandwidth_shift(10e9, 1e9, at_epoch=0)


class TestNetworkScheduleEdgeCases:
    """Epochs exactly on shift boundaries, degenerate knot lists,
    non-monotone epochs (ISSUE 3 satellite)."""

    def _models(self, n):
        return [EdgeNetworkModel(bandwidth_bps=(i + 1) * 1e9)
                for i in range(n)]

    def test_epoch_exactly_on_every_boundary(self):
        """model_at at a knot's start epoch returns the *new* model — the
        shift applies to the boundary epoch itself, for every knot."""
        m = self._models(3)
        sched = NetworkSchedule(knots=((0, m[0]), (2, m[1]), (5, m[2])))
        assert sched.model_at(0) is m[0]
        assert sched.model_at(1) is m[0]
        assert sched.model_at(2) is m[1]          # exactly on the boundary
        assert sched.model_at(4) is m[1]
        assert sched.model_at(5) is m[2]          # exactly on the boundary
        assert sched.model_at(10 ** 9) is m[2]    # far past the last knot

    def test_empty_schedule_rejected(self):
        with pytest.raises(ValueError, match="at least one knot"):
            NetworkSchedule(knots=())

    def test_non_monotone_epochs_rejected(self):
        m = self._models(3)
        with pytest.raises(ValueError, match="strictly increasing"):
            NetworkSchedule(knots=((0, m[0]), (3, m[1]), (2, m[2])))
        with pytest.raises(ValueError, match="strictly increasing"):
            NetworkSchedule(knots=((0, m[0]), (2, m[1]), (2, m[2])))

    def test_first_knot_must_anchor_epoch_zero(self):
        (m,) = self._models(1)
        with pytest.raises(ValueError, match="epoch 0"):
            NetworkSchedule(knots=((3, m),))

    def test_single_knot_covers_all_epochs(self):
        (m,) = self._models(1)
        sched = NetworkSchedule(knots=((0, m),))
        assert sched.num_knots == 1
        for e in (0, 1, 7, 12345):
            assert sched.model_at(e) is m

    def test_negative_epoch_rejected(self):
        (m,) = self._models(1)
        with pytest.raises(ValueError, match=">= 0"):
            NetworkSchedule(knots=((0, m),)).model_at(-3)

    def test_float_like_epochs_coerced(self):
        """Knot epochs are coerced to int on construction."""
        m = self._models(2)
        sched = NetworkSchedule(knots=((0.0, m[0]), (2.0, m[1])))
        assert sched.knots[1][0] == 2
        assert sched.model_at(2) is m[1]


class TestTopologySchedule:
    """Time-varying PS topologies: the NetworkSchedule edge-case contract
    applied to whole fabrics (ISSUE 4 satellite)."""

    def _topos(self, n, workers=2):
        from repro.ps import PSTopology
        return [PSTopology.uniform(1, workers, up_bps=(i + 1) * 1e9)
                for i in range(n)]

    def test_epoch_exactly_on_every_boundary(self):
        """topology_at at a knot's start epoch returns the *new* topology
        — the shift applies to the boundary epoch itself, for every
        knot."""
        from repro.ps import TopologySchedule
        t = self._topos(3)
        sched = TopologySchedule(knots=((0, t[0]), (2, t[1]), (5, t[2])))
        assert sched.topology_at(0) is t[0]
        assert sched.topology_at(1) is t[0]
        assert sched.topology_at(2) is t[1]       # exactly on the boundary
        assert sched.topology_at(4) is t[1]
        assert sched.topology_at(5) is t[2]       # exactly on the boundary
        assert sched.topology_at(10 ** 9) is t[2]
        assert sched.shift_epochs() == (2, 5)

    def test_zero_length_epochs_rejected(self):
        """Two knots at the same epoch would make a zero-length epoch."""
        from repro.ps import TopologySchedule
        t = self._topos(3)
        with pytest.raises(ValueError, match="strictly increasing"):
            TopologySchedule(knots=((0, t[0]), (2, t[1]), (2, t[2])))
        with pytest.raises(ValueError, match="strictly increasing"):
            TopologySchedule(knots=((0, t[0]), (3, t[1]), (2, t[2])))

    def test_empty_and_unanchored_rejected(self):
        from repro.ps import TopologySchedule
        (t,) = self._topos(1)
        with pytest.raises(ValueError, match="at least one knot"):
            TopologySchedule(knots=())
        with pytest.raises(ValueError, match="epoch 0"):
            TopologySchedule(knots=((3, t),))

    def test_negative_epoch_rejected(self):
        from repro.ps import TopologySchedule
        (t,) = self._topos(1)
        with pytest.raises(ValueError, match=">= 0"):
            TopologySchedule(knots=((0, t),)).topology_at(-1)

    def test_worker_count_must_stay_fixed(self):
        """Workers map onto devices/actors and cannot join or leave."""
        from repro.ps import TopologySchedule, PSTopology
        a = PSTopology.uniform(1, 2)
        b = PSTopology.uniform(1, 3)
        with pytest.raises(ValueError, match="num_workers"):
            TopologySchedule(knots=((0, a), (2, b)))

    def test_non_topology_knot_rejected(self):
        from repro.ps import TopologySchedule
        with pytest.raises(TypeError, match="not PSTopology"):
            TopologySchedule(knots=((0, EdgeNetworkModel()),))

    def test_as_topology_schedule_idempotent(self):
        from repro.ps import PSTopology, as_topology_schedule
        topo = PSTopology.uniform(2, 2)
        s = as_topology_schedule(topo)
        assert s.topology_at(7) is topo
        assert as_topology_schedule(s) is s

    def test_float_like_epochs_coerced(self):
        from repro.ps import TopologySchedule
        t = self._topos(2)
        sched = TopologySchedule(knots=((0.0, t[0]), (2.0, t[1])))
        assert sched.knots[1][0] == 2
        assert sched.topology_at(2) is t[1]

    def test_uplink_degradation_helper(self):
        from repro.ps import PSTopology, uplink_degradation
        base = PSTopology.uniform(2, 3, down_bps=10e9, up_bps=4e9)
        sched = uplink_degradation(base, factor=4, at_epoch=2)
        assert sched.topology_at(1) is base
        after = sched.topology_at(2)
        for before_l, after_l in zip(base.links, after.links):
            assert after_l.up.bandwidth_bps == \
                pytest.approx(before_l.up.bandwidth_bps / 4)
            assert after_l.down is before_l.down       # downlinks untouched
        assert after.worker_flops == base.worker_flops
        with pytest.raises(ValueError, match="at_epoch"):
            uplink_degradation(base, factor=4, at_epoch=0)
        with pytest.raises(ValueError, match="factor"):
            uplink_degradation(base, factor=0.0, at_epoch=1)


class TestTopologyScheduler:
    """Epoch-cached consensus / per-worker planning (core plumbing)."""

    def _costs(self):
        from repro.core import random_costs
        from repro.core.costmodel import TopologyCosts
        return TopologyCosts(workers=(
            random_costs(6, seed=0),
            random_costs(6, seed=0, comp_scale=5.0, comm_scale=2.0)))

    def test_consensus_mode_caches_until_boundary(self):
        from repro.core import TopologyScheduler, consensus_decision
        topo = self._costs()
        sched = TopologyScheduler(reschedule_every=3)
        d0 = sched.decision_for_iteration(topo)
        assert d0 == consensus_decision(topo, "dynacomm")[0]
        assert sched.last_makespan == pytest.approx(topo.makespan(*d0))
        t0 = sched.last_scheduling_seconds
        assert sched.decision_for_iteration(topo) == d0    # cached
        assert sched.last_scheduling_seconds == t0         # no re-plan
        sched.decision_for_iteration(topo)                 # iter 3
        sched.decision_for_iteration(topo)                 # boundary: re-plan
        assert sched._iter_seen == 4

    def test_per_worker_mode(self):
        from repro.core import TopologyScheduler, schedule_topology
        topo = self._costs()
        sched = TopologyScheduler(mode="per-worker")
        decisions = sched.decision_for_iteration(topo)
        assert decisions == schedule_topology(topo, "dynacomm")
        assert len(decisions) == topo.num_workers

    def test_overhead_hidden_uses_min_idle_window(self):
        from repro.core import TopologyScheduler
        topo = self._costs()
        sched = TopologyScheduler()
        sched.decision_for_iteration(topo)
        assert topo.idle_window == \
            min(c.dt_push + float(c.gt[0]) for c in topo.workers)
        sched.last_scheduling_seconds = topo.idle_window * 0.5
        assert sched.scheduling_overhead_hidden(topo)
        sched.last_scheduling_seconds = topo.idle_window * 2.0
        assert not sched.scheduling_overhead_hidden(topo)

    def test_validation(self):
        from repro.core import TopologyScheduler
        with pytest.raises(ValueError, match="strategy"):
            TopologyScheduler(strategy="psychic")
        with pytest.raises(ValueError, match="reschedule_every"):
            TopologyScheduler(reschedule_every=0)
        with pytest.raises(ValueError, match="mode"):
            TopologyScheduler(mode="vote")


class TestPSReplanTimeline:
    def test_stale_plan_penalty(self):
        """Freezing the epoch-0 plan across a drift can only lose to
        re-planning (per epoch, the re-plan minimizes over a candidate
        set containing the frozen plan's per-worker optima)."""
        from repro.core import (TopologyScheduler, simulate_ps_replan)
        from repro.core.costmodel import TopologyCosts
        from repro.core import random_costs
        base = TopologyCosts(workers=(
            random_costs(6, seed=1), random_costs(6, seed=2)))
        epoch_costs = [base, base.scaled(comm=4.0), base.scaled(comm=16.0)]
        sched = TopologyScheduler(reschedule_every=1)
        decisions = []
        for c in epoch_costs:
            sched.invalidate()
            decisions.append(sched.decision_for_iteration(c))
        tl = simulate_ps_replan(epoch_costs, decisions)
        assert tl.num_epochs == 3
        assert tl.stale_plan_penalty(0) == pytest.approx(0.0)
        for e in range(3):
            # consensus evaluates the frozen decision among its candidates
            # only at epoch 0; later epochs may not, so only assert the
            # simulated numbers are consistent, not a universal sign
            assert tl.makespans[e] == \
                pytest.approx(tl.replanned[e].makespan)
            assert tl.frozen_makespans[e] == \
                pytest.approx(tl.frozen[e].makespan)

    def test_validation(self):
        from repro.core import simulate_ps_replan, PSReplanTimeline
        from repro.core.costmodel import TopologyCosts
        from repro.core import random_costs
        topo = TopologyCosts(workers=(random_costs(4, seed=0),))
        d = (((1, 4),), ((4, 1),))
        with pytest.raises(ValueError, match="epoch costs"):
            simulate_ps_replan([topo, topo], [d])
        with pytest.raises(ValueError, match="at least one epoch"):
            PSReplanTimeline(replanned=(), frozen=())


class TestLayerTimingHook:
    def test_medians_drop_warmup(self):
        hook = LayerTimingHook(warmup=1)
        for l, (first, rest) in enumerate([(9.0, 1.0), (9.0, 2.0)]):
            hook.record("fc", l, first)      # compile-tainted sample
            hook.record("fc", l, rest)
            hook.record("fc", l, rest)
        np.testing.assert_allclose(hook.median("fc", 2), [1.0, 2.0])

    def test_missing_layer_raises(self):
        hook = LayerTimingHook(warmup=0)
        hook.record("fc", 0, 1.0)
        with pytest.raises(ValueError, match="layer 1"):
            hook.median("fc", 2)

    def test_timed_wrapper_records(self):
        hook = LayerTimingHook(warmup=0)
        fn = hook.timed("bc", 3, lambda x: x + 1)
        assert fn(41) == 42
        assert hook.num_samples("bc", 3) == 1

    def test_costs_assembly(self):
        hook = LayerTimingHook(warmup=0)
        for l in range(3):
            hook.record("fc", l, 1e-3 * (l + 1))
            hook.record("bc", l, 2e-3 * (l + 1))
        net = EdgeNetworkModel(bandwidth_bps=1e9)
        costs = hook.costs(param_bytes=[1e6, 2e6, 3e6], net=net)
        assert costs.num_layers == 3
        np.testing.assert_allclose(costs.fc, [1e-3, 2e-3, 3e-3])
        np.testing.assert_allclose(costs.bc, [2e-3, 4e-3, 6e-3])
        np.testing.assert_allclose(costs.pt, costs.gt)
        assert costs.dt == net.dt
        hook.reset()
        with pytest.raises(ValueError):
            hook.median("fc", 1)


class TestDriftChangesDecision:
    def test_dp_resegment_across_bandwidth_drop(self):
        """The scenario the trainer test exercises, at the cost-model level:
        dynacomm's decision differs between 10 Gbps and 1 Gbps."""
        cfg = get_config("granite-3-2b").reduced()
        profs = layer_profiles(cfg, InputShape("dyn", 32, 8, "train"))
        decisions = []
        for bw in (10e9, 1e9):
            costs = costs_from_profiles(
                profs, net=EdgeNetworkModel(bandwidth_bps=bw),
                compute_flops_per_s=1e10)
            decisions.append(schedule(costs, "dynacomm"))
        assert decisions[0] != decisions[1]


def _zero_loop(cfg, pipe, *, every, shift_epoch=1, **schedule):
    """The zero runtime re-planning over a 10 → 1 Gbps network."""
    from repro.runtime import (MeasureConfig, NetworkConfig, RuntimeConfig,
                               ScheduleConfig, build_runtime)
    config = RuntimeConfig(
        runtime="zero", batch=pipe.batch_size, seq=pipe.seq_len, lr=1e-3,
        schedule=ScheduleConfig(
            reschedule_every=every, network=NetworkConfig(
                bandwidth_gbps=10.0, shift_gbps=1.0,
                shift_epoch=shift_epoch), **schedule),
        measure=MeasureConfig(compute_flops_per_s=1e10))
    return build_runtime(config, model=cfg, data=pipe.batch)


class TestDynamicTrainerSingleDevice:
    def test_constructor_validation(self):
        """The loop's interval and cost source are checked where the
        configuration is made, before any trainer is built."""
        from repro.runtime import MeasureConfig, RuntimeConfig, ScheduleConfig
        with pytest.raises(ValueError, match="reschedule_every"):
            RuntimeConfig(runtime="zero",
                          schedule=ScheduleConfig(reschedule_every=0))
        with pytest.raises(ValueError, match="cost_source"):
            RuntimeConfig(runtime="zero",
                          measure=MeasureConfig(cost_source="psychic"))

    def test_sequential_plan_shape(self):
        from repro.runtime.replan import sequential_plan
        p = sequential_plan(4)
        assert p.forward == ((0, 1, 2, 3),)
        assert p.backward == ((3, 2, 1, 0),)

    def test_hlo_collective_counts(self):
        from repro.runtime.replan import hlo_collective_counts
        hlo = (
            "  %a = f32[4,16]{1,0} all-gather(f32[1,16]{1,0} %x), "
            "dimensions={0}\n"
            "  %b = f32[1,4]{1,0} reduce-scatter(f32[4,4]{1,0} %y), "
            "dimensions={0}\n"
            "  %c = (f32[8]{0}, f32[32]{0}) all-gather-start(f32[8]{0} %z), "
            "dimensions={0}\n")
        assert hlo_collective_counts(hlo) == (2, 1)


class TestEwmaDriftDetector:
    def test_validation(self):
        from repro.core import EwmaDriftDetector
        for kw in ({"alpha": 0.0}, {"alpha": 1.5}, {"threshold": 0.0},
                   {"patience": 0}, {"warmup": -1}):
            with pytest.raises(ValueError):
                EwmaDriftDetector(**kw)
        with pytest.raises(ValueError):
            EwmaDriftDetector().update(-1.0)

    def test_persistent_shift_triggers_once(self):
        from repro.core import EwmaDriftDetector
        det = EwmaDriftDetector(warmup=2, patience=2, threshold=0.3)
        out = [det.update(t) for t in [1.0] * 5 + [2.0] * 6]
        assert sum(out) == 1                      # one trigger per shift
        assert out[6]                             # fires on the 2nd drifted
        assert det.num_triggers == 1
        # after re-seeding at 2.0, a shift back down re-triggers
        out2 = [det.update(t) for t in [1.0] * 3]
        assert sum(out2) == 1

    def test_blip_absorbed_by_patience(self):
        from repro.core import EwmaDriftDetector
        det = EwmaDriftDetector(warmup=1, patience=3, threshold=0.3)
        out = [det.update(t) for t in [1.0, 1.0, 1.0, 5.0, 1.0, 1.0, 5.0,
                                       5.0, 1.0, 1.0]]
        assert not any(out)                       # isolated spikes never fire
        assert det.baseline == pytest.approx(1.0, rel=0.05)

    def test_warmup_never_triggers(self):
        from repro.core import EwmaDriftDetector
        det = EwmaDriftDetector(warmup=5, patience=1, threshold=0.1)
        assert not any(det.update(t) for t in [1.0, 9.0, 1.0, 9.0, 1.0])

    def test_reset(self):
        from repro.core import EwmaDriftDetector
        det = EwmaDriftDetector(warmup=0, patience=1, threshold=0.1)
        det.update(1.0)
        det.reset()
        assert det.baseline is None and det.num_triggers == 0

    def test_state_dict_roundtrip(self):
        """A restored detector continues from the saved baseline instead of
        re-entering warmup (the dynamic loop checkpoints this)."""
        from repro.core import EwmaDriftDetector
        a = EwmaDriftDetector(warmup=2, patience=2, threshold=0.3)
        for t in [1.0, 1.0, 1.0, 2.0]:       # mid-streak: one drifted sample
            a.update(t)
        b = EwmaDriftDetector(warmup=2, patience=2, threshold=0.3)
        b.load_state_dict(a.state_dict())
        assert b.baseline == a.baseline
        assert b.update(2.0)                 # 2nd drifted sample: fires now
        assert not a.state_dict() == b.state_dict()  # b re-seeded at 2.0


class TestCheckpointTextLeaves:
    def test_string_leaf_roundtrip(self, tmp_path):
        """repro.checkpoint carries variable-width text leaves (the
        dynamic loop stores JSON metadata this way)."""
        from repro.checkpoint.ckpt import load_checkpoint, save_checkpoint
        tree = {"meta": np.asarray('{"plan": [1, 2, 3]}'),
                "x": np.arange(4, dtype=np.float32)}
        path = str(tmp_path / "ck.npz")
        save_checkpoint(path, tree, step=7)
        template = {"meta": np.asarray(""), "x": np.zeros(4, np.float32)}
        restored, step = load_checkpoint(path, template)
        assert step == 7
        assert str(restored["meta"]) == '{"plan": [1, 2, 3]}'
        np.testing.assert_array_equal(restored["x"], tree["x"])

    def test_numeric_shape_still_checked(self, tmp_path):
        from repro.checkpoint.ckpt import load_checkpoint, save_checkpoint
        path = str(tmp_path / "ck.npz")
        save_checkpoint(path, {"x": np.zeros(4)})
        with pytest.raises(ValueError, match="shape mismatch"):
            load_checkpoint(path, {"x": np.zeros(5)})


class TestDynamicLoopStateSingleDevice:
    """Checkpoint/restore of the dynamic loop + drift-detector wiring,
    on a 1-device mesh (collectives over a size-1 axis are valid)."""

    @pytest.fixture(scope="class")
    def setup(self):
        from repro.data.pipeline import SyntheticText
        cfg = get_config("granite-3-2b").reduced()
        pipe = SyntheticText(cfg.vocab_size, 32, 4, seed=0)
        return cfg, pipe

    def test_resume_is_bit_identical(self, setup, tmp_path):
        cfg, pipe = setup

        ref = _zero_loop(cfg, pipe, every=2, shift_epoch=2)
        ref_losses = ref.fit(6)

        a = _zero_loop(cfg, pipe, every=2, shift_epoch=2)
        losses = a.fit(3)                         # stop mid-epoch
        path = str(tmp_path / "rt.npz")
        a.save_state(path)

        b = _zero_loop(cfg, pipe, every=2, shift_epoch=2)  # no memory
        b.restore_state(path)
        assert b._data_idx == 3
        assert b.plan == a.plan
        assert [e.step for e in b.events] == [e.step for e in a.events]
        losses += b.fit(3)
        assert losses == ref_losses
        # resume replays the same re-schedule history as the straight run
        assert [(e.step, e.epoch, e.plan) for e in b.events] == \
            [(e.step, e.epoch, e.plan) for e in ref.events]
        # the mid-epoch recompile is not recorded as a scheduling event
        assert len(b.events) == len(ref.events)

    def test_drift_detector_forces_reschedule(self, setup):
        cfg, pipe = setup

        class FireOnce:
            calls = 0

            def update(self, seconds):
                self.calls += 1
                return self.calls == 2            # fires after step 2

        dyn = _zero_loop(cfg, pipe, every=100, drift_detect=True)
        dyn.drift_detector = FireOnce()
        dyn.fit(4)
        triggers = [(e.step, e.trigger) for e in dyn.events]
        assert triggers[0] == (0, "epoch")
        assert (2, "drift") in triggers           # detector-forced re-plan
        assert dyn.scheduler._iter_seen == 4      # epoch alignment intact


class TestDynamicPSTrainerSingleDevice:
    """The ps runtime's loop on a 1-device mesh over a degrading uplink:
    plan swap exactly on the topology-epoch boundary, compiled-step
    cache, and sync losses bit-identical to statically running each
    epoch's plan (the 4-forged-device version runs in the slow
    subprocess check)."""

    STEPS_PER_EPOCH = 2

    @pytest.fixture(scope="class")
    def run(self):
        from repro.data.pipeline import SyntheticText
        from repro.runtime import (RuntimeConfig, ScheduleConfig,
                                   TopologyConfig, build_runtime)

        cfg = get_config("granite-3-2b").reduced()
        pipe = SyntheticText(cfg.vocab_size, 32, 4, seed=0)
        config = RuntimeConfig(
            runtime="ps", batch=4, seq=32, lr=1e-3,
            schedule=ScheduleConfig(
                reschedule_every=self.STEPS_PER_EPOCH,
                topology=TopologyConfig(
                    servers=2, down_gbps=10.0, up_gbps=10.0,
                    worker_flops=1e10, up_shift_factor=10.0,
                    shift_epoch=1)))
        dyn = build_runtime(config, model=cfg, data=pipe.batch)
        losses = dyn.fit(3 * self.STEPS_PER_EPOCH)
        return dyn, dyn.topology, pipe, losses

    def test_plan_swaps_exactly_on_boundary_steps(self, run):
        dyn, _, _, _ = run
        assert [e.step for e in dyn.events] == \
            [i * self.STEPS_PER_EPOCH for i in range(3)]
        assert not dyn.events[0].plan_changed
        assert dyn.events[1].plan_changed, \
            "the 10x uplink degradation must re-segment the push plan"
        assert dyn.events[1].step == self.STEPS_PER_EPOCH
        # the degraded uplink wants fewer, larger pushes... or at least a
        # different decomposition; sanity: backward segmentation moved
        assert dyn.events[1].plan.backward != dyn.events[0].plan.backward

    def test_one_trace_per_distinct_plan(self, run):
        dyn, _, _, _ = run
        assert dyn.traces == len(dyn.plans_seen) == 2
        assert not dyn.events[2].retraced          # epoch 2 keeps the plan
        # one device and no compressor: the state is the leaves, so the
        # step has no wire and no collective to count
        assert dyn.trainer.layout == "leaves"
        for plan in dyn.plans_seen:
            assert dyn.hlo_counts(plan) == (0, 0)

    def test_losses_bit_identical_to_static_plan_sequence(self, run):
        import jax
        from repro.core import consensus_decision
        from repro.models.profiles import layer_profiles
        from repro.models import num_sched_layers
        from repro.core import plan_from_decision
        from repro.optim import adamw
        from repro.ps import PSTrainer

        dyn, sched, pipe, losses = run
        cfg = get_config("granite-3-2b").reduced()
        shape = InputShape("dyn-ps", 32, 4, "train")
        profs = layer_profiles(cfg, shape)
        base = PSTrainer(cfg=cfg, mesh=dyn.trainer.mesh,
                         plan=dyn.plans_seen[0],
                         optimizer=adamw(1e-3),
                         topology=sched.topology_at(0))
        state = base.init_state(jax.random.PRNGKey(0))
        ref, fns = [], {}
        for epoch in range(3):
            costs = sched.topology_at(epoch).topology_costs(profs)
            d, _ = consensus_decision(costs, "dynacomm")
            plan = plan_from_decision(*d, num_sched_layers(cfg))
            if plan not in fns:
                fns[plan] = jax.jit(base.with_plan(plan).build_train_step())
            for i in range(epoch * self.STEPS_PER_EPOCH,
                           (epoch + 1) * self.STEPS_PER_EPOCH):
                state, loss = fns[plan](state, pipe.batch(i))
                ref.append(float(loss))
        assert losses == ref

    def test_overhead_hidden_against_topology_window(self, run):
        """`overhead_hidden` must be exactly the Table I predicate
        against the topology's min Δt + gt¹ window.  (Asserting the flag
        is *True* would be a wall-clock assertion — flaky under CPU
        contention — so the quick suite pins the relationship; the slow
        subprocess check asserts truth on an otherwise-idle run.)"""
        dyn, _, _, _ = run
        for e in dyn.events:
            window = dyn.costs_for_epoch(e.epoch).idle_window
            assert e.overhead_hidden == (e.scheduling_seconds <= window)
            assert e.scheduling_seconds >= 0

    def test_timeline_and_replan_views(self, run):
        """The driver's simulator views: per-epoch timelines of the
        active plan, and the re-planned-vs-frozen stale-plan penalty."""
        dyn, _, _, _ = run
        tl = dyn.timeline()
        assert tl.num_workers == 1
        assert tl.makespan > 0
        rp = dyn.replan_timeline()
        assert rp.num_epochs == 3
        assert rp.stale_plan_penalty(0) == pytest.approx(0.0)
        # under the degraded uplink the re-planned decomposition must be
        # at least as good as freezing the epoch-0 plan
        for e in range(1, 3):
            assert rp.makespans[e] <= rp.frozen_makespans[e] + 1e-12

    def test_constructor_validation(self):
        from repro.runtime import (RuntimeConfig, ScheduleConfig,
                                   TopologyConfig, build_runtime)
        cfg = get_config("granite-3-2b").reduced()
        with pytest.raises(ValueError, match="reschedule_every"):
            ScheduleConfig(reschedule_every=0,
                           topology=TopologyConfig(up_shift_factor=10.0))
        with pytest.raises(ValueError, match="workers"):
            # 4-worker schedule on a 1-device mesh
            build_runtime(RuntimeConfig(
                runtime="ps", batch=4, seq=32,
                schedule=ScheduleConfig(topology=TopologyConfig(
                    servers=1, workers=4, up_shift_factor=10.0))),
                model=cfg)


def _run_helper(name):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "helpers", name)],
        capture_output=True, text=True, env=env, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
class TestDynamicTrainerMultiDevice:
    @pytest.fixture(scope="class")
    def result(self):
        return _run_helper("dynamic_trainer_check.py")

    def test_plan_changes_on_bandwidth_drop(self, result):
        ev = result["events"]
        assert len(ev) == 3                      # one per epoch boundary
        assert [e["step"] for e in ev] == [0, 3, 6]
        assert not ev[0]["changed"]              # first plan isn't a "change"
        assert ev[1]["changed"], "10→1 Gbps drop must re-segment the plan"
        assert (ev[1]["fwd"], ev[1]["bwd"]) != (ev[0]["fwd"], ev[0]["bwd"])

    def test_revisited_plan_hits_step_cache(self, result):
        """Exactly one new trace per distinct plan; the revisit re-traces
        nothing."""
        ev = result["events"]
        assert ev[2]["changed"] and not ev[2]["retraced"]
        assert (ev[2]["fwd"], ev[2]["bwd"]) == (ev[0]["fwd"], ev[0]["bwd"])
        assert result["traces"] == len(result["plans"]) == 2
        assert result["cache_hits"] == 1

    def test_hlo_counts_match_plans(self, result):
        for p in result["plans"]:
            assert p["ag"] == p["fwd"], p
            assert p["rs"] == p["bwd"], p

    def test_losses_bit_identical_to_static_sequence(self, result):
        assert result["losses_dyn"] == result["losses_static"]

    def test_scheduling_overhead_hidden(self, result):
        for e in result["events"]:
            assert e["sched_s"] >= 0
        # The epoch-0 pass has no in-flight gradient push to hide behind
        # (and pays one-time warmup), so Table I's claim is asserted for the
        # steady-state re-schedules only.
        for e in result["events"][1:]:
            assert e["hidden"], "DP must fit in the Δt + gt¹ idle window"


@pytest.mark.slow
class TestDynamicPSTrainerMultiDevice:
    """4-forged-device dynamic-PS run: degrade-then-recover uplinks, plan
    swap + cache revisit + bit-identity vs the static plan sequence (the
    ISSUE 4 acceptance criterion at deployment scale)."""

    @pytest.fixture(scope="class")
    def result(self):
        return _run_helper("dynamic_ps_check.py")

    def test_plan_changes_on_uplink_degradation_and_recovers(self, result):
        ev = result["events"]
        assert len(ev) == 3
        assert [e["step"] for e in ev] == [0, 3, 6]
        assert not ev[0]["changed"]
        assert ev[1]["changed"], \
            "10x slower uplinks must re-segment the consensus plan"
        assert ev[2]["changed"]                   # recovery swaps back
        assert (ev[2]["fwd"], ev[2]["bwd"]) == (ev[0]["fwd"], ev[0]["bwd"])

    def test_revisited_plan_hits_step_cache(self, result):
        assert result["traces"] == len(result["plans"]) == 2
        assert result["cache_hits"] == 1
        assert not result["events"][2]["retraced"]

    def test_hlo_one_pull_one_push_per_segment(self, result):
        for p in result["plans"]:
            assert p["ag"] == p["fwd"], p
            assert p["rs"] == p["bwd"], p

    def test_losses_bit_identical_to_static_sequence(self, result):
        assert result["losses_dyn"] == result["losses_static"]

    def test_scheduling_overhead_hidden(self, result):
        for e in result["events"][1:]:
            assert e["hidden"], \
                "DP must fit the topology's min Δt + gt¹ idle window"
