"""repro.pipeline: partitioner, schedules, transfers, trainer, wiring.

Property layer (hypothesis): the stage partitioner's DP equals brute
force and respects the balanced-load bound; 1F1B streams satisfy their
ordering/in-flight invariants; the analytic bubble fraction equals the
event-driven simulation; DynaComm-segmented boundary transfers never
lose to the whole-tensor baseline.  Integration layer: the trainer's
losses are bit-identical across stage counts (the S=1 run is the
single-device execution of the same decomposition) and match the fused
single-device step to fp32 roundoff; checkpoint resume is bitwise; the
planner decision cache persists through save/restore (resumed re-plans
are pure cache hits).  The 4-forged-device variant (per-stage HLO
collective audit, device placement) lives in
``tests/helpers/pipeline_check.py`` behind ``-m slow``.
"""

import itertools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core import EdgeNetworkModel, Planner, dp_partition
from repro.optim import adamw
from repro.pipeline import (EMBED_LINK, PipelineTrainer,
                            analytic_bubble_fraction, boundary_costs,
                            gpipe_schedule, make_schedule,
                            one_f_one_b_schedule, partition_loads,
                            partition_profiles, plan_boundary, simulate,
                            whole_tensor_decision)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

loads_strategy = st.integers(1, 8).flatmap(
    lambda L: st.tuples(
        st.lists(st.floats(0.01, 100.0), min_size=L, max_size=L),
        st.integers(1, L)))


def _brute_force_bottleneck(loads, parts):
    """Min over all contiguous splits of the max part sum."""
    L = len(loads)
    best = float("inf")
    for cuts in itertools.combinations(range(1, L), parts - 1):
        edges = (0,) + cuts + (L,)
        bottleneck = max(sum(loads[a:b])
                         for a, b in zip(edges[:-1], edges[1:]))
        best = min(best, bottleneck)
    return best


class TestPartition:
    @settings(max_examples=100, deadline=None)
    @given(loads_strategy)
    def test_dp_matches_brute_force(self, inst):
        loads, parts = inst
        result = dp_partition(loads, parts)
        assert result.bottleneck == pytest.approx(
            _brute_force_bottleneck(loads, parts), rel=1e-9)

    @settings(max_examples=100, deadline=None)
    @given(loads_strategy)
    def test_balanced_load_bound(self, inst):
        """bottleneck <= total/parts + max single load (greedy bound)."""
        loads, parts = inst
        result = dp_partition(loads, parts)
        assert result.bottleneck <= \
            sum(loads) / parts + max(loads) + 1e-9

    @settings(max_examples=100, deadline=None)
    @given(loads_strategy)
    def test_segments_cover_contiguously(self, inst):
        loads, parts = inst
        part = partition_loads(loads, parts)
        assert part.segments[0][0] == 1
        assert part.segments[-1][1] == len(loads)
        for (_, hi), (lo, _) in zip(part.segments, part.segments[1:]):
            assert lo == hi + 1
        for s, (lo, hi) in enumerate(part.segments):
            for l in range(lo - 1, hi):
                assert part.stage_of[l] == s
            assert part.layers_of(s) == tuple(range(lo - 1, hi))

    def test_profiles_partition_rejects_too_many_stages(self):
        from repro.configs.base import InputShape
        from repro.models.profiles import layer_profiles
        cfg = get_config("granite-3-2b").reduced()
        profiles = layer_profiles(cfg, InputShape("t", 16, 2, "train"))
        with pytest.raises(ValueError, match="stages"):
            partition_profiles(profiles, len(profiles) + 1,
                               compute_flops_per_s=1e12)


sm_strategy = st.tuples(st.integers(1, 4), st.integers(1, 8))


class TestSchedule:
    @settings(max_examples=60, deadline=None)
    @given(sm_strategy)
    def test_one_f_one_b_in_flight_bound(self, sm):
        """Stage s keeps at most min(S - s, M) forwards in flight."""
        S, M = sm
        sched = one_f_one_b_schedule(S, M)
        for s, stream in enumerate(sched.streams):
            in_flight = peak = 0
            for task in stream:
                in_flight += 1 if task.kind == "F" else -1
                peak = max(peak, in_flight)
            assert in_flight == 0
            assert peak <= min(S - s, M)

    @settings(max_examples=60, deadline=None)
    @given(sm_strategy)
    def test_one_f_one_b_backward_follows_forward(self, sm):
        """B(m) never precedes F(m) in any stage stream."""
        S, M = sm
        sched = one_f_one_b_schedule(S, M)
        for stream in sched.streams:
            seen_fwd = set()
            for task in stream:
                if task.kind == "F":
                    seen_fwd.add(task.microbatch)
                else:
                    assert task.microbatch in seen_fwd

    @settings(max_examples=60, deadline=None)
    @given(sm_strategy)
    def test_gpipe_fill_then_drain(self, sm):
        S, M = sm
        sched = gpipe_schedule(S, M)
        for stream in sched.streams:
            kinds = [t.kind for t in stream]
            assert kinds == ["F"] * M + ["B"] * M

    @pytest.mark.parametrize("name", ("gpipe", "1f1b"))
    @pytest.mark.parametrize("S,M", [(1, 1), (2, 4), (3, 2), (4, 8)])
    def test_analytic_bubble_equals_simulated(self, name, S, M):
        sched = make_schedule(name, S, M)
        tl = simulate(sched, [1.0] * S, [2.0] * S)
        assert tl.bubble_fraction == pytest.approx(
            analytic_bubble_fraction(S, M), abs=1e-12)

    def test_simulate_charges_boundary_transfers(self):
        sched = make_schedule("1f1b", 2, 2)
        free = simulate(sched, [1.0, 1.0], [1.0, 1.0])
        slow = simulate(sched, [1.0, 1.0], [1.0, 1.0],
                        fwd_transfer=[0.5], bwd_transfer=[0.5])
        assert slow.makespan > free.makespan


class TestTransfer:
    NET = EdgeNetworkModel(bandwidth_bps=0.1e9)

    transfer_strategy = st.tuples(
        st.floats(1e4, 1e8),          # activation bytes
        st.integers(1, 6),            # microbatches
        st.integers(1, 4),            # chunks
        st.floats(1e-4, 0.5),         # stage fwd seconds
        st.floats(1e-4, 0.5))         # stage bwd seconds

    @settings(max_examples=60, deadline=None)
    @given(transfer_strategy)
    def test_segmented_never_loses_to_whole(self, inst):
        act, M, chunks, f, b = inst
        costs = boundary_costs(act, M, net=self.NET, stage_fwd_s=f,
                               stage_bwd_s=b, chunks=chunks)
        plan = plan_boundary(0, costs, microbatches=M, chunks=chunks)
        assert plan.fwd_time <= plan.whole_fwd_time + 1e-9
        assert plan.bwd_time <= plan.whole_bwd_time + 1e-9
        assert plan.speedup >= 1.0 - 1e-9

    def test_boundary_costs_structure(self):
        costs = boundary_costs(1e6, 3, net=self.NET, stage_fwd_s=0.05,
                               stage_bwd_s=0.1, chunks=2)
        assert costs.num_layers == 6
        np.testing.assert_allclose(costs.fc, [0, .05, 0, .05, 0, .05])
        np.testing.assert_allclose(costs.bc, [.1, 0, .1, 0, .1, 0])
        f, b = whole_tensor_decision(costs)
        assert f == ((1, 6),) and b == ((1, 6),)

    def test_segmentation_wins_at_edge_bandwidth(self):
        """The tentpole scenario: 100 Mbps, strict win over whole-tensor."""
        costs = boundary_costs(32 * 128 * 512 * 4, 4, net=self.NET,
                               stage_fwd_s=0.05, stage_bwd_s=0.1, chunks=4)
        plan = plan_boundary(0, costs, microbatches=4, chunks=4)
        assert plan.speedup > 1.05

    def test_homogeneous_boundaries_hit_planner_cache(self):
        planner = Planner(cache_size=8)
        costs = boundary_costs(1e6, 4, net=self.NET, stage_fwd_s=0.05,
                               stage_bwd_s=0.1, chunks=2)
        p0 = plan_boundary(0, costs, planner=planner, microbatches=4,
                           chunks=2)
        p1 = plan_boundary(1, costs, planner=planner, microbatches=4,
                           chunks=2)
        assert p0.decision == p1.decision
        assert planner.stats.solves == 1 and planner.stats.hits == 1


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("granite-3-2b").reduced()
    toks = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                              cfg.vocab_size)
    return cfg, {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}


def _run_trainer(cfg, batch, S, M, steps=2, **kw):
    tr = PipelineTrainer(cfg=cfg, optimizer=adamw(1e-3), num_stages=S,
                         num_microbatches=M, **kw)
    state = tr.init_state(jax.random.PRNGKey(0))
    losses = []
    for _ in range(steps):
        state, loss = tr.step(state, batch)
        losses.append(float(loss))
    return tr, state, losses


class TestTrainer:
    @pytest.mark.parametrize("M", (1, 2))
    def test_bit_identical_across_stage_counts(self, tiny, M):
        cfg, batch = tiny
        ref = _run_trainer(cfg, batch, 1, M)[2]
        for S in (2, 4):
            assert _run_trainer(cfg, batch, S, M)[2] == ref

    def test_matches_single_device_reference(self, tiny):
        from repro.models import init_params, train_loss
        cfg, batch = tiny
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw(1e-3)
        ostate = opt.init(params)

        @jax.jit
        def ref_step(params, ostate):
            loss, grads = jax.value_and_grad(
                lambda p: train_loss(cfg, p, batch, aux_weight=0.01))(params)
            params, ostate = opt.update(grads, ostate, params)
            return params, ostate, loss

        ref = []
        for _ in range(2):
            params, ostate, loss = ref_step(params, ostate)
            ref.append(float(loss))
        np.testing.assert_allclose(
            _run_trainer(cfg, batch, 2, 2)[2], ref, rtol=2e-5)

    def test_gpipe_matches_one_f_one_b(self, tiny):
        """Execution order differs; the summed numerators must not."""
        cfg, batch = tiny
        a = _run_trainer(cfg, batch, 2, 2, schedule_name="gpipe")[2]
        b = _run_trainer(cfg, batch, 2, 2, schedule_name="1f1b")[2]
        assert a == b

    def test_ledger_counts_exact(self, tiny):
        cfg, batch = tiny
        tr, _, _ = _run_trainer(cfg, batch, 2, 2, steps=2)
        led = tr.ledger
        # 2 steps x (2 microbatch acts across 1 boundary + 1 embed pull)
        assert led["num_pulls"] == 2 * (2 * 1 + 1)
        # 2 steps x (2 grads across 1 boundary + 2 embed-grad returns)
        assert led["num_pushes"] == 2 * (2 * 1 + 2)
        assert EMBED_LINK in led["boundary_pull_bytes"]

    def test_microbatch_divisibility_enforced(self, tiny):
        cfg, batch = tiny
        tr = PipelineTrainer(cfg=cfg, optimizer=adamw(1e-3), num_stages=2,
                             num_microbatches=3)
        state = tr.init_state(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="divisible"):
            tr.step(state, batch)

    def test_save_restore_resume_bitwise(self, tmp_path):
        from repro.runtime import RuntimeConfig, build_runtime
        cfg = RuntimeConfig.load(os.path.join(
            REPO, "examples", "runtime_configs", "pipeline.json"))
        rt = build_runtime(cfg)
        rt.fit(2)
        path = str(tmp_path / "pipe.npz")
        rt.save_state(path)
        cont = rt.fit(2)
        rt2 = build_runtime(cfg)
        rt2.restore_state(path)
        assert rt2.fit(2) == cont

    def test_transfer_plans_ride_cost_model(self, tiny):
        cfg, batch = tiny
        net = EdgeNetworkModel(bandwidth_bps=0.1e9)
        from repro.core import costs_from_profiles
        from repro.configs.base import InputShape
        from repro.models.profiles import layer_profiles
        profiles = layer_profiles(cfg, InputShape("t", 16, 4, "train"))
        costs = costs_from_profiles(profiles, net=net,
                                    compute_flops_per_s=1e10)
        tr, _, _ = _run_trainer(cfg, batch, 2, 2, costs=costs, net=net,
                                transfer_chunks=2)
        plans = tr.transfer_plans()
        assert len(plans) == 1
        assert plans[0].speedup >= 1.0
        tl = tr.timeline()
        assert tl is not None and tl.makespan > 0


class TestRuntimeWiring:
    def test_pipeline_config_validation(self):
        from repro.runtime import PipelineConfig, RuntimeConfig
        with pytest.raises(ValueError, match="schedule"):
            PipelineConfig(schedule="interleaved")
        with pytest.raises(ValueError, match="pipeline"):
            RuntimeConfig(runtime="zero", batch=2, seq=16,
                          pipeline=PipelineConfig())
        with pytest.raises(ValueError, match="divisible|microbatches"):
            RuntimeConfig(runtime="pipeline", batch=3, seq=16,
                          pipeline=PipelineConfig(microbatches=2))
        cfg = RuntimeConfig(runtime="pipeline", batch=4, seq=16)
        assert cfg.pipeline is not None      # auto-materialized block

    def test_smoke_config_builds_and_steps(self):
        from repro.runtime import RuntimeConfig, build_runtime
        cfg = RuntimeConfig.load(os.path.join(
            REPO, "examples", "runtime_configs", "pipeline.json"))
        rt = build_runtime(cfg)
        losses = rt.fit(1)
        assert len(losses) == 1 and np.isfinite(losses[0])
        assert rt.partition.num_stages == cfg.pipeline.stages
        assert rt.ledger["num_pulls"] > 0

    def test_launcher_flags_require_pipeline_runtime(self):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.train", "--runtime",
             "local", "--stages", "2", "--steps", "1"],
            capture_output=True, text=True, env=env, timeout=120)
        assert proc.returncode != 0
        assert "--runtime pipeline" in proc.stderr


class TestPlannerPersistence:
    def test_state_dict_round_trips_through_json(self):
        from repro.core import random_costs
        planner = Planner(cache_size=8)
        costs = [random_costs(5, seed=s, dt=1e-3) for s in range(3)]
        decisions = [planner.decide(c, "dynacomm") for c in costs]
        blob = json.dumps(planner.state_dict())
        restored = Planner(cache_size=8)
        restored.load_state_dict(json.loads(blob))
        assert [restored.decide(c, "dynacomm") for c in costs] == decisions
        assert restored.stats.hits == 3 and restored.stats.solves == 0

    def test_resumed_replan_is_cache_hit(self, tmp_path):
        """The zero runtime's re-planning loop: save mid-run, restore
        fresh, re-plan at the next epoch boundary — the restored decision
        cache must serve it without a single new DP solve."""
        from repro.runtime import (NetworkConfig, RuntimeConfig,
                                   ScheduleConfig, build_runtime)
        cfg = RuntimeConfig(
            runtime="zero", batch=2, seq=16,
            schedule=ScheduleConfig(
                strategy="dynacomm", reschedule_every=2,
                network=NetworkConfig(bandwidth_gbps=1.0, shift_gbps=0.1,
                                      shift_epoch=1)))
        rt = build_runtime(cfg)
        rt.fit(3)                       # crosses a re-plan boundary
        assert len(rt.planner) > 0
        path = str(tmp_path / "ck.npz")
        rt.save_state(path)
        rt2 = build_runtime(cfg)
        rt2.restore_state(path)
        assert len(rt2.planner) == len(rt.planner)
        rt2.fit(3)                      # next boundary re-plans
        stats = rt2.planner.stats
        assert stats.hits > 0, stats.as_dict()
        assert stats.solves == 0, stats.as_dict()


@pytest.mark.slow
class TestPipelineMultiDevice:
    @pytest.fixture(scope="class")
    def result(self):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tests", "helpers",
                                          "pipeline_check.py")],
            capture_output=True, text=True, env=env, timeout=1200)
        assert proc.returncode == 0, proc.stderr[-3000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def test_losses_bit_identical_across_stage_counts(self, result):
        for M in (1, 4):
            ref = result["losses"][f"S1M{M}"]
            for S in (2, 4):
                assert result["losses"][f"S{S}M{M}"] == ref, (S, M)

    def test_matches_single_device_reference(self, result):
        np.testing.assert_allclose(result["losses"]["S4M4"],
                                   result["reference_losses"], rtol=2e-5)

    def test_stage_programs_have_zero_collectives(self, result):
        for s, counts in enumerate(result["stage_collectives"]):
            assert counts == {"fwd": 0, "bwd": 0}, (s, counts)

    def test_ledger_counts_exact(self, result):
        led = result["ledger"]
        assert led["num_pulls"] == led["expected_pulls"]
        assert led["num_pushes"] == led["expected_pushes"]
        assert led["pull_bytes"] == led["expected_pull_bytes"]
        assert led["push_bytes"] == led["expected_push_bytes"]
