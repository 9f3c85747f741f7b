"""The repro.runtime layer: config round-trips, the registry, the Trainer
protocol over every regime, the plan-once and re-planning paths of zero
and ps, BSP push aggregation, and measured per-worker PS costs.

Every registered runtime is built from its checked-in smoke config
(``examples/runtime_configs/*.json``) and driven single-device; invalid
config combinations must fail at construction with clear ValueErrors.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import (ExecutionConfig, FleetConfig, FleetEventConfig,
                           MeasureConfig, NetworkConfig, RuntimeConfig,
                           ScheduleConfig, TopologyConfig, Trainer,
                           build_runtime, runtime_names)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE_DIR = os.path.join(REPO, "examples", "runtime_configs")

SMOKE = dict(batch=2, seq=16, reduced=True)


def smoke_config_paths():
    paths = sorted(glob.glob(os.path.join(SMOKE_DIR, "*.json")))
    assert paths, f"no smoke configs under {SMOKE_DIR}"
    return paths


# ---------------------------------------------------------------------------
# config: JSON round-trip + validation
# ---------------------------------------------------------------------------


class TestRuntimeConfig:
    def test_every_smoke_config_round_trips(self):
        names = set()
        for path in smoke_config_paths():
            c = RuntimeConfig.load(path)
            assert RuntimeConfig.from_json(c.to_json()) == c, path
            names.add(c.runtime)
        # one smoke config per registered runtime
        assert names == set(runtime_names())

    def test_nested_heterogeneous_round_trip(self):
        c = RuntimeConfig(
            runtime="ps-async", **SMOKE,
            execution=ExecutionConfig(staleness=0, throttle="wait",
                                      aggregate=True),
            schedule=ScheduleConfig(topology=TopologyConfig(
                servers=3, workers=4, down_gbps=(10.0, 10.0, 2.5, 2.5),
                up_gbps=(1.0, 1.0, 0.25, 0.25),
                worker_flops=(4e10, 4e10, 1e10, 1e10))),
            measure=MeasureConfig(remeasure_every=3))
        again = RuntimeConfig.from_json(c.to_json())
        assert again == c
        assert again.schedule.topology.down_gbps == (10.0, 10.0, 2.5, 2.5)

    def test_from_dict_and_json_string_inputs(self):
        c = RuntimeConfig(runtime="zero", **SMOKE)
        assert build_runtime is not None
        assert RuntimeConfig.from_dict(c.to_dict()) == c

    def test_unknown_runtime_rejected(self):
        with pytest.raises(ValueError, match="unknown runtime"):
            RuntimeConfig(runtime="psychic")

    def test_unknown_top_level_field_rejected(self):
        with pytest.raises(ValueError, match="unknown RuntimeConfig"):
            RuntimeConfig.from_dict({"runtime": "zero", "warp": 9})

    def test_staleness_on_sync_runtime_rejected(self):
        with pytest.raises(ValueError, match="staleness"):
            RuntimeConfig(runtime="zero",
                          execution=ExecutionConfig(staleness=1))
        with pytest.raises(ValueError, match="staleness"):
            RuntimeConfig(runtime="ps",
                          execution=ExecutionConfig(staleness=1))

    def test_aggregate_needs_wait_throttle(self):
        with pytest.raises(ValueError, match="wait"):
            ExecutionConfig(throttle="reject", aggregate=True)

    def test_aggregate_rejects_inert_staleness(self):
        """Cohort admission makes k inert under aggregation — a non-zero
        bound is a configuration the runtime would silently ignore."""
        with pytest.raises(ValueError, match="inert"):
            ExecutionConfig(throttle="wait", aggregate=True, staleness=2)

    def test_aggregate_on_sync_runtime_rejected(self):
        with pytest.raises(ValueError, match="aggregate"):
            RuntimeConfig(runtime="ps",
                          execution=ExecutionConfig(throttle="wait",
                                                    aggregate=True))

    def test_topology_on_zero_regime_rejected(self):
        with pytest.raises(ValueError, match="topology"):
            RuntimeConfig(runtime="zero",
                          schedule=ScheduleConfig(topology=TopologyConfig()))

    def test_network_on_ps_regime_rejected(self):
        with pytest.raises(ValueError, match="network"):
            RuntimeConfig(runtime="ps",
                          schedule=ScheduleConfig(network=NetworkConfig()))

    @pytest.mark.parametrize("old,new", [("dynamic", "zero"),
                                         ("dynamic-ps", "ps")])
    def test_merged_runtime_name_refused(self, old, new):
        """The re-planning loop belongs to zero and ps now: an old
        configuration naming its own loop runtime is refused, naming the
        runtime to use."""
        with pytest.raises(ValueError, match=f"now '{new}'"):
            RuntimeConfig(runtime=old)
        with pytest.raises(ValueError, match=f"now '{new}'"):
            RuntimeConfig.from_dict({"runtime": old})

    def test_shifts_on_plan_once_runtimes_rejected(self):
        with pytest.raises(ValueError, match="runtime='zero'"):
            RuntimeConfig(runtime="pipeline",
                          schedule=ScheduleConfig(
                              network=NetworkConfig(shift_gbps=1.0)))
        with pytest.raises(ValueError, match="runtime='ps'"):
            RuntimeConfig(runtime="ps-async",
                          execution=ExecutionConfig(staleness=1),
                          schedule=ScheduleConfig(
                              topology=TopologyConfig(up_shift_factor=4.0)))

    def test_loop_knobs_only_on_zero_and_ps(self):
        for runtime in ("local", "pipeline", "ps-async"):
            with pytest.raises(ValueError, match="drift_detect"):
                RuntimeConfig(runtime=runtime,
                              schedule=ScheduleConfig(drift_detect=True))
            with pytest.raises(ValueError, match="measured"):
                RuntimeConfig(runtime=runtime,
                              measure=MeasureConfig(cost_source="measured"))

    def test_regime_mismatch_rejected(self):
        with pytest.raises(ValueError, match="contradicts"):
            RuntimeConfig(runtime="zero",
                          execution=ExecutionConfig(regime="ps-sync"))

    def test_network_and_topology_mutually_exclusive(self):
        with pytest.raises(ValueError, match="not both"):
            ScheduleConfig(network=NetworkConfig(),
                           topology=TopologyConfig())

    def test_regime_and_is_dynamic_views(self):
        c = RuntimeConfig(runtime="dynamic-ps-async",
                          execution=ExecutionConfig(staleness=1))
        assert c.regime == "ps-async" and c.is_dynamic
        assert not RuntimeConfig(runtime="ps").is_dynamic
        assert not RuntimeConfig(runtime="zero").is_dynamic
        # the loop runs wherever the schedule can change the plan
        for dynamic in (
                RuntimeConfig(runtime="zero", schedule=ScheduleConfig(
                    network=NetworkConfig(shift_gbps=1.0))),
                RuntimeConfig(runtime="ps", schedule=ScheduleConfig(
                    topology=TopologyConfig(up_shift_factor=4.0))),
                RuntimeConfig(runtime="zero", schedule=ScheduleConfig(
                    drift_detect=True)),
                RuntimeConfig(runtime="ps", measure=MeasureConfig(
                    cost_source="measured"))):
            assert dynamic.is_dynamic, dynamic

    def test_per_worker_length_mismatch_rejected_at_build(self):
        topo = TopologyConfig(workers=3, up_gbps=(1.0, 2.0))
        with pytest.raises(ValueError, match="per-worker"):
            topo.build(default_workers=3)

    def test_validation_of_scalars(self):
        for bad in (dict(bandwidth_gbps=0), dict(shift_gbps=-1.0)):
            with pytest.raises(ValueError):
                NetworkConfig(**bad)
        for bad in (dict(servers=0), dict(workers=0),
                    dict(up_shift_factor=0.0)):
            with pytest.raises(ValueError):
                TopologyConfig(**bad)
        for bad in (dict(cost_source="psychic"), dict(remeasure_every=-1),
                    dict(measure_iters=0), dict(measure_warmup=-1),
                    dict(compute_flops_per_s=0)):
            with pytest.raises(ValueError):
                MeasureConfig(**bad)
        with pytest.raises(ValueError, match="strategy"):
            ScheduleConfig(strategy="psychic")
        with pytest.raises(ValueError, match="reschedule_every"):
            ScheduleConfig(reschedule_every=0)
        with pytest.raises(ValueError, match="throttle"):
            ExecutionConfig(throttle="drop")
        with pytest.raises(ValueError, match="optimizer"):
            RuntimeConfig(runtime="zero", optimizer="lion")


class TestFleetConfig:
    def test_round_trip_with_events(self):
        c = RuntimeConfig(
            runtime="fleet-async", **SMOKE,
            execution=ExecutionConfig(staleness=2, throttle="wait"),
            schedule=ScheduleConfig(topology=TopologyConfig(
                servers=2, workers=3)),
            fleet=FleetConfig(events=(
                FleetEventConfig(time=0.01, kind="join", worker=3,
                                 down_gbps=5.0, up_gbps=0.5),
                FleetEventConfig(time=0.03, kind="fail", worker=1,
                                 mode="stall"),
                FleetEventConfig(time=0.05, kind="drift", worker=0,
                                 factor=2.0),
            ), workers_per_shard=2, stall_factor=3.0))
        again = RuntimeConfig.from_json(c.to_json())
        assert again == c
        assert again.fleet.events[0].down_gbps == 5.0

    def test_round_trip_with_churn(self):
        c = RuntimeConfig(
            runtime="fleet-async", **SMOKE,
            fleet=FleetConfig(churn=2.0, horizon=1.5, churn_seed=7))
        assert RuntimeConfig.from_json(c.to_json()) == c

    def test_event_dicts_coerced(self):
        cfg = FleetConfig(events=(
            {"time": 0.1, "kind": "leave", "worker": 0},))
        assert isinstance(cfg.events[0], FleetEventConfig)
        assert cfg.events[0].kind == "leave"

    def test_fleet_field_needs_fleet_runtime(self):
        with pytest.raises(ValueError, match="fleet"):
            RuntimeConfig(runtime="ps-async", fleet=FleetConfig())

    def test_aggregate_rejected_on_fleet(self):
        with pytest.raises(ValueError, match="aggregate"):
            RuntimeConfig(runtime="fleet-async",
                          execution=ExecutionConfig(throttle="wait",
                                                    aggregate=True))

    def test_validation_of_scalars(self):
        for bad in (dict(churn=-1.0), dict(churn=1.0),  # churn w/o horizon
                    dict(churn=1.0, horizon=1.0,
                         events=(FleetEventConfig(time=0.1, kind="leave",
                                                  worker=0),)),
                    dict(stall_factor=1.0), dict(drift_alpha=0.0),
                    dict(drift_patience=0), dict(workers_per_shard=-1)):
            with pytest.raises(ValueError):
                FleetConfig(**bad)
        with pytest.raises(ValueError, match="join"):
            FleetEventConfig(time=0.1, kind="leave", worker=0,
                             down_gbps=5.0)
        with pytest.raises(ValueError, match="kind"):
            FleetEventConfig(time=0.1, kind="explode", worker=0)

    def test_build_schedule_and_detector(self):
        explicit = FleetConfig(events=(
            FleetEventConfig(time=0.1, kind="leave", worker=1),))
        sched = explicit.build_schedule((0, 1, 2))
        assert len(sched) == 1 and sched.events[0].kind == "leave"
        synth = FleetConfig(churn=4.0, horizon=2.0, churn_seed=3)
        a = synth.build_schedule(range(8))
        b = synth.build_schedule(range(8))
        assert a == b                    # seeded churn is reproducible
        det = FleetConfig(drift_threshold=0.5).build_detector()
        assert det.threshold == 0.5

    def test_fleet_runtime_regime_views(self):
        c = RuntimeConfig(runtime="fleet-async",
                          execution=ExecutionConfig(staleness=1))
        assert c.regime == "ps-async" and c.is_dynamic


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_all_runtimes_registered(self):
        assert runtime_names() == ("dynamic-ps-async", "fleet-async",
                                   "local", "pipeline", "ps", "ps-async",
                                   "zero")

    def test_register_unknown_name_rejected(self):
        from repro.runtime.registry import register_runtime
        with pytest.raises(ValueError, match="not a known name"):
            register_runtime("warp-speed")

    def test_duplicate_registration_rejected(self):
        from repro.runtime.registry import register_runtime
        runtime_names()                 # force adapter registration first
        with pytest.raises(ValueError, match="twice"):
            register_runtime("zero")(object)

    def test_bad_config_type_rejected(self):
        with pytest.raises(TypeError, match="config"):
            build_runtime(42)

    def test_bad_data_type_rejected(self):
        with pytest.raises(TypeError, match="data"):
            build_runtime(RuntimeConfig(runtime="local", **SMOKE), data=42)


# ---------------------------------------------------------------------------
# every registered runtime builds from its JSON smoke config and runs
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def built():
    """Lazily built runtimes, one per smoke config, shared module-wide:
    by config file name (``zero_bandwidth_shift.json``) or by runtime
    name, which picks that runtime's baseline config."""
    cache = {}

    def get(key):
        if key.endswith(".json"):
            path = os.path.join(SMOKE_DIR, key)
        else:
            # a runtime may ship variant configs alongside its baseline
            # (ps_async_int8.json, zero_bandwidth_shift.json): the
            # uncompressed one first in name order is the baseline
            paths = sorted(p for p in smoke_config_paths()
                           if RuntimeConfig.load(p).runtime == key)
            assert paths, f"no smoke config for {key}"
            path = min(paths, key=lambda p:
                       RuntimeConfig.load(p).compression.enabled)
        if path not in cache:
            cache[path] = (build_runtime(RuntimeConfig.load(path)), path)
        return cache[path]

    return get


LEDGER_KEYS = {"pull_bytes", "push_bytes", "num_pulls", "num_pushes"}


class TestEveryRuntime:
    @pytest.mark.parametrize("name", [os.path.basename(p)
                                      for p in smoke_config_paths()])
    def test_builds_from_json_and_steps(self, built, name):
        rt, path = built(name)
        assert isinstance(rt, Trainer), f"{name} breaks the protocol"
        losses = rt.fit(2)
        assert len(losses) >= 2 and all(np.isfinite(losses))
        assert LEDGER_KEYS <= set(rt.ledger)
        assert isinstance(rt.config, RuntimeConfig)
        assert rt.config == RuntimeConfig.load(path)   # config preserved

    def test_dynamic_runtimes_reschedule_and_resegment(self, built):
        for name in ("zero_bandwidth_shift.json", "ps_uplink_shift.json"):
            rt, _ = built(name)
            total = rt._data_idx
            rt.fit(4 - min(total, 4))           # reach the shift boundary
            assert len(rt.events) >= 2, name
            assert any(e.plan_changed for e in rt.events), \
                f"{name}: scripted drift must re-segment the plan"
            assert rt.timeline() is not None

    def test_zero_and_ps_share_one_loss_trajectory(self, built):
        """zero and ps run the same compute path (PSTrainer delegates to
        the ZeRO step) on the same data stream — losses are bit-identical
        even though their plans were derived from different cost models
        (losses are plan-independent, the test_dist invariant)."""
        l_zero = built("zero")[0].fit(1)[0]
        l_ps = built("ps")[0].fit(1)[0]
        assert l_zero == l_ps

    def test_ledger_accumulates(self, built):
        rt, _ = built("zero")
        before = rt.ledger["push_bytes"]
        rt.fit(1)
        after = rt.ledger["push_bytes"]
        assert after > before
        per_iter = after - before
        tb = rt.trainer
        from repro.dist.collectives import bucket_bytes
        want = sum(bucket_bytes(tb.specs, b) for b in tb.plan.backward) * \
            tb.axis_size
        assert per_iter == want

    def test_async_events_and_timeline(self, built):
        rt, _ = built("dynamic-ps-async")
        assert rt.timeline() is not None           # the cumulative log
        assert all(hasattr(e, "worker_plans") for e in rt.events)

    def test_step_with_explicit_batch(self, built):
        rt, _ = built("local")
        from repro.data.pipeline import SyntheticText
        pipe = SyntheticText(rt.arch.vocab_size, rt.config.seq,
                             rt.config.batch, seed=3)
        loss = rt.step(pipe.batch(0))
        assert np.isfinite(loss)


class TestSaveRestore:
    def test_dynamic_ps_resume_is_bit_identical(self, tmp_path):
        config = RuntimeConfig(
            runtime="ps", **SMOKE,
            schedule=ScheduleConfig(reschedule_every=2,
                                    topology=TopologyConfig(
                                        servers=2, up_shift_factor=10.0,
                                        shift_epoch=1)))
        ref = build_runtime(config)
        ref_losses = ref.fit(6)
        a = build_runtime(config)
        first = a.fit(3)                          # stop mid-epoch
        path = str(tmp_path / "rt.npz")
        a.save_state(path)
        b = build_runtime(config)
        b.restore_state(path)
        rest = b.fit(3)
        assert first + rest == ref_losses
        # resume replays the same re-schedule history
        assert [(e.step, e.epoch, e.plan) for e in b.events] == \
            [(e.step, e.epoch, e.plan) for e in ref.events]

    def test_wrong_runtime_checkpoint_rejected(self, tmp_path, built):
        rt, _ = built("local")
        path = str(tmp_path / "local.npz")
        rt.save_state(path)
        other = built("zero")[0]
        with pytest.raises(ValueError, match="written by runtime"):
            other.restore_state(path)


class TestPeriodicCheckpoint:
    """fit(checkpoint_every=, checkpoint_path=) — the in-fit periodic
    checkpoint hook on the Trainer protocol."""

    def test_local_mid_run_resume_is_bit_identical(self, tmp_path):
        config = RuntimeConfig(runtime="local", **SMOKE)
        ref_losses = build_runtime(config).fit(5)
        path = str(tmp_path / "ck.npz")
        a = build_runtime(config)
        # the last periodic save lands at step 3 — the checkpoint is a
        # mid-run snapshot, not the final state
        a.fit(5, checkpoint_every=3, checkpoint_path=path)
        b = build_runtime(config)
        b.restore_state(path)
        assert b._data_idx == 3
        assert b.fit(2) == ref_losses[3:]

    def test_async_adapter_checkpoints_on_boundary(self, tmp_path):
        config = RuntimeConfig(
            runtime="ps-async", **SMOKE,
            execution=ExecutionConfig(staleness=1, throttle="wait"),
            schedule=ScheduleConfig(topology=TopologyConfig(servers=1,
                                                            workers=2)))
        path = str(tmp_path / "async.npz")
        rt = build_runtime(config)
        rt.fit(4, checkpoint_every=2, checkpoint_path=path)
        assert os.path.exists(path)
        restored = build_runtime(config)
        restored.restore_state(path)     # round-trips through save_state
        assert np.isfinite(restored.fit(1)[0])

    def test_checkpoint_validation(self, built):
        rt, _ = built("local")
        with pytest.raises(ValueError, match="checkpoint_path"):
            rt.fit(1, checkpoint_every=2)
        with pytest.raises(ValueError, match="checkpoint_every"):
            rt.fit(1, checkpoint_path="somewhere.npz")


# ---------------------------------------------------------------------------
# the removed aliases and drivers: the re-planning names live in
# repro.runtime.replan alone, and the loop runs inside zero and ps
# ---------------------------------------------------------------------------


class TestDeprecationShims:
    def test_moved_classes_warn_and_alias(self):
        """The alias paths are gone: ``repro.dist.dynamic`` no longer
        exists and ``repro.ps.dynamic`` serves none of the moved names."""
        import importlib

        import repro.ps.dynamic as pd
        from repro.runtime import replan
        with pytest.raises(ModuleNotFoundError):
            importlib.import_module("repro.dist.dynamic")
        for name in ("PlanStepCache", "RescheduleEvent",
                     "hlo_collective_counts", "sequential_plan"):
            assert not hasattr(pd, name)
            assert hasattr(replan, name)

    def test_unknown_attribute_still_raises(self):
        import repro.ps.dynamic as pd
        with pytest.raises(AttributeError):
            pd.does_not_exist

    def test_old_style_construction_matches_factory_losses(self, built):
        """Hand-wiring the loop's plan sequence — each epoch's analytic
        decision, one jitted ZeroTrainer step per plan — gives losses
        bit-identical to the factory-built zero runtime re-planning over
        the same shifting network."""
        from repro.configs import get_config
        from repro.core import (costs_from_profiles, plan_from_decision,
                                schedule)
        from repro.data.pipeline import SyntheticText
        from repro.dist.zero import ZeroTrainer
        from repro.models import num_sched_layers
        from repro.models.profiles import layer_profiles
        from repro.runtime.replan import sequential_plan

        rt, path = built("zero_bandwidth_shift.json")
        config = RuntimeConfig.load(path)
        cfg = get_config(config.arch).reduced()
        net = config.schedule.network.build()
        Ls = num_sched_layers(cfg)
        base = ZeroTrainer(cfg=cfg, mesh=rt.trainer.mesh,
                           plan=sequential_plan(Ls),
                           optimizer=config.build_optimizer())
        state = base.init_state(jax.random.PRNGKey(config.seed))
        pipe = SyntheticText(cfg.vocab_size, config.seq, config.batch,
                             seed=config.seed)
        old_losses, fns = [], {}
        for i in range(4):
            costs = costs_from_profiles(
                layer_profiles(cfg, rt.shape),
                net=net.model_at(i // config.schedule.reschedule_every),
                compute_flops_per_s=config.measure.compute_flops_per_s)
            plan = plan_from_decision(
                *schedule(costs, config.schedule.strategy), Ls)
            if plan not in fns:
                fns[plan] = jax.jit(base.with_plan(plan).build_train_step())
            state, loss = fns[plan](state, pipe.batch(i))
            old_losses.append(float(loss))
        assert len(fns) == 2                    # the shift moved the plan
        new = build_runtime(config)             # fresh, same config
        assert new.fit(4) == old_losses


# ---------------------------------------------------------------------------
# zero and ps on a one-knot analytic schedule: planned once, no loop
# ---------------------------------------------------------------------------


class TestStaticPlan:
    @pytest.mark.parametrize("name", ["zero", "ps"])
    def test_static_runtime_plans_once(self, name):
        config = RuntimeConfig.load(os.path.join(SMOKE_DIR, f"{name}.json"))
        assert not config.is_dynamic
        rt = build_runtime(config)
        calls = []
        decide = rt.scheduler.decision_for_iteration
        rt.scheduler.decision_for_iteration = \
            lambda costs: calls.append(costs) or decide(costs)
        # decided at construction, from epoch 0's analytic costs
        assert rt.plan is not None and rt.scheduler._iter_seen == 1
        plan = rt.plan
        batch = rt._batch_fn(0)
        text = rt.compiled_step_text(batch)
        assert "ENTRY" in text
        losses = rt.fit(config.schedule.reschedule_every + 2)
        assert all(np.isfinite(losses))
        assert calls == [] and rt.scheduler._iter_seen == 1
        assert rt.events == () and rt.traces == 0
        assert rt.plan == plan == rt.trainer.plan
        params = rt.trainer.params_from_state(rt._state)
        assert jax.tree_util.tree_leaves(params)
        assert rt.timeline() is not None

    @pytest.mark.parametrize("knob", ["drift_detect", "measured"])
    def test_zero_loop_knobs_build_and_replan(self, knob):
        """Drift detection and measured costs start zero's loop on a
        static network: an event at every epoch boundary, the plan-keyed
        step cache, and (measured) fc/bc timed on this host."""
        schedule = ScheduleConfig(reschedule_every=2,
                                  drift_detect=knob == "drift_detect")
        measure = MeasureConfig(
            cost_source="measured" if knob == "measured" else "analytic",
            measure_iters=1, measure_warmup=0)
        config = RuntimeConfig(runtime="zero", **SMOKE, schedule=schedule,
                               measure=measure)
        assert config.is_dynamic
        rt = build_runtime(config)
        assert rt.plan is None                  # decided at the first step
        assert (rt.drift_detector is not None) == (knob == "drift_detect")
        losses = rt.fit(5)
        assert all(np.isfinite(losses))
        # (a detector may also fire on host timing noise: "drift" events)
        assert {0, 2, 4} <= {e.step for e in rt.events}
        assert rt.traces == len(rt.plans_seen) >= 1
        if knob == "measured":
            assert rt._measured_epoch == 2
            fc, _ = rt._measured_fc_bc
            assert np.all(fc > 0)


# ---------------------------------------------------------------------------
# SSP wait-throttle BSP aggregation (ISSUE 5 satellite)
# ---------------------------------------------------------------------------


def _cnn_loss(layers, batch):
    from repro.models.cnn import small_cnn_loss
    return small_cnn_loss({"layers": layers}, batch["images"],
                          batch["labels"])


def _fixed_batch(*_):
    r = np.random.default_rng(7)
    return {"images": jnp.asarray(r.normal(size=(8, 32, 32, 3)),
                                  jnp.float32),
            "labels": jnp.asarray(r.integers(0, 10, size=(8,)), jnp.int32)}


def _worker_batch(w, i):
    r = np.random.default_rng(100003 * w + i)
    return {"images": jnp.asarray(r.normal(size=(8, 32, 32, 3)),
                                  jnp.float32),
            "labels": jnp.asarray(r.integers(0, 10, size=(8,)), jnp.int32)}


def _agg_trainer(workers, *, aggregate, k=0, throttle="wait"):
    from repro.core import plan_from_decision
    from repro.models.cnn import small_cnn_init
    from repro.optim import sgd
    from repro.ps import AsyncPSTrainer, PSTopology, asymmetric_link
    params = small_cnn_init(jax.random.PRNGKey(0))
    L = len(params["layers"])
    plan = plan_from_decision(((1, 3), (4, L)), ((4, L), (1, 3)), L)
    topo = PSTopology(
        num_servers=2,
        links=tuple(asymmetric_link(10e9, 1e9) for _ in range(workers)),
        worker_flops=(1e10,) * workers)
    return AsyncPSTrainer(init_layers=params["layers"], loss_fn=_cnn_loss,
                          optimizer=sgd(0.05), topology=topo, plan=plan,
                          staleness=k, throttle=throttle,
                          aggregate=aggregate)


class TestBSPAggregation:
    def test_k0_aggregate_is_true_bsp(self):
        """k=0 wait+aggregate: one version bump per round of W pushes,
        zero staleness, nothing rejected, and — with identical per-worker
        data — losses bit-identical to the serialized single-worker run
        (aggregating W identical gradients and dividing by W is exact)."""
        agg = _agg_trainer(4, aggregate=True).run(12, _fixed_batch)
        solo = _agg_trainer(1, aggregate=False).run(3, _fixed_batch)
        heads = [e.result.version for e in agg.events]
        assert heads == [v for v in (1, 2, 3) for _ in range(4)]
        assert agg.max_staleness == 0
        assert agg.num_rejected == 0
        rounds = [agg.losses[i * 4:(i + 1) * 4] for i in range(3)]
        assert all(len(set(r)) == 1 for r in rounds), \
            "a BSP round sees one shared parameter version"
        assert [r[0] for r in rounds] == solo.losses

    def test_aggregate_distinct_batches_matches_host_bsp(self):
        """With distinct per-worker batches the aggregated trajectory is
        bit-identical to a hand-rolled BSP loop using the same grad_fn,
        flatten order, and mean (worker order, sum then divide)."""
        from repro.dist.collectives import flatten_tree, unflatten_tree
        tr = _agg_trainer(2, aggregate=True)
        log = tr.run(6, _worker_batch)           # 3 rounds of 2
        ref = _agg_trainer(2, aggregate=False)   # fresh server, same init
        sv, gf = ref.server, ref._grad_fn
        ref_losses = []
        for rnd in range(3):
            layers = [unflatten_tree(f, s)
                      for f, s in zip(sv.flats(), ref.specs)]
            pushes, losses = [], []
            for w in range(2):
                loss, grads = gf(layers, _worker_batch(w, rnd))
                losses.append(float(loss))
                full = {l: flatten_tree(grads[l], ref.specs[l])
                        for l in range(len(ref.specs))}
                pushes.append((w, rnd, full))
            sv.push_aggregated(pushes)
            ref_losses.extend(losses)
        assert log.losses == ref_losses

    def test_k0_aggregate_tracks_sync_ps_trainer(self):
        """The satellite's anchor: k=0 wait+aggregate with every worker
        on the full batch follows the synchronous PSTrainer on the same
        batch.  Comparison is to fp32 roundoff, not bitwise: PSTrainer's
        per-layer-VJP backward and the async whole-graph autodiff round
        differently (the documented ZeRO-vs-reference gap) — bit-identity
        is asserted against same-compute-path references above."""
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.core.buckets import BucketPlan
        from repro.models import (init_params, num_sched_layers,
                                  params_from_sched_layers,
                                  sched_layer_trees, train_loss)
        from repro.optim import sgd
        from repro.ps import AsyncPSTrainer, PSTopology, PSTrainer

        cfg = get_config("granite-3-2b").reduced()
        Ls = num_sched_layers(cfg)
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        plan = BucketPlan(forward=(tuple(range(Ls)),),
                          backward=(tuple(range(Ls - 1, -1, -1)),))
        sync = PSTrainer(cfg=cfg, mesh=mesh, plan=plan,
                         optimizer=sgd(0.05),
                         topology=PSTopology.uniform(2, 1))
        state = sync.init_state(jax.random.PRNGKey(0))
        step = jax.jit(sync.build_train_step())
        key = jax.random.PRNGKey(3)
        toks = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
        sync_losses = []
        for _ in range(3):
            state, loss = step(state, batch)
            sync_losses.append(float(loss))

        layers = sched_layer_trees(init_params(cfg, jax.random.PRNGKey(0)))

        def loss_fn(ls, b):
            return train_loss(cfg, params_from_sched_layers(ls), b,
                              aux_weight=0.01)

        atr = AsyncPSTrainer(init_layers=layers, loss_fn=loss_fn,
                             optimizer=sgd(0.05),
                             topology=PSTopology.uniform(2, 4),
                             plan=plan, staleness=0, throttle="wait",
                             aggregate=True)
        log = atr.run(12, lambda w, i: batch)    # 3 BSP rounds of 4
        round_losses = [log.losses[i * 4] for i in range(3)]
        np.testing.assert_allclose(round_losses, sync_losses, rtol=2e-5)

    def test_aggregate_requires_wait_throttle(self):
        with pytest.raises(ValueError, match="wait"):
            _agg_trainer(2, aggregate=True, throttle="reject")

    def test_aggregate_heterogeneous_fleet_still_lockstep(self):
        """Slower workers gate the round (BSP semantics): everyone
        contributes exactly once per round, fast workers accumulate
        barrier wait."""
        from repro.core import plan_from_decision
        from repro.models.cnn import small_cnn_init
        from repro.optim import sgd
        from repro.ps import AsyncPSTrainer, PSTopology, asymmetric_link
        params = small_cnn_init(jax.random.PRNGKey(0))
        L = len(params["layers"])
        plan = plan_from_decision(((1, L),), ((1, L),), L)
        topo = PSTopology(
            num_servers=1,
            links=tuple(asymmetric_link(10e9, 1e9) for _ in range(3)),
            worker_flops=(4e10, 4e10, 1e10))
        tr = AsyncPSTrainer(init_layers=params["layers"],
                            loss_fn=_cnn_loss, optimizer=sgd(0.05),
                            topology=topo, plan=plan, staleness=0,
                            throttle="wait", aggregate=True)
        log = tr.run(9, _worker_batch)
        assert log.accepted_by_worker() == {0: 3, 1: 3, 2: 3}
        assert log.max_staleness == 0
        assert log.total_wait_s > 0              # fast workers blocked
        assert log.num_rejected == 0

    def test_server_push_aggregated_validation(self):
        from repro.ps.server import PSServer
        from repro.dist.collectives import make_flat_spec, flatten_tree
        from repro.optim import sgd
        from repro.ps import PSTopology
        trees = [{"w": jnp.arange(4, dtype=jnp.float32)} for _ in range(2)]
        specs = [make_flat_spec(t, 1) for t in trees]
        flats = [flatten_tree(t, s) for t, s in zip(trees, specs)]
        sv = PSServer(specs, PSTopology.uniform(1, 2), sgd(0.1), flats,
                      staleness_bound=0)
        g = {l: jnp.ones((specs[l].padded,), jnp.float32)
             for l in range(2)}
        with pytest.raises(ValueError, match="empty"):
            sv.push_aggregated([])
        with pytest.raises(ValueError, match="one version"):
            sv.push_aggregated([(0, 0, g), (1, 1, g)])
        with pytest.raises(ValueError, match="lacks"):
            sv.push_aggregated([(0, 0, {0: g[0]})])
        res = sv.push_aggregated([(0, 0, g), (1, 0, g)])
        assert [r.accepted for r in res] == [True, True]
        assert sv.version == 1                   # one bump for the group
        # stale group: rejected atomically
        res = sv.push_aggregated([(0, 0, g)])
        assert not res[0].accepted and sv.ledger.rejected_pushes == 1


# ---------------------------------------------------------------------------
# measured per-worker fc/bc in the PS regime (ISSUE 5 satellite)
# ---------------------------------------------------------------------------


class TestMeasuredPSCosts:
    def test_topology_costs_measured_scales_per_worker(self):
        from repro.core.profiler import LayerProfile
        from repro.ps import PSTopology
        topo = PSTopology(num_servers=1,
                          links=PSTopology.uniform(1, 2).links,
                          worker_flops=(2e10, 5e9))
        profiles = [LayerProfile(name=f"l{i}", param_bytes=1e6,
                                 flops_fwd=1e9) for i in range(3)]
        fc = np.array([1e-3, 2e-3, 3e-3])
        bc = 2 * fc
        costs = topo.topology_costs_measured(profiles, fc=fc, bc=bc)
        # ref = fastest worker (2e10): its fc is the measurement as-is
        np.testing.assert_allclose(costs.workers[0].fc, fc)
        # the 4x-slower worker sees 4x the measured times
        np.testing.assert_allclose(costs.workers[1].fc, 4 * fc)
        np.testing.assert_allclose(costs.workers[1].bc, 4 * bc)
        # transmission still per-link analytic
        assert costs.workers[0].dt_push == topo.links[0].up.dt

    def test_topology_costs_measured_validation(self):
        from repro.core.profiler import LayerProfile
        from repro.ps import PSTopology
        topo = PSTopology.uniform(1, 1)
        profiles = [LayerProfile(name="l", param_bytes=1e6, flops_fwd=1e9)]
        with pytest.raises(ValueError, match="one entry per layer"):
            topo.topology_costs_measured(profiles, fc=[1e-3, 2e-3],
                                         bc=[1e-3, 2e-3])
        with pytest.raises(ValueError, match="ref_flops"):
            topo.topology_costs_measured(profiles, fc=[1e-3], bc=[1e-3],
                                         ref_flops=0.0)

    MEASURED_PS = RuntimeConfig(
        runtime="ps", **SMOKE,
        schedule=ScheduleConfig(reschedule_every=2,
                                topology=TopologyConfig(servers=2)),
        measure=MeasureConfig(cost_source="measured", remeasure_every=2,
                              measure_iters=1, measure_warmup=0))

    def test_dynamic_ps_measured_remeasures_on_schedule(self):
        """remeasure_every threads into the ps runtime's loop the way
        zero's loop re-measures (one loop serves both)."""
        rt = build_runtime(self.MEASURED_PS)
        losses = rt.fit(6)
        assert len(losses) == 6
        # epochs 0,1,2 re-planned; measurement at 0, re-measured at 2
        assert [e.epoch for e in rt.events] == [0, 1, 2]
        assert rt._measured_epoch == 2
        # the cached measurement feeds the cost projection
        costs = rt.costs_for_epoch(0)
        np.testing.assert_allclose(np.asarray(costs.workers[0].fc),
                                   np.asarray(rt._measured_fc_bc[0]))

    def test_measured_first_projection_needs_state_and_batch(self):
        rt = build_runtime(self.MEASURED_PS)
        with pytest.raises(ValueError, match="state and batch"):
            rt.costs_for_epoch(0)

    def test_validation(self):
        with pytest.raises(ValueError, match="cost_source"):
            RuntimeConfig(runtime="ps", measure=MeasureConfig(
                cost_source="psychic"))
        with pytest.raises(ValueError, match="remeasure_every"):
            RuntimeConfig(runtime="ps", measure=MeasureConfig(
                cost_source="measured", remeasure_every=-1))
