"""Runtime adapters: the trainers behind one ``Trainer`` protocol.

Each adapter owns everything a regime needs to run — mesh, optimizer,
model/arch config, trainer, training state, transfer accounting — and
presents the uniform protocol surface (``fit`` / ``step`` / ``events`` /
``timeline`` / ``ledger`` / ``save_state`` / ``restore_state``).  The
underlying trainer stays reachable as ``.trainer`` for regime-specific
introspection (HLO counts, plan caches, async run logs).

Unit of progress: a *training step* for the synchronous regimes, an
*accepted gradient push* for the asynchronous ones — ``fit(n)`` always
returns one loss per unit.  Checkpoints written by ``save_state`` embed
the serialized :class:`RuntimeConfig`, so a restore from a mismatched
runtime fails loudly instead of silently misinterpreting buffers.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint.ckpt import load_checkpoint, save_checkpoint
from repro.configs.base import ArchConfig, InputShape
from repro.runtime.config import (FleetConfig, NetworkConfig, RuntimeConfig,
                                  TopologyConfig)
from repro.runtime.registry import register_runtime
from repro.runtime.replan import ReplanMixin, sequential_plan

# per-worker data streams of the async regimes stay disjoint by striding
# the deterministic batch index (the convention every launcher used)
WORKER_STRIDE = 100003


def _data_mesh() -> Mesh:
    devs = jax.devices()
    return Mesh(np.array(devs).reshape(len(devs),), ("data",))


def _plan_ledger(specs, plan, workers: int,
                 compressor: Optional[Any] = None) -> Dict[str, int]:
    """One synchronous iteration's fleet-wide transfer accounting.

    ``push_wire_bytes`` is what the uplink actually carries: compressed
    per-layer payloads plus the per-segment header when a ``compressor``
    is active, the fp32 payload otherwise (pulls always stay fp32)."""
    from repro.dist.collectives import bucket_bytes
    pull = sum(bucket_bytes(specs, b) for b in plan.forward)
    push = sum(bucket_bytes(specs, b) for b in plan.backward)
    if compressor is None:
        push_wire = push
    else:
        push_wire = sum(
            int(round(sum(float(compressor.wire_bytes(specs[l].total * 4))
                          for l in b) + compressor.segment_overhead_bytes))
            for b in plan.backward)
    return {"pull_bytes": pull * workers, "push_bytes": push * workers,
            "pull_wire_bytes": pull * workers,
            "push_wire_bytes": push_wire * workers,
            "num_pulls": len(plan.forward) * workers,
            "num_pushes": len(plan.backward) * workers}


class RuntimeAdapter:
    """Shared bookkeeping of every registered runtime."""

    def __init__(self, config: RuntimeConfig, arch: ArchConfig,
                 batch_fn: Callable[[int], Any]):
        self.config = config
        self.arch = arch
        self._batch_fn = batch_fn
        self._data_idx = 0            # units of progress consumed
        self._eval_events: List[Any] = []
        self.shape = InputShape("runtime", config.seq, config.batch, "train")

    # -- protocol surface ------------------------------------------------

    @property
    def events(self) -> Sequence[Any]:
        return tuple(self._eval_events)

    def timeline(self) -> Optional[Any]:
        return None

    @property
    def ledger(self) -> Dict[str, Any]:
        return {"pull_bytes": 0, "push_bytes": 0,
                "pull_wire_bytes": 0, "push_wire_bytes": 0,
                "num_pulls": 0, "num_pushes": 0}

    @staticmethod
    def _check_eval(eval_fn, eval_every: int) -> None:
        if eval_fn is not None and eval_every < 1:
            raise ValueError(f"eval_fn needs eval_every >= 1, got "
                             f"{eval_every}")

    @staticmethod
    def _check_checkpoint(checkpoint_every: int,
                          checkpoint_path: Optional[str]) -> None:
        if checkpoint_every and checkpoint_path is None:
            raise ValueError("checkpoint_every needs a checkpoint_path")
        if checkpoint_path is not None and checkpoint_every < 1:
            raise ValueError(f"checkpoint_path needs checkpoint_every >= 1, "
                             f"got {checkpoint_every}")

    def _record_eval(self, eval_fn) -> None:
        from repro.runtime.protocol import EvalEvent
        self._eval_events.append(
            EvalEvent(unit=self._data_idx, loss=float(eval_fn())))

    def fit(self, steps: int, *, log_every: int = 0,
            eval_fn: Optional[Callable[[], float]] = None,
            eval_every: int = 0, checkpoint_every: int = 0,
            checkpoint_path: Optional[str] = None) -> List[float]:
        """Run ``steps`` units of progress from the configured data,
        printing a one-line progress report every ``log_every`` units.
        With ``eval_fn`` (zero-arg, returns a scalar loss), evaluate every
        ``eval_every`` units and record an ``EvalEvent`` into ``events``.
        With ``checkpoint_every``/``checkpoint_path``, ``save_state`` runs
        at every ``checkpoint_every``-unit boundary — a killed run
        restarts from the last periodic checkpoint."""
        self._check_eval(eval_fn, eval_every)
        self._check_checkpoint(checkpoint_every, checkpoint_path)
        losses = []
        for _ in range(steps):
            with jax.profiler.TraceAnnotation("repro.data"):
                batch = self._batch_fn(self._data_idx)
            losses.append(self.step(batch))
            if log_every and len(losses) % log_every == 0:
                print(f"step {self._data_idx:4d}  loss {losses[-1]:.4f}")
            if eval_fn is not None and self._data_idx % eval_every == 0:
                self._record_eval(eval_fn)
            if checkpoint_every and \
                    self._data_idx % checkpoint_every == 0:
                self.save_state(checkpoint_path)
        return losses

    def step(self, batch) -> float:
        raise NotImplementedError

    # -- checkpoint plumbing --------------------------------------------

    def _save_tree(self, path: str, tree: Dict[str, Any]) -> None:
        tree = dict(tree)
        tree["config"] = np.asarray(self.config.to_json(indent=None))
        tree["data_idx"] = np.asarray(self._data_idx, np.int64)
        save_checkpoint(path, tree, step=self._data_idx)

    def _load_tree(self, path: str,
                   template: Dict[str, Any]) -> Dict[str, Any]:
        # check the embedded config BEFORE interpreting any buffers: a
        # checkpoint from another regime must fail on provenance, not on
        # whichever template key happens to be missing first
        with np.load(path) as probe:
            if "config" not in probe.files:
                raise ValueError(f"{path} is not a runtime checkpoint "
                                 f"(no embedded config)")
            saved = RuntimeConfig.from_json(str(probe["config"]))
        if saved.runtime != self.config.runtime:
            raise ValueError(
                f"checkpoint {path} was written by runtime "
                f"{saved.runtime!r}; this runtime is "
                f"{self.config.runtime!r} — rebuild from the checkpoint's "
                f"own config")
        template = dict(template)
        template["config"] = np.asarray("")
        template["data_idx"] = np.zeros((), np.int64)
        tree, _ = load_checkpoint(path, template)
        self._data_idx = int(tree["data_idx"])
        return tree

    @staticmethod
    def _replace_like(current, restored):
        """Re-place restored numpy leaves on the current leaves' devices."""
        return jax.tree_util.tree_map(
            lambda cur, new: jax.device_put(
                jnp.asarray(new, cur.dtype), cur.sharding)
            if hasattr(cur, "sharding") else np.asarray(new),
            current, restored)


class _CompiledRuntime(RuntimeAdapter):
    """Base for the mesh-compiled synchronous regimes: holds the training
    state, a jitted step, and per-iteration transfer accounting."""

    def __init__(self, config, arch, batch_fn):
        super().__init__(config, arch, batch_fn)
        self._led = {"pull_bytes": 0, "push_bytes": 0,
                     "pull_wire_bytes": 0, "push_wire_bytes": 0,
                     "num_pulls": 0, "num_pushes": 0}
        self._led_by_plan: Dict[Any, Dict[str, int]] = {}

    def _account(self, specs, plan, workers: int,
                 compressor: Optional[Any] = None) -> None:
        if plan not in self._led_by_plan:
            self._led_by_plan[plan] = _plan_ledger(specs, plan, workers,
                                                   compressor)
        for k, v in self._led_by_plan[plan].items():
            self._led[k] += v

    @property
    def ledger(self) -> Dict[str, Any]:
        led = dict(self._led)
        led["push_compression_ratio"] = (
            led["push_bytes"] / led["push_wire_bytes"]
            if led["push_wire_bytes"] else 1.0)
        return led

    def save_state(self, path: str) -> None:
        self._save_tree(path, {"model": self._state})

    def restore_state(self, path: str) -> None:
        tree = self._load_tree(path, {"model": self._state})
        self._state = self._replace_like(self._state, tree["model"])

    def compiled_step_text(self, batch) -> str:
        """HLO text of the step as compiled for the current state and
        ``batch`` (the runtimes that hold one jitted step: zero, ps)."""
        return self._step_fn.lower(self._state, batch).compile().as_text()


@register_runtime("local", description="single-process jit training, no "
                                       "distribution layer")
class LocalRuntime(RuntimeAdapter):
    """Plain jit training on whatever devices exist (no collectives)."""

    def __init__(self, config, arch, batch_fn):
        super().__init__(config, arch, batch_fn)
        from repro.models import init_params
        from repro.train.loop import build_train_step
        self.optimizer = config.build_optimizer()
        self._params = init_params(arch, jax.random.PRNGKey(config.seed))
        self._opt_state = self.optimizer.init(self._params)
        self._step_fn = jax.jit(build_train_step(
            arch, self.optimizer, aux_weight=config.aux_weight))

    def step(self, batch) -> float:
        self._params, self._opt_state, loss = self._step_fn(
            self._params, self._opt_state, batch)
        self._data_idx += 1
        return float(loss)

    def save_state(self, path: str) -> None:
        self._save_tree(path, {"params": self._params,
                               "opt": self._opt_state})

    def restore_state(self, path: str) -> None:
        tree = self._load_tree(path, {"params": self._params,
                                      "opt": self._opt_state})
        self._params = self._replace_like(self._params, tree["params"])
        self._opt_state = self._replace_like(self._opt_state, tree["opt"])


class _PlannedRuntime(ReplanMixin, _CompiledRuntime):
    """The synchronous regimes (zero, ps): one compiled step of the
    active plan's trainer.

    Where the schedule can change the plan (``config.is_dynamic``: a
    network or topology with more than one knot, measured costs, or a
    drift detector), the run-time loop of :class:`ReplanMixin` re-plans at
    every epoch boundary and swaps compiled steps from its plan-keyed
    cache.  Otherwise the plan is decided once here, from epoch 0's
    analytic costs, and the step is the jitted ``build_train_step`` of its
    trainer."""

    def __init__(self, config, arch, batch_fn):
        super().__init__(config, arch, batch_fn)
        from repro.models.profiles import layer_profiles
        self._dynamic = config.is_dynamic
        self._mesh = _data_mesh()
        self._profiles = layer_profiles(arch, self.shape)

    def _start(self, scheduler, make_trainer) -> None:
        """Build the trainer of the first plan, its state and its step."""
        from repro.core import plan_from_decision
        from repro.core.planner import AsyncPlanner, Planner
        from repro.models import num_sched_layers
        sched = self.config.schedule
        planner = None
        if self._dynamic:
            planner = (AsyncPlanner if sched.async_planning else Planner)(
                cache_size=sched.plan_cache_size)
            scheduler.planner = planner
        self._init_replan(scheduler, planner)
        L = num_sched_layers(self.arch)
        if self._dynamic:
            plan = sequential_plan(L)     # until the first step re-plans
        else:
            self._costs = self._price(0)
            self._decision = scheduler.decision_for_iteration(self._costs)
            plan = self._plan = plan_from_decision(*self._decision, L)
        self.trainer = make_trainer(plan)
        self._state = self.trainer.init_state(
            jax.random.PRNGKey(self.config.seed))
        if not self._dynamic:
            self._step_fn = jax.jit(self.trainer.build_train_step())

    @property
    def events(self):
        return tuple(self._replans) + tuple(self._eval_events)

    def step(self, batch) -> float:
        if self._dynamic:
            self._replan(batch)
        # Hold the input state until the step has run (``old`` goes at
        # return): dropped while the step runs, its buffers (one per leaf
        # in a leaves state) are freed by the runtime after it, racing the
        # next step's output allocations for a higher, varying peak.
        old = self._state
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("repro.dispatch"):
            self._state, loss = self._step_fn(old, batch)
        tr = self.trainer
        self._account(tr.specs, tr.plan, self._mesh.size, tr.compressor)
        self._data_idx += 1
        with jax.profiler.TraceAnnotation("repro.sync"):
            loss = float(loss)
        if self.drift_detector is not None and \
                self.drift_detector.update(time.perf_counter() - t0):
            self._drift_pending = True       # re-plan before the next step
        return loss

    def compiled_step_text(self, batch) -> str:
        if self._dynamic:
            return self._cache.hlo_text(self._plan)
        return super().compiled_step_text(batch)

    def save_state(self, path: str) -> None:
        super().save_state(path)
        if self._dynamic:
            self.save_loop_state(path + ".loop")

    def restore_state(self, path: str) -> None:
        super().restore_state(path)
        if self._dynamic:
            self.restore_loop_state(path + ".loop")


@register_runtime("zero", description="DynaComm-bucketed ZeRO trainer; "
                                      "re-plans per epoch when its network, "
                                      "costs or step times can move the "
                                      "plan")
class ZeroRuntime(_PlannedRuntime):
    """Profile → schedule → bucketed ZeRO trainer over a scalar edge
    network (a one-knot schedule unless it shifts)."""

    def __init__(self, config, arch, batch_fn):
        super().__init__(config, arch, batch_fn)
        from repro.core import DynaCommScheduler, as_schedule
        from repro.dist.zero import ZeroTrainer
        self.network = as_schedule(
            (config.schedule.network or NetworkConfig()).build())
        self._start(
            DynaCommScheduler(
                strategy=config.schedule.strategy,
                reschedule_every=config.schedule.reschedule_every),
            lambda plan: ZeroTrainer(
                cfg=arch, mesh=self._mesh, plan=plan,
                optimizer=config.build_optimizer(),
                zero3=config.execution.zero3, aux_weight=config.aux_weight))

    def _price(self, epoch: int, fc_bc=None):
        """fc/bc analytic (or measured), pt/gt/Δt from the epoch's
        network."""
        from repro.core import LayerCosts, costs_from_profiles
        from repro.models import sched_layer_bytes
        net = self.network.model_at(epoch)
        if fc_bc is None:
            return costs_from_profiles(
                self._profiles, net=net,
                compute_flops_per_s=self.config.measure.compute_flops_per_s)
        t = net.transfer_time(
            np.asarray(sched_layer_bytes(self.arch), np.float64))
        return LayerCosts(pt=t, fc=fc_bc[0], bc=fc_bc[1], gt=t, dt=net.dt)

    def timeline(self):
        from repro.core import decision_from_plan, simulate_iteration
        if self._plan is None or self._costs is None:
            return None
        return simulate_iteration(self._costs,
                                  *decision_from_plan(self._plan))


@register_runtime("ps", description="synchronous parameter-server "
                                    "execution: consensus plan, one pull + "
                                    "one push per segment, re-planned per "
                                    "topology epoch when it can move")
class PSRuntime(_PlannedRuntime):
    """Sync PS: segmented pull/push on the mesh (== ZeRO bitwise) over a
    PS topology (a one-knot schedule unless its uplinks shift).

    With measured costs, this host's per-layer timings are taken to
    describe the fleet's fastest worker, and worker *w* sees them scaled
    by that rate over its own, while pt/gt/Δt still come from its links.
    """

    def __init__(self, config, arch, batch_fn):
        super().__init__(config, arch, batch_fn)
        from repro.core import TopologyScheduler
        from repro.ps import PSTrainer, as_topology_schedule
        topo_cfg = config.schedule.topology or TopologyConfig()
        self.topology = as_topology_schedule(
            topo_cfg.build(default_workers=self._mesh.size))
        self.compressor = config.compression.build()
        self._start(
            TopologyScheduler(
                strategy=config.schedule.strategy,
                reschedule_every=config.schedule.reschedule_every,
                mode="consensus"),
            lambda plan: PSTrainer(
                cfg=arch, mesh=self._mesh, plan=plan,
                optimizer=config.build_optimizer(),
                topology=self.topology.topology_at(0),
                zero3=config.execution.zero3, aux_weight=config.aux_weight,
                compressor=self.compressor))

    def _price(self, epoch: int, fc_bc=None):
        """The epoch's topology projected onto per-worker costs."""
        topo = self.topology.topology_at(epoch)
        if fc_bc is None:
            return topo.topology_costs(self._profiles,
                                       compressor=self.compressor)
        return topo.topology_costs_measured(
            self._profiles, fc=fc_bc[0], bc=fc_bc[1],
            compressor=self.compressor)

    def _enter_epoch(self, epoch: int) -> None:
        # the compiled data path is topology-independent; the trainer's
        # accounting views (segment owners, transfer bytes, timelines)
        # follow the active fabric
        self.trainer.topology = self.topology.topology_at(epoch)

    def timeline(self):
        if self._plan is None or self._costs is None:
            return None
        return self.trainer.timeline_from_costs(self._costs)

    def replan_timeline(self):
        """Re-planned vs frozen-epoch-0-plan makespans across the epochs
        re-scheduled so far (:func:`core.simulator.simulate_ps_replan`) —
        the stale-plan penalty the run-time loop exists to reclaim."""
        from repro.core.buckets import decision_from_plan
        from repro.core.simulator import simulate_ps_replan
        if not self._replans:
            raise ValueError("no reschedule events yet")
        by_epoch = {e.epoch: e.plan for e in self._replans}
        epochs = sorted(by_epoch)
        return simulate_ps_replan(
            [self.costs_for_epoch(e) for e in epochs],
            [decision_from_plan(by_epoch[e]) for e in epochs])


class _AsyncBase(RuntimeAdapter):
    """Shared machinery of the asynchronous (event-loop) regimes.

    A unit of progress is one *accepted* gradient push.  ``fit`` drives
    the per-worker deterministic data streams; ``step(batch)`` feeds the
    given batch to every worker attempt until one more push commits.
    Under BSP aggregation a whole same-version group commits at once;
    ``step`` then returns the group's mean loss (the synchronous-step
    convention) and ``fit`` may return up to ``W - 1`` more losses than
    requested.
    """

    def __init__(self, config, arch, batch_fn):
        super().__init__(config, arch, batch_fn)
        from repro.models import (init_params, params_from_sched_layers,
                                  sched_layer_trees, train_loss)
        self._layers = sched_layer_trees(
            init_params(arch, jax.random.PRNGKey(config.seed)))
        aux = config.aux_weight

        def loss_fn(layer_list, batch):
            return train_loss(arch, params_from_sched_layers(layer_list),
                              batch, aux_weight=aux)

        self._loss_fn = loss_fn
        self._started = False
        self._reported = 0           # accepted events already returned

    # each concrete class provides: _run_pushes(n, wfn) -> AsyncRunLog,
    # and a `_server` property
    def _run_pushes(self, num_pushes, worker_batch_fn):
        raise NotImplementedError

    @property
    def _server(self):
        raise NotImplementedError

    def _worker_batch_fn(self):
        fn = self._batch_fn
        return lambda w, i: fn(w * WORKER_STRIDE + i)

    def _drive(self, pushes: int, wfn) -> List[float]:
        log = self._run_pushes(pushes, wfn)
        self._started = True
        fresh = log.accepted[self._reported:]
        self._reported = len(log.accepted)
        self._data_idx += len(fresh)
        return [e.loss for e in fresh]

    def fit(self, steps: int, *, log_every: int = 0,
            eval_fn: Optional[Callable[[], float]] = None,
            eval_every: int = 0, checkpoint_every: int = 0,
            checkpoint_path: Optional[str] = None) -> List[float]:
        # accepted pushes land in chunks (BSP aggregation can commit a
        # whole cohort), so evals and checkpoints trigger on *boundary
        # crossings* of the cumulative push count rather than exact
        # multiples
        self._check_eval(eval_fn, eval_every)
        self._check_checkpoint(checkpoint_every, checkpoint_path)
        losses: List[float] = []
        wfn = self._worker_batch_fn()
        while len(losses) < steps:
            chunk = min(log_every or steps, steps - len(losses))
            if eval_fn is not None:
                chunk = min(chunk, eval_every - self._data_idx % eval_every)
            if checkpoint_every:
                chunk = min(chunk, checkpoint_every -
                            self._data_idx % checkpoint_every)
            before = self._data_idx
            losses.extend(self._drive(chunk, wfn))
            if log_every:
                print(f"push {self._data_idx:4d}  loss {losses[-1]:.4f}")
            if eval_fn is not None and \
                    self._data_idx // eval_every > before // eval_every:
                self._record_eval(eval_fn)
            if checkpoint_every and self._data_idx // checkpoint_every > \
                    before // checkpoint_every:
                self.save_state(checkpoint_path)
        return losses

    def step(self, batch) -> float:
        fresh = self._drive(1, lambda w, i: batch)
        return float(np.mean(fresh))

    @property
    def ledger(self) -> Dict[str, Any]:
        led = self._server.ledger
        return {"pull_bytes": sum(led.pulled_bytes.values()),
                "push_bytes": sum(led.pushed_bytes.values()),
                "pull_wire_bytes": sum(led.pulled_wire_bytes.values()),
                "push_wire_bytes": sum(led.pushed_wire_bytes.values()),
                "push_compression_ratio": led.compression_ratio("push"),
                "num_pulls": led.num_pulls,
                "num_pushes": led.num_pushes,
                "rejected_pushes": led.rejected_pushes,
                "waited_pushes": led.waited_pushes}

    def save_state(self, path: str) -> None:
        """Checkpoint the server's head parameters + optimizer state.

        Event-loop state (in-flight computations) is not serialized; the
        restore discards the loop, so training resumes from the restored
        parameters at simulated time 0."""
        self._save_tree(path, {"server": self._server.state_dict()})

    def restore_state(self, path: str) -> None:
        tree = self._load_tree(path,
                               {"server": self._server.state_dict()})
        self._server.load_state_dict(tree["server"])
        # in-flight gradients were computed against pre-restore weights
        # and pinned at pre-restore versions: committing them against the
        # rolled-back server would corrupt the trajectory
        self._reset_after_restore()
        self._started = False
        self._reported = 0

    def _reset_after_restore(self) -> None:
        self.trainer.reset_loop()


@register_runtime("ps-async", description="bounded-staleness asynchronous "
                                          "PS: reject or SSP-wait "
                                          "throttle, optional BSP "
                                          "aggregation")
class PSAsyncRuntime(_AsyncBase):
    """Event-driven bounded-staleness execution over a static topology."""

    def __init__(self, config, arch, batch_fn):
        super().__init__(config, arch, batch_fn)
        from repro.core import plan_from_decision
        from repro.core.scheduler import consensus_decision
        from repro.models import num_sched_layers
        from repro.models.profiles import layer_profiles
        from repro.ps import AsyncPSTrainer
        topo_cfg = config.schedule.topology or TopologyConfig()
        topo = topo_cfg.build(default_workers=len(jax.devices()))
        comp = config.compression.build()
        costs = topo.topology_costs(layer_profiles(arch, self.shape),
                                    compressor=comp)
        decision, self.sync_makespan = consensus_decision(
            costs, config.schedule.strategy)
        plan = plan_from_decision(*decision, num_sched_layers(arch))
        self.trainer = AsyncPSTrainer(
            init_layers=self._layers, loss_fn=self._loss_fn,
            optimizer=config.build_optimizer(), topology=topo, plan=plan,
            staleness=config.execution.staleness or 0,
            throttle=config.execution.throttle,
            aggregate=config.execution.aggregate, costs=costs,
            compressor=comp)

    @property
    def _server(self):
        return self.trainer.server

    def _run_pushes(self, num_pushes, wfn):
        return self.trainer.run(num_pushes, wfn, reset=not self._started)

    def timeline(self):
        return self.trainer.log


@register_runtime("dynamic-ps-async",
                  description="per-worker re-planning per topology epoch "
                              "over the bounded-staleness event loop")
class DynamicPSAsyncRuntime(_AsyncBase):
    """Per-worker re-plans swapped into the async loop on epoch bounds."""

    def __init__(self, config, arch, batch_fn):
        super().__init__(config, arch, batch_fn)
        from repro.models.profiles import layer_profiles
        from repro.ps import DynamicAsyncPSTrainer
        topo_cfg = config.schedule.topology or TopologyConfig()
        topo = topo_cfg.build(default_workers=len(jax.devices()))
        self.trainer = DynamicAsyncPSTrainer(
            init_layers=self._layers, loss_fn=self._loss_fn,
            optimizer=config.build_optimizer(), topology=topo,
            pushes_per_epoch=config.schedule.reschedule_every,
            staleness=config.execution.staleness or 0,
            throttle=config.execution.throttle,
            aggregate=config.execution.aggregate,
            strategy=config.schedule.strategy,
            profiles=layer_profiles(arch, self.shape),
            compressor=config.compression.build(),
            async_planning=config.schedule.async_planning,
            plan_cache_size=config.schedule.plan_cache_size)

    @property
    def events(self):
        return tuple(self.trainer.events) + tuple(self._eval_events)

    @property
    def _server(self):
        return self.trainer.trainer.server

    def _run_pushes(self, num_pushes, wfn):
        return self.trainer.run_pushes(num_pushes, wfn)

    def timeline(self):
        return self.trainer.trainer.log


@register_runtime("fleet-async",
                  description="elastic worker fleet on the deterministic "
                              "event engine: churn-driven re-planning, "
                              "server re-sharding, measured drift "
                              "detection")
class FleetRuntime(_AsyncBase):
    """Elastic membership over the bounded-staleness event loop.

    The initial fleet comes from the topology block (one
    :class:`~repro.fleet.WorkerSpec` per configured link); the fleet
    block scripts or synthesizes membership churn and tunes the stall
    and drift detectors.  Unlike the other async adapters, ``save_state``
    also serializes the *event-loop* state (in-flight work, admission
    queue, simulated clock), so a restored run resumes mid-simulation
    bit-identically instead of restarting the loop at time 0."""

    def __init__(self, config, arch, batch_fn):
        super().__init__(config, arch, batch_fn)
        from repro.fleet import FleetTrainer, WorkerSpec
        from repro.models.profiles import layer_profiles
        topo_cfg = config.schedule.topology or TopologyConfig()
        topo = topo_cfg.build(default_workers=len(jax.devices()))
        specs = {w: WorkerSpec(down_bps=link.down.bandwidth_bps,
                               up_bps=link.up.bandwidth_bps,
                               flops=topo.worker_flops[w])
                 for w, link in enumerate(topo.links)}
        fleet_cfg = config.fleet or FleetConfig()
        self.trainer = FleetTrainer(
            init_layers=self._layers, loss_fn=self._loss_fn,
            optimizer=config.build_optimizer(), workers=specs,
            schedule=fleet_cfg.build_schedule(tuple(specs)),
            num_servers=topo.num_servers,
            workers_per_shard=fleet_cfg.workers_per_shard,
            staleness=config.execution.staleness or 0,
            throttle=config.execution.throttle,
            strategy=config.schedule.strategy,
            profiles=layer_profiles(arch, self.shape),
            compressor=config.compression.build(),
            drift_detector=fleet_cfg.build_detector(),
            stall_factor=fleet_cfg.stall_factor,
            check_interval=fleet_cfg.check_interval,
            async_planning=config.schedule.async_planning,
            plan_cache_size=config.schedule.plan_cache_size)

    @property
    def events(self):
        timed = sorted(tuple(self.trainer.replan_events) +
                       tuple(self.trainer.membership_events),
                       key=lambda e: e.sim_time)
        return tuple(timed) + tuple(self._eval_events)

    @property
    def _server(self):
        return self.trainer.server

    def _run_pushes(self, num_pushes, wfn):
        return self.trainer.run(num_pushes, wfn, reset=not self._started)

    def timeline(self):
        return self.trainer.log

    def save_state(self, path: str) -> None:
        """Checkpoint server state plus the live event loop.

        The loop (engine queue, in-flight gradients, SSP barrier,
        membership roster, detector streams, run log) lands next to the
        parameter checkpoint at ``path + ".loop"``."""
        self._save_tree(path, {"server": self.trainer.server.state_dict()})
        self.trainer.save_loop_state(path + ".loop")

    def restore_state(self, path: str) -> None:
        tree = self._load_tree(path,
                               {"server": self.trainer.server.state_dict()})
        self.trainer.server.load_state_dict(tree["server"])
        self.trainer.restore_loop_state(path + ".loop")
        # the loop resumes mid-simulation: keep driving the restored run
        # instead of resetting to time 0
        self._started = True
        log = self.trainer.log
        self._reported = len(log.accepted) if log is not None else 0


@register_runtime("pipeline",
                  description="stage-partitioned pipeline parallelism with "
                              "DynaComm-scheduled activation transfers")
class PipelineRuntime(_CompiledRuntime):
    """Profile → DP stage partition → micro-batch pipeline execution.

    Stages are balanced by profiled fc + bc via
    :func:`repro.pipeline.partition_profiles`; inter-stage activation
    traffic is planned through the shared edge cost model
    (``dp_forward``/``dp_backward`` over virtual boundary layers) riding a
    :class:`~repro.core.planner.Planner`, so homogeneous boundaries are
    one DP solve plus cache hits.  Losses are bit-identical to the
    single-stage execution of the same decomposition at any stage count.
    """

    def __init__(self, config, arch, batch_fn):
        super().__init__(config, arch, batch_fn)
        from repro.core import costs_from_profiles
        from repro.core.planner import Planner
        from repro.models.profiles import layer_profiles
        from repro.pipeline import PipelineTrainer, partition_profiles
        pcfg = config.pipeline        # materialized by RuntimeConfig
        net = (config.schedule.network or NetworkConfig()).build()
        profiles = layer_profiles(arch, self.shape)
        partition = partition_profiles(
            profiles, pcfg.stages,
            compute_flops_per_s=config.measure.compute_flops_per_s)
        self._costs = costs_from_profiles(
            profiles, net=net,
            compute_flops_per_s=config.measure.compute_flops_per_s)
        self.planner = Planner(cache_size=config.schedule.plan_cache_size)
        self.trainer = PipelineTrainer(
            cfg=arch, optimizer=config.build_optimizer(),
            num_stages=pcfg.stages, num_microbatches=pcfg.microbatches,
            schedule_name=pcfg.schedule, aux_weight=config.aux_weight,
            partition=partition, planner=self.planner,
            transfer_strategy=config.schedule.strategy,
            costs=self._costs, net=net, transfer_chunks=pcfg.chunks)
        self._state = self.trainer.init_state(
            jax.random.PRNGKey(config.seed))

    @property
    def partition(self):
        return self.trainer.partition

    def step(self, batch) -> float:
        self._state, loss = self.trainer.step(self._state, batch)
        self._data_idx += 1
        return float(loss)

    @property
    def ledger(self) -> Dict[str, Any]:
        led = dict(self.trainer.ledger)
        led["push_compression_ratio"] = 1.0   # activations stay fp32
        return led

    def timeline(self):
        return self.trainer.timeline()
