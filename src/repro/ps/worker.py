"""Worker-side synchronous PS trainer.

``PSTrainer`` executes a ``BucketPlan`` in the parameter-server topology's
synchronous mode: every iteration, each worker pulls each forward
segment's parameters down (one transmission per segment), runs forward +
backward, and pushes each backward segment's gradients up (one
transmission per segment); the server applies the summed gradients and
all workers observe the new version at the barrier.

On the device mesh this maps exactly onto the bucketed ZeRO step: place
server shard *s*'s partition of every layer's weights on worker device
*s* (server shards co-located with workers, the standard sharded-PS
deployment), and a segment pull **is** the ``all-gather`` of its
weights, a segment push **is** the ``reduce-scatter`` of their
gradients, and the server-side optimizer apply **is** the sharded update
on local partitions.  ``PSTrainer`` therefore drives a
contained :class:`repro.dist.zero.ZeroTrainer` for the compiled data path
— which makes sync-mode losses *bit-identical* to the ZeRO trainer by
construction (asserted by ``tests/test_ps.py``) — and layers the PS
semantics on top: per-topology scheduling (per-worker fc/bc, per-link
asymmetric pt/gt/Δt), per-segment transfer accounting against the
topology's links, and the PS timeline view.

The compiled HLO carries exactly ``len(plan.forward)`` pulls and
``len(plan.backward)`` pushes, each under its bucket's scope — one pull +
one push per segment, 2 transfers per (forward, backward) segment pair —
for every scheduling strategy (``repro.analysis.segment_counts``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

from jax.sharding import Mesh

from repro.configs.base import ArchConfig, InputShape
from repro.core.buckets import BucketPlan, decision_from_plan, \
    plan_from_decision
from repro.core.costmodel import TopologyCosts
from repro.core.scheduler import consensus_decision
from repro.core.simulator import PSTimeline, simulate_ps_iteration
from repro.dist.collectives import bucket_bytes
from repro.dist.zero import ZeroTrainer
from repro.models import model as model_lib
from repro.models.profiles import layer_profiles
from repro.optim import Optimizer
from repro.ps.topology import PSTopology


@dataclasses.dataclass
class PSTrainer:
    """Synchronous segmented-push/pull trainer over a PS topology."""

    cfg: ArchConfig
    mesh: Mesh
    plan: BucketPlan
    optimizer: Optimizer
    topology: PSTopology
    zero3: bool = False
    axis_name: str = "data"
    aux_weight: float = 0.01
    compressor: Optional[Any] = None

    def __post_init__(self):
        if self.compressor is not None and self.compressor.scheme == "none":
            self.compressor = None
        axis = int(self.mesh.shape[self.axis_name])
        if self.topology.num_workers != axis:
            raise ValueError(
                f"topology has {self.topology.num_workers} workers but the "
                f"mesh {self.axis_name!r} axis has {axis} devices — "
                f"synchronous PS runs one worker per device")
        # The compiled data path: co-located server shards make pull/push
        # ring collectives (module docstring) — delegate to the ZeRO step.
        self._zero = ZeroTrainer(cfg=self.cfg, mesh=self.mesh,
                                 plan=self.plan, optimizer=self.optimizer,
                                 zero3=self.zero3, axis_name=self.axis_name,
                                 aux_weight=self.aux_weight,
                                 compressor=self.compressor)
        self.specs = self._zero.specs
        self.num_layers = self._zero.num_layers

    # ------------------------------------------------------------------
    # construction from a topology (profile → per-worker plan → trainer)
    # ------------------------------------------------------------------

    @classmethod
    def from_topology(cls, cfg: ArchConfig, mesh: Mesh,
                      topology: PSTopology, optimizer: Optimizer,
                      input_shape: InputShape, *,
                      strategy: str = "dynacomm",
                      compressor: Optional[Any] = None,
                      **kwargs) -> "PSTrainer":
        """Schedule against the topology and build the trainer.

        Synchronous mode needs one shared plan; the consensus decision
        minimizes the straggler's iteration time (see
        ``core.scheduler.consensus_decision``).  A ``compressor`` is
        threaded into the plan search (pushes are timed on wire bytes, so
        the DP re-segments) and into the execution path."""
        topo_costs = topology.topology_costs(layer_profiles(cfg, input_shape),
                                             compressor=compressor)
        decision, _ = consensus_decision(topo_costs, strategy)
        plan = plan_from_decision(*decision, model_lib.num_sched_layers(cfg))
        return cls(cfg=cfg, mesh=mesh, plan=plan, optimizer=optimizer,
                   topology=topology, compressor=compressor, **kwargs)

    def with_plan(self, plan: BucketPlan) -> "PSTrainer":
        return dataclasses.replace(self, plan=plan)

    # ------------------------------------------------------------------
    # the compiled data path (delegated; see module docstring)
    # ------------------------------------------------------------------

    def init_state(self, key) -> Dict[str, Any]:
        return self._zero.init_state(key)

    def build_train_step(self):
        """jit-able ``step(state, batch) -> (state, mean_loss)`` carrying
        one pull + one push collective per plan segment."""
        return self._zero.build_train_step()

    def params_from_state(self, state) -> Any:
        return self._zero.params_from_state(state)

    @property
    def layout(self) -> str:
        """The contained ZeRO step's state layout (``"flat"``/``"leaves"``)."""
        return self._zero.layout

    # ------------------------------------------------------------------
    # PS accounting: segments → shards, bytes → links
    # ------------------------------------------------------------------

    @property
    def expected_transfers(self) -> Tuple[int, int]:
        """(pulls, pushes) per iteration == the compiled HLO's pull and
        push segments: one of each per segment."""
        return (self.plan.num_forward_collectives,
                self.plan.num_backward_collectives)

    def segment_bytes(self, bucket) -> int:
        """Unpadded f32 payload of one segment's message."""
        return bucket_bytes(self.specs, bucket)

    def segment_owners(self) -> Dict[str, Tuple[int, ...]]:
        """Owning server shard per plan segment, both directions."""
        L = self.num_layers
        return {
            "forward": tuple(self.topology.owner_of_bucket(b, L)
                             for b in self.plan.forward),
            "backward": tuple(self.topology.owner_of_bucket(b, L)
                              for b in self.plan.backward),
        }

    def transfer_bytes(self) -> Dict[str, int]:
        """Per-iteration logical fp32 bytes each worker moves per
        direction."""
        return {
            "pull": sum(self.segment_bytes(b) for b in self.plan.forward),
            "push": sum(self.segment_bytes(b) for b in self.plan.backward),
        }

    def segment_wire_bytes(self, bucket) -> int:
        """Bytes one segment's push puts on the uplink (compressed
        per-layer payloads + per-segment header)."""
        if self.compressor is None:
            return self.segment_bytes(bucket)
        wire = sum(float(self.compressor.wire_bytes(self.specs[l].total * 4))
                   for l in bucket)
        return int(round(wire + self.compressor.segment_overhead_bytes))

    def transfer_wire_bytes(self) -> Dict[str, int]:
        """Per-iteration *wire* bytes per direction (pulls stay fp32)."""
        return {
            "pull": sum(self.segment_bytes(b) for b in self.plan.forward),
            "push": sum(self.segment_wire_bytes(b)
                        for b in self.plan.backward),
        }

    # ------------------------------------------------------------------
    # scheduling / simulation views
    # ------------------------------------------------------------------

    def topology_costs(self, input_shape: InputShape) -> TopologyCosts:
        return self.topology.topology_costs(
            layer_profiles(self.cfg, input_shape),
            compressor=self.compressor)

    def timeline_from_costs(self, costs: TopologyCosts) -> PSTimeline:
        """Per-worker timeline of one synchronous iteration of *this
        trainer's* plan under explicit costs (e.g. a topology epoch's
        projection a caller already holds), skipping the profile
        re-derivation that :meth:`timeline` performs."""
        return simulate_ps_iteration(costs, decision_from_plan(self.plan))

    def timeline(self, input_shape: InputShape) -> PSTimeline:
        """Per-worker timeline of one synchronous iteration of the plan."""
        return self.timeline_from_costs(self.topology_costs(input_shape))

    def estimated_step_seconds(self, input_shape: InputShape) -> float:
        return self.timeline(input_shape).makespan
