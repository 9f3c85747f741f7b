"""Stage-partitioned pipeline-parallel trainer.

``PipelineTrainer`` executes the model as S contiguous stages of sched
layers (embed, blocks..., head — the :class:`StagePartition` decides the
split).  Each stage owns a *jitted per-stage apply*; micro-batch
activations cross stage boundaries as FlatSpec-described flat float32
buffers, and every crossing is accounted in a
:class:`~repro.ps.server.TransferLedger` keyed by boundary index.

Numerical contract — the losses are bit-identical to the single-device
per-layer reference (the ZeroTrainer math on one device) at M = 1 for
any stage count, because every stage runs the *same* per-layer ops in
the same order; only the XLA program boundaries move:

* forward: ``_embed_inputs`` → ``apply_block``... → head, with the CE
  *numerator* accumulated per micro-batch and one division by the
  full-batch mask count at the end (at M = 1 this is literally
  ``cross_entropy``'s sum/maximum/divide);
* backward: per-layer VJPs in descending order inside each stage
  (activations recomputed stage-locally — the standard pipeline
  recompute), with the tied-head embedding cotangent routed back to the
  stage that owns the embedding, exactly like the ZeroTrainer;
* optimizer: the shared ``Optimizer.update`` on the per-sched-layer
  flat buffers.

MoE auxiliary losses are summed per stage then combined in stage order;
with aux ≠ 0 and S > 1 the summation *grouping* differs from the
single-program reference, so MoE configs agree to f32 roundoff rather
than bitwise (dense models emit exact-zero aux and stay bitwise).

``stage_devices=`` places each stage's parameters, batch slice, and
boundary buffers on an explicit device (``jax.device_put`` before each
stage call), so on a forged multi-device host the boundary buffers are
*real* cross-device transfers — the slow 4-device test drives this.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.costmodel import LayerCosts
from repro.dist.collectives import (FlatSpec, flatten_tree, make_flat_spec,
                                    unflatten_tree)
from repro.models import blocks as blocks_lib
from repro.models import model as model_lib
from repro.optim import Optimizer
from repro.pipeline.partition import StagePartition, partition_loads
from repro.pipeline.schedule import (PipelineSchedule, PipelineTimeline,
                                     make_schedule, simulate)
from repro.pipeline.transfer import (TransferPlan, boundary_costs,
                                     plan_boundary)
from repro.ps.server import TransferLedger

#: ledger key for the tied-embedding broadcast to the head stage (the
#: one transfer that is not a neighbor-boundary crossing)
EMBED_LINK = -1


@dataclasses.dataclass
class PipelineTrainer:
    """S-stage pipeline execution of one model over micro-batches."""

    cfg: ArchConfig
    optimizer: Optimizer
    num_stages: int = 2
    num_microbatches: int = 1
    schedule_name: str = "1f1b"
    aux_weight: float = 0.01
    partition: Optional[StagePartition] = None   # default: uniform loads
    stage_devices: Optional[Sequence[Any]] = None
    planner: Optional[Any] = None                # transfer-planning seam
    transfer_strategy: str = "dynacomm"
    costs: Optional[LayerCosts] = None           # for timeline()/plans
    net: Optional[Any] = None                    # EdgeNetworkModel-like
    transfer_chunks: int = 1

    def __post_init__(self):
        if self.cfg.looped:
            raise NotImplementedError(
                f"{self.cfg.name}: a looped stack's passes span every stage; "
                f"it trains on the ZeRO path")
        self.num_layers = model_lib.num_sched_layers(self.cfg)
        if not 1 <= self.num_stages <= self.num_layers:
            raise ValueError(
                f"num_stages must be in [1, {self.num_layers}] "
                f"(sched layers), got {self.num_stages}")
        if self.num_microbatches < 1:
            raise ValueError(f"num_microbatches must be >= 1, got "
                             f"{self.num_microbatches}")
        if self.partition is None:
            self.partition = partition_loads(
                [1.0] * self.num_layers, self.num_stages)
        if self.partition.num_stages != self.num_stages or \
                self.partition.num_layers != self.num_layers:
            raise ValueError(
                f"partition covers {self.partition.num_layers} layers in "
                f"{self.partition.num_stages} stages; trainer wants "
                f"{self.num_layers} layers in {self.num_stages} stages")
        if self.stage_devices is not None and \
                len(self.stage_devices) != self.num_stages:
            raise ValueError("need one device per stage")
        self.schedule: PipelineSchedule = make_schedule(
            self.schedule_name, self.num_stages, self.num_microbatches)

        shapes = jax.eval_shape(
            lambda k: model_lib.init_params(self.cfg, k, jnp.float32),
            jax.random.PRNGKey(0))
        self.specs: List[FlatSpec] = [
            make_flat_spec(tree, 1)
            for tree in model_lib.sched_layer_trees(shapes)]
        self._kinds = self.cfg.layer_kinds()
        self._ledger = TransferLedger()
        self._bspecs: Optional[List[FlatSpec]] = None  # per boundary
        self._fwd_fns = None
        self._bwd_fns = None
        self._transfer_plans: Optional[List[TransferPlan]] = None
        self._den_fn = jax.jit(self._mask_den)
        self._update_fn = jax.jit(self.optimizer.update)
        aw = self.aux_weight / self.num_microbatches

        def combine(nums, den, auxs):
            num = nums[0]
            for x in nums[1:]:
                num = num + x
            aux = auxs[0]
            for a in auxs[1:]:
                aux = aux + a
            return num / den + jnp.asarray(aw, jnp.float32) * aux
        self._combine_fn = jax.jit(combine)

    # ------------------------------------------------------------------
    # per-sched-layer applies (identical math to the ZeroTrainer's)
    # ------------------------------------------------------------------

    def _apply_embed(self, embed_tree, batch):
        return model_lib._embed_inputs(self.cfg, {"embed": embed_tree}, batch)

    def _apply_block(self, block_tree, x, kind):
        y, _, aux = blocks_lib.apply_block(block_tree, x, self.cfg, kind,
                                           mode="train", cache=None)
        return y, aux

    def _padded_labels(self, logits, batch):
        labels = batch["labels"]
        if self.cfg.frontend == "vision":
            nv = logits.shape[1] - labels.shape[1]
            pad = jnp.full(labels.shape[:1] + (nv,), -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        return labels

    def _ce_num(self, final_tree, embed_tree, x, batch):
        """The numerator of ``cross_entropy`` — same ops, no division."""
        logits = model_lib._head(
            self.cfg, {"embed": embed_tree, "final": final_tree}, x)
        labels = self._padded_labels(logits, batch)
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        x32 = logits.astype(jnp.float32)
        m = jax.lax.stop_gradient(jnp.max(x32, axis=-1, keepdims=True))
        lse = jnp.log(jnp.sum(jnp.exp(x32 - m), axis=-1)) + m[..., 0]
        iota = jax.lax.broadcasted_iota(jnp.int32, x32.shape, x32.ndim - 1)
        picked = jnp.sum(jnp.where(iota == safe[..., None], x32, 0.0),
                         axis=-1)
        return jnp.sum((lse - picked) * mask)

    def _mask_den(self, batch):
        """``cross_entropy``'s denominator from the full batch's labels."""
        labels = batch["labels"]
        if self.cfg.frontend == "vision" and "vision_embeds" in batch:
            nv = batch["vision_embeds"].shape[1]
            pad = jnp.full(labels.shape[:1] + (nv,), -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        mask = (labels >= 0).astype(jnp.float32)
        return jnp.maximum(jnp.sum(mask), 1.0)

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------

    def init_state(self, key) -> Dict[str, Any]:
        """Identical init to the single-device reference, flattened."""
        def make(k):
            params = model_lib.init_params(self.cfg, k, jnp.float32)
            flats = [flatten_tree(tree, spec) for tree, spec in
                     zip(model_lib.sched_layer_trees(params), self.specs)]
            return {"flat_params": flats,
                    "opt": self.optimizer.init(flats),
                    "step": jnp.zeros((), jnp.int32)}
        state = jax.jit(make)(key)
        return self._place_state(state)

    def _place_state(self, state):
        """Pin each stage's buffers to its device when stages are placed."""
        if self.stage_devices is None:
            return state
        stage_of = self.partition.stage_of

        def put(l, leaf):
            return jax.device_put(leaf, self.stage_devices[stage_of[l]])
        state = dict(state)
        state["flat_params"] = [put(l, f)
                                for l, f in enumerate(state["flat_params"])]
        return state

    def params_from_state(self, state) -> Any:
        trees = [unflatten_tree(jnp.asarray(f), spec)
                 for f, spec in zip(state["flat_params"], self.specs)]
        return model_lib.params_from_sched_layers(trees)

    # ------------------------------------------------------------------
    # per-stage compiled applies
    # ------------------------------------------------------------------

    def _stage_flats(self, state, s: int) -> Tuple[Any, ...]:
        return tuple(state["flat_params"][l]
                     for l in self.partition.layers_of(s))

    def _make_fwd(self, s: int, bspec_in: Optional[FlatSpec],
                  bspec_out: Optional[FlatSpec]):
        """Stage forward; emits the boundary activation as its FlatSpec
        flat buffer (raw when ``bspec_out`` is None — the shape probe)."""
        layers = self.partition.layers_of(s)
        Ls, kinds = self.num_layers, self._kinds
        has_embed = 0 in layers
        has_head = (Ls - 1) in layers

        def fwd(flats_s, *args):
            trees = {l: unflatten_tree(f, self.specs[l])
                     for l, f in zip(layers, flats_s)}
            i = 0
            if has_embed:
                batch = args[i]; i += 1
                h = self._apply_embed(trees[0], batch)
            else:
                h = unflatten_tree(args[i], bspec_in); i += 1
                if has_head:
                    batch = args[i]; i += 1
            aux = jnp.zeros((), jnp.float32)
            for l in layers:
                if l == 0 or l == Ls - 1:
                    continue
                h, a = self._apply_block(trees[l], h, kinds[l - 1])
                aux = aux + a
            if has_head:
                embed_tree = trees[0] if has_embed \
                    else unflatten_tree(args[i], self.specs[0])
                num = self._ce_num(trees[Ls - 1], embed_tree, h, batch)
                return num, aux
            if bspec_out is not None:
                h = flatten_tree(h, bspec_out)
            return h, aux
        return fwd

    def _make_bwd(self, s: int, bspec_in: Optional[FlatSpec],
                  bspec_out: Optional[FlatSpec]):
        """Stage backward: recompute forward stage-locally, then the same
        descending per-layer VJP loop as the ZeroTrainer."""
        layers = self.partition.layers_of(s)
        Ls, kinds = self.num_layers, self._kinds
        has_embed = 0 in layers
        has_head = (Ls - 1) in layers
        aux_ct_val = self.aux_weight / self.num_microbatches

        def bwd(flats_s, *args):
            trees = {l: unflatten_tree(f, self.specs[l])
                     for l, f in zip(layers, flats_s)}
            i = 0
            if has_embed:
                batch = args[i]; i += 1
                h = self._apply_embed(trees[0], batch)
            else:
                h_in = unflatten_tree(args[i], bspec_in); i += 1
                h = h_in
                if has_head:
                    batch = args[i]; i += 1
            if has_head:
                embed_tree = trees[0] if has_embed \
                    else unflatten_tree(args[i], self.specs[0])
                if not has_embed:
                    i += 1
                den = args[i]; i += 1
            else:
                ct_in = unflatten_tree(args[i], bspec_out); i += 1

            # ---- recompute forward, saving each layer's input ----------
            acts: Dict[int, jnp.ndarray] = {}
            for l in layers:
                if l == 0 or l == Ls - 1:
                    continue
                acts[l] = h
                h, _ = self._apply_block(trees[l], h, kinds[l - 1])
            if has_head:
                acts[Ls - 1] = h

            # ---- descending per-layer VJPs -----------------------------
            one = jnp.ones((), jnp.float32)
            aux_ct = jnp.asarray(aux_ct_val, jnp.float32)
            grads: Dict[int, Any] = {}
            embed_from_head = None
            ct_h = None if has_head else ct_in
            for l in reversed(layers):
                if l == Ls - 1:
                    _, vjp = jax.vjp(
                        lambda pf, pe, hh: self._ce_num(pf, pe, hh,
                                                        batch) / den,
                        trees[l], embed_tree, acts[l])
                    g_final, embed_from_head, ct_h = vjp(one)
                    grads[l] = g_final
                elif l == 0:
                    _, vjp = jax.vjp(
                        lambda pe: self._apply_embed(pe, batch), trees[0])
                    (g_embed,) = vjp(ct_h)
                    if embed_from_head is not None:   # head in same stage
                        g_embed = jax.tree_util.tree_map(
                            jnp.add, g_embed, embed_from_head)
                        embed_from_head = None
                    grads[0] = g_embed
                else:
                    kind = kinds[l - 1]
                    _, vjp = jax.vjp(
                        lambda p, hh, _k=kind: self._apply_block(p, hh, _k),
                        trees[l], acts[l])
                    g_block, ct_h = vjp((ct_h, aux_ct))
                    grads[l] = g_block

            gflats = tuple(flatten_tree(grads[l], self.specs[l])
                           for l in layers)
            outs: List[Any] = [gflats]
            if not has_embed:     # cotangent for the incoming boundary
                outs.append(flatten_tree(ct_h, bspec_in))
            if has_head and not has_embed:  # tied-head embedding grad home
                outs.append(flatten_tree(embed_from_head, self.specs[0]))
            return tuple(outs)
        return bwd

    def _ensure_compiled(self, batch) -> None:
        if self._fwd_fns is not None:
            return
        micro = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                (x.shape[0] // self.num_microbatches,) + tuple(x.shape[1:]),
                x.dtype), batch)
        flat_structs = [jax.ShapeDtypeStruct((spec.padded,), jnp.float32)
                        for spec in self.specs]

        bspecs: List[FlatSpec] = []
        fwd_fns = []
        h_struct = None
        for s in range(self.num_stages):
            bspec_in = bspecs[s - 1] if s > 0 else None
            raw = self._make_fwd(s, bspec_in, None)
            flats_s = tuple(flat_structs[l]
                            for l in self.partition.layers_of(s))
            args = self._fwd_args_struct(s, h_struct, micro, flat_structs)
            out = jax.eval_shape(raw, flats_s, *args)
            if s < self.num_stages - 1:
                bspec = make_flat_spec(out[0], 1)
                bspecs.append(bspec)
                h_struct = jax.ShapeDtypeStruct((bspec.padded,), jnp.float32)
                fwd_fns.append(jax.jit(self._make_fwd(s, bspec_in, bspec)))
            else:
                fwd_fns.append(jax.jit(raw))
        self._bspecs = bspecs
        self._fwd_fns = fwd_fns
        self._bwd_fns = [
            jax.jit(self._make_bwd(
                s,
                bspecs[s - 1] if s > 0 else None,
                bspecs[s] if s < self.num_stages - 1 else None))
            for s in range(self.num_stages)]

    def _fwd_args_struct(self, s, h_struct, micro, flat_structs):
        layers = self.partition.layers_of(s)
        has_embed = 0 in layers
        has_head = (self.num_layers - 1) in layers
        args: List[Any] = []
        if has_embed:
            args.append(micro)
        else:
            args.append(h_struct)
            if has_head:
                args.append(micro)
        if has_head and not has_embed:
            args.append(flat_structs[0])
        return tuple(args)

    def _bwd_args_struct(self, s, micro, flat_structs):
        layers = self.partition.layers_of(s)
        has_embed = 0 in layers
        has_head = (self.num_layers - 1) in layers
        bspec_in = self._bspecs[s - 1] if s > 0 else None
        args: List[Any] = []
        if has_embed:
            args.append(micro)
        else:
            args.append(jax.ShapeDtypeStruct((bspec_in.padded,),
                                             jnp.float32))
            if has_head:
                args.append(micro)
        if has_head:
            if not has_embed:
                args.append(flat_structs[0])
            args.append(jax.ShapeDtypeStruct((), jnp.float32))
        else:
            bspec_out = self._bspecs[s]
            args.append(jax.ShapeDtypeStruct((bspec_out.padded,),
                                             jnp.float32))
        return tuple(args)

    def stage_hlo(self, batch) -> List[Tuple[str, str]]:
        """Compiled (forward, backward) HLO text per stage.

        The conformance pass asserts each per-stage program contains zero
        cross-replica collectives: every inter-stage byte moves through
        the explicit boundary buffers the ledger accounts, never through
        a collective XLA slipped in."""
        self._ensure_compiled(batch)
        micro = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                (x.shape[0] // self.num_microbatches,) + tuple(x.shape[1:]),
                x.dtype), batch)
        flat_structs = [jax.ShapeDtypeStruct((spec.padded,), jnp.float32)
                        for spec in self.specs]
        out = []
        for s in range(self.num_stages):
            flats_s = tuple(flat_structs[l]
                            for l in self.partition.layers_of(s))
            h_struct = None
            if s > 0:
                h_struct = jax.ShapeDtypeStruct(
                    (self._bspecs[s - 1].padded,), jnp.float32)
            fargs = self._fwd_args_struct(s, h_struct, micro, flat_structs)
            bargs = self._bwd_args_struct(s, micro, flat_structs)
            out.append((
                self._fwd_fns[s].lower(flats_s, *fargs).compile().as_text(),
                self._bwd_fns[s].lower(flats_s, *bargs).compile().as_text(),
            ))
        return out

    # ------------------------------------------------------------------
    # the train step (host-driven per-stage pipeline)
    # ------------------------------------------------------------------

    def _split(self, batch) -> List[Any]:
        M = self.num_microbatches
        b0 = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if b0 % M:
            raise ValueError(f"batch size {b0} not divisible by "
                             f"{M} micro-batches")
        mbs = b0 // M
        return [jax.tree_util.tree_map(
                    lambda x: x[m * mbs:(m + 1) * mbs], batch)
                for m in range(M)]

    def _put(self, x, s: int):
        if self.stage_devices is None:
            return x
        return jax.device_put(x, self.stage_devices[s])

    def step(self, state, batch):
        """One optimizer step; returns ``(new_state, loss)``.

        Forward then backward over all micro-batches, stage by stage on
        the host; the :class:`PipelineSchedule` orders the same task set
        on real hardware (and prices it in :meth:`timeline`) — the loss
        and gradients are order-invariant, so the host replay executes
        stages in dependency order."""
        self._ensure_compiled(batch)
        S, M, Ls = self.num_stages, self.num_microbatches, self.num_layers
        micros = self._split(batch)
        den = self._den_fn(self._put(batch, S - 1))
        embed_flat = None
        if S > 1:
            embed_flat = self._put(state["flat_params"][0], S - 1)
            self._ledger.record_pull(EMBED_LINK, self.specs[0].total * 4)
        stage_flats = [tuple(self._put(f, s) for f in
                             self._stage_flats(state, s))
                       for s in range(S)]

        # ---- forward: boundary activations flow down the stages --------
        bnd: List[List[Any]] = [[] for _ in range(M)]   # bnd[m][b] = flat
        nums, auxs = [], []
        for m, mb in enumerate(micros):
            h = None
            for s in range(S):
                args = self._fwd_call_args(s, h, mb, embed_flat)
                out, aux_sm = self._fwd_fns[s](stage_flats[s], *args)
                auxs.append(aux_sm)
                if s < S - 1:
                    h = self._put(out, s + 1)
                    bnd[m].append(h)
                    self._ledger.record_pull(s, self._bspecs[s].total * 4)
                else:
                    nums.append(out)

        # ---- backward: per-stage VJPs, activation grads flow back ------
        acc: List[Optional[Any]] = [None] * Ls
        embed_home = None
        for m, mb in enumerate(micros):
            ct = None
            for s in reversed(range(S)):
                args = self._bwd_call_args(s, m, mb, embed_flat, den, ct,
                                           bnd)
                outs = self._bwd_fns[s](stage_flats[s], *args)
                gflats = outs[0]
                if s > 0:
                    ct = self._put(outs[1], s - 1)
                    self._ledger.record_push(
                        s - 1, self._bspecs[s - 1].total * 4)
                if s == S - 1 and s > 0:
                    efh = self._put(outs[2], 0)
                    self._ledger.record_push(EMBED_LINK,
                                             self.specs[0].total * 4)
                    embed_home = efh if embed_home is None \
                        else jnp.add(embed_home, efh)
                for l, g in zip(self.partition.layers_of(s), gflats):
                    acc[l] = g if acc[l] is None else jnp.add(acc[l], g)
        if embed_home is not None:
            acc[0] = jnp.add(acc[0], embed_home)

        # ---- combine loss + shared optimizer update --------------------
        loss = self._combine_fn(
            tuple(self._put(n, 0) for n in nums), self._put(den, 0),
            tuple(self._put(a, 0) for a in auxs))
        flats_in, opt_in = state["flat_params"], state["opt"]
        if self.stage_devices is not None:
            d0 = self.stage_devices[0]
            flats_in = [jax.device_put(f, d0) for f in flats_in]
            acc = [jax.device_put(g, d0) for g in acc]
            opt_in = jax.device_put(opt_in, d0)
        new_flats, new_opt = self._update_fn(acc, opt_in, flats_in)
        new_state = {"flat_params": new_flats, "opt": new_opt,
                     "step": state["step"] + 1}
        return self._place_state(new_state), loss

    def _fwd_call_args(self, s, h, mb, embed_flat):
        layers = self.partition.layers_of(s)
        has_embed = 0 in layers
        has_head = (self.num_layers - 1) in layers
        mb_s = self._put(mb, s) if (has_embed or has_head) else None
        args: List[Any] = []
        if has_embed:
            args.append(mb_s)
        else:
            args.append(h)
            if has_head:
                args.append(mb_s)
        if has_head and not has_embed:
            args.append(embed_flat)
        return tuple(args)

    def _bwd_call_args(self, s, m, mb, embed_flat, den, ct, bnd):
        layers = self.partition.layers_of(s)
        has_embed = 0 in layers
        has_head = (self.num_layers - 1) in layers
        mb_s = self._put(mb, s) if (has_embed or has_head) else None
        args: List[Any] = []
        if has_embed:
            args.append(mb_s)
        else:
            args.append(bnd[m][s - 1])
            if has_head:
                args.append(mb_s)
        if has_head:
            if not has_embed:
                args.append(embed_flat)
            args.append(self._put(den, s))
        else:
            args.append(ct)
        return tuple(args)

    # ------------------------------------------------------------------
    # accounting / cost-model views
    # ------------------------------------------------------------------

    @property
    def ledger(self) -> Dict[str, Any]:
        led = self._ledger
        return {"pull_bytes": sum(led.pulled_bytes.values()),
                "push_bytes": sum(led.pushed_bytes.values()),
                "pull_wire_bytes": sum(led.pulled_wire_bytes.values()),
                "push_wire_bytes": sum(led.pushed_wire_bytes.values()),
                "num_pulls": led.num_pulls,
                "num_pushes": led.num_pushes,
                "boundary_pull_bytes": dict(led.pulled_bytes),
                "boundary_push_bytes": dict(led.pushed_bytes)}

    def stage_times(self, costs: LayerCosts) -> Tuple[List[float],
                                                      List[float]]:
        """Per-stage per-micro-batch (fwd, bwd) seconds from cost vectors."""
        M = self.num_microbatches
        fwd, bwd = [], []
        for s in range(self.num_stages):
            ls = self.partition.layers_of(s)
            fwd.append(float(sum(costs.fc[l] for l in ls)) / M)
            bwd.append(float(sum(costs.bc[l] for l in ls)) / M)
        return fwd, bwd

    def activation_bytes(self) -> List[int]:
        """Per-boundary micro-batch activation bytes (needs a compiled
        step: boundary shapes come from the first batch)."""
        if self._bspecs is None:
            raise RuntimeError("no boundary specs yet: run a step first")
        return [spec.total * 4 for spec in self._bspecs]

    def transfer_plans(self) -> Optional[List[TransferPlan]]:
        """DynaComm-segmented plan per boundary (None before first step
        or without ``costs``/``net``)."""
        if self._transfer_plans is not None:
            return self._transfer_plans
        if self.costs is None or self.net is None or self._bspecs is None:
            return None
        fwd, bwd = self.stage_times(self.costs)
        plans = []
        for b, nbytes in enumerate(self.activation_bytes()):
            c = boundary_costs(nbytes, self.num_microbatches, net=self.net,
                               stage_fwd_s=fwd[b + 1], stage_bwd_s=bwd[b + 1],
                               chunks=self.transfer_chunks)
            plans.append(plan_boundary(b, c, planner=self.planner,
                                       strategy=self.transfer_strategy,
                                       microbatches=self.num_microbatches,
                                       chunks=self.transfer_chunks))
        self._transfer_plans = plans
        return plans

    def timeline(self) -> Optional[PipelineTimeline]:
        """Simulated replay of the active schedule under the cost model,
        with DynaComm-segmented effective boundary waits."""
        if self.costs is None:
            return None
        fwd, bwd = self.stage_times(self.costs)
        plans = self.transfer_plans()
        if plans:
            fx = [p.effective_waits[0] for p in plans]
            bx = [p.effective_waits[1] for p in plans]
        else:
            fx = bx = None
        return simulate(self.schedule, fwd, bwd,
                        fwd_transfer=fx, bwd_transfer=bx)
