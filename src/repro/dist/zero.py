"""DynaComm-bucketed ZeRO trainer.

The TPU-native adaptation of the paper's pull/push procedures: a
``BucketPlan`` (from ``repro.core.buckets``) drives a data-parallel training
step in which

* where a wire exists — the ``data`` axis has more than one device, or a
  ``compressor`` models the PS wire — parameters live sharded as one padded
  flat float32 buffer per sched layer (``state["flat_params"][l]`` has
  global shape ``(spec.padded,)`` split over the ``data`` axis — ZeRO:
  optimizer state and master weights are never replicated).  That buffer
  is the wire format of a DynaComm segment:
* the forward phase launches **exactly one all-gather per forward bucket**
  (the paper's parameter pull of a transmission segment);
* the backward phase launches **exactly one reduce-scatter per backward
  bucket** (the gradient push), walking layers top-down with per-layer VJPs
  so bucket boundaries are real program structure, not a post-hoc rewrite;
* with ``zero3=True`` the gathered weights are *not* kept alive across the
  forward/backward boundary: every backward bucket that contains a middle
  layer re-pulls its parameters with one extra all-gather (first/last sched
  layers are exempt — the head is hot at the fwd→bwd boundary and the
  embedding VJP needs no weights).

Where no wire exists — one device on the ``data`` axis and no compressor —
a wire format is pure cost (a relayout of every weight into and out of a
1-D buffer each step), so the state holds the parameter leaves themselves:
``state["flat_params"]`` is the model's float32 leaves in their natural
shapes, sched layer by sched layer (each layer's ``tree_flatten`` order).
A bucket's pull is then its layers' leaves, its push the VJP's leaf
gradients, and ZeRO-3's re-pull has nothing to do.  The two layouts share
the step's structure and no packing logic; the mesh and the compressor
pick one (``ZeroTrainer.layout``: ``"flat"`` or ``"leaves"``).

The step is built with ``shard_map`` so the collectives above are the
*only* all-gathers / reduce-scatters in the compiled HLO —
``tests/test_dist.py`` asserts the counts against the plan.

A looped stack (``cfg.loop_steps`` T > 1) applies each block's pulled
weights T times and exits after every pass (``models/model.py``).  Every
application's input is saved; the backward walks the passes T..1, the
blocks top-down inside each, recomputing each application from its input
(an optimization barrier keeps XLA from holding the forward's copies of
all T·L applications instead).  A block's gradient is the sum of its T
VJPs, so no bucket is pushed before pass 1's backward, which follows the
plan as a stack run once does; the head's gradient sums its T exits.

Each part of the step runs under a ``jax.named_scope`` that the profiler
attaches to its device ops: ``zero.pull.b{i}``, ``zero.fwd.L{l}``,
``zero.regather.b{i}``, ``zero.bwd.L{l}``, ``zero.push.b{i}`` (bucket
``i`` of the plan, sched layer ``l``) and ``zero.opt``; in a looped
stack, ``loop.t{t}`` inside a block's ``zero.fwd``/``zero.bwd`` scope for
pass ``t``, and ``loop.exit`` on the exits (final norm, head, gate) and
the exit objective.  Scopes are metadata only: the compiled instructions
are the same without them.  Holding leaves, the pull, push and re-pull
scopes hold no operation.
"""

from __future__ import annotations

import contextlib
import copy
import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.buckets import BucketPlan, flat_layer_order
from repro.dist.collectives import (FlatSpec, compressed_reduce_scatter_bucket,
                                    flatten_tree, gather_bucket,
                                    make_flat_spec, reduce_scatter_bucket,
                                    unflatten_tree)
from repro.models import blocks as blocks_lib
from repro.models import model as model_lib
from repro.optim import Optimizer


class _FlatLayout:
    """A wire exists: one padded 1-D f32 buffer per sched layer, sharded
    over the data axis; a bucket's pull is one all-gather and its push one
    (optionally compressed) reduce-scatter."""

    name = "flat"

    def __init__(self, specs: List[FlatSpec], axis_name: str,
                 compressor: Optional[Any]):
        self.specs, self.axis_name = specs, axis_name
        self.compressor = compressor

    def init(self, trees) -> List[jnp.ndarray]:
        return [flatten_tree(t, s) for t, s in zip(trees, self.specs)]

    def to_trees(self, params) -> List[Any]:
        return [unflatten_tree(jnp.asarray(f), s)
                for f, s in zip(params, self.specs)]

    def pull(self, params, bucket) -> Dict[int, Any]:
        return gather_bucket(params, self.specs, bucket, self.axis_name)

    def push(self, grads, bucket, residuals):
        """``({layer: [its mean gradient shard]}, new residuals or None)``."""
        if self.compressor is None:
            # resolved in this module's namespace when called, so a
            # replaced ``zero.reduce_scatter_bucket`` takes effect
            pushed = reduce_scatter_bucket(grads, self.specs, bucket,
                                           self.axis_name)
            res_out = None
        else:
            pushed, res_out = compressed_reduce_scatter_bucket(
                grads, self.specs, bucket, self.axis_name, self.compressor,
                residuals=residuals)
        axis_size = self.specs[bucket[0]].axis_size       # sum → mean
        return {l: [g / axis_size] for l, g in pushed.items()}, res_out


class _LeafLayout:
    """No wire (one device, no compressor): the state is the parameter
    leaves, sched layer by sched layer; pull and push move nothing."""

    name = "leaves"

    def __init__(self, specs: List[FlatSpec]):
        self.specs = specs
        self._starts = [0]
        for spec in specs:
            self._starts.append(self._starts[-1] + spec.num_leaves)

    def init(self, trees) -> List[jnp.ndarray]:
        return [x for t in trees for x in jax.tree_util.tree_leaves(t)]

    def _tree(self, params, l: int) -> Any:
        leaves = params[self._starts[l]:self._starts[l + 1]]
        return jax.tree_util.tree_unflatten(self.specs[l].treedef, leaves)

    def to_trees(self, params) -> List[Any]:
        return [self._tree(params, l) for l in range(len(self.specs))]

    def pull(self, params, bucket) -> Dict[int, Any]:
        return {l: self._tree(params, l) for l in bucket}

    def push(self, grads, bucket, residuals):
        return {l: jax.tree_util.tree_leaves(grads[l]) for l in bucket}, None


@dataclasses.dataclass
class ZeroTrainer:
    """Bucketed ZeRO data-parallel trainer over a 1-D ``data`` mesh axis."""

    cfg: ArchConfig
    mesh: Mesh
    plan: BucketPlan
    optimizer: Optimizer
    zero3: bool = False
    axis_name: str = "data"
    aux_weight: float = 0.01
    compressor: Optional[Any] = None

    def __post_init__(self):
        if self.compressor is not None and self.compressor.scheme == "none":
            self.compressor = None        # identity: skip the wrapper math
        if self.axis_name not in self.mesh.axis_names:
            raise ValueError(f"mesh has no {self.axis_name!r} axis: "
                             f"{self.mesh.axis_names}")
        self.axis_size = int(self.mesh.shape[self.axis_name])
        self.num_layers = model_lib.num_sched_layers(self.cfg)
        self._validate_plan()

        shapes = jax.eval_shape(
            lambda k: model_lib.init_params(self.cfg, k, jnp.float32),
            jax.random.PRNGKey(0))
        self.specs: List[FlatSpec] = [
            make_flat_spec(tree, self.axis_size)
            for tree in model_lib.sched_layer_trees(shapes)]
        self._kinds = self.cfg.layer_kinds()
        self._layout = (
            _LeafLayout(self.specs)
            if self.axis_size == 1 and self.compressor is None else
            _FlatLayout(self.specs, self.axis_name, self.compressor))

    @property
    def layout(self) -> str:
        """``"leaves"`` (one device, no compressor: no wire) or ``"flat"``."""
        return self._layout.name

    # ------------------------------------------------------------------
    # construction helpers
    # ------------------------------------------------------------------

    def _validate_plan(self) -> None:
        Ls = self.num_layers
        fwd = flat_layer_order(self.plan.forward)
        bwd = flat_layer_order(self.plan.backward)
        if fwd != tuple(range(Ls)):
            raise ValueError(f"forward buckets {self.plan.forward} do not "
                             f"pull layers 0..{Ls - 1} in order")
        if bwd != tuple(range(Ls - 1, -1, -1)):
            raise ValueError(f"backward buckets {self.plan.backward} do not "
                             f"push layers {Ls - 1}..0 in order")

    def with_plan(self, plan: BucketPlan) -> "ZeroTrainer":
        """Same trainer driving a different bucket plan.

        The state layout (``FlatSpec`` per sched layer, flat buffers or
        leaves) depends only on the architecture, the axis size and the
        compressor, never on the plan — so states carry across plan swaps
        unchanged.  Shares the already-computed specs instead of re-running
        ``eval_shape``.
        """
        new = copy.copy(self)
        new.plan = plan
        new._validate_plan()
        return new

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------

    @property
    def _use_residuals(self) -> bool:
        return self.compressor is not None and self.compressor.error_feedback

    def _make_state(self, key) -> Dict[str, Any]:
        params = self._layout.init(model_lib.sched_layer_trees(
            model_lib.init_params(self.cfg, key, jnp.float32)))
        state = {"flat_params": params,
                 "opt": self.optimizer.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        if self._use_residuals:
            # error-feedback residual of each device's own compressed push:
            # row d is device d's (padded,) carry for that sched layer
            state["residuals"] = [
                jnp.zeros((self.axis_size, spec.padded), jnp.float32)
                for spec in self.specs]
        return state

    def _state_layout(self, shapes, one_d, replicated, residual):
        """Map state leaves to shardings/specs: arrays (flat buffers, or
        leaves on a one-device axis) split on the data axis, scalars
        replicated, the error-feedback residuals (2-D, one row per device)
        explicitly."""
        out = {k: jax.tree_util.tree_map(
                   lambda s: one_d if s.ndim >= 1 else replicated, v)
               for k, v in shapes.items() if k != "residuals"}
        if "residuals" in shapes:
            out["residuals"] = [residual for _ in shapes["residuals"]]
        return out

    def _state_shardings(self, shapes):
        return self._state_layout(
            shapes, NamedSharding(self.mesh, P(self.axis_name)),
            NamedSharding(self.mesh, P()),
            NamedSharding(self.mesh, P(self.axis_name, None)))

    def state_structs(self) -> Dict[str, Any]:
        """The state as sharded ``ShapeDtypeStruct``s (nothing allocated):
        what a step is lowered against without a state."""
        shapes = jax.eval_shape(self._make_state, jax.random.PRNGKey(0))
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, self._state_shardings(shapes))

    def init_state(self, key) -> Dict[str, Any]:
        """Init identical to ``init_params(cfg, key)``, laid out and
        sharded."""
        shapes = jax.eval_shape(self._make_state, key)
        return jax.jit(self._make_state,
                       out_shardings=self._state_shardings(shapes))(key)

    # ------------------------------------------------------------------
    # per-sched-layer applies (closed over cfg; used forward AND in VJPs)
    # ------------------------------------------------------------------

    def _apply_embed(self, embed_tree, batch):
        return model_lib._embed_inputs(self.cfg, {"embed": embed_tree}, batch)

    def _apply_block(self, block_tree, x, kind):
        y, _, aux = blocks_lib.apply_block(block_tree, x, self.cfg, kind,
                                           mode="train", cache=None)
        return y, aux

    def _apply_final(self, final_tree, embed_tree, x, batch):
        """Final norm + (possibly embedding-tied) head + masked CE."""
        logits = model_lib._head(
            self.cfg, {"embed": embed_tree, "final": final_tree}, x)
        labels = batch["labels"]
        if self.cfg.frontend == "vision":
            nv = logits.shape[1] - labels.shape[1]
            pad = jnp.full(labels.shape[:1] + (nv,), -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        return model_lib.cross_entropy(logits, labels)

    def _apply_exit(self, final_tree, embed_tree, x, batch, t):
        """A looped stack's exit after pass ``t``: ``(h_t, per-token
        losses, gate logits)``, no gate after the last pass."""
        return model_lib.loop_exit(
            self.cfg, {"embed": embed_tree, "final": final_tree}, x,
            batch["labels"], gate=t < self.cfg.loop_steps)

    # ------------------------------------------------------------------
    # the train step
    # ------------------------------------------------------------------

    def build_train_step(self):
        """Returns jit-able ``step(state, batch) -> (state, mean_loss)``."""
        state_shapes = jax.eval_shape(self._make_state, jax.random.PRNGKey(0))
        state_specs = self._state_layout(
            state_shapes, P(self.axis_name), P(), P(self.axis_name, None))

        def step(state, batch):
            batch_specs = jax.tree_util.tree_map(
                lambda b: P(self.axis_name, *([None] * (b.ndim - 1))), batch)
            fn = jax.shard_map(self._local_step, mesh=self.mesh,
                               in_specs=(state_specs, batch_specs),
                               out_specs=(state_specs, P()),
                               check_vma=False)
            return fn(state, batch)

        return step

    def _local_step(self, state, batch):
        Ls, kinds, layout = self.num_layers, self._kinds, self._layout
        T = self.cfg.loop_steps
        shards = list(state["flat_params"])
        res_local = state.get("residuals")     # local views: (1, padded_l)
        new_res = list(res_local) if res_local is not None else None

        def loop_scope(name):
            return jax.named_scope(name) if T > 1 else contextlib.nullcontext()

        # ---- pull phase: one all-gather per forward bucket --------------
        full: Dict[int, Any] = {}
        for i, bucket in enumerate(self.plan.forward):
            with jax.named_scope(f"zero.pull.b{i}"):
                full.update(layout.pull(shards, bucket))

        # ---- forward, saving each layer application's input -------------
        # A looped stack applies the pulled blocks T times (pass t's input
        # of layer l is acts[t, l]) and exits after every pass.
        acts: Dict[Any, jnp.ndarray] = {}
        exits: List[Any] = []          # per pass: losses (and gate logits)
        aux = jnp.zeros((), jnp.float32)
        with jax.named_scope("zero.fwd.L0"):
            h = self._apply_embed(full[0], batch)
        for t in range(1, T + 1):
            for l in range(1, Ls - 1):
                acts[t, l] = h
                with jax.named_scope(f"zero.fwd.L{l}"), \
                        loop_scope(f"loop.t{t}"):
                    h, a = self._apply_block(full[l], h, kinds[l - 1])
                    aux = aux + a
            acts[t, Ls - 1] = h
            if T > 1:
                with jax.named_scope(f"zero.fwd.L{Ls - 1}"), \
                        jax.named_scope("loop.exit"):
                    h, *out = self._apply_exit(full[Ls - 1], full[0], h,
                                               batch, t)
                    exits.append(out)

        def objective(outs):
            return model_lib.exit_objective(self.cfg, outs, batch["labels"])

        with jax.named_scope(f"zero.fwd.L{Ls - 1}"), loop_scope("loop.exit"):
            if T > 1:
                ce = objective(exits)
            else:
                ce = self._apply_final(full[Ls - 1], full[0], h, batch)
            loss_local = ce + self.aux_weight * aux

        # ---- ZeRO-3: re-pull mid-layer buckets for the backward ---------
        # The barrier keeps the re-gather a distinct program point from the
        # forward pull (so the forward copies are dead after their last
        # forward use and the re-gather cannot be folded into them).
        # Holding leaves, the state is the full weights: nothing to re-pull.
        regathered: Dict[int, Any] = {}
        if self.zero3 and layout.name == "flat":
            barred = list(jax.lax.optimization_barrier(tuple(shards)))
            for i, bucket in enumerate(self.plan.backward):
                if any(0 < l < Ls - 1 for l in bucket):
                    with jax.named_scope(f"zero.regather.b{i}"):
                        regathered.update(layout.pull(barred, bucket))

        # ---- backward: per-layer VJPs, one reduce-scatter per bucket ----
        one = jnp.ones((), jnp.float32)
        aux_ct = jnp.asarray(self.aux_weight, jnp.float32)
        grad_parts: List[Optional[List[jnp.ndarray]]] = [None] * Ls
        embed_from_head = None     # tied-head contribution to the embedding
        ct_h = None                # cotangent w.r.t. the current activation

        def exit_vjp(t, p_final):
            """Pass t's exit: (final grads, embedding grads, cotangent of
            the pass's last block output)."""
            _, vjp = jax.vjp(
                lambda pf, pe, hh: self._apply_exit(pf, pe, hh, batch, t),
                p_final, full[0], acts[t, Ls - 1])
            ct_out = jnp.zeros_like(acts[t, Ls - 1]) if t == T else ct_h
            return vjp((ct_out, *ct_exits[t - 1]))

        def block_vjp(t, l, p_l):
            kind = kinds[l - 1]
            args = (p_l, acts[t, l])
            if T > 1:
                # recompute from the saved input: merged with the forward,
                # XLA keeps all T·L applications' intermediates (2e10 B at
                # the Ouro cell's size, against 1.4e10)
                args = jax.lax.optimization_barrier(args)
            _, vjp = jax.vjp(
                lambda p, hh, _k=kind: self._apply_block(p, hh, _k), *args)
            return vjp((ct_h, aux_ct))

        # A looped stack's passes T..2 first: each layer's gradient is the
        # sum over its T applications, so no bucket is complete, and none
        # pushed, before pass 1's backward below.
        summed: Dict[int, Any] = {}

        def add(l, g):
            """Layer l's gradient summed over its applications so far."""
            summed[l] = g if l not in summed else \
                jax.tree_util.tree_map(jnp.add, g, summed[l])
            return summed[l]

        if T > 1:
            with jax.named_scope(f"zero.bwd.L{Ls - 1}"), \
                    jax.named_scope("loop.exit"):
                _, vjp = jax.vjp(objective, exits)
                (ct_exits,) = vjp(one)
        for t in range(T, 1, -1):
            with jax.named_scope(f"zero.bwd.L{Ls - 1}"), \
                    jax.named_scope("loop.exit"):
                g_final, g_embed_head, ct_h = exit_vjp(
                    t, regathered.get(Ls - 1, full[Ls - 1]))
                add(Ls - 1, g_final)
                add(0, g_embed_head)
            for l in range(Ls - 2, 0, -1):
                with jax.named_scope(f"zero.bwd.L{l}"), \
                        jax.named_scope(f"loop.t{t}"):
                    g_block, ct_h = block_vjp(t, l,
                                              regathered.get(l, full[l]))
                    add(l, g_block)

        for i, bucket in enumerate(self.plan.backward):
            bucket_grads: Dict[int, Any] = {}
            for l in bucket:       # descending layer order within the bucket
                p_l = regathered.get(l, full[l])
                with jax.named_scope(f"zero.bwd.L{l}"):
                    if l == Ls - 1 and T > 1:
                        with jax.named_scope("loop.exit"):
                            g_final, embed_from_head, ct_h = exit_vjp(1, p_l)
                            bucket_grads[l] = add(l, g_final)
                    elif l == Ls - 1:
                        _, vjp = jax.vjp(
                            lambda pf, pe, hh: self._apply_final(pf, pe, hh,
                                                                 batch),
                            p_l, full[0], acts[1, l])
                        g_final, embed_from_head, ct_h = vjp(one)
                        bucket_grads[l] = g_final
                    elif l == 0:
                        _, vjp = jax.vjp(
                            lambda pe: self._apply_embed(pe, batch), p_l)
                        (g_embed,) = vjp(ct_h)
                        bucket_grads[l] = add(l, jax.tree_util.tree_map(
                            jnp.add, g_embed, embed_from_head))
                    else:
                        with loop_scope("loop.t1"):
                            g_block, ct_h = block_vjp(1, l, p_l)
                            bucket_grads[l] = add(l, g_block)
            with jax.named_scope(f"zero.push.b{i}"):
                res_in = ({l: res_local[l][0] for l in bucket}
                          if res_local is not None else None)
                pushed, res_out = layout.push(bucket_grads, bucket, res_in)
                if res_out is not None:
                    for l, r in res_out.items():
                        new_res[l] = r[None, :]
                for l, g in pushed.items():
                    grad_parts[l] = g

        # ---- sharded optimizer update (ZeRO: on local shards only) ------
        grads = [g for part in grad_parts for g in part]
        with jax.named_scope("zero.opt"):
            new_params, new_opt = self.optimizer.update(
                grads, state["opt"], shards)
        loss = jax.lax.pmean(loss_local, self.axis_name)
        new_state = {"flat_params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        if new_res is not None:
            new_state["residuals"] = new_res
        return new_state, loss

    # ------------------------------------------------------------------
    # interop
    # ------------------------------------------------------------------

    def params_from_state(self, state) -> Any:
        """Materialize the canonical (unsharded) param pytree from a state —
        checkpoint/eval interop, not part of the hot path."""
        return model_lib.params_from_sched_layers(
            self._layout.to_trees(state["flat_params"]))
