"""Scan-over-layers forward (compile-time-friendly production path).

The canonical model stores per-layer parameter trees (the view DynaComm
schedules over).  For lowering/compiling the full-scale configs, XLA compile
time is dominated by the unrolled layer stack, so this module provides the
standard MaxText-style alternative: parameters stacked along a leading
group axis and a ``lax.scan`` over pattern-period groups.  Math is
identical to ``model.forward`` (asserted in tests).

Layout: the layer pattern (period p) tiles the stack; full periods are
scanned, the remainder (num_layers mod p) is unrolled.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import blocks as blocks_lib
from repro.models import model as model_lib


def group_count(cfg: ArchConfig) -> Tuple[int, int]:
    p = len(cfg.layer_pattern)
    return cfg.num_layers // p, cfg.num_layers % p


def stack_layer_params(cfg: ArchConfig, params: Dict[str, Any]) -> Dict[str, Any]:
    """Per-layer list → {embed, stack:[p stacked trees], remainder, final}."""
    p = len(cfg.layer_pattern)
    n_groups, rem = group_count(cfg)
    stack = []
    if n_groups > 0:
        for j in range(p):
            trees = [params["layers"][i * p + j] for i in range(n_groups)]
            stack.append(jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *trees))
    remainder = params["layers"][n_groups * p:]
    return {"embed": params["embed"], "stack": stack,
            "remainder": remainder, "final": params["final"]}


def unstack_layer_params(cfg: ArchConfig, sp: Dict[str, Any]) -> Dict[str, Any]:
    p = len(cfg.layer_pattern)
    n_groups, _ = group_count(cfg)
    layers: List[Any] = []
    for i in range(n_groups):
        for j in range(p):
            layers.append(jax.tree_util.tree_map(lambda x: x[i], sp["stack"][j]))
    layers.extend(sp["remainder"])
    return {"embed": sp["embed"], "layers": layers, "final": sp["final"]}


def init_stacked(cfg: ArchConfig, key, dtype=jnp.float32) -> Dict[str, Any]:
    return stack_layer_params(cfg, model_lib.init_params(cfg, key, dtype))


def forward_scanned(cfg: ArchConfig, sp: Dict[str, Any],
                    batch: Dict[str, jnp.ndarray], *, mode: str = "train",
                    remat: bool = True, last_only: bool = False,
                    act_sharding=None, logits_sharding=None,
                    barrier: bool = False, remat_sqrt: int = 0):
    """Returns (logits, caches_or_None, aux).  train/prefill only.

    ``act_sharding``: optional NamedSharding pinned onto the (B, T, d)
    activations at every block boundary — without it GSPMD sometimes
    drifts to replicated-batch layouts inside the stack.
    """
    assert mode in ("train", "prefill")
    if cfg.looped:
        raise NotImplementedError(f"{cfg.name}: a looped stack runs on the "
                                  f"per-layer path (models/model.py)")
    pattern = cfg.layer_pattern
    n_groups, rem = group_count(cfg)

    def pin(x):
        if act_sharding is not None:
            return jax.lax.with_sharding_constraint(x, act_sharding)
        return x

    x = pin(model_lib._embed_inputs(cfg, {"embed": sp["embed"]}, batch))

    def group_body(x, group_trees):
        if barrier:
            # keep the remat-saved carry in bf16: without this XLA hoists the
            # first consumer's f32 convert over the whole (groups, B, T, d)
            # residual stack, doubling its bytes (§Perf, grok iteration 2)
            x = jax.lax.optimization_barrier(x)
        aux = jnp.zeros((), jnp.float32)
        caches = []
        for j, kind in enumerate(pattern):
            x, c, a = blocks_lib.apply_block(group_trees[j], x, cfg, kind,
                                             mode=mode, cache=None)
            x = pin(x)
            aux = aux + a
            caches.append(c)
        return x, (aux, tuple(caches) if mode == "prefill" else None)

    body = jax.checkpoint(group_body) if remat else group_body

    if n_groups > 0 and remat_sqrt > 1 and n_groups % remat_sqrt == 0 \
            and mode == "train":
        # two-level (√-remat) scan: the outer scan checkpoints only
        # n_groups/remat_sqrt carries; each outer step re-runs an inner scan
        # of remat_sqrt groups during backward.  Cuts the dominant
        # (groups, B, T, d) residual stack by the factor at ~1 extra forward
        # of recompute (§Perf, grok iteration 4).
        g1 = n_groups // remat_sqrt
        stack2 = tuple(
            jax.tree_util.tree_map(
                lambda t: t.reshape((g1, remat_sqrt) + t.shape[1:]), tree)
            for tree in sp["stack"])

        def outer_body(x, outer_trees):
            def inner(carry, gp):
                y, (a, _) = body(carry, gp)
                return y, a
            x, auxs = jax.lax.scan(inner, x, outer_trees)
            return x, jnp.sum(auxs)

        x, auxs = jax.lax.scan(jax.checkpoint(outer_body), x, stack2)
        aux = jnp.sum(auxs)
        caches_scanned = None
    elif n_groups > 0:
        x, (auxs, caches_scanned) = jax.lax.scan(
            lambda carry, gp: body(carry, gp), x, tuple(sp["stack"]))
        aux = jnp.sum(auxs)
    else:
        caches_scanned = None
        aux = jnp.zeros((), jnp.float32)

    rem_caches = []
    for r, tree in enumerate(sp["remainder"]):
        kind = pattern[r % len(pattern)]
        x, c, a = blocks_lib.apply_block(tree, x, cfg, kind, mode=mode,
                                         cache=None)
        x = pin(x)
        aux = aux + a
        rem_caches.append(c)

    if last_only:
        x = x[:, -1:]
    logits = model_lib._head(cfg, {"embed": sp["embed"], "final": sp["final"]}, x)
    if logits_sharding is not None:
        logits = jax.lax.with_sharding_constraint(logits, logits_sharding)
    caches = None
    if mode == "prefill":
        caches = {"scanned": caches_scanned, "remainder": rem_caches}
    return logits, caches, aux


def train_loss_scanned(cfg: ArchConfig, sp, batch, *, aux_weight: float = 0.01,
                       remat: bool = True, act_sharding=None,
                       logits_sharding=None, barrier: bool = False,
                       remat_sqrt: int = 0) -> jnp.ndarray:
    logits, _, aux = forward_scanned(cfg, sp, batch, mode="train", remat=remat,
                                     act_sharding=act_sharding,
                                     logits_sharding=logits_sharding,
                                     barrier=barrier, remat_sqrt=remat_sqrt)
    labels = batch["labels"]
    if cfg.frontend == "vision":
        nv = logits.shape[1] - labels.shape[1]
        pad = jnp.full(labels.shape[:1] + (nv,), -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    return model_lib.cross_entropy(logits, labels) + aux_weight * aux
