"""Measured per-sched-layer fc/bc timings (the mxnet.profiler analogue).

Hoisted out of ``repro.dist.dynamic`` so both dynamic drivers share one
implementation: each sched layer's forward apply and VJP is jitted and
timed standalone against a :class:`repro.core.profiler.LayerTimingHook`.
The ZeRO and PS trainers share the state layout, so the same
routine measures either — the PS driver additionally rescales the host
timings to each worker's compute rate
(:meth:`repro.ps.topology.PSTopology.topology_costs_measured`).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import model as model_lib


def measurement_due(fc_bc: Optional[Tuple], measured_epoch: int,
                    epoch: int, remeasure_every: int, *,
                    force: bool = False) -> bool:
    """The shared re-measurement rule of both dynamic drivers: measure
    when nothing is cached, when forced (a drift detector fired), or when
    the cache is ``remeasure_every`` re-plan epochs old
    (``remeasure_every == 0`` ⇒ measure once and keep it)."""
    stale = (remeasure_every > 0 and
             epoch - measured_epoch >= remeasure_every)
    return fc_bc is None or stale or force


def measure_layer_times(zero, hook, state, batch, *, iters: int) -> None:
    """Record ``hook.warmup + iters`` fc/bc wall-time samples per sched
    layer into ``hook`` (resetting it first).

    ``zero`` is a :class:`repro.dist.zero.ZeroTrainer` (the PS trainer's
    contained one qualifies): its per-layer applies are jitted standalone
    — one compilation per distinct layer kind, since same-kind layers
    share shapes — and timed on this host's devices.
    """
    tr = zero
    Ls, kinds = tr.num_layers, tr._kinds
    calls = hook.warmup + iters
    trees = jax.device_get(
        model_lib.sched_layer_trees(tr.params_from_state(state)))
    hook.reset()

    one = jnp.ones((), jnp.float32)
    aux_ct = jnp.asarray(tr.aux_weight, jnp.float32)

    embed_fwd = jax.jit(lambda p, b: tr._apply_embed(p, b))
    h0 = jax.block_until_ready(embed_fwd(trees[0], batch))
    ct_h = jnp.ones_like(h0)
    timed = hook.timed("fc", 0, embed_fwd)
    for _ in range(calls):
        timed(trees[0], batch)
    embed_bwd = jax.jit(lambda p, b, ct: jax.vjp(
        lambda pp: tr._apply_embed(pp, b), p)[1](ct))
    timed = hook.timed("bc", 0, embed_bwd)
    for _ in range(calls):
        timed(trees[0], batch, ct_h)

    # one jitted fwd/bwd per distinct layer kind — layers of the same
    # kind share the compilation (their shapes match)
    blk_fwd = {k: jax.jit(lambda p, x, _k=k: tr._apply_block(p, x, _k))
               for k in set(kinds)}
    blk_bwd = {k: jax.jit(lambda p, x, ct, a, _k=k: jax.vjp(
                   lambda pp, xx: tr._apply_block(pp, xx, _k), p, x
               )[1]((ct, a)))
               for k in set(kinds)}
    for l in range(1, Ls - 1):
        kind = kinds[l - 1]
        timed = hook.timed("fc", l, blk_fwd[kind])
        for _ in range(calls):
            timed(trees[l], h0)
        timed = hook.timed("bc", l, blk_bwd[kind])
        for _ in range(calls):
            timed(trees[l], h0, ct_h, aux_ct)

    fin_fwd = jax.jit(lambda pf, pe, x, b: tr._apply_final(pf, pe, x, b))
    timed = hook.timed("fc", Ls - 1, fin_fwd)
    for _ in range(calls):
        timed(trees[Ls - 1], trees[0], h0, batch)
    fin_bwd = jax.jit(lambda pf, pe, x, b, ct: jax.vjp(
        lambda a, c, d: tr._apply_final(a, c, d, b), pf, pe, x)[1](ct))
    timed = hook.timed("bc", Ls - 1, fin_bwd)
    for _ in range(calls):
        timed(trees[Ls - 1], trees[0], h0, batch, one)
