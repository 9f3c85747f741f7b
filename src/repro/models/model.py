"""Model builder: init / forward / loss / decode over an ArchConfig.

Parameter layout (the "scheduling view" DynaComm consumes)::

    params = {
      "embed":  {...}          # sched layer 0   (token table / input proj)
      "layers": [block_0, ...] # sched layers 1..L
      "final":  {...}          # sched layer L+1 (final norm + untied head)
    }

``num_sched_layers = cfg.num_layers + 2``; per-sched-layer byte counts and
FLOPs come from ``profiles.py`` and feed the DP scheduler directly.

A looped stack (``cfg.loop_steps`` T > 1, Ouro) runs the blocks T times
on the same weights.  After pass t the state goes through the final norm,
``h_t = norm(x)``, which pass t+1 takes; each pass exits through the head,
``z_t = h_t · head``, with per-token loss ``l_t``, and an exit gate,
``lam_t = sigmoid(h_t · gate + gate_bias)`` (held in ``final``; the last
pass's gate is never read).  The exit distribution per token is
``p_t = lam_t prod_{j<t} (1 - lam_j)`` for t < T and
``p_T = prod_{j<T} (1 - lam_j)``; the loss is the mean over tokens of
``sum_t p_t l_t - beta H(p)`` (:func:`exit_objective`).  At T = 1 there
is one exit, p_1 = 1 and H = 0, so the loss is the plain cross-entropy
and no gate is held.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import blocks
from repro.models.layers import (dense, embed, init_dense, init_embedding,
                                 logits_from_embedding, rms_norm, softcap)

Params = Dict[str, Any]


def init_params(cfg: ArchConfig, key, dtype=jnp.float32) -> Params:
    kinds = cfg.layer_kinds()
    keys = jax.random.split(key, cfg.num_layers + 2)
    p: Params = {"embed": {}, "layers": [], "final": {}}

    if cfg.frontend != "audio":
        p["embed"]["table"] = init_embedding(keys[0], cfg.vocab_size,
                                             cfg.d_model, dtype)
    else:
        # audio: frames arrive pre-embedded (stub frontend); learn a proj
        p["embed"]["in_proj"] = init_dense(keys[0], cfg.d_model, cfg.d_model,
                                           dtype)

    for i, kind in enumerate(kinds):
        p["layers"].append(blocks.init_block(keys[1 + i], cfg, kind, dtype))

    p["final"]["norm"] = jnp.zeros((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        p["final"]["head"] = init_dense(keys[-1], cfg.d_model, cfg.vocab_size,
                                        dtype)
    if cfg.looped:
        p["final"]["gate"] = init_dense(jax.random.fold_in(keys[-1], 1),
                                        cfg.d_model, 1, dtype)
        p["final"]["gate_bias"] = jnp.zeros((1,), dtype)
    return p


def _embed_inputs(cfg: ArchConfig, params: Params, batch: Dict[str, jnp.ndarray]):
    """Produce the (B, T, d) input sequence from the modality-specific batch."""
    if cfg.frontend == "audio":
        return dense(batch["frames"], params["embed"]["in_proj"])
    x = embed(batch["tokens"], params["embed"]["table"], cfg.embed_scale)
    if cfg.frontend == "vision" and "vision_embeds" in batch:
        v = batch["vision_embeds"].astype(x.dtype)
        x = jnp.concatenate([v, x], axis=1)
    return x


def _project(cfg: ArchConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Logits of final-normed states: the (possibly embedding-tied) head."""
    if cfg.tie_embeddings:
        return logits_from_embedding(x, params["embed"]["table"],
                                     cfg.final_logit_softcap)
    return softcap(dense(x, params["final"]["head"]), cfg.final_logit_softcap)


def _head(cfg: ArchConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    return _project(cfg, params, rms_norm(x, params["final"]["norm"],
                                          cfg.norm_eps))


def exit_heads(cfg: ArchConfig, params: Params, h: jnp.ndarray,
               labels: jnp.ndarray, gate: bool = True):
    """``(per-token losses, gate logits)`` of one pass's final-normed state
    ``h``; ``(losses,)`` for the last pass, whose gate is not read."""
    losses = token_losses(_project(cfg, params, h), labels)
    if not gate:
        return (losses,)
    logits = dense(h, params["final"]["gate"])[..., 0] \
        + params["final"]["gate_bias"][0]
    return losses, logits


def loop_exit(cfg: ArchConfig, params: Params, x: jnp.ndarray,
              labels: jnp.ndarray, gate: bool = True):
    """One pass's exit from the state ``x`` after its last block: ``(h_t,
    *exit_heads)``; ``h_t`` is what the next pass takes."""
    h = rms_norm(x, params["final"]["norm"], cfg.norm_eps)
    return (h, *exit_heads(cfg, params, h, labels, gate))


def exit_objective(cfg: ArchConfig, exits, labels: jnp.ndarray) -> jnp.ndarray:
    """Expected loss under the exit distribution less ``beta`` times its
    entropy, the mean over positions with label >= 0.  ``exits`` holds
    :func:`exit_heads`'s ``(B, S)`` arrays, one tuple a pass."""
    log_stay = jnp.zeros(exits[0][0].shape, jnp.float32)  # log prod (1 - lam)
    expected = entropy = jnp.zeros(exits[0][0].shape, jnp.float32)
    for t, (loss_t, *gate_t) in enumerate(exits):
        if gate_t:
            log_p = log_stay - jax.nn.softplus(-gate_t[0])   # log lam_t
            log_stay = log_stay - jax.nn.softplus(gate_t[0])
        else:
            log_p = log_stay
        p = jnp.exp(log_p)
        expected = expected + p * loss_t
        entropy = entropy - p * log_p
    mask = (labels >= 0).astype(jnp.float32)
    per_token = expected - cfg.exit_entropy_weight * entropy
    return jnp.sum(per_token * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _stack(cfg: ArchConfig, params: Params, x: jnp.ndarray, *, mode: str,
           caches: Optional[List[Any]], remat: bool):
    """The blocks, ``cfg.loop_steps`` times over; a looped stack closes
    each pass with the final norm.  Returns (the state after each pass,
    the new caches, aux_loss); a looped stack's cache of layer ``i`` holds
    one cache a pass."""
    kinds = cfg.layer_kinds()
    aux = jnp.zeros((), jnp.float32)
    new_caches: List[List[Any]] = [[] for _ in kinds]
    passes = []
    for t in range(cfg.loop_steps):
        for i, kind in enumerate(kinds):
            cache_i = None
            if caches is not None:
                cache_i = caches[i][t] if cfg.looped else caches[i]
            apply = blocks.apply_block
            if remat and mode == "train":
                apply = jax.checkpoint(
                    lambda p, h, _cfg=cfg, _k=kind:
                    blocks.apply_block(p, h, _cfg, _k, mode="train",
                                       cache=None))
                x, c, a = apply(params["layers"][i], x)
            else:
                x, c, a = apply(params["layers"][i], x, cfg, kind,
                                mode=mode, cache=cache_i)
            new_caches[i].append(c)
            aux = aux + a
        if cfg.looped:
            x = rms_norm(x, params["final"]["norm"], cfg.norm_eps)
        passes.append(x)
    if not cfg.looped:
        new_caches = [c for (c,) in new_caches]
    return passes, new_caches, aux


def forward(cfg: ArchConfig, params: Params, batch: Dict[str, jnp.ndarray], *,
            mode: str = "train", caches: Optional[List[Any]] = None,
            remat: bool = False, last_only: bool = False
            ) -> Tuple[jnp.ndarray, Optional[List[Any]], jnp.ndarray]:
    """Returns (logits, new_caches_or_None, aux_loss); a looped stack's
    logits are its last pass's."""
    if mode == "decode":
        x = embed(batch["token"], params["embed"]["table"], cfg.embed_scale) \
            if cfg.frontend != "audio" else None
        if x is None:
            raise ValueError("encoder-only model has no decode mode")
    else:
        x = _embed_inputs(cfg, params, batch)

    passes, new_caches, aux = _stack(cfg, params, x, mode=mode,
                                     caches=caches, remat=remat)
    x = passes[-1]
    if last_only:
        x = x[:, -1:]           # narrow before the (huge) vocab projection
    logits = _project(cfg, params, x) if cfg.looped else \
        _head(cfg, params, x)
    out_caches = new_caches if mode in ("prefill", "decode") else None
    return logits, out_caches, aux


def init_caches(cfg: ArchConfig, batch: int, max_len: int,
                dtype=jnp.float32) -> List[Any]:
    caches = [blocks.init_block_cache(cfg, kind, batch, max_len, dtype)
              for kind in cfg.layer_kinds()]
    if cfg.looped:
        return [[c] * cfg.loop_steps for c in caches]
    return caches


# ---------------------------------------------------------------------------
# losses / steps
# ---------------------------------------------------------------------------


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean CE over positions with label >= 0.

    Written in one-hot select-reduce form (not take_along_axis): a gather
    along a vocab-sharded axis would force GSPMD to all-gather the *global*
    logits; iota-compare-select partitions cleanly along both batch and
    vocab axes.
    """
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(token_losses(logits, labels) * mask) \
        / jnp.maximum(jnp.sum(mask), 1.0)


def token_losses(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Cross-entropy at every position (a label < 0 is read as 0; the
    callers mask it out)."""
    safe = jnp.maximum(labels, 0)
    x = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(x, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(x - m), axis=-1)) + m[..., 0]
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    picked = jnp.sum(jnp.where(iota == safe[..., None], x, 0.0), axis=-1)
    return lse - picked


def train_loss(cfg: ArchConfig, params: Params, batch: Dict[str, jnp.ndarray],
               *, aux_weight: float = 0.01, remat: bool = False) -> jnp.ndarray:
    if cfg.looped:
        passes, _, aux = _stack(cfg, params, _embed_inputs(cfg, params, batch),
                                mode="train", caches=None, remat=remat)
        labels = batch["labels"]
        exits = [exit_heads(cfg, params, h, labels, t + 1 < len(passes))
                 for t, h in enumerate(passes)]
        return exit_objective(cfg, exits, labels) + aux_weight * aux
    logits, _, aux = forward(cfg, params, batch, mode="train", remat=remat)
    labels = batch["labels"]
    if cfg.frontend == "vision":
        # vision tokens prepended: pad labels with ignore for those positions
        nv = logits.shape[1] - labels.shape[1]
        pad = jnp.full(labels.shape[:1] + (nv,), -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    return cross_entropy(logits, labels) + aux_weight * aux


def decode_step(cfg: ArchConfig, params: Params, token: jnp.ndarray,
                caches: List[Any]) -> Tuple[jnp.ndarray, List[Any]]:
    """serve_step: one token (B, 1) against the caches → (logits, caches)."""
    logits, new_caches, _ = forward(cfg, params, {"token": token},
                                    mode="decode", caches=caches)
    return logits, new_caches


# ---------------------------------------------------------------------------
# scheduling view
# ---------------------------------------------------------------------------


def num_sched_layers(cfg: ArchConfig) -> int:
    return cfg.num_layers + 2


def sched_layer_trees(params: Params) -> List[Any]:
    """Per-sched-layer parameter pytrees (embed, blocks..., final)."""
    return [params["embed"], *params["layers"], params["final"]]


def params_from_sched_layers(trees: List[Any]) -> Params:
    return {"embed": trees[0], "layers": list(trees[1:-1]), "final": trees[-1]}


def tree_bytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def sched_layer_bytes(cfg: ArchConfig, dtype=jnp.float32) -> List[int]:
    """Per-sched-layer parameter bytes, via eval_shape (no allocation)."""
    shapes = jax.eval_shape(
        lambda k: init_params(cfg, k, dtype), jax.random.PRNGKey(0))
    return [tree_bytes(t) for t in sched_layer_trees(shapes)]


def param_count(cfg: ArchConfig) -> int:
    shapes = jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.float32), jax.random.PRNGKey(0))
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
