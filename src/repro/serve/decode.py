"""Serving: prefill + KV-cache decode with batched requests.

``prefill`` runs the full-sequence forward and returns per-layer caches;
``build_decode_step`` yields the jit-able one-token ``serve_step`` that the
decode dry-run shapes (decode_32k / long_500k) lower on the production mesh.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import model as model_lib


def pad_caches(cfg: ArchConfig, caches: List[Any], max_len: int) -> List[Any]:
    """Grow global-attention KV caches to max_len (decode writes past t)."""
    from repro.models.attention import KVCache

    def grow(c):
        if isinstance(c, KVCache) and c.k.shape[1] < max_len:
            pad = max_len - c.k.shape[1]
            c = KVCache(
                k=jnp.pad(c.k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                v=jnp.pad(c.v, ((0, 0), (0, pad), (0, 0), (0, 0))),
                pos=c.pos)
        return c

    out = []
    for kind, c in zip(cfg.layer_kinds(), caches):
        if kind == "global_attn":          # a looped stack: one a pass
            c = [grow(x) for x in c] if cfg.looped else grow(c)
        out.append(c)
    return out


def prefill(cfg: ArchConfig, params, batch: Dict[str, jnp.ndarray], *,
            max_len: int | None = None) -> Tuple[jnp.ndarray, List[Any]]:
    """Returns (last-position logits, caches sized for max_len decode)."""
    logits, caches, _ = model_lib.forward(cfg, params, batch, mode="prefill",
                                          last_only=True)
    if max_len is not None:
        caches = pad_caches(cfg, caches, max_len)
    return logits, caches


def build_decode_step(cfg: ArchConfig):
    def serve_step(params, token, caches):
        return model_lib.decode_step(cfg, params, token, caches)
    return serve_step


def batched_generate(cfg: ArchConfig, params, prompts: jnp.ndarray, *,
                     max_new_tokens: int, greedy: bool = True,
                     key=None) -> jnp.ndarray:
    """Generate continuations for a batch of same-length prompts."""
    b, t = prompts.shape
    logits, caches = prefill(cfg, params, {"tokens": prompts},
                             max_len=t + max_new_tokens)
    step = jax.jit(build_decode_step(cfg))

    tokens = []
    cur = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    # prefill only cached t tokens; decode continues from position t
    for i in range(max_new_tokens):
        tokens.append(cur)
        logits, caches = step(params, cur, caches)
        if greedy or key is None:
            cur = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            cur = jax.random.categorical(sub, logits[:, -1])[:, None] \
                .astype(jnp.int32)
    return jnp.concatenate(tokens, axis=1)
