"""Mixture-of-Experts FFN with capacity-bounded scatter dispatch.

Top-k routing → position-in-expert via cumulative counts → scatter tokens
into an ``(E, C, d)`` dispatch buffer → batched per-expert (gated) FFN →
gather + weighted combine.  FLOPs are proportional to *active* parameters
(E·C ≈ tokens·top_k·capacity_factor), not total experts, so the roofline's
MODEL_FLOPS = 6·N_active·D comparison is honest.

Tokens beyond an expert's capacity are dropped (their combine weight is
zero) — standard capacity-factor semantics.

The four stages run under the profiler scopes ``moe.route``,
``moe.dispatch``, ``moe.experts`` and ``moe.combine``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import activation_fn, dense, init_dense


def expert_capacity(num_tokens: int, cfg: ArchConfig) -> int:
    cap = int(num_tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(cap, cfg.top_k)


def init_moe_params(key, cfg: ArchConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    def expert_stack(k, din, dout):
        keys = jax.random.split(k, e)
        return jnp.stack([init_dense(keys[i], din, dout, dtype) for i in range(e)])
    p = {
        "router": init_dense(ks[0], d, e, dtype),
        "up": expert_stack(ks[1], d, f),
        "down": expert_stack(ks[2], f, d),
    }
    if cfg.gated_mlp:
        p["gate"] = expert_stack(ks[3], d, f)
    return p


def router_load_balance_loss(probs: jnp.ndarray, expert_idx: jnp.ndarray,
                             num_experts: int) -> jnp.ndarray:
    """Switch-style auxiliary loss: E * Σ_e fraction_e · mean_prob_e."""
    counts = jnp.sum(jax.nn.one_hot(expert_idx, num_experts), axis=(0, 1))
    frac = counts / jnp.maximum(jnp.sum(counts), 1.0)
    mean_prob = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(frac * mean_prob)


def apply_moe(params, x: jnp.ndarray, cfg: ArchConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, d) → (output, aux_loss)."""
    b, t, d = x.shape
    n = b * t
    e, k = cfg.num_experts, cfg.top_k
    cap = expert_capacity(n, cfg)
    xf = x.reshape(n, d)

    with jax.named_scope("moe.route"):
        logits = dense(xf, params["router"]).astype(jnp.float32)   # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)                      # (N, k)
        top_p = (top_p / jnp.sum(top_p, axis=-1, keepdims=True)
                 ).astype(x.dtype)

    with jax.named_scope("moe.dispatch"):
        # position within each expert, assignment-major order
        flat_e = top_e.reshape(-1)                                  # (N*k,)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)         # (N*k, E)
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot              # exclusive
        pos = jnp.sum(pos_in_e * onehot, axis=1)                    # (N*k,)
        keep = pos < cap
        slot = flat_e * cap + jnp.where(keep, pos, 0)               # (N*k,)

        # scatter tokens into the dispatch buffer (dropped tokens write
        # nowhere)
        buf = jnp.zeros((e * cap, d), x.dtype)
        src = jnp.repeat(xf, k, axis=0) * keep[:, None].astype(x.dtype)
        buf = buf.at[slot].add(jnp.where(keep[:, None], src, 0.0))
        buf = buf.reshape(e, cap, d)

    with jax.named_scope("moe.experts"):
        # batched per-expert gated FFN
        act = activation_fn(cfg.activation)
        up = jnp.einsum("ecd,edf->ecf", buf, params["up"].astype(x.dtype))
        if "gate" in params:
            up = act(jnp.einsum("ecd,edf->ecf", buf,
                                params["gate"].astype(x.dtype))) * up
        else:
            up = act(up)
        out_buf = jnp.einsum("ecf,efd->ecd", up,
                             params["down"].astype(x.dtype))
        out_buf = out_buf.reshape(e * cap, d)

    with jax.named_scope("moe.combine"):
        # gather back and combine with routing weights (dropped → weight 0)
        gathered = out_buf[slot]                                    # (N*k, d)
        w = top_p.reshape(-1) * keep.astype(x.dtype)
        combined = jnp.sum((gathered * w[:, None]).reshape(n, k, d), axis=1)

    aux = router_load_balance_loss(probs, top_e, e)
    return combined.reshape(b, t, d), aux
