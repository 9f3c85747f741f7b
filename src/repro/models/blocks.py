"""Per-layer block: init / apply dispatch over the config's layer kinds.

Pre-norm: ``x + mixer(norm1(x))`` then ``x + mlp(norm2(x))``; with
``cfg.sandwich_norm`` each branch's output is normed too (``post_norm1``,
``post_norm2``), as Ouro's blocks are.

Every block is addressable individually — DynaComm schedules transmissions
layer-by-layer, so the model deliberately exposes `init_block` / `apply_block`
instead of a fused scan-only stack.  (A `lax.scan` fast path exists in
model.py for homogeneous stacks.)
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerKind
from repro.models import attention, ssm
from repro.models.layers import apply_mlp, init_mlp, rms_norm
from repro.models.moe import apply_moe, init_moe_params


def init_block(key, cfg: ArchConfig, kind: LayerKind, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p: dict = {"norm1": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.sandwich_norm:
        p["post_norm1"] = jnp.zeros((cfg.d_model,), dtype)
    if kind in ("global_attn", "local_attn"):
        p["attn"] = attention.init_attn_params(ks[0], cfg, dtype)
    elif kind == "mlstm":
        p["mlstm"] = ssm.init_mlstm_params(ks[0], cfg, dtype)
    elif kind == "slstm":
        p["slstm"] = ssm.init_slstm_params(ks[0], cfg, dtype)
    elif kind == "rglru":
        p["rglru"] = ssm.init_rglru_params(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)

    if cfg.d_ff > 0:
        p["norm2"] = jnp.zeros((cfg.d_model,), dtype)
        if cfg.sandwich_norm:
            p["post_norm2"] = jnp.zeros((cfg.d_model,), dtype)
        if cfg.is_moe:
            p["moe"] = init_moe_params(ks[1], cfg, dtype)
        else:
            p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype)
    return p


def init_block_cache(cfg: ArchConfig, kind: LayerKind, batch: int,
                     max_len: int, dtype=jnp.float32):
    if kind == "global_attn":
        return attention.init_cache(cfg, batch, max_len, local=False, dtype=dtype)
    if kind == "local_attn":
        return attention.init_cache(cfg, batch, max_len, local=True, dtype=dtype)
    if kind == "mlstm":
        return ssm.init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return ssm.init_slstm_state(cfg, batch)
    if kind == "rglru":
        return ssm.init_rglru_state(cfg, batch, dtype=dtype)
    raise ValueError(kind)


def apply_block(params, x: jnp.ndarray, cfg: ArchConfig, kind: LayerKind, *,
                mode: str, cache: Any = None
                ) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if kind in ("global_attn", "local_attn"):
        out, new_cache = attention.attention(
            params["attn"], h, cfg, local=(kind == "local_attn"),
            mode=mode, cache=cache)
    elif kind == "mlstm":
        out, new_cache = ssm.apply_mlstm(params["mlstm"], h, cfg, mode=mode,
                                         state=cache)
    elif kind == "slstm":
        out, new_cache = ssm.apply_slstm(params["slstm"], h, cfg, mode=mode,
                                         state=cache)
    elif kind == "rglru":
        out, new_cache = ssm.apply_rglru(params["rglru"], h, cfg, mode=mode,
                                         state=cache)
    else:
        raise ValueError(kind)
    if cfg.sandwich_norm:
        out = rms_norm(out, params["post_norm1"], cfg.norm_eps)
    x = x + out

    if cfg.d_ff > 0:
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        if cfg.is_moe:
            out2, aux = apply_moe(params["moe"], h2, cfg)
        else:
            out2 = apply_mlp(params["mlp"], h2, cfg.activation)
        if cfg.sandwich_norm:
            out2 = rms_norm(out2, params["post_norm2"], cfg.norm_eps)
        x = x + out2
    return x, new_cache, aux
