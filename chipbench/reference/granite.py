"""Plain reference of a Granite decoder (dense or sparse experts).

Straightforward ``jax.numpy`` in float32, written from the configuration
file's published keys (Hugging Face names) and nothing of the program.
Where the program departs from the published model (the file's
``departures``), the reference follows the value the program runs:

* tokens are embedded from the table and scaled by
  ``embedding_multiplier``;
* each layer is pre-norm: ``x + attn(norm1(x))``, then
  ``x + mlp(norm2(x))``, each residual branch scaled by
  ``residual_multiplier``; RMS norms scale by ``1 + w`` with ``w`` held
  as an offset from one;
* attention is grouped-query, rotary (half-split rotation, base
  ``rope_theta``), causal, with scores scaled by ``attention_multiplier``;
* the MLP is SwiGLU, ``down(silu(gate x) * up x)``; with
  ``num_local_experts`` it is a routed layer: softmax router, the top
  ``num_experts_per_tok`` experts with their probabilities renormalised,
  at most ``capacity`` assignments per expert, taken in token order and
  then rank order, the rest dropped, and every expert computed on every
  token and masked (no dispatch buffer);
* the router's balance loss is ``E * sum_e f_e * P_e``, with ``f_e`` the
  share of all top-k assignments that chose ``e`` and ``P_e`` its mean
  router probability, weighted by ``router_aux_loss_coef``;
* logits are the final-normed states against the tied table, divided by
  ``logits_scaling``; the loss is the mean cross-entropy.

Weights are drawn from the seed by the initialisation scheme the
configuration states (``init`` below): the random draws of the program's
documented initialisation, reproduced here and not taken from it.

Every matrix product goes through :func:`matmul`, whose precision is the
reference's (``highest``: float32 products) or a control's.

:func:`train_flops_per_step` is the architecture's operation count, by
the conventions of ``chipbench/flops.py``.
"""

from __future__ import annotations

from fractions import Fraction
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _round_e4m3(x):
    """Round float32 to float8 e4m3 (3 mantissa bits, min exponent -6),
    half to even, saturating at 448."""
    x = jnp.clip(x, -E4M3_MAX, E4M3_MAX)
    _, e = jnp.frexp(x)                          # |x| = m * 2**e, m in [.5, 1)
    step = jnp.exp2(jnp.maximum(e - 1, -6).astype(jnp.float32) - 3.0)
    return jnp.round(x / step) * step


def _fp8(x):
    """Per-tensor scaled e4m3 rounding, as fp8 training recipes store a
    matmul input."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return _round_e4m3(x / scale) * scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return jnp.einsum(spec, _fp8(a), _fp8(b), precision=HIGHEST)


def _fp8_fwd(spec, a, b):
    return _fp8_einsum(spec, a, b), (a, b)


def _fp8_bwd(spec, res, ct):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     _fp8(a), _fp8(b))
    return vjp(_fp8(ct))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def matmul(spec: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        return _fp8_einsum(spec, a, b)
    raise ValueError(f"unknown precision {precision!r}")


def setting(cfg: Dict, key: str) -> float:
    """A key's value as run: where the program departs from the published
    model, the file gives the value it runs under ``departures``."""
    departures = cfg.get("departures", {})
    return float(departures[key]["run"] if key in departures else cfg[key])


def _dims(cfg: Dict):
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    return d, heads, int(cfg["num_key_value_heads"]), \
        int(cfg.get("head_dim") or d // heads)


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape) * (1.0 / np.sqrt(fan_in))


def init(cfg: Dict, key):
    """The stated initialisation from ``key = PRNGKey(seed)``: layer ``i``
    takes ``split(key, L + 2)[1 + i]``, split in three (mixer, MLP,
    spare); weights are unit normals over ``sqrt(fan_in)``, the table is
    normals times 0.02, norms are zero (scale one)."""
    d, heads, kv_heads, hd = _dims(cfg)
    layers, vocab = int(cfg["num_hidden_layers"]), int(cfg["vocab_size"])
    ff = int(cfg["intermediate_size"])
    experts = int(cfg.get("num_local_experts") or 0)
    keys = jax.random.split(key, layers + 2)
    params = {"embed": {"table": jax.random.normal(keys[0], (vocab, d))
                        * 0.02},
              "layers": [], "final": {"norm": jnp.zeros((d,))}}
    for i in range(layers):
        k_attn, k_mlp, _ = jax.random.split(keys[1 + i], 3)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        layer = {"norm1": jnp.zeros((d,)), "norm2": jnp.zeros((d,)),
                 "attn": {"wq": _normal(kq, (d, heads * hd), d),
                          "wk": _normal(kk, (d, kv_heads * hd), d),
                          "wv": _normal(kv, (d, kv_heads * hd), d),
                          "wo": _normal(ko, (heads * hd, d), heads * hd)}}
        if experts:
            kr, ku, kd, kg = jax.random.split(k_mlp, 4)

            def stack(k, din, dout):
                return jax.vmap(lambda ke: _normal(ke, (din, dout), din))(
                    jax.random.split(k, experts))
            layer["moe"] = {"router": _normal(kr, (d, experts), d),
                            "up": stack(ku, d, ff), "down": stack(kd, ff, d),
                            "gate": stack(kg, d, ff)}
        else:
            ku, kd, kg = jax.random.split(k_mlp, 3)
            layer["mlp"] = {"up": _normal(ku, (d, ff), d),
                            "down": _normal(kd, (ff, d), ff),
                            "gate": _normal(kg, (d, ff), d)}
        params["layers"].append(layer)
    return params


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, theta):
    """x: (B, T, H, hd); rotate the two halves of each head."""
    hd, t = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, cfg, prec):
    b, t, _ = x.shape
    _, heads, kv_heads, hd = _dims(cfg)
    q = matmul("btd,df->btf", x, p["wq"], prec).reshape(b, t, heads, hd)
    k = matmul("btd,df->btf", x, p["wk"], prec).reshape(b, t, kv_heads, hd)
    v = matmul("btd,df->btf", x, p["wv"], prec).reshape(b, t, kv_heads, hd)
    theta = float(cfg["rope_theta"])
    q, k = _rope(q, theta), _rope(k, theta)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = matmul("bqhd,bkhd->bhqk", q, k, prec) \
        * setting(cfg, "attention_multiplier")
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = matmul("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, prec)
    return matmul("btf,fd->btd", o.reshape(b, t, heads * hd), p["wo"], prec)


def _mlp(p, x, prec):
    g = matmul("btd,df->btf", x, p["gate"], prec)
    u = matmul("btd,df->btf", x, p["up"], prec)
    return matmul("btf,fd->btd", jax.nn.silu(g) * u, p["down"], prec)


def capacity(cfg: Dict, tokens: int) -> int:
    experts, top_k = int(cfg["num_local_experts"]), \
        int(cfg["num_experts_per_tok"])
    share = tokens * top_k * setting(cfg, "capacity_factor") / experts
    return max(int(share), top_k)


def _moe(p, x, cfg, prec):
    """Returns (output, balance loss)."""
    b, t, d = x.shape
    n = b * t
    experts, top_k = int(cfg["num_local_experts"]), \
        int(cfg["num_experts_per_tok"])
    xf = x.reshape(n, d)
    probs = jax.nn.softmax(matmul("nd,de->ne", xf, p["router"], prec), -1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(top_e.reshape(-1), experts)       # (n*k, E)
    earlier = jnp.cumsum(chosen, axis=0) - chosen
    rank = jnp.sum(earlier * chosen, axis=1)                  # in expert
    kept = (rank < capacity(cfg, n)).astype(jnp.float32)
    weight = (top_p.reshape(-1) * kept)[:, None] * chosen      # (n*k, E)
    weight = weight.reshape(n, top_k, experts).sum(axis=1)     # (n, E)
    g = matmul("nd,edf->enf", xf, p["gate"], prec)
    u = matmul("nd,edf->enf", xf, p["up"], prec)
    y = matmul("enf,efd->end", jax.nn.silu(g) * u, p["down"], prec)
    out = matmul("end,ne->nd", y, weight, prec)
    share = jnp.sum(chosen, axis=0) / (n * top_k)
    balance = experts * jnp.sum(share * jnp.mean(probs, axis=0))
    return out.reshape(b, t, d), balance


def loss(cfg: Dict, params, tokens, labels, precision: str = "highest"):
    """Mean cross-entropy plus the weighted balance loss of one block of
    rows, in float32."""
    eps = float(cfg["rms_norm_eps"])
    res = setting(cfg, "residual_multiplier")
    table = params["embed"]["table"]
    x = table[tokens] * setting(cfg, "embedding_multiplier")
    balance = jnp.zeros(())
    for p in params["layers"]:
        x = x + res * _attention(p["attn"], _norm(x, p["norm1"], eps), cfg,
                                 precision)
        h = _norm(x, p["norm2"], eps)
        if "moe" in p:
            out, bal = _moe(p["moe"], h, cfg, precision)
            balance = balance + bal
        else:
            out = _mlp(p["mlp"], h, precision)
        x = x + res * out
    x = _norm(x, params["final"]["norm"], eps)
    logits = matmul("btd,vd->btv", x, table, precision) \
        / setting(cfg, "logits_scaling")
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked) \
        + float(cfg.get("router_aux_loss_coef", 0.0)) * balance


# ---------------------------------------------------------------------------
# the operation count
# ---------------------------------------------------------------------------


def active_params_per_token(cfg: Dict) -> Fraction:
    """Weights a token multiplies: per layer the projections
    ``d*q + 2*d*kv + q*d`` and a SwiGLU ``3*d*ff``, or for routed experts
    the router ``d*E`` and ``top_k * held / E`` experts of ``3*d*ff``
    (``held``: the file's ``num_local_experts``, ``E``: its published
    count); the head ``d*V`` once."""
    d, heads, kv_heads, hd = _dims(cfg)
    q, kv = heads * hd, kv_heads * hd
    ff = int(cfg["intermediate_size"])
    per_layer = Fraction(d * q + 2 * d * kv + q * d)
    held = int(cfg.get("num_local_experts") or 0)
    if held:
        experts = int(cfg.get("published", {}).get("num_local_experts", held))
        top_k = int(cfg["num_experts_per_tok"])
        per_layer += d * experts + Fraction(top_k * held, experts) * 3 * d * ff
    else:
        per_layer += 3 * d * ff
    return int(cfg["num_hidden_layers"]) * per_layer \
        + d * int(cfg["vocab_size"])


def train_flops_per_step(cfg: Dict, batch: int, seq: int,
                         causal: bool = True) -> int:
    """Operations of one training step; ``causal=False`` counts the
    scores over the whole square, as :func:`loss` computes them."""
    _, heads, _, hd = _dims(cfg)
    scores = flops.score_flops(heads * hd, heads * hd, seq, causal) \
        * int(cfg["num_hidden_layers"])
    return int(flops.TRAIN_OPS_PER_WEIGHT * active_params_per_token(cfg)
               * batch * seq) + batch * scores
