"""Plain reference of Ouro's looped decoder (ByteDance, arXiv:2510.25741).

Straightforward ``jax.numpy`` in float32, written from the configuration
file's published keys (Hugging Face names), its ``assumed`` lines and
nothing of the program.  Matrix products, norms and rotary embedding are
those of ``reference/granite.py``:

* tokens are looked up in the table, unscaled;
* one pass runs the ``num_hidden_layers`` blocks; each block is pre- and
  post-norm around full causal multi-head attention (rotary over the whole
  head, base ``rope_theta``, scores over the square root of the head size)
  and around a SwiGLU MLP: ``x + n2(attn(n1(x)))``, then
  ``x + n4(mlp(n3(x)))``; RMS norms scale by ``1 + w``, ``w`` held as an
  offset from one;
* the stack runs ``total_ut_steps`` (T) times on the same weights; after
  pass t the state goes through the final norm, ``h_t``, which pass t+1
  takes; pass t's logits are ``h_t`` against the untied head and its
  per-token loss ``l_t`` their cross-entropy; for t < T its exit gate is
  ``lam_t = sigmoid(h_t . gate + gate_bias)``, the last pass's gate is not
  read;
* the exit distribution per token is ``p_t = lam_t prod_{j<t} (1 - lam_j)``
  for t < T and ``p_T = prod_{j<T} (1 - lam_j)``; the loss is the mean over
  tokens of ``sum_t p_t l_t - beta H(p)``, ``beta`` the file's
  ``exit_entropy_weight``.

With ``"reference_remat": true`` in the file, each block application and
each pass's head are rematerialised (``jax.checkpoint``): the numbers are
the same, and plain autodiff over 16 block applications and 4 heads at a
cell's size would not fit one chip.  The tiny configuration that
``tests/test_flops.py`` counts leaves it out, since recomputed products
would count twice.

Weights are drawn from the seed by the initialisation scheme the
configuration states (``init``): the random draws of the program's
documented initialisation, reproduced here and not taken from it.

:func:`train_flops_per_step` is the architecture's operation count, by
the conventions of ``chipbench/flops.py``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops

_granite = flops.reference("granite")
matmul, _norm, _rope = _granite.matmul, _granite._norm, _granite._rope


def _dims(cfg: Dict):
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    if int(cfg["num_key_value_heads"]) != heads:
        raise ValueError("multi-head attention: the reference takes as many "
                         "key/value heads as query heads")
    return d, heads, int(cfg["head_dim"])


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape) * (1.0 / np.sqrt(fan_in))


def init(cfg: Dict, key):
    """The stated initialisation from ``key = PRNGKey(seed)``: layer ``i``
    takes ``split(key, L + 2)[1 + i]``, split in three (attention, MLP,
    spare); the head takes the last key and the gate ``fold_in(last, 1)``;
    weights are unit normals over ``sqrt(fan_in)``, the table is normals
    times 0.02, norms and the gate's bias are zero."""
    d, heads, hd = _dims(cfg)
    layers, vocab = int(cfg["num_hidden_layers"]), int(cfg["vocab_size"])
    ff = int(cfg["intermediate_size"])
    keys = jax.random.split(key, layers + 2)
    zeros = jnp.zeros((d,))
    params = {"embed": {"table": jax.random.normal(keys[0], (vocab, d))
                        * 0.02},
              "layers": [],
              "final": {"norm": zeros,
                        "head": _normal(keys[-1], (d, vocab), d),
                        "gate": _normal(jax.random.fold_in(keys[-1], 1),
                                        (d, 1), d),
                        "gate_bias": jnp.zeros((1,))}}
    for i in range(layers):
        k_attn, k_mlp, _ = jax.random.split(keys[1 + i], 3)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        ku, kd, kg = jax.random.split(k_mlp, 3)
        params["layers"].append({
            "norm1": zeros, "post_norm1": zeros,
            "norm2": zeros, "post_norm2": zeros,
            "attn": {"wq": _normal(kq, (d, heads * hd), d),
                     "wk": _normal(kk, (d, heads * hd), d),
                     "wv": _normal(kv, (d, heads * hd), d),
                     "wo": _normal(ko, (heads * hd, d), heads * hd)},
            "mlp": {"up": _normal(ku, (d, ff), d),
                    "down": _normal(kd, (ff, d), ff),
                    "gate": _normal(kg, (d, ff), d)}})
    return params


def _attention(p, x, cfg, prec):
    b, t, _ = x.shape
    _, heads, hd = _dims(cfg)
    theta = float(cfg["rope_theta"])
    q, k, v = (matmul("btd,df->btf", x, p[w], prec).reshape(b, t, heads, hd)
               for w in ("wq", "wk", "wv"))
    q, k = _rope(q, theta), _rope(k, theta)
    s = matmul("bqhd,bkhd->bhqk", q, k, prec) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = matmul("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, prec)
    return matmul("btf,fd->btd", o.reshape(b, t, heads * hd), p["wo"], prec)


def _block(p, x, cfg, prec):
    eps = float(cfg["rms_norm_eps"])
    x = x + _norm(_attention(p["attn"], _norm(x, p["norm1"], eps), cfg, prec),
                  p["post_norm1"], eps)
    m = p["mlp"]
    h = _norm(x, p["norm2"], eps)
    out = matmul("btf,fd->btd",
                 jax.nn.silu(matmul("btd,df->btf", h, m["gate"], prec))
                 * matmul("btd,df->btf", h, m["up"], prec), m["down"], prec)
    return x + _norm(out, p["post_norm2"], eps)


def _exit(final, h, labels, gate: bool, prec):
    """Pass t's per-token loss and, but for the last pass, its gate's
    logit ``z_t`` (``lam_t = sigmoid(z_t)``)."""
    logits = matmul("btd,dv->btv", h, final["head"], prec)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    loss = jax.nn.logsumexp(logits, axis=-1) - picked
    if not gate:
        return loss, None
    return loss, matmul("btd,do->bto", h, final["gate"], prec)[..., 0] \
        + final["gate_bias"][0]


def loss(cfg: Dict, params, tokens, labels, precision: str = "highest"):
    """The exit objective of one block of rows, in float32.  The exit
    distribution is taken in logarithms, ``log lam = log_sigmoid(z)`` and
    ``log (1 - lam) = log_sigmoid(-z)``: a gate that saturates in float32
    (``lam`` rounds to 1 at ``z`` above about 17) would leave ``p_t`` at 0
    and ``p_t log p_t`` undefined."""
    eps = float(cfg["rms_norm_eps"])
    steps = int(cfg["total_ut_steps"])
    block = partial(_block, cfg=cfg, prec=precision)
    exit_ = partial(_exit, prec=precision)
    if cfg.get("reference_remat"):
        block = jax.checkpoint(block)
        exit_ = jax.checkpoint(exit_, static_argnums=(3,))
    x = params["embed"]["table"][tokens]
    log_stay = jnp.zeros(tokens.shape)          # log prod_{j<t} (1 - lam_j)
    expected = entropy = jnp.zeros(tokens.shape)
    for t in range(steps):
        for p in params["layers"]:
            x = block(p, x)
        x = _norm(x, params["final"]["norm"], eps)
        loss_t, z = exit_(params["final"], x, labels, t + 1 < steps)
        log_p = log_stay
        if z is not None:
            log_p = log_stay + jax.nn.log_sigmoid(z)
            log_stay = log_stay + jax.nn.log_sigmoid(-z)
        p_t = jnp.exp(log_p)
        expected = expected + p_t * loss_t
        entropy = entropy - p_t * log_p
    return jnp.mean(expected - float(cfg["exit_entropy_weight"]) * entropy)


# ---------------------------------------------------------------------------
# the operation count
# ---------------------------------------------------------------------------


def weights_per_pass(cfg: Dict) -> int:
    """Weights a token multiplies in one pass: per layer the projections
    ``4 * d * q`` (as many key/value heads as query heads) and a SwiGLU
    ``3 * d * ff``; the head ``d * V``."""
    d, heads, hd = _dims(cfg)
    per_layer = 4 * d * heads * hd + 3 * d * int(cfg["intermediate_size"])
    return int(cfg["num_hidden_layers"]) * per_layer \
        + d * int(cfg["vocab_size"])


def train_flops_per_step(cfg: Dict, batch: int, seq: int,
                         causal: bool = True) -> int:
    """Operations of one training step: every weight of a pass, the head
    included, once a pass, and the gate's ``d`` in every pass but the last
    (whose gate is not read); the scores of every layer in every pass.
    ``causal=False`` counts the scores over the whole square, as
    :func:`loss` computes them."""
    d, heads, hd = _dims(cfg)
    steps = int(cfg["total_ut_steps"])
    weights = steps * weights_per_pass(cfg) + (steps - 1) * d
    scores = flops.score_flops(heads * hd, heads * hd, seq, causal) \
        * int(cfg["num_hidden_layers"]) * steps
    return flops.TRAIN_OPS_PER_WEIGHT * weights * batch * seq \
        + batch * scores
