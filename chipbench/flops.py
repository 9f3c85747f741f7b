"""Operations one training step requires, computed from the shapes.

Read from a configuration file's published keys, not from the program.
A training step is a forward and a backward pass: 2 operations per
multiply-accumulate forward and 4 backward, so ``6 * N_active`` per token
for the weights, where ``N_active`` counts each weight a token multiplies:

* attention projections ``d*q + 2*d*kv + q*d`` per layer;
* a gated MLP ``3*d*ff`` per layer, or for sparse experts the router
  ``d*E`` plus ``top_k * 3*d*f``: the experts a token is routed to, not
  the capacity slots a program may fill;
* the output head ``d*V`` once (a tied table is counted as the head; the
  embedding lookup is a gather and costs no operations).

Attention scores add, per layer and sequence, ``2*q*T*(T+1)/2`` for
``Q K^T`` and as much for ``P V`` under the causal mask, three times over
for forward and backward.  Norms, softmax and the optimizer are left out,
as is any forward that a program recomputes.
"""

from __future__ import annotations

from typing import Dict


def _dims(cfg: Dict):
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    head_dim = int(cfg.get("head_dim") or d // heads)
    return d, heads * head_dim, int(cfg["num_key_value_heads"]) * head_dim


def active_params_per_token(cfg: Dict) -> int:
    d, q, kv = _dims(cfg)
    per_layer = d * q + 2 * d * kv + q * d
    experts = int(cfg.get("num_local_experts") or 0)
    if experts:
        top_k = int(cfg["num_experts_per_tok"])
        per_layer += d * experts + top_k * 3 * d * int(cfg["intermediate_size"])
    else:
        per_layer += 3 * d * int(cfg["intermediate_size"])
    return int(cfg["num_hidden_layers"]) * per_layer \
        + d * int(cfg["vocab_size"])


def attention_flops_per_sequence(cfg: Dict, seq: int) -> int:
    """Forward and backward score operations of one causal sequence."""
    _, q, _ = _dims(cfg)
    forward = 2 * (2 * q * seq * (seq + 1) // 2)
    return 3 * forward * int(cfg["num_hidden_layers"])


def train_flops_per_step(cfg: Dict, batch: int, seq: int) -> int:
    return (6 * active_params_per_token(cfg) * batch * seq
            + batch * attention_flops_per_sequence(cfg, seq))
