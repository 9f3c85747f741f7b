"""The traffic generator: a ring of distinct training batches from a seed.

One general generator reads a traffic file (``traffic/<mix>.json``):

* ``batch_per_chip``, ``seq``: rows a step on each chip (the global
  batch is this times the chips), and tokens per row;
* ``zipf_exponent``: token ``r`` (0-based) is drawn with probability
  proportional to ``1 / (r + 1) ** zipf_exponent``;
* ``labels``: ``"permuted"`` gives each token a fixed successor,
  ``label = perm[token]`` with ``perm`` a permutation drawn from the seed,
  so a model can learn the mapping;
* ``ring``: how many distinct batches are drawn; step ``i`` trains on
  batch ``i % ring``.
* ``chips`` (optional): the chips the mix is drawn for; a cell on
  another number of chips is refused.

The distribution and the label rule are those of the program's
``SyntheticText`` stream; the whole ring is drawn at once, by inverse-CDF
sampling, before any timing starts.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def global_batch(traffic: Dict, chips: int) -> int:
    return int(traffic["batch_per_chip"]) * chips


def make_ring(traffic: Dict, vocab: int, chips: int, seed: int):
    """``(tokens, labels)``, each int32 of shape ``(ring, batch, seq)``."""
    if traffic.get("labels", "permuted") != "permuted":
        raise ValueError(f"unknown label rule {traffic['labels']!r}")
    ring, seq = int(traffic["ring"]), int(traffic["seq"])
    batch = global_batch(traffic, chips)
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) \
        ** float(traffic["zipf_exponent"])
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    rng = np.random.default_rng([seed, 0])
    u = rng.random((ring, batch, seq))
    tokens = np.minimum(np.searchsorted(cdf, u, side="right"),
                        vocab - 1).astype(np.int32)
    perm = np.random.default_rng([seed, 1]).permutation(vocab)
    labels = perm[tokens].astype(np.int32)
    return tokens, labels
