"""Operations one training step requires, computed from the shapes.

Read from a configuration file's published keys, not from the program.
Each architecture brings its own count: the file's ``reference`` names
``reference/<name>.py``, whose ``train_flops_per_step(cfg, batch, seq)``
this module calls.  The conventions every count keeps:

* a training step is a forward and a backward pass: 2 operations per
  multiply-accumulate forward and 4 backward, so ``TRAIN_OPS_PER_WEIGHT``
  (6) per weight a token multiplies;
* a token's experts are counted where they are held: where the file
  holds ``held`` of a layer's ``E`` routed experts (the chip's share of a
  deployment), a token multiplies ``top_k * held / E`` experts here, not
  the capacity slots a program may fill; a router keeps its ``E`` outputs;
* the output head is counted once, tied or untied; an embedding lookup
  is a gather and costs no operations;
* attention scores add, per layer and sequence, ``Q K^T`` and ``P V``
  over the causal pairs (:func:`score_flops`), three times over for
  forward and backward.

Norms, softmax and the optimizer are left out, as is any forward that a
program recomputes.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))
#: operations a weight costs per token in a forward and a backward pass
TRAIN_OPS_PER_WEIGHT = 6


def score_flops(qk_width: int, v_width: int, seq: int,
                causal: bool = True) -> int:
    """Forward and backward operations of one layer's attention scores
    over one sequence: ``Q K^T`` contracts ``qk_width`` (heads times the
    query-key head size) and ``P V`` makes ``v_width``, each over the
    ``T (T + 1) / 2`` causal pairs, or all ``T * T`` where not causal."""
    pairs = seq * (seq + 1) // 2 if causal else seq * seq
    forward = 2 * (qk_width + v_width) * pairs
    return 3 * forward


def reference(name: str):
    """The reference module ``reference/<name>.py``."""
    path = os.path.join(HERE, "reference", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def train_flops_per_step(config: Dict, batch: int, seq: int) -> int:
    """Operations of one step of ``batch`` rows of ``seq`` tokens, by the
    count of the reference that the configuration file names."""
    return int(reference(config["reference"]).train_flops_per_step(
        config, batch, seq))
