"""The comparison that decides ``correct`` for a training cell.

The program trains its first ``CHECK_STEPS`` steps through the window's
own call and feed; the plain reference (``reference/<name>.py``, float32
at highest precision) follows the same three steps from the same seed and
batches.  Three numbers are compared, each against the cell's limit:

* ``loss_gap``: over the three steps, the largest relative gap between
  the program's loss and the reference's;
* ``grad_gap``: the first gradient as the optimizer received it, worked
  out from the program's Adam state after one step (``m / (1 - b1)``),
  leaf by leaf: the gap between the program's norm and the reference's,
  over the larger of the reference's norm of that leaf and of the median
  leaf; the worst leaf;
* ``update_gap``: the same for each leaf's change over the three steps.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's move under Adam by round-off alone and are left out.

The reference runs data parallel as the program does: each chip's rows
form a block, the loss and gradient are the means over the blocks.  It
runs after the program's state is freed, the blocks in turn on the first
device, so that every cell's reference is one program (one entry of the
compile cache, shared by the cells of a configuration).
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

CHECK_STEPS = 3
#: a leaf's reference gradient under this share of the median leaf's
#: counts as nought to rounding (its change is not compared)
STILL_LEAF = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "update_gap")


def norms(tree) -> Dict[str, jnp.ndarray]:
    """Norm of every leaf, keyed by its path (traceable)."""
    return {jax.tree_util.keystr(p):
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def leaf_norms(tree) -> Dict[str, float]:
    """Norm of every leaf, keyed by its path, on the host."""
    return {k: float(v) for k, v in jax.jit(norms)(tree).items()}


def diff_norms(after, before) -> Dict[str, float]:
    """Norm of ``after - before`` leaf by leaf (trees of one structure)."""
    flat_a, _ = jax.tree_util.tree_flatten_with_path(after)
    flat_b = jax.tree_util.tree_leaves(before)
    fn = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    return {jax.tree_util.keystr(p): float(fn(a, b))
            for (p, a), b in zip(flat_a, flat_b)}


def adam(opt: Dict):
    """AdamW as the cell states it, on whole float32 trees."""
    b1, b2, eps, lr = (float(opt[k]) for k in ("b1", "b2", "eps", "lr"))
    wd = float(opt.get("weight_decay", 0.0))

    def update(params, grads, m, v, step):
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m,
                                   grads)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v,
                                   grads)
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * p), params, m, v)
        return params, m, v

    return jax.jit(update, static_argnums=4)


def reference_steps(model, cfg: Dict, opt: Dict, seed: int, tokens, labels,
                    blocks: int, precision: str = "highest",
                    rows: Optional[Callable[[int], slice]] = None):
    """The reference's first ``CHECK_STEPS`` steps.

    ``tokens``/``labels``: ``(steps, batch, seq)`` host arrays.  The batch
    is cut into ``blocks`` blocks of rows (the chips); ``rows(block_size)``
    narrows every block to a slice of its rows (a fault that leaves rows
    out).  Returns the losses, the first gradient's leaf norms, and the
    initial and final parameters (on the device)."""
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: model.loss(cfg, p, t, l, precision)))
    params0 = jax.jit(model.init, static_argnums=0)(
        _frozen(cfg), jax.device_put(jax.random.PRNGKey(seed),
                                     jax.local_devices()[0]))
    params = params0
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    m, v = zeros, zeros
    update = adam(opt)
    losses, first = [], None
    batch = tokens.shape[1]
    size = batch // blocks
    keep = rows(size) if rows is not None else slice(0, size)
    home = jax.local_devices()[0]
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    for step in range(CHECK_STEPS):
        loss, grads = 0.0, None
        for b in range(blocks):
            sl = slice(b * size, (b + 1) * size)
            value, g = grad_fn(params,
                               jax.device_put(tokens[step, sl][keep], home),
                               jax.device_put(labels[step, sl][keep], home))
            loss += float(value)
            grads = g if grads is None else add(grads, g)
        losses.append(loss / blocks)
        grads = jax.tree_util.tree_map(lambda g: g / blocks, grads)
        if first is None:
            first = leaf_norms(grads)
        params, m, v = update(params, grads, m, v, step + 1)
        del grads
    return {"losses": losses, "grad_norms": first, "params0": params0,
            "params": params}


class _frozen(dict):
    """A configuration dict that can be a static jit argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items(), key=lambda kv: kv[0])))


def _median(values: Sequence[float]) -> float:
    return float(np.median(np.asarray(values, np.float64)))


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   leaves: Sequence[str]) -> float:
    med = _median([ref[k] for k in leaves])
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves]
    return max(gaps) if gaps else float("nan")


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers from the program's readings (``losses``,
    ``grad_norms``, ``change_norms``) and the reference's run, whose
    ``change_norms`` have been filled in."""
    losses = [abs(p - r) / abs(r) for p, r in zip(prog["losses"],
                                                  ref["losses"])]
    if len(prog["losses"]) != len(ref["losses"]) or \
            not all(math.isfinite(x) for x in prog["losses"]):
        losses.append(float("inf"))
    leaves = sorted(ref["grad_norms"])
    if sorted(prog["grad_norms"]) != leaves:
        return {k: float("inf") for k in NUMBERS}
    med = _median([ref["grad_norms"][k] for k in leaves])
    moving = [k for k in leaves if ref["grad_norms"][k] >= STILL_LEAF * med]
    return {"loss_gap": max(losses),
            "grad_gap": worst_leaf_gap(prog["grad_norms"],
                                       ref["grad_norms"], leaves),
            "update_gap": worst_leaf_gap(prog["change_norms"],
                                         ref["change_norms"], moving)}


def change_norms(final, ref: Dict) -> Dict[str, float]:
    """Per-leaf norm of ``final - ref['params0']``, ``final`` the program's
    parameter tree after the checked steps."""
    if len(jax.tree_util.tree_leaves(final)) != \
            len(jax.tree_util.tree_leaves(ref["params0"])):
        return {}
    return diff_norms(final, ref["params0"])


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(math.isfinite(values[k]) and values[k] <= limits[k]
               for k in limits)


def lines(values: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    return [f"check {k} {values[k]!r} limit {limits[k]!r}" for k in limits]
