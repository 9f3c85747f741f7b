"""``flops.py`` and each reference's operation count against counts worked
out by hand, against the counts the accepted benchmark ran with, and
against the matrix products each reference's loss and gradient make."""

import glob
import json
import math
import os

import jax
import jax.extend
import jax.numpy as jnp
import pytest

from chipbench import flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
GRANITE = flops.reference("granite")

TINY_DENSE = {"reference": "granite", "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
              "intermediate_size": 128}
TINY_MOE = dict(TINY_DENSE, intermediate_size=32, num_local_experts=4,
                num_experts_per_tok=2)


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_dense_active_params_by_hand():
    # per layer: wq 64*64 + wk, wv 64*32 each + wo 64*64 = 12288;
    # SwiGLU 3*64*128 = 24576; two layers; tied head 64*256 = 16384
    assert GRANITE.active_params_per_token(TINY_DENSE) == \
        2 * (12288 + 24576) + 16384


def test_moe_counts_routed_experts_not_capacity():
    # router 64*4 = 256 and the top 2 of 4 experts, 3*64*32 each
    per_layer = 12288 + 256 + 2 * 3 * 64 * 32
    assert GRANITE.active_params_per_token(TINY_MOE) == \
        2 * per_layer + 16384


def test_moe_counts_the_experts_held_here():
    # 2 of a published 8 experts held: the router keeps its 8 outputs, a
    # token's top 2 lie here 2 * 2 / 8 = 0.5 times
    cfg = dict(TINY_MOE, num_local_experts=2,
               published={"num_local_experts": 8})
    per_layer = 12288 + 64 * 8 + 0.5 * 3 * 64 * 32
    assert GRANITE.active_params_per_token(cfg) == 2 * per_layer + 16384
    # 32 tokens: 6 operations a weight
    assert flops.train_flops_per_step(cfg, 2, 16) == \
        6 * (2 * per_layer + 16384) * 32 + 2 * 2 * 3 * (2 * 2 * 64 * 136)


def test_causal_attention_scores_by_hand():
    # per layer and sequence, forward: Q K^T and P V over the 16*17/2
    # causal pairs, 2 operations a multiply-add, q = 64: 2 * 2 * 64 * 136
    forward = 2 * 2 * 64 * 136
    assert flops.score_flops(64, 64, 16) == 3 * forward
    # over the whole square: 16 * 16 pairs
    assert flops.score_flops(64, 64, 16, causal=False) == \
        3 * 2 * 2 * 64 * 256
    # a query-key width apart from the value width (latent attention)
    assert flops.score_flops(96, 64, 16) == 3 * 2 * (96 + 64) * 136


def test_step_flops_by_hand():
    # batch 2 x seq 16 = 32 tokens
    expected = 6 * 90112 * 32 + 2 * 3 * (2 * 2 * 64 * 136) * 2
    assert flops.train_flops_per_step(TINY_DENSE, 2, 16) == expected == \
        17719296


def test_granite_dense_matches_its_published_size():
    cfg = _config("granite-3-2b.l4")
    # 4 layers of 60.82M matmul weights and the 100.67M tied table: the
    # 344.0M parameters less the 18432 of the norms
    n = GRANITE.active_params_per_token(cfg)
    assert n == 4 * (2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192) \
        + 2048 * 49155
    assert abs(n - 343.94e6) < 0.01e6


@pytest.mark.parametrize("name,batch,expected", [
    ("granite-3-2b.l4", 8, 8555927175168),
    ("granite-3-2b.l4", 32, 34223708700672),
    ("granite-moe-1b-a400m.l4", 8, 2838075801600)])
def test_config_files_keep_their_counts(name, batch, expected):
    """The integers the one-formula count gave these files before each
    reference brought its own: ``step_mfu`` reads as it did."""
    assert flops.train_flops_per_step(_config(name), batch, 512) == expected


def _dot_flops(jaxpr) -> int:
    """Operations of every ``dot_general`` in a jaxpr, sub-jaxprs
    included (a scan's body counted ``length`` times)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            (_, rc), (_, rb) = eqn.params["dimension_numbers"]
            free = math.prod(n for i, n in enumerate(rhs)
                             if i not in rc and i not in rb)
            total += 2 * math.prod(lhs) * free
        times = eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    total += times * _dot_flops(sub)
    return total


REFERENCES = sorted(os.path.basename(p)[:-3] for p in
                    glob.glob(os.path.join(BENCH, "reference", "*.py")))


@pytest.mark.parametrize("name", REFERENCES)
def test_reference_count_is_its_loss_and_gradient(name):
    """At the reference's tiny configuration (``data/tiny-<name>.json``,
    no routed experts), its count equals the matrix products of its loss
    and gradient, the scores counted over the whole square as the loss
    computes them."""
    path = os.path.join(HERE, "data", f"tiny-{name}.json")
    assert os.path.isfile(path), f"reference {name} has no {path}"
    with open(path) as f:
        cfg = json.load(f)
    model = flops.reference(name)
    batch, seq = 2, 16
    params = jax.eval_shape(lambda k: model.init(cfg, k),
                            jax.random.PRNGKey(0))
    rows = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda p, t, l: model.loss(cfg, p, t, l)))(params, rows, rows)
    assert _dot_flops(jaxpr.jaxpr) == \
        model.train_flops_per_step(cfg, batch, seq, causal=False)


def test_config_files_state_their_cuts():
    """Each configuration file lists the keys it cuts as BENCHMARK.json
    does, states the published value of each, and keeps a departure of
    the program apart from the published keys."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["configs"]:
        with open(os.path.join(os.path.dirname(BENCH), entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == entry["name"]
        assert cfg["reduced"] == entry["reduced"]
        assert set(cfg["reduced"]) == set(cfg["published"])
        assert not set(cfg.get("departures", {})) & set(cfg)
        assert cfg["arch"]["num_layers"] == cfg["num_hidden_layers"]
