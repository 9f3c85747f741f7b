"""``flops.py`` against counts worked out by hand."""

import json
import os

from chipbench import flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_DENSE = {"hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "num_hidden_layers": 2, "vocab_size": 256,
              "intermediate_size": 128}
TINY_MOE = dict(TINY_DENSE, intermediate_size=32, num_local_experts=4,
                num_experts_per_tok=2)


def test_dense_active_params_by_hand():
    # per layer: wq 64*64 + wk, wv 64*32 each + wo 64*64 = 12288;
    # SwiGLU 3*64*128 = 24576; two layers; tied head 64*256 = 16384
    assert flops.active_params_per_token(TINY_DENSE) == \
        2 * (12288 + 24576) + 16384


def test_moe_counts_routed_experts_not_capacity():
    # router 64*4 = 256 and the top 2 of 4 experts, 3*64*32 each
    per_layer = 12288 + 256 + 2 * 3 * 64 * 32
    assert flops.active_params_per_token(TINY_MOE) == 2 * per_layer + 16384


def test_causal_attention_scores_by_hand():
    # per layer and sequence, forward: Q K^T and P V over the 16*17/2
    # causal pairs, 2 operations a multiply-add, q = 64: 2 * 2 * 64 * 136
    forward = 2 * 2 * 64 * 136
    assert flops.attention_flops_per_sequence(TINY_DENSE, 16) == \
        3 * forward * 2


def test_step_flops_by_hand():
    # batch 2 x seq 16 = 32 tokens
    expected = 6 * 90112 * 32 + 2 * 3 * (2 * 2 * 64 * 136) * 2
    assert flops.train_flops_per_step(TINY_DENSE, 2, 16) == expected == \
        17719296


def test_granite_dense_matches_its_published_size():
    with open(os.path.join(BENCH, "configs", "granite-3-2b.l4.json")) as f:
        cfg = json.load(f)
    # 4 layers of 60.82M matmul weights and the 100.67M tied table: the
    # 344.0M parameters less the 18432 of the norms
    n = flops.active_params_per_token(cfg)
    assert n == 4 * (2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192) \
        + 2048 * 49155
    assert abs(n - 343.94e6) < 0.01e6


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
