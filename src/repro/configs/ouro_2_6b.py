"""ouro-2.6b [hf:ByteDance/Ouro-2.6B, arXiv:2510.25741] — looped dense
stack: 48 layers run 4 times on one set of weights, sandwich norms, an
exit head and gate after every pass."""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="ouro-2.6b",
    family="dense",
    citation="hf:ByteDance/Ouro-2.6B",
    num_layers=48,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    d_ff=5632,
    vocab_size=49152,
    activation="silu",
    gated_mlp=True,
    rope_theta=1e6,
    norm_eps=1e-6,
    tie_embeddings=False,
    embed_scale=False,
    loop_steps=4,
    sandwich_norm=True,
    exit_entropy_weight=0.1,
)
