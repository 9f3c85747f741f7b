"""Launch layer: HLO collective parsing, roofline math, mesh builders,
input specs, and a real (subprocess) dry-run smoke."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import INPUT_SHAPES, get_config
from repro.launch.hlo_analysis import Roofline, collective_bytes, roofline
from repro.launch.specs import decode_specs, input_specs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HLO_SAMPLE = """
HloModule test
ENTRY %main {
  %p0 = bf16[128,256]{1,0} parameter(0)
  %p1 = f32[64]{0} parameter(1)
  %ag = bf16[512,256]{1,0} all-gather(%p0), dimensions={0}
  %ar = f32[64]{0} all-reduce(%p1), to_apply=%add
  %rs = f32[16]{0} reduce-scatter(%p1), dimensions={0}
  %cp = bf16[128,256]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
  %ag2 = bf16[512,256]{1,0} all-gather-start(%p0), dimensions={0}
}
"""


class TestCollectiveBytes:
    def test_parses_operand_bytes(self):
        out = collective_bytes(HLO_SAMPLE)
        p0 = 128 * 256 * 2
        p1 = 64 * 4
        assert out["all-gather"] == 2 * p0      # ag + ag-start
        assert out["all-reduce"] == p1
        assert out["reduce-scatter"] == p1
        assert out["collective-permute"] == p0
        assert out["_counts"]["all-gather"] == 2

    def test_empty(self):
        out = collective_bytes("HloModule empty")
        assert sum(v for k, v in out.items() if not k.startswith("_")) == 0


class TestRoofline:
    def test_terms_and_dominant(self):
        rl = roofline(flops=197e12, hbm_bytes=819e9 / 2,
                      coll={"all-gather": int(50e9 // 4)}, chips=256)
        assert rl.compute_s == pytest.approx(1.0)
        assert rl.memory_s == pytest.approx(0.5)
        assert rl.collective_s == pytest.approx(0.25)
        assert rl.dominant == "compute"
        assert rl.bound_time == pytest.approx(1.0)


class TestSpecs:
    @pytest.mark.parametrize("arch", ["granite-3-2b", "llava-next-34b",
                                      "hubert-xlarge", "xlstm-350m"])
    def test_input_specs_shapes(self, arch):
        cfg = get_config(arch)
        shape = INPUT_SHAPES["train_4k"]
        specs = input_specs(cfg, shape)
        assert "labels" in specs
        if cfg.frontend == "audio":
            assert specs["frames"].shape == (256, 4096, cfg.d_model)
        elif cfg.frontend == "vision":
            nv = min(cfg.num_vision_tokens, 4095)
            assert specs["vision_embeds"].shape == (256, nv, cfg.d_model)
            assert specs["tokens"].shape == (256, 4096 - nv)
        else:
            assert specs["tokens"].shape == (256, 4096)

    def test_decode_specs_cache_sizes(self):
        cfg = get_config("gemma2-2b")
        token, caches = decode_specs(cfg, INPUT_SHAPES["decode_32k"])
        assert token.shape == (128, 1)
        assert len(caches) == cfg.num_layers
        from repro.models.attention import KVCache
        for kind, c in zip(cfg.layer_kinds(), caches):
            assert isinstance(c, KVCache)
            want = cfg.sliding_window if kind == "local_attn" else 32768
            assert c.k.shape == (128, want, cfg.num_kv_heads, cfg.head_dim)

    def test_no_allocation(self):
        """Specs must be ShapeDtypeStructs, never device arrays."""
        cfg = get_config("grok-1-314b")
        specs = input_specs(cfg, INPUT_SHAPES["train_4k"])
        for leaf in jax.tree_util.tree_leaves(specs):
            assert isinstance(leaf, jax.ShapeDtypeStruct)


class TestAnalyticFlopsMatchUnrolledHLO:
    def test_dense_block_flops_within_20pct(self):
        """The §Roofline methodology: analytic per-layer FLOPs track XLA's
        cost analysis on an *unrolled* single-device lowering."""
        import dataclasses
        from repro.models import init_params, train_loss
        from repro.models.profiles import layer_profiles
        from repro.configs.base import InputShape

        cfg = dataclasses.replace(
            get_config("granite-3-2b").reduced(num_layers=2, d_model=256),
            vocab_size=512)
        shape = InputShape("t", 128, 4, "train")
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((4, 128), jnp.int32),
                 "labels": jnp.zeros((4, 128), jnp.int32)}
        lowered = jax.jit(
            lambda p, b: train_loss(cfg, p, b)).lower(params, batch)
        cost = lowered.compile().cost_analysis()
        hlo_flops = float(cost.get("flops", 0))
        analytic_fwd = sum(p.flops_fwd for p in layer_profiles(cfg, shape))
        assert hlo_flops > 0
        # Empirically XLA-CPU cost_analysis attributes ≈ the FORWARD dots
        # only (backward fusion flops unreported) — which is why §Roofline
        # uses max(HLO, analytic).  Assert the forward-side agreement.
        ratio = hlo_flops / analytic_fwd
        assert 0.8 < ratio < 1.3, f"analytic fwd model off: ratio {ratio}"


@pytest.mark.slow
class TestDryRunSubprocess:
    def test_one_combo_compiles_with_512_devices(self):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "granite-moe-1b-a400m", "--shape", "decode_32k"],
            capture_output=True, text=True, env=env, timeout=900, cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "[ok] granite-moe-1b-a400m x decode_32k" in proc.stdout

    def test_skip_policy_is_reported(self):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "hubert-xlarge", "--shape", "long_500k"],
            capture_output=True, text=True, env=env, timeout=300, cwd=REPO)
        assert proc.returncode == 0
        assert "[skip] hubert-xlarge x long_500k" in proc.stdout
