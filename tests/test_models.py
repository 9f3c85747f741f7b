"""Per-architecture smoke tests + model-level consistency properties."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHITECTURES, get_config
from repro.data.pipeline import SyntheticText, batch_for
from repro.models import (decode_step, forward, init_caches, init_params,
                          num_sched_layers, param_count, sched_layer_bytes,
                          sched_layer_trees, train_loss)
from repro.models import scanned
from repro.optim import adamw

ALL_ARCHS = sorted(ARCHITECTURES)


def make_batch(cfg, B, T, key):
    if cfg.frontend == "audio":
        return {"frames": jax.random.normal(key, (B, T, cfg.d_model)) * 0.02,
                "labels": jnp.zeros((B, T), jnp.int32)}
    if cfg.frontend == "vision":
        nv = cfg.num_vision_tokens
        return {"tokens": jnp.ones((B, T - nv), jnp.int32),
                "vision_embeds": jax.random.normal(
                    key, (B, nv, cfg.d_model)) * 0.02,
                "labels": jnp.zeros((B, T - nv), jnp.int32)}
    toks = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}


# ---------------------------------------------------------------------------
# (f) per-arch smoke: reduced variant, one forward + one train step on CPU
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ALL_ARCHS)
class TestArchSmoke:
    def test_forward_shapes_and_finite(self, arch):
        cfg = get_config(arch).reduced()
        assert cfg.num_layers == 2 and cfg.d_model <= 512
        if cfg.is_moe:
            assert cfg.num_experts <= 4
        params = init_params(cfg, jax.random.PRNGKey(0))
        B, T = 2, 32
        batch = make_batch(cfg, B, T, jax.random.PRNGKey(1))
        logits, caches, aux = forward(cfg, params, batch, mode="train")
        exp_t = T if cfg.frontend != "vision" else T
        assert logits.shape == (B, exp_t, cfg.vocab_size)
        assert caches is None
        assert bool(jnp.all(jnp.isfinite(logits))), f"{arch}: NaN logits"

    def test_one_train_step(self, arch):
        cfg = get_config(arch).reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw(1e-3)
        opt_state = opt.init(params)
        batch = make_batch(cfg, 2, 32, jax.random.PRNGKey(1))

        @jax.jit
        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: train_loss(cfg, p, batch))(params)
            params, opt_state = opt.update(grads, opt_state, params)
            return params, opt_state, loss

        p1, o1, loss1 = step(params, opt_state, batch)
        _, _, loss2 = step(p1, o1, batch)
        assert np.isfinite(float(loss1)) and np.isfinite(float(loss2))
        assert float(loss2) < float(loss1), f"{arch}: loss did not descend"

    def test_decode_step_or_skip(self, arch):
        cfg = get_config(arch).reduced()
        if cfg.encoder_only:
            pytest.skip("encoder-only: no decode step (documented skip)")
        params = init_params(cfg, jax.random.PRNGKey(0))
        caches = init_caches(cfg, 2, 64)
        logits, new_caches = decode_step(cfg, params,
                                         jnp.ones((2, 1), jnp.int32), caches)
        assert logits.shape == (2, 1, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits)))
        assert len(new_caches) == cfg.num_layers


# ---------------------------------------------------------------------------
# consistency properties
# ---------------------------------------------------------------------------


DECODE_ARCHS = ["granite-3-2b", "gemma2-2b", "gemma3-4b", "xlstm-350m",
                "recurrentgemma-2b", "llava-next-34b", "ouro-2.6b"]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_prefill_decode_matches_full_forward(arch):
    cfg = get_config(arch).reduced()
    if cfg.is_moe:
        cfg = dataclasses.replace(cfg, capacity_factor=100.0)
    from repro.serve.decode import build_decode_step, prefill
    params = init_params(cfg, jax.random.PRNGKey(1))
    B, T, P = 2, 24, 12
    key = jax.random.PRNGKey(2)
    if cfg.frontend == "vision":
        pytest.skip("vision prefill exercised via batch path")
    toks = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    full_logits, _, _ = forward(cfg, params, {"tokens": toks}, mode="train")
    logits, caches = prefill(cfg, params, {"tokens": toks[:, :P]}, max_len=T)
    step = build_decode_step(cfg)
    errs = [float(jnp.max(jnp.abs(logits[:, -1] - full_logits[:, P - 1])))]
    for i in range(P, T):
        logits, caches = step(params, toks[:, i:i + 1], caches)
        errs.append(float(jnp.max(jnp.abs(logits[:, 0] - full_logits[:, i]))))
    assert max(errs) < 5e-4, f"{arch}: decode diverged from full forward"


@pytest.mark.parametrize("arch", ["granite-3-2b", "gemma2-2b", "xlstm-350m",
                                  "recurrentgemma-2b", "grok-1-314b"])
def test_scanned_matches_unrolled(arch):
    cfg = get_config(arch).reduced(num_layers=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 32, jax.random.PRNGKey(1))
    logits_u, _, aux_u = forward(cfg, params, batch, mode="train")
    sp = scanned.stack_layer_params(cfg, params)
    logits_s, _, aux_s = scanned.forward_scanned(cfg, sp, batch, mode="train",
                                                 remat=False)
    np.testing.assert_allclose(np.asarray(logits_u), np.asarray(logits_s),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_u), float(aux_s), rtol=1e-5)


def test_chunked_attention_matches_full():
    from repro.models.attention import _mask_bias, _sdpa, _sdpa_chunked
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, T, H, HKV, hd = 2, 256, 4, 2, 32
    q = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, HKV, hd))
    v = jax.random.normal(ks[2], (B, T, HKV, hd))
    pos = jnp.arange(T)
    for causal, window, cap in [(True, 0, 0.0), (True, 48, 0.0),
                                (True, 0, 30.0), (False, 0, 0.0)]:
        bias = _mask_bias(pos, pos, causal=causal, window=window,
                          dtype=jnp.float32)
        full = _sdpa(q, k, v, bias, 2, cap)
        chk = _sdpa_chunked(q, k, v, n_rep=2, cap=cap, causal=causal,
                            window=window, chunk=64)
        np.testing.assert_allclose(np.asarray(full), np.asarray(chk),
                                   atol=2e-5)


def test_mlstm_chunkwise_matches_parallel():
    from repro.models.ssm import _mlstm_chunkwise, _mlstm_parallel
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    B, H, T, hd = 2, 2, 128, 16
    q, k, v = (jax.random.normal(ks[i], (B, H, T, hd)) for i in range(3))
    ig = jax.random.normal(ks[3], (B, H, T))
    fg = jax.random.normal(ks[4], (B, H, T)) + 2.0
    h_par = _mlstm_parallel(q, k, v, ig, fg)
    h_chk, _ = _mlstm_chunkwise(q, k, v, ig, fg, chunk=32)
    np.testing.assert_allclose(np.asarray(h_par), np.asarray(h_chk),
                               atol=5e-4)


def test_cross_entropy_matches_naive():
    from repro.models.model import cross_entropy
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (4, 8, 33))
    labels = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 33)
    labels = labels.at[0, 0].set(-1)   # ignored position
    got = float(cross_entropy(logits, labels))
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = np.asarray(labels) >= 0
    naive = -np.asarray(logp)[np.arange(4)[:, None], np.arange(8)[None, :],
                              np.maximum(np.asarray(labels), 0)]
    want = float((naive * mask).sum() / mask.sum())
    assert got == pytest.approx(want, rel=1e-6)


# ---------------------------------------------------------------------------
# config exactness (the assigned table) + profiles
# ---------------------------------------------------------------------------


EXPECT = {
    # name: (layers, d_model, heads, kv, d_ff, vocab)
    "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
    "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
    "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
    "gemma3-4b": (34, 2560, 8, 4, 10240, 262144),
    "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
    "gemma-7b": (28, 3072, 16, 16, 24576, 256000),
    "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
    "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
    "gemma2-2b": (26, 2304, 8, 4, 9216, 256000),
    "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
    "ouro-2.6b": (48, 2048, 16, 16, 5632, 49152),
}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_config_matches_assignment(arch):
    cfg = get_config(arch)
    want = EXPECT[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == want
    assert cfg.citation


def test_moe_details():
    g = get_config("granite-moe-1b-a400m")
    assert (g.num_experts, g.top_k) == (32, 8)
    k = get_config("grok-1-314b")
    assert (k.num_experts, k.top_k) == (8, 2)


def test_param_counts_near_model_cards():
    # billions, generous tolerance (embeddings/tying conventions vary)
    targets = {"grok-1-314b": 314, "llava-next-34b": 34, "gemma-7b": 8.5,
               "gemma3-4b": 4, "gemma2-2b": 2.6, "recurrentgemma-2b": 2.7,
               "granite-3-2b": 2.5, "granite-moe-1b-a400m": 1.3,
               "hubert-xlarge": 1.0, "xlstm-350m": 0.45, "ouro-2.6b": 2.6}
    for arch, tgt in targets.items():
        n = param_count(get_config(arch)) / 1e9
        assert abs(n - tgt) / tgt < 0.25, f"{arch}: {n:.2f}B vs {tgt}B"


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_sched_layer_profiles(arch):
    from repro.configs.base import INPUT_SHAPES
    from repro.models.profiles import layer_profiles
    cfg = get_config(arch)
    profs = layer_profiles(cfg, INPUT_SHAPES["train_4k"])
    assert len(profs) == num_sched_layers(cfg)
    assert all(p.flops_fwd >= 0 and p.param_bytes >= 0 for p in profs)
    assert sum(p.flops_fwd for p in profs) > 0
    bytes_ = sched_layer_bytes(cfg)
    assert sum(bytes_) == param_count(cfg) * 4


def test_data_pipeline_deterministic():
    p = SyntheticText(vocab_size=128, seq_len=16, batch_size=4, seed=7)
    b1, b2 = p.batch(3), p.batch(3)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = p.batch(4)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))
