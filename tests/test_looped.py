"""A looped stack (Ouro, arXiv:2510.25741): the blocks run T times on one
set of weights, with an exit head and gate after every pass.

On the CPU at a tiny size, with seeded random weights: the model's loss
and gradients against the plain reference of ``chipbench/reference/
ouro.py``; the ZeRO step against ``jax.value_and_grad`` of the model's
loss on one device (``leaves``) and on four (``flat``, in a subprocess);
the step's scopes; and, at T = 1, the programs of a stack run once as they
were before the loop existed.
"""

import dataclasses
import hashlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import get_config
from repro.core import plan_from_decision, random_costs, schedule
from repro.dist.zero import ZeroTrainer
from repro.models import init_params, num_sched_layers, train_loss
from repro.optim import adamw, sgd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: SHA-256 of the lowered programs of stacks run once (granite-3-2b and
#: granite-moe reduced, batch 2 x 16, the dynacomm plan of
#: ``random_costs(seed=0, dt=1e-3)``), as they were before the loop: the
#: one-device ZeRO step and ``value_and_grad`` of ``train_loss``; and the
#: four-device ZeRO-2 and ZeRO-3 steps at batch 8 x 16
#: (``helpers/looped_check.py``)
ONE_PASS = {
    "granite-3-2b": (
        "0f1c802a5c525a01", "6e383125ab4b33cc"),
    "granite-moe-1b-a400m": (
        "fb97203a59973a6e", "5d38ecb31bb96d9b"),
}
ONE_PASS_FOUR_DEVICES = {
    "zero3=False":
        "824bedd55887bf6b4774c8e30f1ddaf86638f1fc121a700469b4b91792484889",
    "zero3=True":
        "ca9faab41b2aa635b9ca394a8bf524944f7a205b68c2e34b7eba8cdab72080a6",
}


def tiny_ouro(loops=2):
    return dataclasses.replace(
        get_config("ouro-2.6b").reduced(d_model=64, vocab=256),
        loop_steps=loops)


def reference_config(cfg):
    """The configuration file's keys for ``reference/ouro.py``."""
    return {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "num_hidden_layers": cfg.num_layers,
            "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "total_ut_steps": cfg.loop_steps,
            "exit_entropy_weight": cfg.exit_entropy_weight}


def make_batch(cfg, rows=2, seq=16, seed=3):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0,
                              cfg.vocab_size)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}


def trainer(cfg, optimizer, devices=1, **kw):
    Ls = num_sched_layers(cfg)
    plan = plan_from_decision(
        *schedule(random_costs(Ls, seed=0, dt=1e-3), "dynacomm"), Ls)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("data",))
    return ZeroTrainer(cfg=cfg, mesh=mesh, plan=plan, optimizer=optimizer,
                       **kw)


@pytest.mark.parametrize("loops", [2, 4])
def test_loss_and_gradients_match_reference(loops):
    """Program and reference on the same weights and batch.  Both are
    float32 on the CPU; the program sums the exit distribution in log
    space, the reference as products, so they agree to float32 rounding
    over a few hundred operations: 2e-5 relative on the loss, and on each
    gradient leaf 1e-4 of its largest entry."""
    from chipbench import flops
    ref = flops.reference("ouro")
    cfg = tiny_ouro(loops)
    params = init_params(cfg, jax.random.PRNGKey(5))
    batch = make_batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: train_loss(cfg, p, batch, aux_weight=0.0)))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(reference_config(cfg), p, batch["tokens"],
                           batch["labels"])))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-5)
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    want = jax.tree_util.tree_flatten_with_path(ref_grads)[0]
    assert [p for p, _ in got] == [p for p, _ in want]
    for (path, g), (_, r) in zip(got, want):
        scale = float(jnp.max(jnp.abs(r)))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=0,
                                   atol=1e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_one_device_step_matches_value_and_grad():
    """The ZeRO step on one device (the state as parameter leaves): each
    block's pulled weights serve both passes and its gradient is their
    sum.  SGD, so the update is the gradient; float32 rounding of a
    differently ordered sum."""
    cfg = tiny_ouro()
    tr = trainer(cfg, sgd(0.5), aux_weight=0.0)
    assert tr.layout == "leaves"
    state = tr.init_state(jax.random.PRNGKey(0))
    params = tr.params_from_state(state)
    batch = make_batch(cfg)
    state, loss = jax.jit(tr.build_train_step())(state, batch)
    ref_loss, grads = jax.jit(jax.value_and_grad(
        lambda p: train_loss(cfg, p, batch, aux_weight=0.0)))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    want = jax.tree_util.tree_map(lambda p, g: p - 0.5 * g, params, grads)
    for a, b in zip(jax.tree_util.tree_leaves(tr.params_from_state(state)),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_step_scopes_name_every_pass():
    """Each block application runs under ``zero.fwd.L{l}/loop.t{t}`` or
    ``zero.bwd.L{l}/loop.t{t}``, the exits and the objective under
    ``loop.exit`` in the head's layer; the ``zero.*`` scope outermost."""
    cfg = tiny_ouro()
    tr = trainer(cfg, adamw(1e-3), aux_weight=0.0)
    text = jax.jit(tr.build_train_step()).lower(
        tr.state_structs(), make_batch(cfg)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    Ls = num_sched_layers(cfg)
    for t in (1, 2):
        for l in range(1, Ls - 1):
            for part in ("fwd", "bwd"):
                assert any(f"zero.{part}.L{l}/loop.t{t}/" in n
                           for n in names), (part, l, t)
    for part in ("fwd", "bwd"):
        assert any(f"zero.{part}.L{Ls - 1}/loop.exit/" in n for n in names)
    scoped = [n for n in names if "loop." in n]
    assert all(re.search(r"/zero\.(fwd|bwd)\.L\d+/loop\.", n)
               for n in scoped), scoped


@pytest.mark.parametrize("arch", sorted(ONE_PASS))
def test_one_pass_programs_are_unchanged(arch):
    """At T = 1 with no sandwich norms the loop adds nothing: the one-device
    ZeRO step and the model's loss and gradient lower to the programs they
    were before it (``ONE_PASS``), so their results are bit-identical."""
    cfg = get_config(arch).reduced()
    assert cfg.loop_steps == 1 and not cfg.sandwich_norm
    tr = trainer(cfg, adamw(1e-3))
    shapes = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32),
              "labels": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    step = jax.jit(tr.build_train_step()).lower(
        tr.state_structs(), shapes).as_text()
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    loss = jax.jit(jax.value_and_grad(
        lambda p, b: train_loss(cfg, p, b))).lower(params, shapes).as_text()
    assert tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                 for t in (step, loss)) == ONE_PASS[arch]


def test_one_pass_ouro_runs_as_a_dense_stack():
    """Ouro at T = 1 without sandwich norms is a plain decoder: the same
    lowered step as a configuration that never heard of the loop."""
    ouro = dataclasses.replace(tiny_ouro(1), sandwich_norm=False)
    plain = dataclasses.replace(ouro, name="plain", loop_steps=1,
                                exit_entropy_weight=0.0)
    texts = [jax.jit(trainer(c, adamw(1e-3)).build_train_step()).lower(
        trainer(c, adamw(1e-3)).state_structs(), make_batch(c)).as_text()
        for c in (ouro, plain)]
    assert texts[0] == texts[1]
    assert "gate" not in init_params(ouro, jax.random.PRNGKey(0))["final"]


@pytest.mark.slow
class TestFourDevices:
    @pytest.fixture(scope="class")
    def result(self):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tests", "helpers",
                                          "looped_check.py")],
            capture_output=True, text=True, env=env, timeout=1200)
        assert proc.returncode == 0, proc.stderr[-3000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def test_flat_step_matches_value_and_grad(self, result):
        r = result["looped"]
        assert r["layout"] == "flat"
        assert r["loss"] == pytest.approx(r["ref_loss"], rel=1e-6)
        assert r["param_gap"] < 1e-6

    def test_one_push_per_backward_bucket(self, result):
        r = result["looped"]
        assert r["ag"] == r["fwd_buckets"]
        assert r["rs"] == r["bwd_buckets"]

    def test_one_pass_programs_are_unchanged(self, result):
        assert result["one_pass_sha256"] == ONE_PASS_FOUR_DEVICES
