"""The Ouro cell's pieces on the CPU: a tiny looped configuration run
whole through the harness under the real cell's limits, the fp8 control
failing them, the reference's initialisation against the program's, and
the loop readers on a hand-made trace.

The tiny cell is ``tiny-ouro.4chip``: the CPU here has four virtual
devices, and a cell runs on as many chips as JAX sees, so the tiny cell
takes four, as ``tiny-moe.4chip`` stands for the one-chip MoE cell.
"""

import json
import os
import shutil
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import check, run
from chipbench import trace as trace_lib
from chipbench.tests.tiny import make_root

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "tiny-ouro.4chip"
REAL_CELL = "ouro-2.6b.l4.zero.1chip"
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 256}
TINY_ARCH = {"num_layers": 2, "d_model": 64, "num_heads": 4,
             "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
             "vocab_size": 256}
READERS = ("loop_stack_ms", "loop_exit_ms")


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``make_root``'s tree with a tiny Ouro configuration (every key of the
    real file, the widths shrunk, T = 4 kept) and its cell, which checks
    against the real cell's limits."""
    dest = make_root(str(tmp_path_factory.mktemp("bench")))
    here = os.path.join(dest, "chipbench")
    cfg = _load(os.path.join(here, "configs", "ouro-2.6b.l4.json"))
    cfg.update(TINY, name="tiny-ouro")
    cfg["arch"].update(TINY_ARCH)
    _dump(cfg, os.path.join(here, "configs", "tiny-ouro.json"))
    shutil.copy(os.path.join(here, "cells", REAL_CELL + ".json"),
                os.path.join(here, "cells", CELL + ".json"))
    bench = _load(os.path.join(dest, "BENCHMARK.json"))
    bench["configs"].append({"name": "tiny-ouro",
                             "file": "chipbench/configs/tiny-ouro.json",
                             "reduced": cfg["reduced"]})
    bench["workloads"].append({"name": CELL, "config": "tiny-ouro",
                               "traffic": "tiny-8x16-dp4", "chips": 4,
                               "why": "a tiny stand-in for the CPU"})
    for metric in bench["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"] = [CELL]
    _dump(bench, os.path.join(dest, "BENCHMARK.json"))
    return dest


def run_cell(root, capsys, trace=0, seed=2 ** 31 + 17):
    result = run.main(["--workload", CELL, "--seed", str(seed),
                       "--seconds", "0.5", "--trace", str(trace)],
                      root=root, require_tpu=False)
    out, _ = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == result
    return result


def test_limits_are_the_cells(root):
    cell = run.load_cell(root, CELL)
    assert cell.settings == _load(os.path.join(
        REPO, "chipbench", "cells", REAL_CELL + ".json"))
    assert cell.config["total_ut_steps"] == cell.config["arch"]["loop_steps"]


def test_sound_run(root, capsys):
    result = run_cell(root, capsys)
    assert result["correct"] is True, result["check"]
    assert set(result["metrics"]) == {"tokens_per_s", "step_p95_ms",
                                      "setup_s"}


def test_traced_run(root, capsys):
    result = run_cell(root, capsys, trace=1, seed=31)
    assert result["correct"] is True, result["check"]
    # no device plane on the CPU: the loop readers find nothing
    assert not set(READERS) & set(result["metrics"])


def test_control_fails(root, capsys, monkeypatch):
    """The reference in float8 in the program's place is not correct."""
    def control(rt, b1):
        cell = run.load_cell(root, CELL)
        model = run.flops.reference("ouro")
        tokens, labels = run.data.make_ring(
            cell.traffic, cell.config["vocab_size"], cell.chips, 25)
        out = check.reference_steps(model, cell.config,
                                    cell.settings["optimizer"], 25,
                                    tokens[:3], labels[:3], cell.chips,
                                    precision="fp8")
        for _ in range(check.CHECK_STEPS):
            rt.fit(1)
        return {"losses": out["losses"], "grad_norms": out["grad_norms"],
                "final": lambda: out["params"]}

    monkeypatch.setattr(run, "checked_steps", control)
    assert run_cell(root, capsys, seed=25)["correct"] is False


def test_reference_follows_the_initialisation(root):
    from repro.models import model as model_lib
    cell = run.load_cell(root, CELL)
    model = run.flops.reference("ouro")
    key = jax.random.PRNGKey(2 ** 31 + 9)
    ours = model.init(cell.config, key)
    theirs = model_lib.init_params(run.arch_config(cell.config), key)
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(theirs)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ours)[0],
                            jax.tree_util.tree_leaves(theirs)):
        assert jnp.array_equal(a, b), jax.tree_util.keystr(path)


def test_loop_readers_on_a_handmade_trace():
    """One device, one step of 1000 ns: a block of pass 2 in the forward
    (100 ns) and of pass 1 in the backward (250), an exit in each pass
    (50 and 120), the optimizer (80), the embedding (30)."""
    step = "jit(step)/shard_map/"
    scopes = {"%fusion.1": step + "zero.fwd.L1/loop.t2/dot_general",
              "%fusion.2": step + "zero.fwd.L3/loop.exit/exp",
              "%fusion.3": step + "zero.bwd.L3/loop.exit/transpose(jvp("
                                  "dot_general))",
              "%fusion.4": step + "zero.bwd.L2/loop.t1/transpose(jvp("
                                  "dot_general))",
              "%fusion.5": step + "zero.opt/mul",
              "%fusion.6": step + "zero.fwd.L0/gather"}
    ops = [("%fusion.1 fusion", 0, 100), ("%fusion.2 fusion", 100, 150),
           ("%fusion.3 fusion", 150, 270), ("%fusion.4 fusion", 270, 520),
           ("%fusion.5 fusion", 520, 600), ("%fusion.6 fusion", 600, 630)]
    trace = trace_lib.Trace(window=(0, 1000), steps=1, devices=[ops],
                            async_ops=[[]], host=[], scopes=scopes)
    values = run.read_metrics(REPO, [{"name": n, "unit": "ms"}
                                     for n in READERS],
                              types.SimpleNamespace(trace=trace))
    assert values["loop_stack_ms"]["value"] == pytest.approx(350e-6)
    assert values["loop_exit_ms"]["value"] == pytest.approx(170e-6)
    # a step with no loop scopes (a stack run once) reads nothing
    trace.scopes = {k: v.replace("/loop.t2", "").replace("/loop.t1", "")
                    .replace("/loop.exit", "") for k, v in scopes.items()}
    assert run.read_metrics(REPO, [{"name": n, "unit": "ms"}
                                   for n in READERS],
                            types.SimpleNamespace(trace=trace)) == {}
