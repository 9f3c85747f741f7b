"""A rehearsal of whole runs on the CPU at a tiny size.

The harness's look for a chip is skipped (``require_tpu=False``); the
rest of a run is driven as on the chip: the ring, the runtime, the checked
steps, the window, the reference and the result line.  Numbers from these
runs are CPU numbers and are only checked for their shape.

Then the timed path is broken underneath, once for each fault a training
cell can have, and ``correct`` has to come out false; and the control,
the reference in float8 put in the program's place, has to fail too.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from chipbench import check, run
from chipbench.tests.tiny import make_root

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench")))


def run_cell(root, capsys, cell="tiny-dense.4chip", trace=0, seed=2 ** 31 + 5):
    result = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "0.5", "--trace", str(trace)],
                      root=root, require_tpu=False)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == result
    return result, err


def test_sound_run(root, capsys):
    result, err = run_cell(root, capsys)
    assert result["correct"] is True
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "check"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    # CPU numbers under the cell's end-to-end names; no peak memory here
    assert set(result["metrics"]) == {"tokens_per_s", "step_p95_ms",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 4
    limits = {k: v["limit"] for k, v in result["check"].items()}
    assert set(limits) == set(check.NUMBERS)
    # the compared numbers are the last lines on standard error
    assert err.strip().splitlines()[-3:] == check.lines(
        {k: v["value"] for k, v in result["check"].items()}, limits)


def test_sound_moe_run(root, capsys):
    result, _ = run_cell(root, capsys, cell="tiny-moe.4chip", seed=11)
    assert result["correct"] is True


def test_traced_run(root, capsys):
    result, _ = run_cell(root, capsys, trace=1, seed=12)
    assert result["correct"] is True
    # no device plane on the CPU: every device reader finds nothing
    assert result["metrics"] == {}
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["device"]["window_s"] > 0


def test_refuses_a_platform_without_tpu(root, capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "tiny-dense.4chip", "--seed", "1",
                  "--seconds", "1"], root=root)
    assert "no TPU" in str(exc.value.code)
    assert "{" not in capsys.readouterr().out


def test_refuses_another_chip_count(root, capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "tiny-dense.1chip", "--seed", "1",
                  "--seconds", "1"], root=root, require_tpu=False)
    assert "asks for 1 chips" in str(exc.value.code)
    assert "{" not in capsys.readouterr().out


def test_each_pair_of_config_and_traffic_once():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        work = json.load(f)["workloads"]
    pairs = [(w["config"], w["traffic"]) for w in work]
    assert len(set(pairs)) == len(pairs)


def test_refuses_traffic_drawn_for_other_chips(tmp_path):
    root = make_root(str(tmp_path))
    assert run.load_cell(root, "tiny-dense.4chip").traffic["chips"] == 4
    path = os.path.join(root, "chipbench", "traffic",
                        "tiny-2x16-per-chip.json")
    with open(path) as f:
        traffic = json.load(f)
    with open(path, "w") as f:
        json.dump(dict(traffic, chips=4), f)
    with pytest.raises(run.Refused, match="drawn for 4 chips"):
        run.load_cell(root, "tiny-dense.1chip")


def test_command_fails_without_chip_or_program(tmp_path):
    """The command as the benchmark names it: on this CPU it exits
    non-zero and prints no result, and so it does in a tree that holds
    only the benchmark's own files."""
    only = tmp_path / "only"
    only.mkdir()
    (only / "BENCHMARK.json").write_text(
        open(os.path.join(REPO, "BENCHMARK.json")).read())
    subprocess.run(["cp", "-r", os.path.join(REPO, "chipbench"),
                    str(only / "chipbench")], check=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    for where in (REPO, str(only)):
        proc = subprocess.run(
            [sys.executable, "chipbench/run.py", "--workload",
             "granite-3-2b.l4.zero.1chip", "--seed", "1", "--seconds", "1",
             "--trace", "0"], cwd=where, env=env, capture_output=True,
            text=True, timeout=300)
        assert proc.returncode != 0
        assert "{" not in proc.stdout


def test_compile_cache_follows_the_environment(tmp_path, monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert run.use_compile_cache(str(tmp_path)) == str(tmp_path / "env")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert run.use_compile_cache(str(tmp_path)) == os.path.join(
            str(tmp_path), ".chipbench", "jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# the timed path broken underneath
# ---------------------------------------------------------------------------


def _wrap_step(monkeypatch, wrap):
    from repro.dist.zero import ZeroTrainer
    build = ZeroTrainer.build_train_step

    def patched(self):
        return wrap(build(self))

    monkeypatch.setattr(ZeroTrainer, "build_train_step", patched)


def test_fault_state_unchanged(root, capsys, monkeypatch):
    _wrap_step(monkeypatch, lambda step: lambda state, batch:
               (state, step(state, batch)[1]))
    result, _ = run_cell(root, capsys, seed=21)
    assert result["correct"] is False


def test_fault_half_batch(root, capsys, monkeypatch):
    def half(step):
        def f(state, batch):
            return step(state, {k: v[:v.shape[0] // 2]
                                for k, v in batch.items()})
        return f
    _wrap_step(monkeypatch, half)
    result, _ = run_cell(root, capsys, seed=22)
    assert result["correct"] is False


def test_fault_no_exchange(root, capsys, monkeypatch):
    from repro.dist import zero
    from repro.dist.collectives import flatten_tree

    def local_push(grads, specs, bucket, axis_name):
        me = jax.lax.axis_index(axis_name)
        return {l: jax.lax.dynamic_index_in_dim(
            flatten_tree(grads[l], specs[l]).reshape(
                specs[l].axis_size, -1), me, keepdims=False)
                for l in bucket}

    monkeypatch.setattr(zero, "reduce_scatter_bucket", local_push)
    result, _ = run_cell(root, capsys, seed=23)
    assert result["correct"] is False


def test_fault_loss_altered(root, capsys, monkeypatch):
    _wrap_step(monkeypatch, lambda step: lambda state, batch: (
        lambda out: (out[0], out[1] * 1.01))(step(state, batch)))
    result, _ = run_cell(root, capsys, seed=24)
    assert result["correct"] is False


def test_control_fails(root, capsys, monkeypatch):
    """The reference in float8 in the program's place: the precision
    below the configuration's is not correct."""
    def control(rt, b1):
        cell = run.load_cell(root, "tiny-dense.4chip")
        model = run._load_module(os.path.join(
            root, "chipbench", "reference", "granite.py"), "reference")
        tokens, labels = run.data.make_ring(
            cell.traffic, cell.config["vocab_size"], cell.chips, 25)
        out = check.reference_steps(model, cell.config,
                                    cell.settings["optimizer"], 25,
                                    tokens[:3], labels[:3], cell.chips,
                                    precision="fp8")
        for _ in range(check.CHECK_STEPS):
            rt.fit(1)          # the window continues from the fourth batch
        return {"losses": out["losses"], "grad_norms": out["grad_norms"],
                "final": lambda: out["params"]}

    monkeypatch.setattr(run, "checked_steps", control)
    result, _ = run_cell(root, capsys, seed=25)
    assert result["correct"] is False


def test_reference_follows_the_initialisation(root):
    """The reference draws the weights the program's documented
    initialisation draws, from the seed alone."""
    from repro.models import model as model_lib
    cell = run.load_cell(root, "tiny-moe.4chip")
    model = run._load_module(os.path.join(
        root, "chipbench", "reference", "granite.py"), "reference")
    key = jax.random.PRNGKey(2 ** 31 + 9)
    ours = model.init(cell.config, key)
    theirs = model_lib.init_params(run.arch_config(cell.config), key)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ours)[0],
                            jax.tree_util.tree_leaves(theirs)):
        assert jnp.array_equal(a, b), jax.tree_util.keystr(path)
