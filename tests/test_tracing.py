"""The ZeRO step names its parts for the profiler.

``dist/zero.py`` wraps each bucket's pull and push, each sched layer's
forward and backward, the ZeRO-3 re-pulls and the optimizer in
``jax.named_scope`` (``zero.pull.b{i}``, ``zero.fwd.L{l}``, ...), and
``models/moe.py`` its routing, dispatch, experts and combine (``moe.*``).
The runtime marks the host's data fetch, dispatch and loss read with
``jax.profiler.TraceAnnotation`` spans (``repro.data``,
``repro.dispatch``, ``repro.sync``).  The checks run in a subprocess
(``helpers/tracing_check.py``) on 1 and on 4 forged CPU devices, for the
reduced dense and MoE configs and the dense one under ZeRO-3.  The state
is the parameter leaves; on one device pull, push and re-pull do nothing,
and carry no scope.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
CASES = ("dense", "moe", "dense_zero3")


@pytest.fixture(scope="module", params=(1, 4), ids=("1dev", "4dev"))
def result(request):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "helpers", "tracing_check.py"),
         str(request.param)],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
def test_every_layer_and_bucket_has_its_scope(result, case):
    r = result[case]
    scopes = set(r["scopes"])
    for l in range(r["sched_layers"]):
        assert f"zero.fwd.L{l}" in scopes and f"zero.bwd.L{l}" in scopes
    assert "zero.opt" in scopes
    wire = {s for s in scopes if s.startswith(("zero.pull.", "zero.push."))}
    assert r["layout"] == "leaves"
    if r["devices"] == 1:
        # one device, no wire: pull and push are the leaves themselves
        assert not wire, wire
        return
    for i in range(len(r["forward"])):
        assert f"zero.pull.b{i}" in scopes
    for i in range(len(r["backward"])):
        assert f"zero.push.b{i}" in scopes


def test_regather_scopes_under_zero3(result):
    r = result["dense_zero3"]
    middle = [i for i, b in enumerate(r["backward"])
              if any(0 < l < r["sched_layers"] - 1 for l in b)]
    assert middle
    regathers = {s for s in r["scopes"] if s.startswith("zero.regather.")}
    # on one device the state is the full weights: nothing to re-pull
    assert regathers == (set() if r["devices"] == 1 else
                         {f"zero.regather.b{i}" for i in middle})
    assert not any(s.startswith("zero.regather.")
                   for s in result["dense"]["scopes"])


def test_moe_scopes_only_in_the_moe_step(result):
    moe = {"moe.route", "moe.dispatch", "moe.experts", "moe.combine"}
    assert moe <= set(result["moe"]["scopes"])
    assert not moe & set(result["dense"]["scopes"])


@pytest.mark.parametrize("case", CASES)
def test_a_handful_of_instructions_outside_every_zero_scope(result, case):
    # the step counter, the loss's mean over devices (psum and divide),
    # and under ZeRO-3 the barrier before the re-pulls
    unscoped = result[case]["unscoped"]
    assert len(unscoped) <= 4, unscoped
    assert {op for op, _ in unscoped} <= {"add", "all-reduce", "divide",
                                          "opt-barrier"}, unscoped


@pytest.mark.parametrize("case", CASES)
def test_scopes_are_metadata_only(result, case):
    r = result[case]
    assert r["losses"] == r["plain_losses"]
    assert r["counts"] == r["plain_counts"]


def test_host_spans_once_per_step_in_order(result):
    spans = result["dense"]["host_spans"]
    assert [name for _, name in spans] == \
        ["repro.data", "repro.dispatch", "repro.sync"] * 2
    assert len({line for line, _ in spans}) == 1


def _train(tmp_path, *flags):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "granite-3-2b", "--reduced", "--runtime", "zero", "--batch", "2",
         "--seq", "16", "--log-every", "0", *flags],
        capture_output=True, text=True, env=env, timeout=600, cwd=tmp_path)


def test_train_trace_records_the_units_after_the_first(tmp_path):
    from jax.profiler import ProfileData
    proc = _train(tmp_path, "--steps", "3", "--trace", str(tmp_path / "tr"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "[trace] units 2-3 written under" in proc.stdout
    (path,) = (tmp_path / "tr").rglob("*.xplane.pb")
    names = [e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events]
    assert names.count("repro.dispatch") == 2
    assert names.count("repro.sync") == 2


def test_train_trace_needs_a_unit_after_the_first(tmp_path):
    proc = _train(tmp_path, "--steps", "1", "--trace", str(tmp_path / "tr"))
    assert proc.returncode != 0
    assert "--steps >= 2" in proc.stderr
