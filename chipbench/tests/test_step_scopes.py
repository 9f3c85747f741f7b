"""The join of a trace's operations to the compiled step's scopes, and the
readers of device time by scope, against a hand-made compiled text
(``data/scoped_step.hlo``) and trace (``data/step_scope_trace.json``).

The trace has two devices, the second 10 ns behind the first, and two
steps of 500 ns in a window of 1000 ns.  A step of device 0, in ns from
its start, with the scope of each operation:

* an asynchronous all-gather in flight over [0, 100], its done over
  [80, 90] (``zero.pull.b0``), and over [90, 100] a relayout in the body
  of a loop that reads the gathered buffer, without metadata: pull 100;
* ``%fusion.1`` over [100, 160] (``zero.fwd.L1``) and ``%fusion.2`` over
  [160, 200] (``zero.fwd.L1/moe.dispatch``): fwd 100;
* ``%fusion.3`` over [200, 260] (``zero.bwd.L1/transpose(jvp(
  moe.combine))``) and ``%fusion.4`` over [260, 340] (``zero.bwd.L1``):
  bwd 140, of which 60 MoE movement beside the 40 of the forward;
* an asynchronous all-reduce in flight over [300, 420] and its done over
  [360, 400], without metadata, of the ``zero.push.b0`` fusion that packs
  the gradient; ``%fusion.4`` owns [300, 340] and ``%fusion.5``
  (``zero.opt``) [400, 420] of it: push 60;
* ``%fusion.5`` over [400, 440]: opt 40;
* ``%copy.6`` over [440, 460], a copy of the state without metadata:
  unscoped 20.

Busy 460 ns a step, the sum of the parts.
"""

import os
import types

import pytest

from chipbench import trace as trace_lib
from chipbench.run import read_metrics

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
PARTS = {"pull_ms": 100, "fwd_ms": 100, "bwd_ms": 140, "push_ms": 60,
         "optimizer_ms": 40, "unscoped_ms": 20}
READERS = tuple(PARTS) + ("moe_dispatch_ms",)


def _text(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return f.read()


@pytest.fixture
def trace():
    trace = trace_lib.Trace.from_json(_text("step_scope_trace.json"))
    trace_lib.join_scopes(trace, _text("scoped_step.hlo"))
    return trace


def read(name, trace):
    run = types.SimpleNamespace(trace=trace)
    out = read_metrics(REPO, [{"name": name, "unit": "ms"}], run)
    return out[name]["value"] if name in out else None


def test_join_keeps_the_traced_operations_scopes(trace):
    # an instruction without metadata takes its first scoped operand's
    # path, or its loop's; the copy of the state finds none
    pull = "jit(step)/shard_map/zero.pull.b0/all_gather"
    push = "jit(step)/shard_map/zero.push.b0/pad"
    assert trace.scopes == {
        "%all-gather-start.1": pull, "%all-gather-done.1": pull,
        "%dynamic-update-slice.9": pull,
        "%fusion.1": "jit(step)/shard_map/zero.fwd.L1/exp",
        "%fusion.2":
            "jit(step)/shard_map/zero.fwd.L1/moe.dispatch/scatter-add",
        "%fusion.3": "jit(step)/shard_map/zero.bwd.L1/"
                     "transpose(jvp(moe.combine))/dot_general",
        "%fusion.4": "jit(step)/shard_map/zero.bwd.L1/dot_general",
        "%all-reduce-start.2": push, "%all-reduce-done.2": push,
        "%fusion.5": "jit(step)/shard_map/zero.opt/mul"}


def test_op_names_of_the_whole_text():
    names = trace_lib.op_names(_text("scoped_step.hlo"))
    # the fused computation's root and the unused fusion keep their own
    assert names["%exponential.1"] == "jit(step)/shard_map/zero.fwd.L1/exp"
    assert names["%fusion.7"] == "jit(step)/shard_map/zero.push.b0/pad"
    # the loop, its body and condition, and the tuples around it read the
    # gathered buffer
    for name in ("%tuple.8", "%while.8", "%param.8", "%get-tuple-element.8",
                 "%tuple.9", "%param.9", "%constant.9",
                 "%get-tuple-element.10"):
        assert names[name].endswith("zero.pull.b0/all_gather"), name
    # the ROOT tuple's first operand is the optimizer's
    assert names["%tuple.11"].endswith("zero.opt/mul")
    for name in ("%param.1", "%copy.6"):
        assert name not in names


def test_scope_paths_lose_their_wrappers(trace):
    assert trace_lib.scope_path(trace, "%fusion.3 fusion kOutput f32[8]") == [
        "step", "shard_map", "zero.bwd.L1", "moe.combine", "dot_general"]
    assert trace_lib.scope_path(trace, "%copy.6 copy f32[8]") == []
    assert trace_lib.step_part(["step", "zero.regather.b1", "x"]) == "pull"
    assert trace_lib.step_part(["step", "zero.fwd.L1", "moe.route"]) == "fwd"
    assert trace_lib.step_part(["step", "shard_map"]) == "unscoped"


def test_owned_time_splits_the_busy_time(trace):
    for d in range(2):
        owned = trace_lib.owned_time(trace, d)
        assert sum(owned.values()) == pytest.approx(
            trace_lib.length(trace_lib.busy(trace, d))) == 920
        # the collectives own only what no compute covers
        assert owned["%all-gather-start.1 all-gather-start (f32[2], f32[8])"] \
            == 160
        assert owned["%all-reduce-start.2 all-reduce-start f32[8]"] == 40
        assert owned["%fusion.4 fusion kOutput f32[8]"] == 160


@pytest.mark.parametrize("name", list(PARTS))
def test_part_readers(trace, name):
    # ns a step and device, read in ms
    assert read(name, trace) == pytest.approx(PARTS[name] * 1e-6)


def test_parts_add_up_to_the_busy_time(trace):
    busy = trace_lib.length(trace_lib.busy(trace, 0)) / trace.steps * 1e-6
    assert sum(read(name, trace) for name in PARTS) == pytest.approx(busy)


def test_moe_dispatch_reads_dispatch_and_combine_in_both_passes(trace):
    assert read("moe_dispatch_ms", trace) == pytest.approx(100e-6)


def test_comm_readers_read_the_scoped_step(trace):
    # collectives [0, 100] and [300, 420] a step; [0, 90] of the first
    # (the relayout runs beside its last 10 ns) and [340, 400] of the
    # second overlap no compute
    assert read("comm_ms", trace) == pytest.approx(220e-6)
    assert read("comm_exposed_ms", trace) == pytest.approx(150e-6)


def test_a_reader_finds_nothing_where_no_operation_has_its_scope(trace):
    # one device's step without a wire: no pull or push operation
    for ops in trace.devices + trace.async_ops:
        ops[:] = [o for o in ops if "all-" not in o[0]
                  and "%dynamic-update-slice" not in o[0]]
    assert read("pull_ms", trace) is None
    assert read("push_ms", trace) is None
    assert read("fwd_ms", trace) == pytest.approx(100e-6)
    for ops in trace.devices:
        ops[:] = [o for o in ops if "%copy" not in o[0]
                  and "%fusion.2" not in o[0] and "%fusion.3" not in o[0]]
    assert read("unscoped_ms", trace) is None
    assert read("moe_dispatch_ms", trace) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_scopes(name, trace):
    # a trace read without the compiled step's text
    trace.scopes = {}
    assert read(name, trace) is None
    # the parent's fixtures carry no scopes
    old = trace_lib.Trace.from_json(_text("small_trace.json"))
    assert old.scopes == {} and read(name, old) is None
    trace.devices, trace.async_ops = [], []
    assert read(name, trace) is None
