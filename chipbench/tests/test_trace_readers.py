"""Each per-layer reader against a small trace whose numbers are worked
out by hand (``data/small_trace.json``: two devices, two steps, a window
of 1000 ns).

Device 0 runs fusions over [0, 300] and [250, 500], waits in an
all-gather-done over [600, 650], and has an asynchronous all-gather in
flight over [400, 700]: busy [0, 700], collectives [400, 700], of which
[500, 700] overlap no other operation.  Device 1 runs a fusion over
[100, 400] and an asynchronous all-reduce over [300, 900] (its copy over
the whole window is no collective and no operation on the compute line):
busy [100, 900], collectives [300, 900], exposed [400, 900].
"""

import os
import types

import pytest

from chipbench import trace as trace_lib
from chipbench.run import read_metrics

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture
def run():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        trace = trace_lib.Trace.from_json(f.read())
    return types.SimpleNamespace(trace=trace, chips=2, flops_per_step=1e6,
                                 peak_flops=4e12)


def read(name, run):
    metric = {"name": name, "unit": "x"}
    out = read_metrics(REPO, [metric], run)
    return out[name]["value"] if name in out else None


def test_device_idle_share_is_the_largest_over_devices(run):
    # device 0 idles 300 of 1000 ns, device 1 200
    assert read("device_idle_share", run) == pytest.approx(30.0)


def test_step_mfu(run):
    # 2 steps x 1e6 operations over 1e-6 s x 2 chips x 4e12 = 25 %
    assert read("step_mfu", run) == pytest.approx(25.0)


def test_comm_ms(run):
    # (300 + 600) / 2 devices / 2 steps = 225 ns
    assert read("comm_ms", run) == pytest.approx(225e-6)


def test_comm_exposed_ms(run):
    # (200 + 500) / 2 devices / 2 steps = 175 ns
    assert read("comm_exposed_ms", run) == pytest.approx(175e-6)


def test_readers_find_nothing_without_devices(run):
    run.trace.devices, run.trace.async_ops = [], []
    for name in ("device_idle_share", "step_mfu", "comm_ms",
                 "comm_exposed_ms"):
        assert read(name, run) is None


def test_comm_readers_find_nothing_without_collectives(run):
    run.trace.async_ops = [[], []]
    run.trace.devices = [[o for o in ops if "all-gather" not in o[0]]
                         for ops in run.trace.devices]
    assert read("comm_ms", run) is None
    assert read("comm_exposed_ms", run) is None


def test_breakdown(run):
    trace = run.trace
    assert trace_lib.top_device_ops(trace) == [
        ["%fusion.1 fusion kLoop f32[8]", pytest.approx(300e-9)],
        ["%fusion.2 fusion kOutput f32[8]", pytest.approx(125e-9)],
        ["%all-gather-done.1 all-gather-done f32[32]",
         pytest.approx(25e-9)]]
    # device 0 idles over [700, 1000]; the host was reading the loss back
    assert trace_lib.idle_gaps(trace) == [
        ["np.asarray(jax.Array)", pytest.approx(300e-9)]]


def test_interval_arithmetic():
    assert trace_lib.union([(5, 7), (0, 2), (1, 3)], clip=(1, 6)) == \
        [(1, 3), (5, 6)]
    assert trace_lib.minus([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace_lib.length([(0, 2), (3, 5)]) == 4


def test_short_name_from_hlo_text():
    text = ("%fusion.49 = (f32[60821504]{0:T(1024)}, f32[8]{0}) fusion("
            "f32[60821504]{0:T(1024)} %p), kind=kLoop, calls=%fc.75")
    assert trace_lib.short_name(text) == \
        "%fusion.49 fusion kLoop (f32[60821504], f32[8])"
    assert trace_lib.short_name(
        "%all-gather.18 = f32[182464512]{0:T(1024)} all-gather(f32[4] %x)"
    ) == "%all-gather.18 all-gather f32[182464512]"
    assert trace_lib.short_name("jit_step(123)") == "jit_step(123)"
