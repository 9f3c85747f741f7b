"""The readers of the program's host spans, against a trace whose numbers
are worked out by hand (``data/scoped_trace.json``: two devices, two
steps, a window of 1000 ns).

The host marks each step's data fetch (``repro.data``), the call of the
jitted step (``repro.dispatch``, with JAX's ``PjitFunction`` inside) and
the loss read (``repro.sync``, with JAX's ``np.asarray`` inside).  Two
dispatch spans lie inside the window, [20, 50] and [520, 570]; one starts
before it and one ends after it.  Device 0 runs over [60, 420] and
[580, 940], so it idles over [0, 60], [420, 580] and [940, 1000].
"""

import os
import types

import pytest

from chipbench import trace as trace_lib
from chipbench.run import read_metrics

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
NEW = ("dispatch_ms",)


def _run(fixture):
    with open(os.path.join(HERE, "data", fixture)) as f:
        trace = trace_lib.Trace.from_json(f.read())
    return types.SimpleNamespace(trace=trace, chips=2, flops_per_step=1e6,
                                 peak_flops=4e12)


@pytest.fixture
def run():
    return _run("scoped_trace.json")


def read(name, run):
    out = read_metrics(REPO, [{"name": name, "unit": "x"}], run)
    return out[name]["value"] if name in out else None


def test_dispatch_ms_is_the_median_span_inside_the_window(run):
    # spans of 30 and 50 ns inside the window; the two cut by it are left out
    assert read("dispatch_ms", run) == pytest.approx(40e-6)
    run.trace.host.append(("repro.dispatch", 700.0, 710.0))
    assert read("dispatch_ms", run) == pytest.approx(30e-6)


def test_dispatch_ms_needs_a_device_plane(run):
    # the CPU rehearsal's trace has no device plane: no chip reading
    run.trace.devices, run.trace.async_ops = [], []
    assert read("dispatch_ms", run) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_a_trace_without_program_spans(name):
    # the parent program's trace: no repro.* spans
    assert read(name, _run("small_trace.json")) is None


def test_accepted_readers_read_the_scoped_trace(run):
    # device 0 idles 60 + 160 + 60 = 280 of 1000 ns, device 1 the same
    assert read("device_idle_share", run) == pytest.approx(28.0)
    assert read("step_mfu", run) == pytest.approx(25.0)


def test_idle_gaps_are_named_by_the_program_spans(run):
    # the innermost host span over each gap's middle: the gap between the
    # steps is the next step's data fetch
    assert trace_lib.idle_gaps(run.trace) == [
        ["repro.data", pytest.approx(160e-9)],
        ["PjitFunction(step)", pytest.approx(60e-9)],
        ["repro.data", pytest.approx(60e-9)]]


def _events(*events, stats=""):
    """``events { ... }`` entries: (metadata id, start ns, duration ns)."""
    return " ".join(f"events {{ metadata_id: {m} offset_ps: {a * 1000} "
                    f"duration_ps: {d * 1000} {stats} }}" for m, a, d in events)


def _metadata(*names):
    return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for i, n in enumerate(names, 1))


# A v5e trace as the profiler writes it (looked at on the chip): the
# device plane has "Steps", "XLA Modules", "XLA Ops" and "Async XLA Ops"
# lines, each op named by its HLO text without metadata and carrying only
# timing stats; the host's "python3" thread has the harness's step spans,
# the program's spans and JAX's own inside them.
XSPACE = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "Steps" timestamp_ns: 0 {_events((3, 60, 360))} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {_events((2, 60, 360), (2, 580, 360))} }}
  lines {{ id: 3 name: "XLA Ops" timestamp_ns: 0
    {_events((1, 60, 360), (1, 580, 360),
             stats='stats { metadata_id: 1 str_value: "1.0" }')} }}
  lines {{ id: 4 name: "Async XLA Ops" timestamp_ns: 0 }}
  {_metadata("%fusion.1 = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, "
             "calls=%fc.1", "jit_step(123)", "0")}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Time Scale Multiplier" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
    {_events((1, 0, 500), (1, 500, 500), (2, 0, 20), (3, 20, 30),
             (4, 25, 20), (5, 50, 430), (6, 55, 415), (2, 500, 20),
             (3, 520, 50), (4, 525, 40), (5, 570, 390), (6, 575, 380))} }}
  {_metadata("train", "repro.data", "repro.dispatch", "PjitFunction(step)",
             "repro.sync", "np.asarray(jax.Array)")}
}}
"""


def test_load_keeps_the_program_spans_of_an_xplane(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    trace = trace_lib.load(str(path))
    assert trace.window == (0.0, 1000.0) and trace.steps == 2
    assert [name for name, _, _ in trace.host] == [
        "repro.data", "repro.dispatch", "PjitFunction(step)", "repro.sync",
        "np.asarray(jax.Array)"] * 2
    assert trace.devices == [[("%fusion.1 fusion kLoop f32[8]", 60.0, 420.0),
                              ("%fusion.1 fusion kLoop f32[8]", 580.0,
                               940.0)]]
    run = types.SimpleNamespace(trace=trace)
    assert read("dispatch_ms", run) == pytest.approx(40e-6)


def test_load_joins_the_compiled_steps_scopes(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    step_text = (
        '  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'calls=%fc.1, metadata={op_type="exp" '
        'op_name="jit(step)/zero.fwd.L1/exp" stack_frame_id=2}\n'
        '  ROOT %fusion.7 = f32[8]{0} fusion(f32[8]{0} %fusion.1), '
        'kind=kLoop, calls=%fc.2, metadata={op_name="jit(step)/zero.opt"}\n')
    assert trace_lib.load(str(path)).scopes == {}
    trace = trace_lib.load(str(path), step_text)
    # only the operations the trace holds
    assert trace.scopes == {"%fusion.1": "jit(step)/zero.fwd.L1/exp"}
    run = types.SimpleNamespace(trace=trace)
    # device 0 runs %fusion.1 over 720 ns in 2 steps
    assert read("fwd_ms", run) == pytest.approx(360e-6)
    assert read("optimizer_ms", run) is None
