#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up builds the cell's runtime through ``repro.runtime.build_runtime``
(weights made on the device from the seed), draws the ring of batches
from the seed and places it on the device, and trains the first
``check.CHECK_STEPS`` steps through the window's own call, which compiles
or loads every program the window runs, then ``WARM_STEPS`` untimed
ones.  The window then calls
``rt.fit(1)`` for ``--seconds``; each call ends on the loss as a Python
float.  After the window the peak memory is read, the program is freed,
and the plain reference follows the checked steps to decide ``correct``.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
the cell's per-layer metrics, the device's busy and window seconds, and a
breakdown; the trace's operations take their scopes from the compiled
step's text, read in set-up.  The last line of standard output is the
result as JSON; the last lines of standard error give each compared
number beside its limit.

Exits non-zero, printing no result, when JAX finds no TPU, fewer or more
chips than the cell asks for, or no program beside the benchmark.
"""

from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, data, flops, hlo  # noqa: E402
from chipbench import trace as trace_lib  # noqa: E402

#: everything a run writes: the compile cache and the traces
WORK_DIR = ".chipbench"
#: untimed steps between the checked ones and the window
WARM_STEPS = 10


class Refused(SystemExit):
    """A run that cannot stand: exits non-zero and prints no result."""

    def __init__(self, message: str):
        super().__init__(f"chipbench: {message}")


def log(message: str) -> None:
    print(message, flush=True)


# ---------------------------------------------------------------------------
# the cell, by name
# ---------------------------------------------------------------------------


def _read_json(path: str):
    if not os.path.isfile(path):
        raise Refused(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str):
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    work = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    here = os.path.join(root, "chipbench")
    config = _read_json(os.path.join(root, configs[work["config"]]["file"]))
    traffic = _read_json(os.path.join(here, "traffic",
                                      work["traffic"] + ".json"))
    settings = _read_json(os.path.join(here, "cells", name + ".json"))
    if int(traffic.get("chips", work["chips"])) != int(work["chips"]):
        raise Refused(f"traffic {work['traffic']!r} is drawn for "
                      f"{traffic['chips']} chips, the cell asks for "
                      f"{work['chips']}")

    def applies(metric):
        return name in metric.get("workloads", [name])

    return types.SimpleNamespace(
        name=name, chips=int(work["chips"]), config=config,
        traffic=traffic, settings=settings,
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
        peaks=_read_json(os.path.join(here, "peaks.json")))


def _load_module(path: str, label: str):
    if not os.path.isfile(path):
        raise Refused(f"missing {path}")
    spec = importlib.util.spec_from_file_location(label, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_metrics(root: str, metrics, run):
    """Each metric's reader (``metrics/<name>.py``) over this run; a
    reader that finds nothing to read leaves its metric out."""
    out = {}
    for m in metrics:
        reader = _load_module(
            os.path.join(root, "chipbench", "metrics", m["name"] + ".py"),
            "chipbench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# process set-up
# ---------------------------------------------------------------------------


def use_compile_cache(root: str) -> str:
    """JAX's persistent compile cache, every program kept: the directory
    that ``JAX_COMPILATION_CACHE_DIR`` names, else a fixed path in the
    checkout.  The key takes in the programs' metadata, so that a program
    loaded from the cache carries the scopes of the code that runs."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, WORK_DIR, "jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


class CompileCounter:
    """Counts traces and executables built (compiled or loaded from the
    cache) through JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring
        from jax._src import dispatch
        self.traces = self.compiles = 0
        self._events = {dispatch.JAXPR_TRACE_EVENT: "traces",
                        dispatch.BACKEND_COMPILE_EVENT: "compiles"}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        attr = self._events.get(event)
        if attr:
            setattr(self, attr, getattr(self, attr) + 1)

    def snapshot(self):
        return self.traces, self.compiles


def check_devices(chips: int, require_tpu: bool, peaks):
    import jax
    devices = jax.devices()
    first = devices[0]
    log(f"[device] platform {first.platform}, kind {first.device_kind}, "
        f"count {len(devices)}, jax {jax.__version__}")
    if require_tpu and first.platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {first.platform})")
    if len(devices) != chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees "
                      f"{len(devices)}")
    if require_tpu and first.device_kind not in peaks:
        raise Refused(f"no peaks for device kind {first.device_kind!r} in "
                      f"peaks.json")
    return devices


def peak_bytes(devices):
    """The largest ``peak_bytes_in_use`` over ``devices``, or None where
    the backend keeps no such count."""
    stats = [d.memory_stats() for d in devices]
    if not all(s and "peak_bytes_in_use" in s for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def arch_config(config):
    from repro.configs.base import ArchConfig
    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in config["arch"].items()}
    return ArchConfig(**fields)


def place_ring(devices, tokens, labels):
    """The ring on the device, each batch split over the data axis as the
    ZeRO step takes it."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    sharding = NamedSharding(Mesh(np.array(devices), ("data",)),
                             P("data", None))
    return [{"tokens": jax.device_put(t, sharding),
             "labels": jax.device_put(l, sharding)}
            for t, l in zip(tokens, labels)]


def build(cell, arch, seed: int, batches):
    from repro.runtime import RuntimeConfig, ScheduleConfig, build_runtime
    s, tr = cell.settings, cell.traffic
    config = RuntimeConfig(
        runtime=s["runtime"], reduced=False,
        batch=data.global_batch(tr, cell.chips), seq=int(tr["seq"]),
        optimizer=s["optimizer"]["name"], lr=float(s["optimizer"]["lr"]),
        seed=seed, aux_weight=float(cell.config.get("router_aux_loss_coef",
                                                    0.0)),
        schedule=ScheduleConfig(strategy=s["strategy"]))
    return build_runtime(config, model=arch,
                         data=lambda i: batches[i % len(batches)])


# ---------------------------------------------------------------------------
# the program's readings for the check
# ---------------------------------------------------------------------------


def checked_steps(rt, b1: float):
    """The first steps, through the window's call: their losses, the
    first gradient's leaf norms (from Adam's first moment, reduced in one
    program so that no second copy of the parameters is made), and the
    flat parameters after the last, copied to the host.  ``final`` turns
    those into the parameter tree; call it once the program is freed."""
    import jax
    to_tree = rt.trainer.params_from_state
    first_grad_norms = jax.jit(lambda mu: check.norms(to_tree(
        {"flat_params": [m / (1.0 - b1) for m in mu]})))
    losses, grads = [], None
    for step in range(check.CHECK_STEPS):
        (loss,) = rt.fit(1)
        losses.append(loss)
        if step == 0:
            grads = {k: float(v) for k, v in
                     first_grad_norms(rt._state["opt"].mu).items()}
    flats = jax.device_get(rt._state["flat_params"])
    tree = jax.jit(to_tree)
    return {"losses": losses, "grad_norms": grads,
            "final": lambda: tree({"flat_params": flats})}


def plan_lines(rt, step_text):
    plan = rt.plan
    log(f"[plan] {len(plan.forward)} pull / {len(plan.backward)} push "
        f"segments, forward {plan.forward}, backward {plan.backward}")
    counts = hlo.pulls_and_pushes(step_text)
    log(f"[hlo] compiled per step: {counts['pulls']} pulls "
        f"({counts['pull_bytes']} B), {counts['pushes']} pushes "
        f"({counts['push_bytes']} B), {counts['small_all_reduces']} "
        f"all-reduces of at most {hlo.SMALL_BYTES} B")
    return counts


def reference_check(cell, seed, tokens, labels, program):
    model = flops.reference(cell.config["reference"])
    ref = check.reference_steps(model, cell.config, cell.settings["optimizer"],
                                seed, tokens[:check.CHECK_STEPS],
                                labels[:check.CHECK_STEPS], cell.chips)
    ref["change_norms"] = check.diff_norms(ref["params"], ref["params0"])
    program["change_norms"] = check.change_norms(program["final"](), ref)
    return check.numbers(program, ref)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    return args


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True,
         start: float = PROCESS_START) -> dict:
    args = parse_args(argv)
    cell = load_cell(root, args.workload)
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"no program (repro package) under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)

    import jax
    log(f"[cache] {use_compile_cache(root)}")
    devices = check_devices(cell.chips, require_tpu, cell.peaks)
    kind = devices[0].device_kind
    counter = CompileCounter()

    def phase(name):
        log(f"[setup] {name} at {time.time() - start:.3f} s, peak "
            f"{peak_bytes(devices)} B")

    phase("jax ready")
    arch = arch_config(cell.config)
    seed32 = args.seed % 2 ** 32     # what jax.random.PRNGKey keeps of it
    tokens, labels = data.make_ring(cell.traffic, arch.vocab_size,
                                    cell.chips, args.seed)
    batches = place_ring(devices, tokens, labels)
    phase("ring placed")
    rt = build(cell, arch, seed32, batches)
    phase("runtime built")
    program = checked_steps(rt, float(cell.settings["optimizer"]["b1"]))
    log(f"[check] program losses {program['losses']}")
    phase("checked steps run")
    step_text = rt.compiled_step_text(batches[0])
    plan_lines(rt, step_text)
    phase("compiled step read")
    tokens_per_step = tokens.shape[1] * tokens.shape[2]

    trace_dir = os.path.join(root, WORK_DIR, "trace",
                             f"{cell.name}.{args.seed}")
    # A profiler session opened and closed before the window puts the TPU
    # runtime's dispatch on its fast path in every process (~1.2 ms a step
    # on one v5e, where a process without one lands at random on that or
    # on ~6 ms); traced and untraced runs then time the same runtime.  A
    # process of the program that opens no profiler can land on the slow
    # path: the window measures the fast one.
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    jax.profiler.stop_trace()
    shutil.rmtree(trace_dir, ignore_errors=True)
    for _ in range(WARM_STEPS):
        rt.fit(1)
    # Set-up leaves a large heap of long-lived objects; frozen, they are no
    # longer walked by the collections that the window's steps trigger.
    gc.collect()
    gc.freeze()
    phase(f"profiler opened and closed, {WARM_STEPS} warm steps run, "
          f"heap frozen")
    if args.trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    before = counter.snapshot()
    walls, losses = [], []
    t_start = time.perf_counter()
    setup_s = time.time() - start
    while True:
        t0 = time.perf_counter()
        with jax.profiler.StepTraceAnnotation(trace_lib.STEP_SPAN,
                                              step_num=len(walls)):
            (loss,) = rt.fit(1)
        t1 = time.perf_counter()
        walls.append(t1 - t0)
        losses.append(loss)
        if t1 - t_start >= args.seconds:
            break
    window_s = time.perf_counter() - t_start
    if args.trace:
        jax.profiler.stop_trace()
    after = counter.snapshot()

    peak = peak_bytes(devices)
    # the allocator's count leaves out the scratch a program reserves when
    # it loads; the other counts are printed beside it
    log(f"[memory] {[d.memory_stats() for d in devices]}")
    slowest = sorted(range(len(walls)), key=lambda i: -walls[i])[:3]
    log(f"[window] {len(walls)} steps in {window_s!r} s; traces "
        f"{after[0] - before[0]}, compiles {after[1] - before[1]} inside "
        f"the window; peak_bytes_in_use {peak}; slowest steps "
        + ", ".join(f"#{i} {walls[i] * 1e3:.1f} ms" for i in slowest))

    gc.unfreeze()
    del rt, batches
    gc.collect()
    t_ref = time.perf_counter()
    values = reference_check(cell, seed32, tokens, labels, program)
    log(f"[reference] {time.perf_counter() - t_ref!r} s")
    limits = {k: float(v) for k, v in cell.settings["limits"].items()}
    failed = sum(not math.isfinite(x) for x in losses)
    correct = check.verdict(values, limits) and failed == 0

    peaks = cell.peaks.get(kind, {})
    run = types.SimpleNamespace(
        walls=walls, steps=len(walls), window_s=window_s,
        tokens_per_step=tokens_per_step, setup_s=setup_s, peak_bytes=peak,
        chips=cell.chips, peak_flops=peaks.get("bf16_flops_per_s"),
        flops_per_step=flops.train_flops_per_step(
            cell.config, tokens.shape[1], tokens.shape[2]),
        trace=None)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(walls), "failed": failed}
    if args.trace:
        run.trace = trace_lib.load(trace_lib.find_xplane(trace_dir),
                                   step_text)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = read_metrics(root, cell.per_layer, run)
        busy = [trace_lib.length(trace_lib.busy(run.trace, d))
                for d in range(len(run.trace.devices))]
        device["busy_s"] = sum(busy) / max(len(busy), 1) * 1e-9
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": trace_lib.top_device_ops(run.trace),
            "idle_gaps": trace_lib.idle_gaps(run.trace)}
    else:
        result["metrics"] = read_metrics(root, cell.end_to_end, run)
    result["device"] = device
    result["check"] = {k: {"value": values[k], "limit": limits[k]}
                       for k in limits}
    for line in check.lines(values, limits):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
