"""Subprocess helper: the ZeRO step's profiler scopes and host spans.

    python tracing_check.py <devices>

Forges ``<devices>`` host devices, then for the reduced dense and MoE
configs (and the dense one under ZeRO-3) builds the ``zero`` runtime and
reports, as one JSON line:

* the plan (buckets, sched layers), the state layout, the data axis's
  devices, and every ``zero.*`` / ``moe.*`` name component found in the
  compiled step's ``op_name`` metadata (under ZeRO-3, the step's as JAX
  hands it to XLA);
* the non-trivial instructions of the entry computation, as JAX hands the
  step to XLA, whose ``op_name`` carries no ``zero.*`` scope;
* two steps' losses, and the same with ``jax.named_scope`` stubbed out,
  with both compiled steps' instruction and fusion counts;
* the names and threads of the ``repro.*`` host spans in a
  ``jax.profiler`` trace around ``fit(2)`` (dense only).
"""

import os
import sys

DEVICES = int(sys.argv[1])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={DEVICES}"

import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from repro.runtime import RuntimeConfig, build_runtime  # noqa: E402
from repro.runtime.config import ExecutionConfig  # noqa: E402

TRIVIAL = {"parameter", "constant", "tuple", "get-tuple-element"}
_INSTR = re.compile(r"^\s*(?:ROOT )?%?(\S+) = (?:\([^=]*?\)|\S+) "
                    r"([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:zero|moe)\.[\w.]+")


def entry_instructions(hlo_text):
    """``(opcode, op_name)`` of each instruction of the ENTRY computation,
    a broadcast of a constant (a splat) counted as a constant."""
    out, constants, inside = [], set(), False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            inside = True
        elif inside and line.startswith("}"):
            break
        elif inside:
            m = _INSTR.match(line)
            if m is None:
                continue
            name, op = m.groups()
            if op == "constant" or (op == "broadcast" and re.search(
                    r"broadcast\(%?([\w.\-]+)\)", line).group(1)
                    in constants):
                constants.add(name)
                op = "constant"
            op_name = _OP_NAME.search(line)
            out.append((op, op_name.group(1) if op_name else ""))
    return out


def opcode_counts(hlo_text):
    """Instructions and fusions of the whole compiled module."""
    ops = [m.group(2) for m in map(_INSTR.match, hlo_text.splitlines()) if m]
    return {"instructions": len(ops), "fusions": ops.count("fusion")}


def scopes(hlo_text):
    return sorted({s for name in _OP_NAME.findall(hlo_text)
                   for s in _SCOPE.findall(name)})


def runtime(arch, zero3=False):
    return build_runtime(RuntimeConfig(
        runtime="zero", arch=arch, reduced=True, batch=4, seq=16, seed=3,
        execution=ExecutionConfig(zero3=zero3)))


def host_spans(rt):
    """``(line, name)`` of every ``repro.*`` span in a trace of fit(2)."""
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            rt.fit(2)
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        data = ProfileData.from_file(path)
        spans = []
        for plane in data.planes:
            for line in plane.lines:
                spans += [(e.start_ns, f"{plane.name}/{line.name}", e.name)
                          for e in line.events
                          if e.name.startswith("repro.")]
    return [[line, name] for _, line, name in sorted(spans)]


def check(arch, zero3=False, trace=False):
    rt = runtime(arch, zero3)
    batch = rt._batch_fn(0)
    compiled = rt.compiled_step_text(batch)
    lowered = rt._step_fn.lower(rt._state, batch).as_text(dialect="hlo",
                                                           debug_info=True)
    out = {"forward": [list(b) for b in rt.plan.forward],
           "backward": [list(b) for b in rt.plan.backward],
           "sched_layers": rt.trainer.num_layers,
           "layout": rt.trainer.layout,
           "devices": rt.trainer.axis_size,
           # under ZeRO-3 the CPU compiler merges each re-pull with its
           # forward twin (the TPU compiler keeps them apart,
           # tests/test_chip_compile.py): read the scopes JAX hands it
           "scopes": scopes(lowered if zero3 else compiled),
           "unscoped": [[op, name] for op, name in entry_instructions(lowered)
                        if op not in TRIVIAL and "zero." not in name],
           "counts": opcode_counts(compiled),
           "losses": rt.fit(2)}
    if trace:
        out["host_spans"] = host_spans(rt)
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        plain = runtime(arch, zero3)
        out["plain_counts"] = opcode_counts(plain.compiled_step_text(batch))
        out["plain_losses"] = plain.fit(2)
    finally:
        jax.named_scope = real
    return out


def main():
    assert len(jax.devices()) == DEVICES
    result = {"dense": check("granite-3-2b", trace=True),
              "moe": check("granite-moe-1b-a400m"),
              "dense_zero3": check("granite-3-2b", zero3=True)}
    print(json.dumps(result))


if __name__ == "__main__":
    main()
