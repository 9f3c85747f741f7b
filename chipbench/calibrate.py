#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 101-112 \\
        --faults 3 --out <file.jsonl>

For every seed, in one process: the program's first steps through the
runtime a run builds, then the plain reference, and the compared numbers
(``check.numbers``): the lower readings.  For the first ``--faults``
seeds, the reference put in the program's place with a fault, compared
with the sound reference: the upper readings.

* ``control``: every matrix product with float8 e4m3 inputs (the
  precision below the one the configuration states);
* ``half_batch``: half of each chip's rows left out, the mean taken over
  the rest;
* ``no_exchange`` (several chips): no exchange between chips, so the
  first chip steps on its own rows alone.

Two faults need no run: a step that returns its state unchanged reads 1
on ``grad_gap`` and ``update_gap`` (no moment, no change), and a loss
altered by a share ``x`` where it is produced reads ``x`` on
``loss_gap``, give or take the sound reading.

Each line of ``--out`` is one reading; the benchmark's own runs never
call this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, data, flops, run  # noqa: E402


def seed_list(text: str):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def reference_readings(result, ref):
    return {"losses": result["losses"], "grad_norms": result["grad_norms"],
            "change_norms": check.diff_norms(result["params"],
                                             ref["params0"])}


def sound_reference(model, cell, seed32, tokens, labels):
    """The reference's checked steps with their change norms; its initial
    parameters are kept on the host and its final ones dropped, so that a
    fault's reference fits on the device beside it."""
    ref = check.reference_steps(model, cell.config, cell.settings["optimizer"],
                                seed32, tokens, labels, cell.chips)
    ref["change_norms"] = check.diff_norms(ref["params"], ref["params0"])
    ref["params0"] = jax.device_get(ref["params0"])
    del ref["params"]
    return ref


def fault_readings(model, cell, seed32, tokens, labels, ref):
    """``(kind, numbers, seconds)`` of each fault's reference against the
    sound one, ``ref``; ``tokens``/``labels`` are the checked steps'."""
    size = tokens.shape[1] // cell.chips
    faults = {
        "control": dict(precision="fp8"),
        "half_batch": dict(rows=lambda n: slice(0, n // 2)),
    }
    if cell.chips > 1:
        faults["no_exchange"] = dict(blocks=1)
    for kind, kw in faults.items():
        t0 = time.perf_counter()
        blocks = kw.pop("blocks", cell.chips)
        t, l = (tokens, labels) if blocks == cell.chips else \
            (tokens[:, :size], labels[:, :size])
        result = check.reference_steps(model, cell.config,
                                       cell.settings["optimizer"], seed32,
                                       t, l, blocks, **kw)
        values = check.numbers(reference_readings(result, ref), ref)
        del result
        gc.collect()
        yield kind, values, time.perf_counter() - t0


def calibrate(argv=None, *, root: str = run.ROOT, require_tpu: bool = True):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last, inclusive")
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    cell = run.load_cell(root, args.workload)
    sys.path.insert(0, os.path.join(root, "src"))
    run.log(f"[cache] {run.use_compile_cache(root)}")
    devices = run.check_devices(cell.chips, require_tpu, cell.peaks)
    arch = run.arch_config(cell.config)
    model = flops.reference(cell.config["reference"])
    opt = cell.settings["optimizer"]
    steps = check.CHECK_STEPS

    with open(args.out, "w") as out:
        def record(seed, kind, values, seconds):
            line = {"workload": cell.name, "seed": seed, "kind": kind,
                    "seconds": seconds, **values}
            out.write(json.dumps(line) + "\n")
            out.flush()
            run.log(json.dumps(line))

        for i, seed in enumerate(seed_list(args.seeds)):
            t0 = time.perf_counter()
            seed32 = seed % 2 ** 32
            tokens, labels = data.make_ring(cell.traffic, arch.vocab_size,
                                            cell.chips, seed)
            batches = run.place_ring(devices, tokens, labels)
            rt = run.build(cell, arch, seed32, batches)
            program = run.checked_steps(rt, float(opt["b1"]))
            del rt, batches
            gc.collect()
            tokens, labels = tokens[:steps], labels[:steps]
            ref = sound_reference(model, cell, seed32, tokens, labels)
            program["change_norms"] = check.change_norms(
                program["final"](), ref)
            record(seed, "program", check.numbers(program, ref),
                   time.perf_counter() - t0)
            if i < args.faults:
                for kind, values, seconds in fault_readings(
                        model, cell, seed32, tokens, labels, ref):
                    record(seed, kind, values, seconds)
            del ref
            gc.collect()


if __name__ == "__main__":
    calibrate()
