#!/usr/bin/env python3
"""Smoke run of the DynaComm-planned ZeRO trainer on a TPU.

Drives the repository's main path once, through the factory every
launcher uses (``repro.runtime.build_runtime``), and checks what comes
out.  It is a proof that the system starts and trains on the chip, not a
benchmark: the times it prints are single wall-clock readings.

    python3 chip_smoke.py              # one chip: ZeRO phase + int8 PS phase
    python3 chip_smoke.py --chips 4    # four chips: the cross-chip ZeRO phase

It exits non-zero, printing no result, when JAX finds no TPU or when any
check fails.  On success its last line is one JSON object naming the
device.

Model.  granite-3-2b (hf:ibm-granite/granite-3.0-2b-base) at its
published widths: d_model 2048, 32 query and 8 KV heads of 64, SwiGLU
d_ff 8192, vocabulary 49155, tied embedding.  Depth is cut from 40
layers to 4: the model is dense with one layer kind, so one period of
its layer pattern is one layer; 4 blocks plus the embedding and the
final norm give the DP six sched layers to segment.  Weights are random
from seed 0; tokens come from the seeded synthetic Zipf stream
(``repro.data.pipeline.SyntheticText``).

Memory on one 16 GB v5e, from ``compiled.memory_analysis()`` of the step
compiled for a described v5e at batch 8 x seq 512:

* 344.0M parameters: 100.7M in the tied embedding, 60.8M per layer;
* ZeRO state = f32 params + Adam moments, 12 B/param = 4.13 GB, held
  twice because the step does not donate it (old in, new out): 8.26 GB;
* temporaries 2.90 GB: gathered params, per-layer grads, and the f32
  logits (8 x 512 x 49155 = 0.81 GB) with their cotangent;
* total 11.16 GB.  The int8 push adds the error-feedback residuals
  (1.38 GB in, 1.38 GB out): 12.97 GB.  Four chips hold 4.81 GB each.

Checks.  The ZeRO phase's step-1 loss is compared with the model's plain
loss (``repro.models.model.train_loss`` on the same initial weights and
batch, f32 at highest matmul precision).  It is not compared with
ln(vocab): with the tied embedding at this initialisation each position's
own input token dominates its logits, so the initial loss sits near 23,
not near ln(49155) = 10.80.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "granite-3-2b"
LAYERS = 4
BATCH, SEQ = 8, 512
ZERO_STEPS = 6
PS_STEPS = 2
FOUR_CHIP_STEPS = 3
# the trainer's f32 matmuls run at the TPU's default precision (bf16
# products, f32 sums); the reference runs at highest precision
REFERENCE_RTOL = 1e-2
# the same loss computed through the PS runtime's program
PS_RTOL = 1e-3
# two bucket plans of one model: same math, other collective grouping
PLAN_RTOL = 1e-3


def fail(message: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {message}")


def smoke_model():
    from repro.configs import get_config
    return dataclasses.replace(get_config(ARCH), num_layers=LAYERS)


def smoke_config(runtime: str, strategy: str, *, batch: int = BATCH,
                 seq: int = SEQ, **kwargs):
    from repro.runtime import RuntimeConfig, ScheduleConfig
    return RuntimeConfig(runtime=runtime, arch=ARCH, reduced=False,
                         batch=batch, seq=seq,
                         schedule=ScheduleConfig(strategy=strategy), **kwargs)


def first_batch(arch, config):
    from repro.data.pipeline import SyntheticText
    return SyntheticText(arch.vocab_size, config.seq, config.batch,
                         seed=config.seed).batch(0)


def train(rt, steps: int, label: str):
    """``steps`` steps, one at a time; ``fit`` returns the loss as a
    Python float, so each wall time ends on the device's result."""
    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        (loss,) = rt.fit(1)
        wall = time.perf_counter() - t0
        print(f"[{label}] step {i + 1} loss {loss!r} wall {wall:.3f} s",
              flush=True)
        losses.append(loss)
    if not all(math.isfinite(x) for x in losses):
        fail(f"{label}: non-finite loss in {losses}")
    return losses


def plan_line(label: str, plan) -> str:
    return (f"[{label}] bucket plan: {len(plan.forward)} pull / "
            f"{len(plan.backward)} push segments, forward {plan.forward}, "
            f"backward {plan.backward}")


def reference_loss(arch, config) -> float:
    """The model's plain f32 loss on the runtime's initial weights and
    first batch."""
    from repro.models import model as model_lib

    def loss(key, batch):
        params = model_lib.init_params(arch, key)
        return model_lib.train_loss(arch, params, batch,
                                    aux_weight=config.aux_weight)

    with jax.default_matmul_precision("highest"):
        return float(jax.jit(loss)(jax.random.PRNGKey(config.seed),
                                   first_batch(arch, config)))


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def phase_zero(arch, config):
    """ZeRO with the dynacomm plan: trains, and its first loss agrees
    with the reference."""
    from repro.runtime import build_runtime
    ref = reference_loss(arch, config)
    print(f"[zero] reference step-1 loss {ref!r} (ln vocab "
          f"{math.log(arch.vocab_size)!r})", flush=True)
    rt = build_runtime(config, model=arch)
    print(plan_line("zero", rt.plan), flush=True)
    losses = train(rt, ZERO_STEPS, "zero")
    print(f"[zero] peak_bytes_in_use {peak_bytes(jax.devices()[0])}")
    rel = abs(losses[0] - ref) / abs(ref)
    print(f"[zero] step-1 loss vs reference: relative difference {rel!r}")
    if rel > REFERENCE_RTOL:
        fail(f"zero: step-1 loss {losses[0]} is not within "
             f"{REFERENCE_RTOL} of the reference {ref}")
    if not losses[-1] < losses[0]:
        fail(f"zero: last loss {losses[-1]} is not below the first "
             f"{losses[0]}")
    del rt
    gc.collect()
    return losses


def kernel_vs_reference(n: int) -> None:
    """The int8 kernels against their jnp oracles on the device, at one
    real layer length: the payload and the decoded values exactly, the
    scales within 1 ulp."""
    from repro.kernels.compress.ops import dequantize_unpack, quantize_pack
    from repro.kernels.compress.ref import (dequantize_unpack_ref,
                                            quantize_pack_ref)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, n), jnp.float32)
    payload, scales = quantize_pack(x, (n,))
    payload_ref, scales_ref = jax.jit(quantize_pack_ref,
                                      static_argnums=1)(x, (n,))
    bad = int(jnp.sum(payload != payload_ref))
    ulps = int(np.max(np.abs(
        np.asarray(scales).view(np.int32).astype(np.int64)
        - np.asarray(scales_ref).view(np.int32))))
    out = dequantize_unpack(payload_ref, scales_ref, (n,), n)
    out_ref = jax.jit(dequantize_unpack_ref, static_argnums=(2, 3))(
        payload_ref, scales_ref, (n,), n)
    bad_out = int(jnp.sum(out != out_ref))
    print(f"[ps-int8] kernel vs reference at length {n}: payload "
          f"mismatches {bad}, scale max ulps {ulps}, decoded mismatches "
          f"{bad_out}", flush=True)
    if bad or ulps > 1 or bad_out:
        fail("ps-int8: the int8 kernels disagree with their reference")


def phase_ps_int8(arch, config, zero_first_loss: float) -> None:
    """Sync PS with int8 push compression through the Pallas kernels."""
    from repro.kernels.compress.ops import aligned
    from repro.runtime import build_runtime
    rt = build_runtime(config, model=arch)
    compressor = rt.trainer.compressor
    print(f"[ps-int8] compressor {compressor.scheme}, use_kernel "
          f"{compressor.use_kernel}")
    print(plan_line("ps-int8", rt.plan), flush=True)
    if not compressor.use_kernel:
        fail("ps-int8: the compressor does not use the Pallas kernels")
    hlo = rt.compiled_step_text(first_batch(arch, config))
    kernels = hlo.count("tpu_custom_call")
    print(f"[ps-int8] tpu_custom_call in the compiled step: {kernels}")
    if not kernels:
        fail("ps-int8: no Pallas kernel in the compiled step")
    losses = train(rt, PS_STEPS, "ps-int8")
    rel = abs(losses[0] - zero_first_loss) / abs(zero_first_loss)
    print(f"[ps-int8] step-1 loss vs zero step 1: relative difference "
          f"{rel!r}")
    if rel > PS_RTOL:
        fail(f"ps-int8: step-1 loss {losses[0]} disagrees with the zero "
             f"runtime's {zero_first_loss}")
    kernel_vs_reference(aligned(rt.trainer.specs[1].padded))
    print(f"[ps-int8] peak_bytes_in_use {peak_bytes(jax.devices()[0])}")
    del rt
    gc.collect()


def push_and_pull_counts(hlo: str):
    """(pull, push) segments in a compiled step: the bucket scopes that
    hold a collective (``repro.analysis.segment_counts``).  A segment of
    sharded leaves moves one collective per leaf, and XLA's TPU backend
    compiles many of the pushes as fusions without metadata; the native
    reduce-scatters of each push bucket carry its scope."""
    from repro.analysis.hlo import collective_counts, segment_counts
    counts = collective_counts(hlo)
    print(f"[zero-4] compiled collectives: "
          + ", ".join(f"{counts[k]} {k}" for k in
                      ("all-gather", "reduce-scatter", "all-reduce")))
    return segment_counts(hlo)


def phase_four_chips(arch, strategies=("dynacomm", "sequential"),
                     batch: int = BATCH, seq: int = SEQ):
    """ZeRO over the 4-device data axis under two bucket plans: one pull
    per pull segment and one push per push segment, and the same
    losses."""
    from repro.runtime import build_runtime
    losses = {}
    for strategy in strategies:
        config = smoke_config("zero", strategy, batch=batch, seq=seq)
        rt = build_runtime(config, model=arch)
        plan = rt.plan
        print(plan_line(f"zero-4/{strategy}", plan), flush=True)
        pulls, pushes = push_and_pull_counts(
            rt.compiled_step_text(first_batch(arch, config)))
        if (pulls, pushes) != (len(plan.forward), len(plan.backward)):
            fail(f"zero-4/{strategy}: {pulls} pulls / {pushes} pushes "
                 f"compiled for {len(plan.forward)} / "
                 f"{len(plan.backward)} segments")
        losses[strategy] = train(rt, FOUR_CHIP_STEPS, f"zero-4/{strategy}")
        del rt
        gc.collect()
    first, second = (losses[s] for s in strategies)
    identical = first == second
    worst = max(abs(a - b) / abs(b) for a, b in zip(first, second))
    print(f"[zero-4] {strategies[0]} vs {strategies[1]}: losses "
          f"bit-identical {identical}, largest relative difference "
          f"{worst!r}")
    if worst > PLAN_RTOL:
        fail(f"zero-4: the plans' losses differ by {worst} relative")
    return losses


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cross-chip ZeRO phase")
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package under {src}; run chip_smoke.py from a "
             f"checkout of the repository")
    sys.path.insert(0, src)
    from repro.launch.chip import device_line, use_compile_cache
    cache = use_compile_cache()
    print(device_line(), flush=True)
    print(f"[cache] {cache}")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devices[0].platform}); this "
             f"smoke run does not fall back to another device")
    if len(devices) != args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} devices")

    arch = smoke_model()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(arch)
    else:
        from repro.runtime import CompressionConfig
        zero = phase_zero(arch, smoke_config("zero", "dynacomm"))
        phase_ps_int8(arch, smoke_config(
            "ps", "dynacomm", compression=CompressionConfig(scheme="int8")),
            zero[0])
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
