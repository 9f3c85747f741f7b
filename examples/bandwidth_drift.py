"""Bandwidth-drift demo: watch the schedule re-segment mid-training.

The run-time loop of the paper (Section IV-C), end to end: a ~100M-param
model trains under the DynaComm-bucketed ZeRO trainer while the edge
uplink degrades from 10 Gbps to 1 Gbps at ``--shift-epoch``.  On the epoch
boundary the profiler re-derives pt/gt/Δt from the new network condition,
the DP re-plans, and the ``zero`` runtime swaps in the compiled step for
the new bucket plan (cached by plan, so a later recovery to 10 Gbps swaps
back without re-tracing).  The whole regime — drifting network included —
is one ``RuntimeConfig`` literal built through ``build_runtime``; the
ASCII timelines show *why* the decision moves: cheaper transmission
favours more, smaller segments overlapped with compute; an expensive link
amortizes Δt over fewer, larger ones.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/bandwidth_drift.py --steps 60
"""

import argparse
import dataclasses

import jax

from repro.configs import get_config
from repro.core.viz import render_timeline
from repro.runtime import (MeasureConfig, NetworkConfig, RuntimeConfig,
                           ScheduleConfig, build_runtime)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--steps-per-epoch", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--bw-gbps", type=float, default=10.0)
    ap.add_argument("--shift-gbps", type=float, default=1.0)
    ap.add_argument("--shift-epoch", type=int, default=1)
    ap.add_argument("--worker-flops", type=float, default=1e10)
    args = ap.parse_args()

    cfg = dataclasses.replace(
        get_config(args.arch).reduced(num_layers=args.layers,
                                      d_model=args.d_model, vocab=8192),
        name=f"{args.arch}-drift-demo")
    n_dev = len(jax.devices())
    print(f"devices: {n_dev}  arch: {cfg.name}  layers: {cfg.num_layers}  "
          f"uplink: {args.bw_gbps:g} Gbps → {args.shift_gbps:g} Gbps at "
          f"epoch {args.shift_epoch}")

    config = RuntimeConfig(
        runtime="zero", arch=cfg.name, batch=args.batch, seq=args.seq,
        schedule=ScheduleConfig(
            reschedule_every=args.steps_per_epoch,
            network=NetworkConfig(bandwidth_gbps=args.bw_gbps,
                                  shift_gbps=args.shift_gbps,
                                  shift_epoch=args.shift_epoch)),
        measure=MeasureConfig(compute_flops_per_s=args.worker_flops))
    rt = build_runtime(config, model=cfg)

    done = 0
    while done < args.steps:
        losses = rt.fit(min(10, args.steps - done))
        done += len(losses)
        print(f"step {done:4d}  epoch {rt.epoch}  "
              f"loss {losses[-1]:.4f}  buckets "
              f"{len(rt.plan.forward)}/{len(rt.plan.backward)}")

    print("\nre-scheduling history:")
    shown = set()
    for e in rt.events:
        pulls, pushes = rt.hlo_counts(e.plan)
        print(f"  epoch {e.epoch:3d}: {len(e.plan.forward)} pull / "
              f"{len(e.plan.backward)} push buckets "
              f"(hlo {pulls} pull / {pushes} push)  "
              f"{'RE-SEGMENTED' if e.plan_changed else 'unchanged'}"
              f"{' via step cache' if e.plan_changed and not e.retraced else ''}"
              f"  sched {e.scheduling_seconds * 1e3:.2f} ms "
              f"hidden={e.overhead_hidden}")
        if e.plan not in shown:
            shown.add(e.plan)
            costs = rt.costs_for_epoch(e.epoch)
            # forward buckets back to the paper's 1-indexed segments
            segments = tuple((b[0] + 1, b[-1] + 1) for b in e.plan.forward)
            bw = rt.network.model_at(e.epoch).bandwidth_bps / 1e9
            print(f"  --- forward timeline at {bw:g} Gbps ---")
            for line in render_timeline(costs, segments,
                                        phase="forward").splitlines():
                print(f"  {line}")

    changed = any(e.plan_changed for e in rt.events)
    print(f"\nplans traced: {rt.traces}  cache hits: {rt.cache_hits}")
    print("schedule re-segmented under drift" if changed
          else "WARNING: decision did not change — try --worker-flops 1e9")


if __name__ == "__main__":
    main()
