"""Training launcher: a thin client of the ``repro.runtime`` registry.

Every regime is one :class:`repro.runtime.RuntimeConfig` built through
:func:`repro.runtime.build_runtime` — the flags below are nothing but an
argparse → config mapping, and ``--config runtime.json`` bypasses them
entirely (``--dump-config`` prints the equivalent JSON for any flag
combination, which is exactly what the smoke configs under
``examples/runtime_configs/`` contain).

Runtimes (``--runtime``, ``--staleness k`` switches the ps variants to
their asynchronous form):

* ``local`` (default) — single-process jit training on whatever devices
  exist; reduced configs runnable on CPU.
* ``zero`` — the DynaComm-bucketed ZeRO trainer over a 1-D data mesh,
  schedule chosen by ``--strategy``.  On a static network the plan is
  decided once at startup; ``--bw-shift-gbps`` (a bandwidth drift),
  ``--cost-source measured`` or ``--drift-detect`` (re-schedule from
  *observed* step times) start the run-time loop (paper Section IV-C):
  re-plan every ``--steps-per-epoch`` steps, swap compiled steps when the
  decision changes.
* ``ps`` — the parameter-server subsystem: ``--ps-servers`` shards behind
  asymmetric ``--down-gbps``/``--up-gbps`` links, consensus-planned;
  ``--up-shift-gbps`` degrades every uplink at ``--shift-epoch`` and the
  plan follows per topology epoch.  With ``--staleness k``:
  bounded-staleness asynchronous execution (``--throttle reject|wait``;
  ``--aggregate`` commits same-version pushes as one BSP step), and with
  ``--up-shift-gbps`` as well, per-worker re-plans swapped into the async
  event loop (``dynamic-ps-async``).
* ``fleet-async`` — elastic membership over the deterministic event
  engine: ``--fleet-schedule events.json`` scripts joins/leaves/failures/
  drift (a JSON list of fleet event dicts), each membership change
  re-plans every surviving worker and re-shards the server.
* ``pipeline`` — stage-partitioned pipeline parallelism: ``--stages S``
  contiguous stages balanced by profiled fc+bc, ``--microbatches M``
  micro-batches per step under ``--pipeline-schedule`` (gpipe | 1f1b),
  with inter-stage activations crossing each boundary as
  DynaComm-scheduled segments (``--transfer-chunks`` splits each
  micro-batch's boundary tensor for finer overlap).

Examples::

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --reduced --steps 50
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b \
        --reduced --runtime zero --strategy dynacomm --steps 50
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --reduced --runtime zero --steps 60 --steps-per-epoch 20 \
        --bw-gbps 10 --bw-shift-gbps 1 --shift-epoch 1
    PYTHONPATH=src python -m repro.launch.train \
        --config examples/runtime_configs/ps_uplink_shift.json --steps 12
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ARCHITECTURES
from repro.runtime import (CompressionConfig, ExecutionConfig, FleetConfig,
                           MeasureConfig, NetworkConfig, PipelineConfig,
                           RuntimeConfig, ScheduleConfig, TopologyConfig,
                           build_runtime)


def config_from_flags(args) -> RuntimeConfig:
    """The argparse → RuntimeConfig mapping (the whole launcher logic)."""
    name = args.runtime
    if args.staleness is not None and name == "ps":
        name = "ps-async" if args.up_shift_gbps is None \
            else "dynamic-ps-async"

    network = topology = None
    if name in ("zero", "pipeline"):
        # pass the shift through even for 'pipeline': RuntimeConfig owns
        # the "a drift needs the run-time loop" diagnostic
        network = NetworkConfig(
            bandwidth_gbps=args.bw_gbps,
            shift_gbps=args.bw_shift_gbps,
            shift_epoch=args.shift_epoch)
    elif name != "local":
        up_shift = None
        if args.up_shift_gbps is not None:
            if args.up_shift_gbps <= 0:
                raise SystemExit(f"--up-shift-gbps must be positive, got "
                                 f"{args.up_shift_gbps}")
            up_shift = args.up_gbps / args.up_shift_gbps
        topology = TopologyConfig(
            servers=args.ps_servers,
            workers=args.ps_workers if name.endswith("async") else None,
            down_gbps=args.down_gbps, up_gbps=args.up_gbps,
            worker_flops=args.worker_flops,
            up_shift_factor=up_shift, shift_epoch=args.shift_epoch)

    fleet = None
    if args.fleet_schedule is not None and name != "fleet-async":
        raise SystemExit("--fleet-schedule scripts elastic membership; it "
                         "needs --runtime fleet-async")
    if name == "fleet-async":
        events = ()
        if args.fleet_schedule is not None:
            import json
            with open(args.fleet_schedule) as fh:
                events = tuple(json.load(fh))
        fleet = FleetConfig(events=events,
                            workers_per_shard=args.workers_per_shard)

    pipeline = None
    stages = getattr(args, "stages", None)
    microbatches = getattr(args, "microbatches", None)
    if name == "pipeline":
        pipeline = PipelineConfig(
            stages=stages or 2, microbatches=microbatches or 2,
            schedule=getattr(args, "pipeline_schedule", "1f1b"),
            chunks=getattr(args, "transfer_chunks", 1))
    elif stages is not None or microbatches is not None:
        raise SystemExit("--stages/--microbatches configure the pipeline "
                         "runtime; add --runtime pipeline")

    return RuntimeConfig(
        runtime=name, arch=args.arch, reduced=args.reduced,
        fleet=fleet, pipeline=pipeline,
        batch=args.batch, seq=args.seq,
        optimizer=args.optimizer, lr=args.lr,
        schedule=ScheduleConfig(
            strategy=args.strategy,
            reschedule_every=args.steps_per_epoch,
            drift_detect=args.drift_detect,
            async_planning=args.async_planning,
            plan_cache_size=args.plan_cache_size,
            network=network, topology=topology),
        execution=ExecutionConfig(
            staleness=args.staleness, throttle=args.throttle,
            aggregate=args.aggregate),
        measure=MeasureConfig(
            cost_source=args.cost_source,
            compute_flops_per_s=args.worker_flops),
        compression=CompressionConfig(
            scheme=args.compress,
            topk_fraction=(args.topk_fraction
                           if args.compress == "topk" else None),
            error_feedback=not args.no_error_feedback))


def _print_plan(rt) -> None:
    """The ZeRO-driven regimes' plan and state layout, once at startup
    (``leaves`` with no compressor, ``flat`` with one); a re-planning run
    has no plan before its first step."""
    layout = getattr(getattr(rt, "trainer", None), "layout", None)
    if layout is None:
        return
    plan = rt.plan
    segments = ("" if plan is None else
                f"{len(plan.forward)} pull / {len(plan.backward)} push "
                f"segments, ")
    print(f"[plan] {segments}state layout {layout}")


def _print_events(rt) -> None:
    for e in rt.events:
        if hasattr(e, "resharded"):          # fleet re-plan
            reshard = f" resharded→{e.num_servers} shards " \
                      f"({e.migrated_bytes / 1e6:.1f} MB moved)" \
                      if e.resharded else ""
            print(f"t={e.sim_time:8.3f} @push {e.at_push:4d}: re-plan "
                  f"({e.reason}, worker {e.worker}) — {e.num_workers} "
                  f"workers, "
                  f"{'re-segmented' if e.plan_changed else 'unchanged'}"
                  f"{reshard}  sched {e.scheduling_seconds * 1e3:.2f} ms "
                  f"hidden={e.overhead_hidden}")
        elif hasattr(e, "fleet_size"):       # fleet membership change
            print(f"t={e.sim_time:8.3f}: {e.kind} worker {e.worker} "
                  f"(fleet size {e.fleet_size})")
        elif hasattr(e, "worker_plans"):     # async per-worker re-plan
            segs = [(len(p.forward), len(p.backward))
                    for p in e.worker_plans]
            print(f"epoch {e.epoch:3d} @push {e.at_push:4d}: per-worker "
                  f"pull/push segments {segs}  "
                  f"{'re-segmented' if e.plan_changed else 'unchanged'}  "
                  f"sched {e.scheduling_seconds * 1e3:.2f} ms "
                  f"hidden={e.overhead_hidden}")
        else:                                # sync RescheduleEvent
            pulls, pushes = rt.hlo_counts(e.plan)
            print(f"epoch {e.epoch:3d} step {e.step:4d}: "
                  f"{len(e.plan.forward)} pull / {len(e.plan.backward)} "
                  f"push segments (hlo {pulls} pull / {pushes} push)  "
                  f"{'re-segmented' if e.plan_changed else 'unchanged'}"
                  f"{' [cache hit]' if e.plan_changed and not e.retraced else ''}"
                  f"  sched {e.scheduling_seconds * 1e3:.2f} ms "
                  f"hidden={e.overhead_hidden}")
    if rt.config.regime in ("zero", "ps-sync") and rt.config.is_dynamic:
        print(f"[{rt.config.runtime}] traces {rt.traces}, "
              f"cache hits {rt.cache_hits}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None,
                    help="build the runtime from this RuntimeConfig JSON "
                         "file instead of the flags below")
    ap.add_argument("--dump-config", action="store_true",
                    help="print the RuntimeConfig JSON for these flags "
                         "and exit")
    ap.add_argument("--arch", choices=sorted(ARCHITECTURES),
                    default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale variant (CPU-friendly)")
    ap.add_argument("--runtime",
                    choices=("local", "zero", "ps", "ps-async",
                             "dynamic-ps-async", "fleet-async", "pipeline"),
                    default="local",
                    help="registry name; --staleness k still upgrades "
                         "ps to its -async form (dynamic-ps-async with "
                         "--up-shift-gbps)")
    ap.add_argument("--strategy", default="dynacomm",
                    choices=("sequential", "lbl", "ibatch", "dynacomm"))
    # scheduling knobs (zero + ps runtimes)
    ap.add_argument("--steps-per-epoch", type=int, default=20,
                    help="re-scheduling interval of the re-planning loop")
    ap.add_argument("--bw-gbps", type=float, default=10.0,
                    help="edge uplink bandwidth (Gbit/s)")
    ap.add_argument("--bw-shift-gbps", type=float, default=None,
                    help="drift the uplink to this bandwidth at --shift-epoch")
    ap.add_argument("--shift-epoch", type=int, default=1)
    ap.add_argument("--async-planning", action="store_true",
                    help="pre-plan epoch e+1's decision during epoch e "
                         "(the paper's gt¹ idle window); decisions stay "
                         "bit-identical, only where they are computed "
                         "moves")
    ap.add_argument("--plan-cache-size", type=int, default=256,
                    help="memoized (strategy, costs) -> decision entries "
                         "kept by the planner (LRU)")
    ap.add_argument("--cost-source", choices=("analytic", "measured"),
                    default="analytic")
    ap.add_argument("--drift-detect", action="store_true",
                    help="zero/ps: also re-schedule when observed step "
                         "times drift (EWMA detector)")
    # parameter-server knobs (ps runtimes)
    ap.add_argument("--ps-servers", type=int, default=2,
                    help="number of server shards")
    ap.add_argument("--ps-workers", type=int, default=None,
                    help="async mode only: logical worker count "
                         "(sync mode runs one worker per device)")
    ap.add_argument("--down-gbps", type=float, default=10.0,
                    help="server→worker (pull) bandwidth per link")
    ap.add_argument("--up-gbps", type=float, default=1.0,
                    help="worker→server (push) bandwidth per link")
    ap.add_argument("--staleness", type=int, default=None,
                    help="bounded-staleness k: switch the ps runtimes to "
                         "asynchronous execution")
    ap.add_argument("--throttle", choices=("reject", "wait"),
                    default="reject",
                    help="async ps: evict stale pushes (reject) or SSP "
                         "wait-at-barrier (wait)")
    ap.add_argument("--aggregate", action="store_true",
                    help="async ps wait throttle: commit same-version "
                         "pushes as one BSP step")
    ap.add_argument("--up-shift-gbps", type=float, default=None,
                    help="ps: degrade every uplink to this bandwidth at "
                         "--shift-epoch")
    ap.add_argument("--fleet-schedule", default=None,
                    help="fleet-async: JSON file holding a list of fleet "
                         "event dicts (time/kind/worker/...) to script "
                         "membership churn")
    ap.add_argument("--workers-per-shard", type=int, default=0,
                    help="fleet-async: let the shard count track the "
                         "fleet size (0 keeps --ps-servers fixed)")
    ap.add_argument("--worker-flops", type=float, default=1e10,
                    help="edge-worker compute rate fed to the profiler")
    # pipeline knobs (pipeline runtime)
    ap.add_argument("--stages", type=int, default=None,
                    help="pipeline: number of contiguous stages (DP-"
                         "balanced by profiled fc+bc; default 2)")
    ap.add_argument("--microbatches", type=int, default=None,
                    help="pipeline: micro-batches per step (must divide "
                         "--batch; default 2)")
    ap.add_argument("--pipeline-schedule", choices=("gpipe", "1f1b"),
                    default="1f1b",
                    help="pipeline: micro-batch order (GPipe fill/drain "
                         "or PipeDream-flush 1F1B)")
    ap.add_argument("--transfer-chunks", type=int, default=1,
                    help="pipeline: boundary-tensor chunks per micro-batch "
                         "for DynaComm-segmented activation transfers")
    ap.add_argument("--compress", choices=("none", "int8", "topk"),
                    default="none",
                    help="ps runtimes: compress gradient pushes (int8 "
                         "per-tile quantization or top-k sparsification)")
    ap.add_argument("--topk-fraction", type=float, default=0.01,
                    help="fraction of entries kept by --compress topk")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="disable error-feedback residual accumulation "
                         "on compressed pushes")
    ap.add_argument("--steps", type=int, default=100,
                    help="units of progress to run (must be >= 1)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", choices=("adamw", "sgd"), default="adamw")
    ap.add_argument("--checkpoint", default=None,
                    help="save the runtime state here every "
                         "--checkpoint-every units and after training")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="write a jax.profiler trace of every unit after "
                         "the first (compiling) one under DIR")
    args = ap.parse_args()

    if args.config is not None:
        config = RuntimeConfig.load(args.config)
    else:
        config = config_from_flags(args)
    if args.dump_config:
        print(config.to_json())
        return
    if args.steps < 1:
        raise SystemExit(f"--steps must be >= 1, got {args.steps}")
    if args.trace and args.steps < 2:
        raise SystemExit("--trace records the units after the first: "
                         "give --steps >= 2")

    from repro.configs import get_config
    if get_config(config.arch).frontend != "none":
        raise SystemExit("train.py drives text archs; stubbed-modality "
                         "archs are exercised via the dry-run and tests")

    from repro.launch.chip import device_line, use_compile_cache
    use_compile_cache()
    if args.trace:
        # the cache's key leaves op_name metadata out, so a program loaded
        # from it could carry the scopes of the build that wrote it
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    print(device_line())
    rt = build_runtime(config)
    spec = f"[{config.runtime}] arch {config.arch}" + \
        (" (reduced)" if config.reduced else "") + \
        f", strategy {config.schedule.strategy}"
    if config.regime == "ps-async":
        spec += (f", k={config.execution.staleness or 0} "
                 f"({config.execution.throttle}"
                 f"{'+aggregate' if config.execution.aggregate else ''})")
    if config.runtime == "fleet-async" and config.fleet is not None:
        spec += f", fleet events {len(config.fleet.events)}" \
            if config.fleet.events else \
            f", fleet churn {config.fleet.churn}/s"
    if config.runtime == "pipeline":
        spec += (f", S={config.pipeline.stages} "
                 f"M={config.pipeline.microbatches} "
                 f"({config.pipeline.schedule})")
    print(spec)
    _print_plan(rt)

    t0 = time.perf_counter()
    losses = []
    # periodic checkpointing now rides inside fit(); the outer loop only
    # chunks by the logging cadence for the wall-clock progress line
    while len(losses) < args.steps:
        chunk = min(args.log_every or args.steps, args.steps - len(losses))
        if args.trace and not losses:
            chunk = 1                  # the compiling unit, untraced
        elif args.trace and len(losses) == 1:
            jax.profiler.start_trace(args.trace)
        losses.extend(rt.fit(
            chunk,
            checkpoint_every=(args.checkpoint_every if args.checkpoint
                              else 0),
            checkpoint_path=args.checkpoint))
        if args.log_every:
            dt = (time.perf_counter() - t0) / max(len(losses), 1)
            print(f"step {len(losses):4d}  loss {losses[-1]:.4f}  "
                  f"{dt:.3f}s/step")
    if args.trace:
        jax.profiler.stop_trace()
        print(f"[trace] units 2-{len(losses)} written under {args.trace}")

    _print_events(rt)
    led = rt.ledger
    print(f"[{config.runtime}] {len(losses)} units, final loss "
          f"{losses[-1]:.4f}; transfers: "
          f"{led['pull_bytes'] / 1e6:.1f} MB down / "
          f"{led['push_bytes'] / 1e6:.1f} MB up "
          f"({led['num_pulls']} pulls, {led['num_pushes']} pushes)")
    if config.compression.enabled:
        print(f"[{config.runtime}] push wire "
              f"{led['push_wire_bytes'] / 1e6:.1f} MB "
              f"({config.compression.scheme}, "
              f"{led['push_compression_ratio']:.2f}x vs fp32)")
    if args.checkpoint:
        rt.save_state(args.checkpoint)
        print(f"saved runtime state to {args.checkpoint}")


if __name__ == "__main__":
    main()
