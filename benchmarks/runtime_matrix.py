"""Runtime-regime x model-family cost matrix (``repro.runtime``).

One bench, ``runtime_matrix``, published by CI as part of
``BENCH_pipeline.json``: every registered runtime regime priced against
one representative of each model family — paper CNN (vgg19),
transformer (granite-3-2b), MoE (granite-moe-1b-a400m), SSM
(recurrentgemma-2b) — entirely at the analytic cost-model level (no jax
compiles), so the sweep is cheap enough to run on every CI pass.

Per (family, runtime) the row reports the one-optimizer-step makespan
under that regime's communication pattern and the speedup over the
unoverlapped sequential baseline of the same regime:

* ``local`` — pure compute, no communication (the floor);
* ``zero`` — single shared uplink, DynaComm vs sequential decomposition;
  ``zero-shifted`` prices it after a mid-run bandwidth shift to
  ``SHIFT_GBPS``, the plan its re-planning loop moves to;
* ``ps`` — heterogeneous PS fleet, consensus decision, straggler
  makespan; ``ps-shifted`` after every uplink shifts to ``SHIFT_GBPS``;
* ``ps-async`` / ``dynamic-ps-async`` / ``fleet-async`` — per-worker
  decisions, mean worker iteration (``dynamic-ps-async`` after the
  uplink shift; ``fleet-async`` adds a 4x straggler to the roster);
* ``pipeline`` — 4-stage balanced partition, 1F1B replay with
  DynaComm-segmented boundary transfers vs whole-tensor.
"""

from __future__ import annotations

from typing import Dict, List

FAMILIES = (
    ("cnn", "vgg19"),
    ("transformer", "granite-3-2b"),
    ("moe", "granite-moe-1b-a400m"),
    ("ssm", "recurrentgemma-2b"),
)

BANDWIDTH_GBPS = 1.0
SHIFT_GBPS = 0.25            # the shifted rows' mid-run drift target
COMPUTE_FLOPS = 1e12
STAGES = 4
MICROBATCHES = 4


def _profiles(family: str, model: str):
    """(profiles, per-micro-batch boundary activation bytes)."""
    if family == "cnn":
        from repro.models.cnn import PAPER_CNNS
        # Mid-network VGG feature map (28x28x512, f32) per sample.
        return PAPER_CNNS[model](batch=32), 8 * 28 * 28 * 512 * 4
    from repro.configs import INPUT_SHAPES, get_config
    from repro.models.profiles import layer_profiles
    cfg = get_config(model)
    shape = INPUT_SHAPES["train_4k"]
    act = (shape.global_batch // MICROBATCHES) * shape.seq_len \
        * cfg.d_model * 4
    return layer_profiles(cfg, shape), act


def _topology(straggler: bool = False, up_gbps: float = BANDWIDTH_GBPS):
    from repro.ps import PSTopology, asymmetric_link
    workers = 4
    flops = [COMPUTE_FLOPS if w < workers // 2 else COMPUTE_FLOPS / 2
             for w in range(workers)]
    if straggler:
        flops[-1] = COMPUTE_FLOPS / 4
    return PSTopology(
        num_servers=2,
        links=tuple(asymmetric_link(2e9, up_gbps * 1e9,
                                    rtt_s=0.01, setup_s=0.003)
                    for _ in range(workers)),
        worker_flops=tuple(flops))


def _single_link_rows(family, model, profiles, net_gbps):
    """zero: one uplink, DynaComm vs sequential decomposition."""
    from repro.core import (EdgeNetworkModel, costs_from_profiles,
                            iteration_time, schedule)
    net = EdgeNetworkModel(bandwidth_bps=net_gbps * 1e9)
    costs = costs_from_profiles(profiles, net=net,
                                compute_flops_per_s=COMPUTE_FLOPS)
    dyn = schedule(costs, "dynacomm")
    seq = schedule(costs, "sequential")
    return (iteration_time(costs, *dyn), iteration_time(costs, *seq))


def _ps_rows(family, model, profiles, *, straggler=False,
             up_gbps=BANDWIDTH_GBPS):
    """(consensus makespan, per-worker mean, sequential makespan)."""
    import numpy as np

    from repro.core import consensus_decision, iteration_time, schedule
    topo = _topology(straggler=straggler,
                     up_gbps=up_gbps).topology_costs(profiles)
    _, makespan = consensus_decision(topo, "dynacomm")
    _, seq_makespan = consensus_decision(topo, "sequential")
    per_worker = [iteration_time(c, *schedule(c, "dynacomm"))
                  for c in topo.workers]
    return makespan, float(np.mean(per_worker)), seq_makespan


def _pipeline_row(family, model, profiles, act_bytes):
    from repro.core import EdgeNetworkModel
    from repro.pipeline import (boundary_costs, make_schedule,
                                partition_profiles, plan_boundary, simulate)

    net = EdgeNetworkModel(bandwidth_bps=BANDWIDTH_GBPS * 1e9)
    part = partition_profiles(profiles, STAGES,
                              compute_flops_per_s=COMPUTE_FLOPS)
    fwd, bwd, fx, bx, wx_f, wx_b = [], [], [], [], [], []
    for s, (lo, hi) in enumerate(part.segments):
        f = sum(p.flops_fwd for p in profiles[lo - 1:hi]) / COMPUTE_FLOPS
        b = sum(p.bwd for p in profiles[lo - 1:hi]) / COMPUTE_FLOPS
        fwd.append(f / MICROBATCHES)
        bwd.append(b / MICROBATCHES)
    for bdy in range(STAGES - 1):
        costs = boundary_costs(act_bytes, MICROBATCHES, net=net,
                               stage_fwd_s=fwd[bdy + 1],
                               stage_bwd_s=bwd[bdy], chunks=4)
        plan = plan_boundary(bdy, costs, microbatches=MICROBATCHES,
                             chunks=4)
        fx.append(plan.effective_waits[0])
        bx.append(plan.effective_waits[1])
        wx_f.append(plan.whole_waits[0])
        wx_b.append(plan.whole_waits[1])
    sched = make_schedule("1f1b", STAGES, MICROBATCHES)
    seg = simulate(sched, fwd, bwd, fwd_transfer=fx, bwd_transfer=bx)
    whole = simulate(sched, fwd, bwd, fwd_transfer=wx_f, bwd_transfer=wx_b)
    return seg, whole, part


def runtime_matrix() -> List[Dict]:
    """Every runtime regime priced against every model family."""
    rows = []
    for family, model in FAMILIES:
        profiles, act_bytes = _profiles(family, model)
        compute = sum(p.flops_fwd + p.bwd for p in profiles) / COMPUTE_FLOPS

        def row(runtime, iteration_s, baseline_s, **extra):
            rows.append({
                "family": family, "model": model, "runtime": runtime,
                "iteration_s": round(iteration_s, 4),
                "sequential_s": round(baseline_s, 4),
                "speedup": round(baseline_s / iteration_s, 4)
                if iteration_s > 0 else 1.0, **extra})

        row("local", compute, compute)

        dyn, seq = _single_link_rows(family, model, profiles,
                                     BANDWIDTH_GBPS)
        row("zero", dyn, seq)
        dyn_s, seq_s = _single_link_rows(family, model, profiles,
                                         SHIFT_GBPS)
        row("zero-shifted", dyn_s, seq_s, shifted_gbps=SHIFT_GBPS)

        mk, mean_w, seq_mk = _ps_rows(family, model, profiles)
        row("ps", mk, seq_mk)
        row("ps-async", mean_w, seq_mk)
        mk_s, mean_s, seq_s = _ps_rows(family, model, profiles,
                                       up_gbps=SHIFT_GBPS)
        row("ps-shifted", mk_s, seq_s, shifted_gbps=SHIFT_GBPS)
        row("dynamic-ps-async", mean_s, seq_s, shifted_gbps=SHIFT_GBPS)
        mk_f, mean_f, seq_f = _ps_rows(family, model, profiles,
                                       straggler=True)
        row("fleet-async", mean_f, seq_f, straggler_makespan=round(mk_f, 4))

        seg, whole, part = _pipeline_row(family, model, profiles, act_bytes)
        row("pipeline", seg.makespan, whole.makespan,
            stages=STAGES, microbatches=MICROBATCHES,
            bubble=round(seg.bubble_fraction, 4),
            partition=[list(s) for s in part.segments])
    return rows


MATRIX_BENCHES = {
    "runtime_matrix": runtime_matrix,
}
