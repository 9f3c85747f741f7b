"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combo.

The ONLY entry point that forges 512 host devices — the flag must be set
before any jax initialization, hence the first two lines.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results.jsonl]
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHITECTURES, INPUT_SHAPES, get_config, shape_applicable
from repro.dist.sharding import (batch_shardings, cache_shardings,
                                 params_shardings)
from repro.launch.hlo_analysis import collective_bytes, roofline
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import decode_specs, input_specs, state_specs
from repro.models import model as model_lib
from repro.models import scanned
from repro.models.profiles import model_flops_per_token
from repro.optim import adamw
from repro.serve.decode import build_decode_step
from repro.train.loop import build_train_step

# Microbatched gradient accumulation for the biggest trainings (keeps the
# per-device activation footprint inside HBM; see DESIGN.md §5).
ACCUM_STEPS = {
    "grok-1-314b": 8,
    "llava-next-34b": 8,
    "gemma-7b": 4,
    "gemma3-4b": 2,
}


def _tokens_per_step(cfg, shape) -> float:
    if shape.mode == "decode":
        return shape.global_batch        # one token per sequence
    return shape.global_batch * shape.seq_len


def build_lowered(arch: str, shape_name: str, *, multi_pod: bool,
                  fsdp: bool = True, dtype=jnp.bfloat16,
                  accum: int | None = None, remat: bool = True,
                  cache_seq_over_model: bool = False, barrier: bool = False,
                  remat_sqrt: int = 0, moe_ep: bool = False):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)

    if shape.mode == "train":
        opt = adamw(1e-4)
        params_s = jax.eval_shape(
            lambda k: scanned.init_stacked(cfg, k, dtype), jax.random.PRNGKey(0))
        opt_s = jax.eval_shape(opt.init, params_s)
        batch_s = input_specs(cfg, shape, dtype)
        if accum is None:
            accum = ACCUM_STEPS.get(arch, 1) if shape_name == "train_4k" else 1

        data_axes = tuple(a for a in mesh.axis_names if a != "model")
        d_entry = data_axes if len(data_axes) > 1 else data_axes[0]
        act_sh = NamedSharding(mesh, P(d_entry, None, None))
        logit_sh = NamedSharding(mesh, P(d_entry, None, "model"))

        def loss_fn(sp, batch):
            return scanned.train_loss_scanned(cfg, sp, batch, remat=remat,
                                              act_sharding=act_sh,
                                              logits_sharding=logit_sh,
                                              barrier=barrier,
                                              remat_sqrt=remat_sqrt)

        if accum == 1:
            def step(sp, opt_state, batch):
                loss, grads = jax.value_and_grad(loss_fn)(sp, batch)
                sp, opt_state = opt.update(grads, opt_state, sp)
                return sp, opt_state, loss
        else:
            def step(sp, opt_state, batch):
                def reshape(x):
                    return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])
                micro = jax.tree_util.tree_map(reshape, batch)

                def body(carry, mb):
                    gacc, lacc = carry
                    loss, grads = jax.value_and_grad(loss_fn)(sp, mb)
                    gacc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), gacc, grads)
                    return (gacc, lacc + loss), None

                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), sp)
                (grads, lsum), _ = jax.lax.scan(body, (zeros, 0.0), micro)
                grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
                sp, opt_state = opt.update(grads, opt_state, sp)
                return sp, opt_state, lsum / accum

        psh = params_shardings(cfg, params_s, mesh, fsdp=fsdp, moe_ep=moe_ep)
        osh = params_shardings(cfg, opt_s, mesh, fsdp=fsdp, moe_ep=moe_ep)
        bsh = batch_shardings(batch_s, mesh)
        jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None))
        lowered = jitted.lower(params_s, opt_s, batch_s)

    elif shape.mode == "prefill":
        params_s = jax.eval_shape(
            lambda k: scanned.init_stacked(cfg, k, dtype), jax.random.PRNGKey(0))
        batch_s = input_specs(cfg, shape, dtype)

        data_axes = tuple(a for a in mesh.axis_names if a != "model")
        act_sh = NamedSharding(
            mesh, P(data_axes if len(data_axes) > 1 else data_axes[0],
                    None, None))

        def fn(sp, batch):
            logits, caches, _ = scanned.forward_scanned(
                cfg, sp, batch, mode="prefill", remat=False, last_only=True,
                act_sharding=act_sh)
            return logits, caches

        psh = params_shardings(cfg, params_s, mesh, fsdp=fsdp)
        bsh = batch_shardings(batch_s, mesh)
        jitted = jax.jit(fn, in_shardings=(psh, bsh))
        lowered = jitted.lower(params_s, batch_s)

    else:  # decode
        params_s, _ = state_specs(cfg, adamw(1e-4), dtype)
        token_s, caches_s = decode_specs(cfg, shape, dtype)
        psh = params_shardings(cfg, params_s, mesh, fsdp=fsdp)
        tsh = batch_shardings(token_s, mesh)
        csh = cache_shardings(caches_s, mesh, batch=shape.global_batch,
                              seq_over_model=cache_seq_over_model)
        step = build_decode_step(cfg)
        jitted = jax.jit(step, in_shardings=(psh, tsh, csh),
                         out_shardings=(None, csh))
        lowered = jitted.lower(params_s, token_s, caches_s)

    return lowered, mesh, cfg, shape


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            fsdp: bool = True, verbose: bool = True, **kw) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "mode": shape.mode, "status": "skip" if not ok else "pending",
    }
    if not ok:
        rec["reason"] = reason
        if verbose:
            print(f"[skip] {arch} x {shape_name}: {reason}")
        return rec

    t0 = time.perf_counter()
    try:
        lowered, mesh, cfg, shape = build_lowered(
            arch, shape_name, multi_pod=multi_pod, fsdp=fsdp, **kw)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        chips = mesh.devices.size

        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        rl = roofline(flops=flops, hbm_bytes=bytes_acc, coll=coll, chips=chips)

        model_fl = model_flops_per_token(cfg) * _tokens_per_step(cfg, shape)
        if shape.mode != "train":
            model_fl /= 3.0          # forward only (no 2x backward)

        rec.update({
            "status": "ok",
            "chips": chips,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_bytes":
                    getattr(mem, "generated_code_size_in_bytes", None),
            },
            "flops_per_device": flops,
            "hbm_bytes_per_device": bytes_acc,
            "collective_bytes_per_device": rl.coll_bytes,
            "collective_detail": {k: v for k, v in coll.items()
                                  if not k.startswith("_")},
            "collective_counts": coll["_counts"],
            "roofline": {
                "compute_s": rl.compute_s,
                "memory_s": rl.memory_s,
                "collective_s": rl.collective_s,
                "dominant": rl.dominant,
            },
            "model_flops_global": model_fl,
            "model_flops_per_device": model_fl / chips,
            "useful_flop_ratio":
                (model_fl / chips) / flops if flops else None,
        })
        if verbose:
            r = rec["roofline"]
            print(f"[ok] {arch} x {shape_name} x {mesh_name}: "
                  f"compile {t_compile:.1f}s | "
                  f"compute {r['compute_s']:.3e}s mem {r['memory_s']:.3e}s "
                  f"coll {r['collective_s']:.3e}s -> {r['dominant']}-bound | "
                  f"temp {rec['memory']['temp_bytes']}")
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
        if verbose:
            print(f"[ERROR] {arch} x {shape_name} x {mesh_name}: {e}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHITECTURES), default=None)
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--cache-seq-over-model", action="store_true")
    ap.add_argument("--barrier", action="store_true")
    ap.add_argument("--remat-sqrt", type=int, default=0)
    ap.add_argument("--moe-ep", action="store_true")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    combos = []
    archs = sorted(ARCHITECTURES) if (args.all or not args.arch) else [args.arch]
    shapes = sorted(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                combos.append((arch, shape, mp))

    for arch, shape, mp in combos:
        rec = run_one(arch, shape, multi_pod=mp, fsdp=not args.no_fsdp,
                      accum=args.accum, barrier=args.barrier,
                      remat_sqrt=args.remat_sqrt, moe_ep=args.moe_ep,
                      cache_seq_over_model=args.cache_seq_over_model)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
