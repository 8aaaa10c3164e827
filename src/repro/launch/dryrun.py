"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) pair this lowers + compiles the real
train / prefill / serve step on the production mesh — single-pod (16, 16)
= 256 chips and multi-pod (2, 16, 16) = 512 chips — using ShapeDtypeStruct
stand-ins (no allocation).  Failures (sharding mismatch, OOM at compile,
unsupported collective) are bugs in the framework.

Per pair it records: memory_analysis (bytes/device), cost_analysis (FLOPs /
bytes for the §Roofline report) and the collective-traffic breakdown parsed
from the optimized HLO.  Results go to JSON for benchmarks/roofline.py.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun
  python -m repro.launch.dryrun --arch calo3dgan --multi-pod
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"       # a virtual CPU pool, never a chip
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks the device count on first init.
if not os.environ.get("REPRO_XLA_FULL_OPT"):
    # Reduce LLVM codegen effort for the CPU stand-in backend (8x faster
    # compiles).  GSPMD partitioning, layout & memory assignment — the
    # things the dry-run proves — run identically; cost/memory analysis
    # values were verified unchanged vs. full optimization.
    os.environ["XLA_FLAGS"] += (" --xla_backend_optimization_level=0"
                                " --xla_llvm_disable_expensive_passes=true")
import argparse
import json
import time
import traceback

import jax

from repro.configs import base as config_base
from repro.launch import build as build_lib
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.parallel import collectives, jaxpr_cost


def run_pair(arch_id: str, shape_name: str, *, multi_pod: bool = False,
             rules_name: str = "fsdp_tp", policy_name: str = "bf16",
             save_hlo: str = "", remat: bool = True, data: int = 16,
             model: int = 16, seq_shard: bool = False,
             microbatches: int = 1, train_seq_shard: bool = True,
             verbose: bool = True) -> dict:
    """Lower + compile one (arch, shape, mesh) combination; return metrics."""
    from repro.parallel import sharding as sharding_lib

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod, data=data, model=model)
    rec = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "devices": mesh.devices.size, "rules": rules_name,
        "policy": policy_name, "seq_shard": seq_shard,
    }
    _seq_ctx = sharding_lib.seq_sharding(seq_shard)
    _seq_ctx.__enter__()

    if arch_id == "calo3dgan":
        if shape_name != "train_4k":    # GAN has one workload: training
            return {**rec, "status": "skipped",
                    "reason": "GAN: train only (paper's workload)"}
        with mesh:
            built = build_lib.build_gan_train(mesh, policy_name=policy_name)
    else:
        cfg = config_base.get_config(arch_id)
        shape = config_base.INPUT_SHAPES[shape_name]
        if not api.decode_supported(cfg, shape):
            return {**rec, "status": "skipped",
                    "reason": "decode shape unsupported (DESIGN.md notes)"}
        with mesh:
            if shape.kind == "train":
                built = build_lib.build_train(
                    arch_id, shape_name, mesh, rules_name=rules_name,
                    policy_name=policy_name, remat=remat,
                    microbatches=microbatches,
                    seq_shard=train_seq_shard)
            elif shape.kind == "prefill":
                built = build_lib.build_prefill(
                    arch_id, shape_name, mesh, rules_name=rules_name,
                    policy_name=policy_name)
            else:
                built = build_lib.build_serve(
                    arch_id, shape_name, mesh, rules_name=rules_name,
                    policy_name=policy_name)

    try:
        with mesh:
            lowered = built.lower()
    finally:
        _seq_ctx.__exit__(None, None, None)
    t_lower = time.time() - t0
    with mesh:
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collectives.collective_stats(hlo)                  # loop-scaled
    coll_raw = collectives.collective_stats(hlo, scale_loops=False)
    # exact structural FLOPs/bytes from the jaxpr (XLA's cost_analysis
    # counts scan bodies once; the jaxpr walk multiplies by trip count)
    jc = jaxpr_cost.cost_of(built.fn, *built.args)

    rec.update({
        "status": "ok",
        "kind": built.kind,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "argument_bytes_per_device": int(getattr(
            mem, "argument_size_in_bytes", 0)),
        "output_bytes_per_device": int(getattr(
            mem, "output_size_in_bytes", 0)),
        "temp_bytes_per_device": int(getattr(
            mem, "temp_size_in_bytes", 0)),
        "peak_bytes_per_device": int(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)),
        "collectives": coll,
        "collective_result_bytes": sum(v["bytes"] for v in coll.values()),
        "collective_result_bytes_unscaled": sum(
            v["bytes"] for v in coll_raw.values()),
        "jaxpr_flops": jc["flops"],
        "jaxpr_bytes": jc["bytes"],
    })
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
        rec["hlo_path"] = save_hlo
    if verbose:
        print(f"[dryrun] {arch_id:16s} {shape_name:12s} mesh={rec['mesh']:9s}"
              f" OK  flops={rec['flops']:.3e}"
              f" bytes={rec['bytes_accessed']:.3e}"
              f" coll={rec['collective_result_bytes']:.3e}"
              f" peakB/dev={rec['peak_bytes_per_device']:.3e}"
              f" (lower {t_lower:.0f}s compile {t_compile:.0f}s)", flush=True)
    return rec


ALL_ARCHS = config_base.ARCH_IDS          # 10 assigned + calo3dgan
ALL_SHAPES = tuple(config_base.INPUT_SHAPES)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--rules", default="fsdp_tp",
                    choices=("dp", "tp", "fsdp_tp"))
    ap.add_argument("--policy", default="bf16")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--save-hlo", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--data", type=int, default=16,
                    help="data-axis size (data*model must be 256)")
    ap.add_argument("--model", type=int, default=16)
    ap.add_argument("--seq-shard", action="store_true",
                    help="shard the residual seq dim over 'model'")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="gradient-accumulation microbatches per step")
    ap.add_argument("--no-train-seq-shard", action="store_true",
                    help="disable seq sharding inside train steps")
    args = ap.parse_args()

    archs = ALL_ARCHS if args.all or not args.arch else (args.arch,)
    shapes = ALL_SHAPES if args.all or not args.shape else (args.shape,)
    pods = (False, True) if args.both_meshes else (args.multi_pod,)

    results, failures = [], []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                try:
                    rec = run_pair(arch, shape, multi_pod=mp,
                                   rules_name=args.rules,
                                   policy_name=args.policy,
                                   save_hlo=args.save_hlo,
                                   remat=not args.no_remat,
                                   data=args.data, model=args.model,
                                   seq_shard=args.seq_shard,
                                   microbatches=args.microbatch,
                                   train_seq_shard=not args.no_train_seq_shard)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "FAILED", "error": f"{type(e).__name__}: {e}"}
                    failures.append(rec)
                    print(f"[dryrun] {arch} {shape} multi_pod={mp} FAILED:")
                    traceback.print_exc()
                results.append(rec)
                jax.clear_caches()      # bound compile-cache memory

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out if args.out.endswith(".json")
                  else args.out + ".json", "w") as f:
            json.dump(results, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    print(f"\n[dryrun] {n_ok} ok, {n_skip} skipped, {len(failures)} failed "
          f"of {len(results)}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
