import os
os.environ["JAX_PLATFORMS"] = "cpu"       # a virtual CPU pool, never a chip
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=32 "
                           "--xla_backend_optimization_level=0 "
                           "--xla_llvm_disable_expensive_passes=true")
"""Fig. 4 (center/right): hardware-layout / worker-configuration sweep at a
fixed 32 devices.

The paper varied (workers x GPUs-per-worker) at 32 total GPUs and found
"more GPUs per worker" beats "many small workers" (communication overhead).
The mesh analogue: (data, model) factorizations of 32 chips.  We compile
qwen2-1.5b train_4k (batch cut to fit the small pool) under each layout and
compare the roofline collective term — the paper's communication penalty,
derived from the compiled collective schedule instead of wall time.
"""
import numpy as np


def run(layouts=((32, 1), (16, 2), (8, 4), (4, 8))):
    import jax
    from jax.sharding import Mesh
    from repro.launch import build as build_lib
    from repro.launch.mesh import HARDWARE
    from repro.parallel import collectives, jaxpr_cost
    from benchmarks.roofline import ici_per_chip_bytes

    devs = np.array(jax.devices())
    rows = []
    for (d, m) in layouts:
        mesh = Mesh(devs[: d * m].reshape(d, m), ("data", "model"))
        with mesh:
            built = build_lib.build_train(
                "qwen2-1.5b", "train_4k", mesh, rules_name="fsdp_tp")
            # shrink global batch 256 -> 32 to match the 32-chip pool
            import jax as _jax
            b = {"tokens": _jax.ShapeDtypeStruct((32, 4096), np.int32)}
            lowered = built.fn.lower(built.args[0], built.args[1], b)
            compiled = lowered.compile()
            jc = jaxpr_cost.cost_of(built.fn, built.args[0], built.args[1], b)
        coll = collectives.collective_stats(compiled.as_text())
        n = d * m
        compute_s = jc["flops"] / (n * HARDWARE["peak_flops_bf16"])
        memory_s = jc["bytes"] / (n * HARDWARE["hbm_bw"])
        coll_s = ici_per_chip_bytes(coll, n) / HARDWARE["ici_bw"]
        rows.append({
            "layout": f"data={d} x model={m}",
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": coll_s,
            "step_bound_s": max(compute_s, memory_s, coll_s),
            "coll_bytes_per_chip": ici_per_chip_bytes(coll, n),
        })
        jax.clear_caches()
    return rows


def main(argv=None):
    import argparse
    import json
    import time

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="", help="write BENCH-schema JSON here")
    args = ap.parse_args(argv)
    t0 = time.time()
    rows = run()
    print("bench_fig4_layout: (data x model) layouts at 32 chips, "
          "qwen2-1.5b train (global batch 32)")
    print(f"{'layout':>18} {'compute_s':>10} {'memory_s':>10} "
          f"{'coll_s':>10} {'bound_s':>10}")
    for r in rows:
        print(f"{r['layout']:>18} {r['compute_s']:>10.2e} "
              f"{r['memory_s']:>10.2e} {r['collective_s']:>10.2e} "
              f"{r['step_bound_s']:>10.2e}")
    best = min(rows, key=lambda r: r["step_bound_s"])
    print(f"best layout: {best['layout']} (paper: fewer, larger workers win)")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"benchmark": "fig4_layout",
                       "seconds": round(time.time() - t0, 3),
                       "rows": rows}, f, indent=2, default=str)
        print(f"[wrote {args.out}]")
    return rows


if __name__ == "__main__":
    main()
