import os
os.environ["JAX_PLATFORMS"] = "cpu"       # a virtual CPU pool, never a chip
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=128 "
                           "--xla_backend_optimization_level=0 "
                           "--xla_llvm_disable_expensive_passes=true")
"""Fig. 2 (right): weak scaling over multi-GPU NODES for the 3DGAN.

Runs in its OWN process (sets a 128-device pool before importing jax).
For each node count we fold the virtual devices into the paper's
hierarchical ``(node, device)`` topology (8 V100-class GPUs per node),
compile the GAN step THROUGH THE UNIFIED ENGINE (``--loop`` /
``--grad-reduce`` select the strategy) at the paper's per-device BS=128
(global batch grows with devices: weak scaling), and report TWO curves
side by side:

- measured: the roofline-derived step/epoch time from the COMPILED
  program — jaxpr FLOPs/bytes against the topology's per-device
  constants, plus the compiled collective traffic priced on the
  topology's NVLink/NIC links;
- predicted: the cloud planner's curve (`cloud/planner.py`) — the
  committed measured single-node step baseline
  (``results/BENCH_fig1_loop.json``) replayed through the interconnect
  model.  No efficiency table anywhere on either path.

Per-strategy overlap accounting (``--grad-reduce all`` records every
strategy side by side):

- ``exposed_comm_s`` — MEASURED: the traced program's collective
  schedule (`parallel/jaxpr_cost.collective_schedule`, custom loop —
  the builtin loop's collectives are inserted by GSPMD after lowering,
  so its column stays null) prices only the collectives with no
  independent later compute to hide under;
- ``modeled_exposed_comm_s`` — the interconnect model's exposure
  (`cloud/interconnect.exposed_comm_s` with the real per-round
  tail-bucket plan from ``adversarial.grad_reduce_traffic``) applied to
  the SAME measured payload;
- ``step_gap_s`` — |modeled - measured|: the model-fidelity gap the
  ``--check`` gate pins (overlap's gap must not exceed hierarchical's);
- ``state_bytes_per_device`` / ``state_bytes_per_device_zero1`` and the
  ``opt_master_bytes_per_device*`` pair — what one device holds with a
  replicated vs ZeRO-1-sharded (`optim.optimizers.zero1`) optimizer;
  ``--check`` also pins zero1's optimizer+master bytes to ~replicated/N.

``--out`` writes the BENCH_fig2_weakscaling.json artifact (the schema
``benchmarks/run.py`` records for every bench).
"""
import argparse
import json
import time

import numpy as np


def _state_rows(cfg, n):
    """Per-device state-byte columns: replicated vs ZeRO-1 over n shards
    (shapes only — nothing is allocated)."""
    import jax
    from repro.optim import optimizers as opt_lib
    from repro.parallel import jaxpr_cost
    from repro.train import engine as engine_lib

    def shapes(g_opt, d_opt):
        task = engine_lib.gan_task(cfg, g_opt, d_opt)
        return jax.eval_shape(task.init, jax.random.key(0))

    rep = shapes(opt_lib.rmsprop(1e-4), opt_lib.rmsprop(1e-4))
    z = shapes(opt_lib.zero1(opt_lib.rmsprop(1e-4), n),
               opt_lib.zero1(opt_lib.rmsprop(1e-4), n))
    # "optimizer + master" per device: the replicated baseline's masters
    # are the f32 params themselves, zero1 folds its master copy into
    # the sharded optimizer subtree
    om_rep = (jaxpr_cost.per_device_state_bytes(
        {"g": rep.g_opt, "d": rep.d_opt}, 1)
        + jaxpr_cost.per_device_state_bytes(
            {"g": rep.g_params, "d": rep.d_params}, 1))
    om_z = jaxpr_cost.per_device_state_bytes(
        {"g": z.g_opt, "d": z.d_opt}, n)
    return {
        "state_bytes_per_device": jaxpr_cost.per_device_state_bytes(rep, 1),
        "state_bytes_per_device_zero1":
            jaxpr_cost.per_device_state_bytes(z, n),
        "opt_master_bytes_per_device": om_rep,
        "opt_master_bytes_per_device_zero1": om_z,
    }


def run(node_counts=(1, 2, 4, 8, 16), devices_per_node=8, loop="builtin",
        grad_reduce="hierarchical", bucket_mb=4.0, results_dir="results"):
    import jax
    from jax.sharding import Mesh
    from repro.cloud import interconnect, planner
    from repro.configs import calo3dgan
    from repro.core import adversarial
    from repro.launch import build as build_lib
    from repro.launch.mesh import gpu_topology
    from repro.parallel import collectives, jaxpr_cost

    strategies = (collectives.GRAD_REDUCE_STRATEGIES
                  if grad_reduce == "all" else (grad_reduce,)
                  if isinstance(grad_reduce, str) else tuple(grad_reduce))
    bucket_bytes = int(bucket_mb * (1 << 20))
    cfg = calo3dgan.config()
    traffic = adversarial.grad_reduce_traffic(cfg, bucket_bytes)
    try:
        anchor = planner.load_anchor(results_dir)
    except (OSError, KeyError, ValueError):
        anchor = None

    devs = np.array(jax.devices())
    rows = []
    for nodes in node_counts:
        topo = gpu_topology(nodes, devices_per_node)
        n = topo.total_devices
        mesh = Mesh(devs[:n].reshape(nodes, devices_per_node),
                    ("node", "device"))
        state_cols = _state_rows(cfg, n)
        for strat in strategies:
            pred = (planner.weak_scaling_curve(
                anchor, node_counts=(nodes,),
                devices_per_node=devices_per_node, strategy=strat,
                bucket_bytes=bucket_bytes,
                tail_bytes=traffic.get("tail_bytes"))[0]
                if anchor is not None else None)
            with mesh:
                built = build_lib.build_gan_train(mesh, policy_name="bf16",
                                                  loop=loop,
                                                  grad_reduce=strat,
                                                  bucket_mb=bucket_mb)
                lowered = built.lower()
                compiled = lowered.compile()
            jc = jaxpr_cost.cost_of(built.fn, *built.args)
            sched = jaxpr_cost.schedule_of(built.fn, *built.args)
            coll = collectives.collective_stats(compiled.as_text())
            compute_s = jc["flops"] / (n * topo.peak_flops)
            memory_s = jc["bytes"] / (n * topo.hbm_bw)
            # the compiled program's own all-reduce payload (per-device
            # HLO result bytes), priced on the topology's links
            ar_bytes = sum(v["bytes"] for k, v in coll.items())
            coll_s = interconnect.allreduce_s(ar_bytes, topo, strat,
                                              bucket_bytes)
            step_s = max(compute_s, memory_s) + coll_s
            # measured vs modeled exposure, both priced on the SAME
            # measured payload (coll_s) so the gap isolates schedule
            # fidelity, not payload accounting
            meas_frac = (sched["exposed_frac"]
                         if sched["n_collectives"] else None)
            model_total = sum(
                interconnect.allreduce_s(b, topo, strat, bucket_bytes)
                for _, b in traffic["rounds"])
            model_exposed = interconnect.exposed_comm_s(
                traffic["rounds"], topo, strat, bucket_bytes,
                compute_s=compute_s, tail_bytes=traffic.get("tail_bytes"))
            model_frac = model_exposed / model_total if model_total else 1.0
            exposed_s = None if meas_frac is None else coll_s * meas_frac
            modeled_s = coll_s * model_frac
            global_batch = 128 * n
            # same dataset scale as the predicted column (planner rows)
            steps_per_epoch = planner.EPOCH_SAMPLES / global_batch
            row = {
                "topology": topo.name, "nodes": nodes, "devices": n,
                "global_batch": global_batch,
                "loop": loop, "grad_reduce": strat,
                "measured_step_s": step_s,
                "measured_epoch_s": step_s * steps_per_epoch,
                "measured_compute_s": compute_s,
                "measured_memory_s": memory_s,
                "measured_collective_s": coll_s,
                "hlo_collective_bytes": ar_bytes,
                "jaxpr_collective_bytes": jc["collective_bytes"],
                "reduce_scatter_bytes": jc["reduce_scatter_bytes"],
                "all_gather_bytes": jc["all_gather_bytes"],
                "exposed_comm_s": exposed_s,
                "measured_exposed_frac": meas_frac,
                "modeled_exposed_comm_s": modeled_s,
                "modeled_exposed_frac": model_frac,
                "step_gap_s": (None if exposed_s is None
                               else abs(modeled_s - exposed_s)),
                **state_cols,
            }
            if pred is not None:
                row.update({
                    "predicted_step_s": pred["step_s_pred"],
                    "predicted_epoch_s": pred["epoch_s_pred"],
                    "predicted_comm_s": pred["comm_s_pred"],
                    "anchor_step_s": anchor.step_s,
                    "anchor_source": anchor.source,
                })
            rows.append(row)
            jax.clear_caches()
    # efficiencies, each strategy normalized to its own single-node row
    for strat in strategies:
        srows = [r for r in rows if r["grad_reduce"] == strat]
        ideal0 = srows[0]["measured_epoch_s"] * srows[0]["devices"]
        for r in srows:
            r["measured_efficiency"] = (ideal0 / r["devices"]
                                        / r["measured_epoch_s"])
        if anchor is not None:
            p0 = srows[0]["predicted_step_s"]
            for r in srows:
                r["predicted_efficiency"] = p0 / r["predicted_step_s"]
    return rows


def check(rows) -> list:
    """The scaleout gate (``--check``): returns a list of failure strings.

    1. model fidelity — where measured exposure exists (custom loop),
       overlap's |modeled - measured| exposure gap must not exceed
       hierarchical's at the same node count;
    2. ZeRO-1 memory — per-device optimizer+master bytes must be
       ~replicated/N (padding + the step scalar allow 10% + 64 KiB).
    """
    failures = []
    by_nodes = {}
    for r in rows:
        by_nodes.setdefault(r["nodes"], {})[r["grad_reduce"]] = r
    for nodes, strats in sorted(by_nodes.items()):
        o, h = strats.get("overlap"), strats.get("hierarchical")
        if o and h and o["step_gap_s"] is not None \
                and h["step_gap_s"] is not None:
            if o["step_gap_s"] > h["step_gap_s"] + 1e-12:
                failures.append(
                    f"nodes={nodes}: overlap model gap "
                    f"{o['step_gap_s']:.3e}s > hierarchical "
                    f"{h['step_gap_s']:.3e}s")
        any_row = next(iter(strats.values()))
        n = any_row["devices"]
        if n > 1:
            rep = any_row["opt_master_bytes_per_device"]
            z = any_row["opt_master_bytes_per_device_zero1"]
            bound = rep / n * 1.10 + 65536
            if z > bound:
                failures.append(
                    f"nodes={nodes}: zero1 opt+master {z}B/device > "
                    f"replicated/N bound {bound:.0f}B (replicated {rep}B)")
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--loop", default="builtin",
                    choices=("builtin", "custom"))
    ap.add_argument("--grad-reduce", default="hierarchical",
                    choices=("flat", "hierarchical", "overlap", "all"))
    ap.add_argument("--bucket-mb", type=float, default=4.0)
    ap.add_argument("--node-counts", default="1,2,4,8,16",
                    help="comma-separated node counts (8 devices each)")
    ap.add_argument("--devices-per-node", type=int, default=8)
    ap.add_argument("--results", default="results",
                    help="dir holding BENCH_fig1_loop.json (the measured "
                         "single-node anchor the predictions replay)")
    ap.add_argument("--check", action="store_true",
                    help="gate: overlap's measured-vs-modeled exposure gap "
                         "<= hierarchical's, and zero1 state ~ 1/N "
                         "(exit 1 on failure)")
    ap.add_argument("--out", default="",
                    help="write BENCH-schema JSON here")
    args = ap.parse_args(argv)
    node_counts = tuple(int(x) for x in args.node_counts.split(","))
    t0 = time.time()
    rows = run(node_counts=node_counts,
               devices_per_node=args.devices_per_node, loop=args.loop,
               grad_reduce=args.grad_reduce, bucket_mb=args.bucket_mb,
               results_dir=args.results)
    print(f"bench_fig2_weakscaling: 3DGAN weak scaling over (node, device) "
          f"(BS=128/device, {args.loop} loop, {args.grad_reduce} reduce)")
    have_pred = "predicted_efficiency" in rows[0]
    hdr = (f"{'devices':>8} {'reduce':>13} {'meas_epoch_s':>12} "
           f"{'meas_eff':>9} {'exp_comm_ms':>11} {'gap_ms':>8}"
           + (f" {'pred_eff':>9}" if have_pred else ""))
    print(hdr)
    for r in rows:
        exp = r["exposed_comm_s"]
        gap = r["step_gap_s"]
        line = (f"{r['devices']:>8} {r['grad_reduce']:>13} "
                f"{r['measured_epoch_s']:>12.1f} "
                f"{r['measured_efficiency']:>9.3f} "
                f"{'-' if exp is None else format(exp * 1e3, '.3f'):>11} "
                f"{'-' if gap is None else format(gap * 1e3, '.3f'):>8}")
        if have_pred:
            line += f" {r['predicted_efficiency']:>9.3f}"
        print(line)
    r0 = rows[0]
    print(f"state bytes/device at {r0['devices']} devices: replicated "
          f"{r0['state_bytes_per_device']}, zero1 "
          f"{r0['state_bytes_per_device_zero1']} (opt+master "
          f"{r0['opt_master_bytes_per_device']} -> "
          f"{r0['opt_master_bytes_per_device_zero1']})")
    print("paper Fig.2-right: ~linear to 128 devices; both columns derive "
          "from measurement + structure, no efficiency table")
    rc = 0
    if args.check:
        failures = check(rows)
        for f in failures:
            print(f"CHECK FAIL: {f}")
        if not failures:
            print("check OK: overlap model gap <= hierarchical's; zero1 "
                  "opt+master state ~ replicated/N")
        rc = 1 if failures else 0
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"benchmark": "fig2_weakscaling",
                       "seconds": round(time.time() - t0, 3),
                       "rows": rows}, f, indent=2, default=str)
        print(f"[wrote {args.out}]")
    if rc:
        raise SystemExit(rc)
    return rows


if __name__ == "__main__":
    main()
