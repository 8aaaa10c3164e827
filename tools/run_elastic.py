#!/usr/bin/env python
import argparse
import json
import os
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default="results/elastic_trace.json",
                    help="committed FaultPlan JSON to replay")
    ap.add_argument("--steps", type=int, default=0,
                    help="global steps (0: read from the trace's meta)")
    ap.add_argument("--devices", type=int, default=4,
                    help="virtual host devices (XLA_FLAGS, set pre-import)")
    ap.add_argument("--loop", default="builtin",
                    choices=("builtin", "custom"))
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--budget", type=float, default=40.0,
                    help="USD budget for the planner recommend() replay")
    ap.add_argument("--deadline", type=float, default=2e5,
                    help="deadline (s) for the planner recommend() replay")
    ap.add_argument("--loss-tol", type=float, default=2e-5,
                    help="max |faulted - clean| final-loss gap (--check)")
    ap.add_argument("--kl-tol", type=float, default=0.05,
                    help="max per-profile KL gap vs the clean run (--check)")
    ap.add_argument("--out", default="results/BENCH_elastic.json")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero when a physics/loss gate fails")
    return ap.parse_args(argv)


ARGS = parse_args()
os.environ["JAX_PLATFORMS"] = "cpu"       # a virtual CPU pool, never a chip
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={ARGS.devices} "
    + os.environ.get("XLA_FLAGS", ""))
"""Elastic-training driver: execute a planner schedule through a fault trace.

The end-to-end §5.1 story in one command: take the cost frontier's
preemptible recommendation, run the 3DGAN on a virtual ``(node, device)``
topology while replaying a committed preemption trace
(``results/elastic_trace.json``) through `train/faults.FaultInjector`,
and measure what elasticity actually costs:

- an UNINTERRUPTED run and the FAULTED run (same seed, same data replay,
  same checkpoint cadence) — final losses and physics-validation KLs are
  compared directly, the "zero lost physics" gate;
- lost steps / recovery seconds / checkpoint fallbacks / re-meshes from
  the `train/elastic.ElasticEngine` report;
- the measured overhead fraction folded back into the cost frontier
  (`cloud/planner.apply_elastic_overhead`) and ``recommend()`` re-asked —
  does preemptible capacity still win after paying for recovery?

Writes ``results/BENCH_elastic.json``; ``--check`` turns the loss + KL
comparisons into exit status for CI (elastic-smoke job).

  PYTHONPATH=src python tools/run_elastic.py --check
"""

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))


def main(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import calo3dgan
    from repro.core import gan, validation
    from repro.data.calo import CaloSimulator, CaloSpec
    from repro.cloud import planner
    from repro.train import faults
    from repro.train.elastic import ElasticEngine
    from repro.optim import optimizers as opt_lib
    from repro.train import engine as engine_lib

    assert len(jax.devices()) >= args.devices, jax.devices()

    with open(args.trace) as f:
        trace_meta = json.load(f)
    plan = faults.FaultPlan.from_json(trace_meta)
    steps = args.steps or int(trace_meta.get("steps", 12))
    nodes, dpn = trace_meta.get("topology", [2, 2])
    batch = int(trace_meta.get("global_batch", 8))
    cfg = calo3dgan.bench()
    spec = CaloSpec(image_shape=cfg.image_shape)
    rng = jax.random.key(1)

    def make_batches(start):
        # fresh seeded sim + skip: the stream from global step `start` on
        # is EXACTLY what an uninterrupted run would have seen
        return CaloSimulator(spec, seed=11).batches(batch, skip=start)

    def run(tmp, injector):
        task = engine_lib.gan_task(cfg, opt_lib.rmsprop(1e-4),
                                   opt_lib.rmsprop(1e-4))
        eng = ElasticEngine(nodes, dpn, loop=args.loop, ckpt_dir=tmp,
                            ckpt_every=args.ckpt_every, keep=args.keep)
        t0 = time.perf_counter()
        state, report = eng.fit(task, make_batches, steps, rng=rng,
                                injector=injector)
        jax.block_until_ready(state)
        return state, report, time.perf_counter() - t0

    def physics(state):
        mc = next(CaloSimulator(spec, seed=77).batches(256))
        noise = jax.random.normal(jax.random.key(7), (256, cfg.latent_dim))
        fake = gan.generate(state.g_params, noise, jnp.asarray(mc["e_p"]),
                            jnp.asarray(mc["theta"]), cfg)
        return validation.validation_report(np.asarray(fake), mc["image"],
                                            np.asarray(mc["e_p"]),
                                            mc["e_p"])

    import tempfile
    with tempfile.TemporaryDirectory() as td:
        print(f"[clean] {steps} steps on {nodes}x{dpn} ({args.loop} loop)")
        clean_state, clean_rep, clean_s = run(os.path.join(td, "clean"),
                                              None)
        print(f"[clean] {clean_s:.1f}s  "
              f"losses={_losses(clean_rep['metrics'])}")
        print(f"[faulted] replaying {args.trace}: "
              f"{[ (e.step, e.kind) for e in plan.events ]}")
        injector = faults.FaultInjector(plan)
        faulted_state, rep, faulted_s = run(os.path.join(td, "faulted"),
                                            injector)
        print(f"[faulted] {faulted_s:.1f}s  losses="
              f"{_losses(rep['metrics'])}  recoveries="
              f"{rep['preemptions']} (remesh {rep['remeshes']}, restart "
              f"{rep['restarts']}), lost {rep['lost_steps']} steps, "
              f"recovery {rep['recovery_s'] * 1e3:.0f}ms, "
              f"ckpt fallbacks {rep['fallbacks']}")
        unfired = [e for e in plan.events if e not in injector.fired]
        if unfired:
            print(f"WARNING: {len(unfired)} trace events never fired: "
                  f"{unfired}")

        loss_diff = max(abs(float(rep["metrics"][k])
                            - float(clean_rep["metrics"][k]))
                        for k in ("g_loss", "d_loss_real", "d_loss_fake"))
        clean_phys, faulted_phys = physics(clean_state), physics(
            faulted_state)
        kl_keys = [k for k in clean_phys if k.endswith("_kl")]
        kl_diff = max(abs(faulted_phys[k] - clean_phys[k]) for k in kl_keys)
        print(f"final-loss gap {loss_diff:.2e} (tol {args.loss_tol:g}); "
              f"physics-KL gap {kl_diff:.2e} (tol {args.kl_tol:g})")

    # -- fold the measured overhead back into the planner -------------------
    overhead = max(faulted_s / clean_s - 1.0, 0.0)
    frontier = planner.cost_frontier(5200.0)
    rec = planner.recommend(frontier, args.budget, args.deadline)
    derated = planner.apply_elastic_overhead(frontier, overhead)
    rec_el = planner.recommend(derated, args.budget, args.deadline)
    for tag, r in (("naive", rec), ("elastic-aware", rec_el)):
        print(f"recommend[{tag}]: "
              + (f"{r['device']} x{r['n']} ${r['total_cost_usd']:.2f}"
                 if r else "infeasible"))

    payload = {
        "bench": "elastic", "loop": args.loop, "steps": steps,
        "topology": [nodes, dpn], "trace": os.path.basename(args.trace),
        "rows": {
            "clean_s": clean_s, "faulted_s": faulted_s,
            "overhead_frac": overhead,
            "recovery_s": rep["recovery_s"],
            "lost_steps": rep["lost_steps"],
            "preemptions": rep["preemptions"],
            "remeshes": rep["remeshes"],
            "restarts": rep["restarts"],
            "ckpt_fallbacks": rep["fallbacks"],
            "ckpt_saved": rep["ckpt_stats"]["saved"],
            "loss_diff": loss_diff, "kl_diff": kl_diff,
        },
        "recommend": {
            "budget_usd": args.budget, "deadline_s": args.deadline,
            "naive": rec, "elastic_aware": rec_el,
        },
        "physics": {"clean": clean_phys, "faulted": faulted_phys},
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"[wrote {args.out}]")

    if args.check:
        ok = (loss_diff <= args.loss_tol and kl_diff <= args.kl_tol
              and rep["lost_steps"] <= steps and not unfired)
        print("elastic gate:", "OK" if ok else "FAIL")
        return 0 if ok else 1
    return 0


def _losses(metrics):
    return {k: round(float(v), 5) for k, v in metrics.items()
            if k.endswith("loss") or "_loss_" in k}


if __name__ == "__main__":
    raise SystemExit(main(ARGS))
