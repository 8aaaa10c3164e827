"""Serving launcher: LM continuous batching OR 3DGAN fast simulation.

Two routes, selected by ``--model``:

- ``--model lm`` (default) — batched-request decode demo over any
  decodable LM architecture (`serve/engine.py` slot pool).
- ``--model gan`` — the paper's deliverable: serve calorimeter showers
  from a trained 3DGAN generator through the bucketed fast-simulation
  engine (`serve/simulate.py`), with the rolling physics gate checking
  every window against fresh Monte Carlo.

Usage:
  python -m repro.launch.serve --arch qwen2-1.5b --reduced --requests 8
  python -m repro.launch.serve --model gan --reduced --requests 16 \
      --ckpt ckpts/gan  # generator saved by launch/train --ckpt
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.configs import base as config_base
from repro.launch import compile_cache
from repro.launch.mesh import make_dev_mesh
from repro.models import api
from repro.serve.engine import Request, ServeEngine


def _resolve_pallas_routing(cfg, args):
    """Kernel routing: --pallas-attn/--pallas-ssm override, else
    REPRO_PALLAS_ATTN / REPRO_PALLAS_SSM, else off
    (`autotune.default_use_pallas`).  Frozen into the config here, so
    the decision is trace-time static."""
    import dataclasses as _dc

    from repro.kernels import autotune as autotune_lib
    attn = (args.pallas_attn if args.pallas_attn is not None
            else autotune_lib.default_use_pallas("REPRO_PALLAS_ATTN"))
    ssm = (args.pallas_ssm if args.pallas_ssm is not None
           else autotune_lib.default_use_pallas("REPRO_PALLAS_SSM"))
    return _dc.replace(cfg, use_pallas_attn=attn, use_pallas_ssm=ssm)


def serve_lm(args):
    cfg = (config_base.reduced_config(args.arch) if args.reduced
           else config_base.get_config(args.arch))
    if not cfg.decode_supported:
        raise SystemExit(f"{args.arch} does not support decode")
    cfg = _resolve_pallas_routing(cfg, args)
    model = api.get_model(cfg)
    params = model.init(jax.random.key(args.seed), cfg)
    mesh = make_dev_mesh(data=len(jax.devices()))

    eng = ServeEngine(cfg, params, slots=args.slots, max_len=args.max_len,
                      mesh=mesh)
    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        plen = int(rng.integers(4, args.prompt_len + 1))
        eng.submit(Request(rid=rid,
                           prompt=rng.integers(0, cfg.vocab, plen,
                                               dtype=np.int32),
                           max_new_tokens=args.max_new))
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    total_new = sum(len(r.tokens) for r in done)
    print(f"served {len(done)} requests, {total_new} tokens "
          f"in {dt:.1f}s ({total_new / dt:.1f} tok/s)")
    for r in sorted(done, key=lambda r: r.rid)[:4]:
        print(f"  req {r.rid}: prompt[:4]={r.prompt[:4].tolist()} "
              f"-> {r.tokens[:8]}...")


def serve_gan(args):
    from repro.configs import calo3dgan
    from repro.core import gan, validation
    from repro.data.calo import CaloSimulator, CaloSpec
    from repro.serve.replicas import ReplicaFaultInjector, ReplicaGroup
    from repro.serve.scheduler import SchedulerConfig
    from repro.serve.simulate import PhysicsGate, SimRequest, SimulateEngine
    from repro.train import checkpoint as ckpt_lib
    from repro.train.faults import FaultPlan

    cfg = calo3dgan.reduced() if args.reduced else calo3dgan.config()
    if args.ckpt and os.path.exists(os.path.join(args.ckpt, "arrays.npz")):
        params = ckpt_lib.restore_gan_generator(args.ckpt, cfg)
        policy_name = ckpt_lib.manifest_precision(args.ckpt)
        print(f"restored generator from {args.ckpt} "
              f"(step {ckpt_lib.latest_step(args.ckpt)}, "
              f"precision={policy_name})")
    else:
        params = gan.init_generator(jax.random.key(args.seed), cfg)
        policy_name = "f32"
        print("WARNING: no --ckpt given (or not found) — serving an "
              "UNTRAINED generator; the physics gate will show it")

    sim = CaloSimulator(CaloSpec(image_shape=cfg.image_shape),
                        seed=args.seed + 1)
    mc = next(sim.batches(max(args.gate_window, 256)))
    gate = PhysicsGate(validation.reference_profiles(mc["image"], mc["e_p"]),
                       window=args.gate_window)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    mesh = make_dev_mesh(data=len(jax.devices()))

    # resilience wiring: SLA-derived admission + replica failover
    sched = None
    if args.sla_s > 0 and args.drain_rate > 0:
        sched = SchedulerConfig.for_sla(args.drain_rate, args.sla_s,
                                        promote_after_steps=args.promote_after)
    elif args.promote_after > 0:
        sched = SchedulerConfig(promote_after_steps=args.promote_after)
    replicas = None
    if args.replicas > 1 or args.chaos_trace:
        injector = (ReplicaFaultInjector(FaultPlan.load(args.chaos_trace))
                    if args.chaos_trace else None)
        replicas = ReplicaGroup(max(args.replicas, 2), injector=injector,
                                hedge_stall_ms=args.hedge_stall_ms)
    eng = SimulateEngine(cfg, params, buckets=buckets, mesh=mesh, gate=gate,
                         policy_name=policy_name, sched=sched,
                         replicas=replicas, max_kl=args.max_kl)
    eng.warmup()

    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        eng.submit(SimRequest(
            rid=rid,
            primary_energy=float(rng.uniform(10.0, 500.0)),
            n_events=int(rng.integers(1, args.max_events + 1)),
            seed=int(rng.integers(0, 2**31 - 1)),
            deadline_s=args.sla_s if args.sla_s > 0 else None,
            priority=int(rng.integers(0, args.priorities))))
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    gate.flush()
    n_ev = eng.stats["events_generated"]
    lats = sorted(r.latency_s for r in done)

    def pct(q):   # empty-safe percentile (same indexing as the bench)
        return 1e3 * lats[min(len(lats) - 1, int(len(lats) * q))] if lats \
            else 0.0

    print(f"served {len(done)} requests / {n_ev} events in {dt:.2f}s "
          f"({n_ev / dt:.1f} events/s); "
          f"latency p50={pct(0.50):.0f}ms p99={pct(0.99):.0f}ms")
    print(f"  steps={eng.stats['steps']} bucket_steps="
          f"{eng.stats['bucket_steps']} padded={eng.stats['padded_events']} "
          f"transfers={eng.stats['device_transfers']} "
          f"compiles={eng.compile_count}")
    if eng.rejected:
        print(f"  rejected {len(eng.rejected)} requests:")
        for r in eng.rejected[:8]:
            print(f"    req {r.rid}: {r.error['reason']} — "
                  f"{r.error['detail']}")
    if replicas is not None:
        print(f"  replicas: {replicas.health_report()} "
              f"group_stats={replicas.stats}")
    report = eng.degraded_report()
    if report["mode"] != "healthy":
        print(f"  DEGRADED: {report['mode']} shed={report['shed']}")
    for i, rep in enumerate(gate.reports):
        print(f"  gate window {i}: "
              + " ".join(f"{k}={rep[k]:.4f}" for k in
                         ("longitudinal_kl", "transverse_x_kl",
                          "transverse_y_kl", "response_rel_err")))
    if gate.drifted(args.max_kl):
        print(f"  GATE: profile divergence exceeds --max-kl {args.max_kl} "
              "— generator drift (or an untrained generator)")


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("lm", "gan"), default="lm",
                    help="lm: continuous-batching decode; gan: 3DGAN "
                         "fast-simulation service")
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    # lm route
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--pallas-attn", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="route attention through the Pallas kernels "
                         "(default: off; env REPRO_PALLAS_ATTN overrides)")
    ap.add_argument("--pallas-ssm", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="route SSM scans through the Pallas kernels "
                         "(default: off; env REPRO_PALLAS_SSM overrides)")
    # gan route
    ap.add_argument("--ckpt", default="",
                    help="generator checkpoint dir (launch/train --ckpt)")
    ap.add_argument("--max-events", type=int, default=64,
                    help="request sizes drawn uniformly from [1, max]")
    ap.add_argument("--buckets", default="8,32,128",
                    help="comma-separated fixed batch buckets")
    ap.add_argument("--gate-window", type=int, default=256,
                    help="events per physics-gate report")
    ap.add_argument("--max-kl", type=float, default=1.0,
                    help="drift threshold on the worst profile KL")
    # gan resilience (serve/scheduler.py + serve/replicas.py)
    ap.add_argument("--sla-s", type=float, default=0.0,
                    help="per-request latency SLA in seconds (0 = no "
                         "deadlines, no admission bound)")
    ap.add_argument("--drain-rate", type=float, default=0.0,
                    help="measured service throughput (events/s) used to "
                         "derive the admission bound from --sla-s")
    ap.add_argument("--promote-after", type=int, default=0,
                    help="age-based promotion after this many passed-over "
                         "bucket steps (0 = off)")
    ap.add_argument("--priorities", type=int, default=1,
                    help="draw request priorities uniformly from "
                         "[0, priorities)")
    ap.add_argument("--replicas", type=int, default=1,
                    help=">1 enables the replica failover group")
    ap.add_argument("--chaos-trace", default="",
                    help="replay a train/faults.FaultPlan JSON against the "
                         "replica group (e.g. results/serve_chaos_trace.json)")
    ap.add_argument("--hedge-stall-ms", type=float, default=200.0,
                    help="hedge scripted stalls at/above this many ms")
    args = ap.parse_args()
    if args.model == "gan":
        serve_gan(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
