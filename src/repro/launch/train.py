"""Training launcher: GAN (the paper's workload) or any assigned LM arch.

Runs on whatever devices exist (CPU in this container, TPU pod in prod —
the same build path the dry-run compiles for 256/512 chips).

Both workloads route through the unified data-parallel engine
(`train/engine.py`), which implements the paper's two loop strategies:

  --loop builtin   jit + NamedSharding; the compiler places per-device
                   batches (the tf.distribute analogue)
  --loop custom    shard_map; explicit per-device batch assignment,
                   local updates, explicit psum gradient reduction
  --loop naive     (GAN only) the keras.train_on_batch baseline with
                   sequential host-side generator-input init

Usage:
  python -m repro.launch.train --arch calo3dgan --steps 200 --loop custom
  python -m repro.launch.train --arch qwen2-1.5b --reduced --steps 50
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as config_base
from repro.data.calo import CaloSimulator, CaloSpec
from repro.data.tokens import MarkovTokens
from repro.launch import compile_cache
from repro.launch.mesh import make_dev_mesh, make_node_mesh
from repro.models import api
from repro.optim import optimizers as opt_lib
from repro.parallel import sharding
from repro.substrate.precision import get_policy
from repro.train import checkpoint as ckpt_lib
from repro.train import engine as engine_lib
from repro.train.metrics import MetricLog


def train_gan(args, mesh, log: MetricLog):
    from repro.configs import calo3dgan
    from repro.core import adversarial, gan, validation

    cfg = calo3dgan.reduced() if args.reduced else calo3dgan.config()
    # --precision beats --policy (the legacy spelling, still honored when
    # given explicitly) beats the config's precision field; the resolved
    # name is recorded in the checkpoint manifest for serving restore
    precision = args.precision or args.policy or cfg.precision
    g_opt = opt_lib.rmsprop(args.lr)
    d_opt = opt_lib.rmsprop(args.lr)

    sim = CaloSimulator(CaloSpec(image_shape=cfg.image_shape), seed=args.seed)
    B = args.batch or cfg.batch_size

    if args.loop == "naive":
        precision = "f32"               # the baseline is measured pure-f32
        state = adversarial.init_state(jax.random.key(args.seed), cfg,
                                       g_opt, d_opt)
        step = adversarial.NaiveStep(cfg, g_opt, d_opt, seed=args.seed)
        for i, batch in zip(range(args.steps), sim.batches(B)):
            state, m = step(state, batch)
            log.log(i, **m)
    else:
        # "fused" is the legacy name for the jit'd single-program loop —
        # that is exactly the engine's builtin mode.
        loop = "builtin" if args.loop == "fused" else args.loop
        task = engine_lib.gan_task(cfg, g_opt, d_opt,
                                   policy=get_policy(precision),
                                   microbatches=args.microbatches)
        # the 3DGAN is PURE data parallelism: every mesh axis is a replica
        eng = engine_lib.Engine(
            mesh, loop, dp_axes=tuple(mesh.axis_names),
            grad_reduce=args.grad_reduce or cfg.grad_reduce,
            bucket_mb=args.bucket_mb or cfg.reduce_bucket_mb)
        state, _ = eng.fit(task, sim.batches(B), args.steps,
                           rng=jax.random.key(args.seed), log=log,
                           log_every=args.log_every,
                           sync_every=args.sync_every or None)

    # physics validation vs fresh Monte Carlo
    mc = next(sim.batches(256))
    noise = jax.random.normal(jax.random.key(7), (256, cfg.latent_dim))
    fake = gan.generate(state.g_params, noise, jnp.asarray(mc["e_p"]),
                        jnp.asarray(mc["theta"]), cfg)
    rep = validation.validation_report(np.asarray(fake), mc["image"],
                                       mc["e_p"], mc["e_p"])
    print("physics validation:", {k: round(v, 4) for k, v in rep.items()})
    if args.ckpt:
        ckpt_lib.save(args.ckpt, state.g_params, step=args.steps,
                      extra={"kind": "gan_generator",
                             "precision": precision})
        print(f"saved generator to {args.ckpt} (precision={precision})")
    return state


def train_lm(args, mesh, log: MetricLog):
    cfg = (config_base.reduced_config(args.arch) if args.reduced
           else config_base.get_config(args.arch))
    from repro.launch.serve import _resolve_pallas_routing
    cfg = _resolve_pallas_routing(cfg, args)
    model = api.get_model(cfg)
    policy = get_policy(args.policy or "f32")
    optimizer = opt_lib.adamw(opt_lib.warmup_cosine(args.lr, 20, args.steps))

    loop = "builtin" if args.loop == "fused" else args.loop
    task = engine_lib.lm_task(model, cfg, optimizer, policy=policy,
                              microbatches=args.microbatches)
    eng = engine_lib.Engine(mesh, loop,
                            grad_reduce=args.grad_reduce or "flat",
                            bucket_mb=args.bucket_mb or 4.0)

    B, S = args.batch or 8, args.seq or 256
    data = MarkovTokens(cfg.vocab, seed=args.seed)

    def gen():
        if cfg.family == "audio":
            while True:
                yield {"audio_emb": np.random.default_rng(0).normal(
                           0, 1, (B, S, cfg.d_model)).astype(np.float32),
                       "tokens": data.sample(B, min(S, cfg.max_target_positions))}
        elif cfg.family == "vlm":
            n_patch = 16
            while True:
                pos = np.broadcast_to(np.arange(S, dtype=np.int32),
                                      (3, B, S)).copy()
                yield {"tokens": data.sample(B, S - n_patch),
                       "embeds": np.zeros((B, n_patch, cfg.d_model), np.float32),
                       "positions": pos}
        else:
            while True:
                yield {"tokens": data.sample(B, S)}

    t0 = time.time()
    state, _ = eng.fit(task, gen(), args.steps,
                       rng=jax.random.key(args.seed), log=log,
                       log_every=args.log_every,
                       sync_every=args.sync_every or None)
    dt = time.time() - t0
    print(f"{args.arch}: {sharding.count_params(state.params):,} params "
          f"({'reduced' if args.reduced else 'full'}), loop={loop}")
    print(f"{args.steps} steps in {dt:.1f}s "
          f"({args.steps * B * S / dt:.0f} tok/s)")
    if args.ckpt:
        ckpt_lib.save(args.ckpt, state.params, step=args.steps,
                      extra={"arch": args.arch})
    return state.params


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="calo3dgan",
                    choices=config_base.ARCH_IDS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--loop", default="builtin",
                    choices=("builtin", "custom", "fused", "naive"),
                    help="builtin: jit+NamedSharding; custom: shard_map "
                         "with explicit psum; fused: legacy alias of "
                         "builtin; naive: host-orchestrated GAN baseline")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient accumulation inside each step")
    ap.add_argument("--grad-reduce", default="",
                    choices=("", "flat", "hierarchical", "overlap"),
                    help="gradient-reduction strategy: flat psum-mean, "
                         "hierarchical 2-level (intra-node psum + bucketed "
                         "inter-node psums over a (node, device) mesh), or "
                         "overlap (reverse-order buckets issued inside the "
                         "backward pass); empty defers to the config's "
                         "grad_reduce field")
    ap.add_argument("--bucket-mb", type=float, default=0.0,
                    help="inter-node bucket size (MiB) for hierarchical "
                         "grad-reduce (0: config default)")
    ap.add_argument("--nodes", type=int, default=0,
                    help="fold the host devices into a virtual "
                         "(nodes, devices/node) 2-level mesh instead of "
                         "the flat (data, model) dev mesh")
    ap.add_argument("--policy", default="",
                    help="LM mixed-precision policy name (default f32); "
                         "for the GAN arch an explicit value is honored "
                         "as a legacy alias of --precision")
    ap.add_argument("--precision", default="",
                    help="GAN precision policy (f32|bf16|fp16); empty "
                         "defers to --policy, then the config's "
                         "precision field (bf16)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--pallas-attn", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="LM archs: route attention through the Pallas "
                         "kernels (default: off; env REPRO_PALLAS_ATTN "
                         "overrides)")
    ap.add_argument("--pallas-ssm", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="LM archs: route SSM scans through the Pallas "
                         "kernels (default: off; env REPRO_PALLAS_SSM "
                         "overrides)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log", default="")
    ap.add_argument("--log-every", type=int, default=1,
                    help="steps per metric window; >1 removes the "
                         "per-step device->host sync (async dispatch)")
    ap.add_argument("--sync-every", type=int, default=0,
                    help="force a device sync every N steps to bound "
                         "run-ahead (0: never)")
    args = ap.parse_args(argv)
    if args.loop == "naive" and args.arch != "calo3dgan":
        ap.error("--loop naive is the GAN train_on_batch baseline; "
                 "LM archs support builtin/custom/fused")

    mesh = (make_node_mesh(nodes=args.nodes) if args.nodes
            else make_dev_mesh(data=len(jax.devices())))
    log = MetricLog(args.log or None, print_every=max(args.steps // 20, 1))
    if args.arch == "calo3dgan":
        train_gan(args, mesh, log)
    else:
        train_lm(args, mesh, log)


if __name__ == "__main__":
    main()
