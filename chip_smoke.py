#!/usr/bin/env python
"""Smoke run of the 3DGAN main path on a TPU, at the paper's full width.

Drives the same code the launchers run: `train/engine.py`'s ``gan_task``
plus ``Engine.fit`` for both loops (as ``launch/train.py`` does), then
``checkpoint.save`` -> ``restore_gan_generator`` -> ``SimulateEngine``
serving fast-sim requests (as ``launch/serve.py --model gan`` does), on
``calo3dgan.config()``: 51x51x25 showers, latent 254, batch 128 per
replica, bf16 policy, random weights from ``--seed``.

    python chip_smoke.py              # one chip: train (both loops) + serve
    python chip_smoke.py --chips 4    # data-parallel path on 4 chips only

One process, no subprocesses.  Exits non-zero without a TPU (or with
Pallas forced into interpret mode) and on any failed check; the last
line of stdout is ``{"ok": true, "device": {...}}`` only on success.
Timings printed on the way are a smoke reading, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# agreement between two runs of the same bf16 step that differ in
# reduction order only (loop strategy, device count): |a - b| <= ABS_TOL +
# REL_TOL * |a|.  CPU rehearsals of the same comparisons at
# calo3dgan.reduced() on 4 devices gave gaps of 1.4e-4 (4.6e-5 relative)
# at step 0 and 7.4e-4 (5.5e-4 relative) at the last step; the limit is
# ~10x the relative gap.  Taking the real-batch gradient from one of the
# four shards moved the last step by 2.3e-2 (7.2e-3 relative) and fails.
REL_TOL = 5e-3
ABS_TOL = 1e-3
# metrics that depend only on the real batch and the shared init: the
# custom loop folds each replica's index into its rng (paper §3), so its
# generator-side metrics draw other noise than the builtin loop's
NOISE_FREE = ("d_loss_real", "d_acc_real")
# accuracies count events, so after an update one event on the decision
# boundary may flip between runs; the last step compares the rest
EVENT_COUNTS = ("d_acc_real", "d_acc_fake")
STEPS = 3
REQUESTS = 16
BUCKETS = (8, 32, 128)


class Check(Exception):
    """A smoke check failed."""


def check(cond, msg):
    if not cond:
        raise Check(msg)


class CompileClock:
    """Seconds JAX spent compiling, from its monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


class StepTimer:
    """``Engine.fit`` hook: wall time of each step, blocked on the state."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.marks = []

    def __call__(self, gstep, state):
        import jax
        jax.block_until_ready(state)
        self.marks.append(time.perf_counter())

    def seconds(self):
        edges = [self.t0] + self.marks
        return [b - a for a, b in zip(edges, edges[1:])]


class Rows:
    """Minimal ``log`` sink for ``Engine.fit``: keeps each step's means."""

    def __init__(self):
        self.rows = []

    def log(self, step, **metrics):
        self.rows.append(dict(metrics, step=step))


def fit(cfg, mesh, loop, batches, seed, clock):
    """``launch/train.py``'s GAN path, one step per batch; returns the
    final state, the per-step metrics and the engine."""
    import jax
    from repro.optim import optimizers as opt_lib
    from repro.substrate.precision import get_policy
    from repro.train import engine as engine_lib

    task = engine_lib.gan_task(cfg, opt_lib.rmsprop(1e-4),
                               opt_lib.rmsprop(1e-4),
                               policy=get_policy(cfg.precision))
    eng = engine_lib.Engine(mesh, loop, dp_axes=tuple(mesh.axis_names),
                            grad_reduce=cfg.grad_reduce,
                            bucket_mb=cfg.reduce_bucket_mb)
    rows, timer = Rows(), StepTimer()
    compiled_before = clock.seconds
    steps = len(batches)
    state, _ = eng.fit(task, iter(batches), steps, rng=jax.random.key(seed),
                       log=rows, hooks=(timer,))
    step_s = timer.seconds()
    check(len(rows.rows) == steps, f"{loop}: {len(rows.rows)} of {steps} "
          "steps logged")
    for r in rows.rows:
        for k, v in r.items():
            check(math.isfinite(v), f"{loop} step {r['step']}: {k}={v}")
        check(r.get("nonfinite_skips", 0.0) == 0.0,
              f"{loop} step {r['step']}: nonfinite_skips="
              f"{r.get('nonfinite_skips')}")
    warm = step_s[1:]
    print(f"[smoke reading] {loop} loop on {mesh.size} device(s), global "
          f"batch {len(batches[0]['e_p'])}: compile "
          f"{clock.seconds - compiled_before:.1f}s, first step "
          f"{step_s[0]:.2f}s, warm steps "
          f"{', '.join(f'{s * 1e3:.1f}ms' for s in warm)}", flush=True)
    print(f"  {loop} step 0: " + " ".join(
        f"{k}={v:.5g}" for k, v in rows.rows[0].items() if k != "step"),
        flush=True)
    return state, rows.rows, eng


def agree(name, ref, other, keys):
    """Metrics of one step agree within ABS_TOL + REL_TOL * |ref|."""
    gap = rel = 0.0
    for k in keys:
        a, b = ref[k], other[k]
        check(abs(a - b) <= ABS_TOL + REL_TOL * abs(a),
              f"{name}: step {ref['step']} {k} {a!r} vs {b!r} outside "
              f"rel {REL_TOL} + abs {ABS_TOL}")
        gap = max(gap, abs(a - b))
        rel = max(rel, abs(a - b) / abs(a) if a else 0.0)
    print(f"  {name}: step {ref['step']} {', '.join(keys)} agree within rel "
          f"{REL_TOL} + abs {ABS_TOL} (largest gap {gap:.3g}, relative "
          f"{rel:.3g})", flush=True)


def train_phase(cfg, mesh, seed, clock):
    """Both loops, same seed, same batches; returns the builtin state."""
    from repro.data.calo import CaloSimulator, CaloSpec

    sim = CaloSimulator(CaloSpec(image_shape=cfg.image_shape), seed=seed)
    batches = [b for _, b in zip(range(STEPS), sim.batches(cfg.batch_size))]
    state, rows_b, _ = fit(cfg, mesh, "builtin", batches, seed, clock)
    _, rows_c, _ = fit(cfg, mesh, "custom", batches, seed, clock)
    agree("builtin vs custom", rows_b[0], rows_c[0], NOISE_FREE)
    return state


def serve_phase(cfg, g_params, mesh, seed):
    """Checkpoint the generator, restore it, serve fast-sim requests."""
    import jax
    import numpy as np
    from repro.core import validation
    from repro.data.calo import CaloSimulator, CaloSpec
    from repro.serve.simulate import PhysicsGate, SimRequest, SimulateEngine
    from repro.train import checkpoint as ckpt_lib

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        ckpt_lib.save(ckpt, g_params, step=3,
                      extra={"kind": "gan_generator",
                             "precision": cfg.precision})
        params = ckpt_lib.restore_gan_generator(ckpt, cfg)
        policy_name = ckpt_lib.manifest_precision(ckpt)
    for a, b in zip(jax.tree.leaves(g_params), jax.tree.leaves(params)):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              "restored generator differs from the trained one")

    sim = CaloSimulator(CaloSpec(image_shape=cfg.image_shape), seed=seed + 1)
    mc = next(sim.batches(256))
    gate = PhysicsGate(validation.reference_profiles(mc["image"], mc["e_p"]),
                       window=256)
    eng = SimulateEngine(cfg, params, buckets=BUCKETS, mesh=mesh, gate=gate,
                         policy_name=policy_name)
    t = time.perf_counter()
    eng.warmup()
    warm_s = time.perf_counter() - t
    check(eng.compile_count == len(BUCKETS),
          f"warmup compiled {eng.compile_count} programs for "
          f"{len(BUCKETS)} buckets")

    rng = np.random.default_rng(seed)
    reqs = [SimRequest(rid=i, primary_energy=float(rng.uniform(10.0, 500.0)),
                       n_events=int(rng.integers(1, 65)),
                       seed=int(rng.integers(0, 2**31 - 1)))
            for i in range(REQUESTS)]
    for r in reqs:
        eng.submit(r)
    t = time.perf_counter()
    done = eng.run()
    run_s = time.perf_counter() - t
    gate.flush()

    check(len(done) == REQUESTS and not eng.rejected,
          f"{len(done)} of {REQUESTS} requests done, "
          f"{len(eng.rejected)} rejected")
    for r in reqs:
        check(r.status == "done", f"request {r.rid} is {r.status}")
        check(r.images.shape == (r.n_events, *cfg.image_shape, 1),
              f"request {r.rid}: images {r.images.shape}")
        img = np.asarray(r.images, np.float32)
        check(np.isfinite(img).all() and (img >= 0).all(),
              f"request {r.rid}: non-finite or negative energies")
    check(eng.compile_count == len(BUCKETS),
          f"serving recompiled: {eng.compile_count} programs")
    check(gate.reports, "the physics gate produced no report")
    for rep in gate.reports:
        check(all(math.isfinite(v) for v in rep.values()),
              f"non-finite gate report {rep}")
    n_ev = eng.stats["events_generated"]
    print(f"[smoke reading] served {len(done)} requests / {n_ev} events in "
          f"{run_s:.2f}s after a {warm_s:.1f}s warmup of buckets {BUCKETS}; "
          f"bucket steps {eng.stats['bucket_steps']}, compiles "
          f"{eng.compile_count}", flush=True)
    print("  gate: " + " ".join(f"{k}={v:.4f}" for k, v in
                                gate.reports[-1].items()), flush=True)


def data_parallel_phase(cfg, devices, seed, clock):
    """The paper's weak scaling: both loops on a len(devices)-way data
    mesh at ``cfg.batch_size`` per replica, against the builtin loop on
    one device at the same global batch and the same init."""
    import jax
    import numpy as np
    from repro.data.calo import CaloSimulator, CaloSpec
    from repro.launch.mesh import make_mesh

    n = len(devices)
    per = cfg.batch_size
    sim = CaloSimulator(CaloSpec(image_shape=cfg.image_shape), seed=seed)
    batches = [b for _, b in zip(range(STEPS), sim.batches(per * n))]
    one = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    mesh = make_mesh((n, 1), ("data", "model"), devices=devices)

    _, rows_1, _ = fit(cfg, one, "builtin", batches, seed, clock)
    for loop in ("builtin", "custom"):
        state, rows, eng = fit(cfg, mesh, loop, batches, seed, clock)
        name = f"{loop} on {n} vs builtin on 1"
        if loop == "builtin":
            # same noise, same updates: the last step, taken after the
            # reduced gradients were applied, must agree as well
            keys = sorted(rows_1[0].keys() - {"step"})
            agree(name, rows_1[0], rows[0], keys)
            agree(name, rows_1[-1], rows[-1],
                  [k for k in keys if k not in EVENT_COUNTS])
        else:
            agree(name, rows_1[0], rows[0], NOISE_FREE)
        placed = next(iter(eng.data_iter(batches[:1])))
        for k, leaf in placed.items():
            shards = leaf.addressable_shards
            check(len(shards) == n
                  and len({s.device for s in shards}) == n
                  and all(s.data.shape[0] == per for s in shards),
                  f"{loop}: batch leaf {k!r} shards "
                  f"{[(str(s.device), s.data.shape) for s in shards]}")
        for path, leaf in jax.tree_util.tree_leaves_with_path(state):
            if not hasattr(leaf, "addressable_shards"):
                continue
            shards = leaf.addressable_shards
            check(len({s.device for s in shards}) == n,
                  f"{loop}: {jax.tree_util.keystr(path)} on "
                  f"{len(shards)} devices")
            first = np.asarray(shards[0].data)
            check(all(np.array_equal(first, np.asarray(s.data))
                      for s in shards[1:]),
                  f"{loop}: replicas of {jax.tree_util.keystr(path)} "
                  "differ after training")
        print(f"  {loop}: every batch leaf split into {n} shards of {per} "
              f"on {n} devices; replicated state equal on all {n}",
              flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()

    import jax
    from repro.configs import calo3dgan
    from repro.kernels import autotune
    from repro.launch.mesh import make_dev_mesh

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing "
                 "to run on another backend")
    if autotune.default_interpret():
        sys.exit("chip_smoke: Pallas is forced into interpret mode "
                 "(REPRO_PALLAS_INTERPRET); refusing to run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")
    print(f"device: {dev.platform} / {dev.device_kind} x {len(devices)}; "
          f"compile cache: {cache_dir}", flush=True)

    cfg = calo3dgan.config()
    print(f"config: image {cfg.image_shape}, latent {cfg.latent_dim}, "
          f"G {cfg.gen_channels}, D {cfg.disc_channels}, batch "
          f"{cfg.batch_size}/replica, precision {cfg.precision}", flush=True)
    clock = CompileClock()
    try:
        if args.chips == 4:
            data_parallel_phase(cfg, devices[:4], args.seed, clock)
        else:
            mesh = make_dev_mesh()
            state = train_phase(cfg, mesh, args.seed, clock)
            serve_phase(cfg, state.g_params, mesh, args.seed)
    except Check as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)


if __name__ == "__main__":
    main()
