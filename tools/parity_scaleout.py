#!/usr/bin/env python
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"       # a virtual CPU pool, never a chip
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
"""2x2 virtual-topology parity: grad-reduce strategies + ZeRO-1 optimizer.

Folds 4 virtual CPU devices into a ``(node=2, device=2)`` mesh (so both
collective levels are REAL multi-participant reductions) and runs the
reduced 3DGAN a few steps under every (loop, grad_reduce) combination —
flat psum-mean, hierarchical (intra-node psum + bucketed inter-node
psums), and overlap (reverse-order buckets issued from inside the
backward pass, `parallel/collectives.OverlapReduce`).  Every strategy
must match flat to f32 summation-order tolerance for BOTH engine loops.

A second gate trains the custom loop with the ZeRO-1 sharded optimizer
(`optim.optimizers.zero1`: reduce-scatter-style sharded update +
all-gather, master/optimizer state partitioned over the mesh axes) and
pins its trajectory to the replicated-optimizer run.

This is the fail-fast gate CI's scaleout-smoke job runs so topology or
sharded-state regressions never reach a pod.

  PYTHONPATH=src python tools/parity_scaleout.py   # exit 0 on parity
"""

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

STEPS = 2
TOL = 2e-5          # f32 summation-order rounding across 4 replicas


def _max_diff(a, b):
    import jax
    import numpy as np
    leaves = zip(
        jax.tree.leaves(a.g_params) + jax.tree.leaves(a.d_params),
        jax.tree.leaves(b.g_params) + jax.tree.leaves(b.d_params))
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in leaves)


def main():
    import jax

    from repro.configs import calo3dgan
    from repro.data.calo import CaloSimulator, CaloSpec
    from repro.launch.mesh import make_node_mesh
    from repro.optim import optimizers as opt_lib
    from repro.train import engine as engine_lib

    assert len(jax.devices()) >= 4, jax.devices()
    cfg = calo3dgan.reduced()
    mesh = make_node_mesh(2, 2)
    sim = CaloSimulator(CaloSpec(image_shape=cfg.image_shape), seed=3)
    batches = [next(sim.batches(8)) for _ in range(STEPS)]

    def train(loop, strat, make_opt):
        task = engine_lib.gan_task(cfg, make_opt(), make_opt())
        eng = engine_lib.Engine(mesh, loop, dp_axes=("node", "device"),
                                grad_reduce=strat, bucket_mb=0.05)
        state = eng.init_state(task, jax.random.key(0))
        step = eng.compile_step(task, batches[0])
        rng = jax.random.key(1)
        for b in batches:
            rng, k = jax.random.split(rng)
            state, _ = step(state, b, k)
        return state

    rmsprop = lambda: opt_lib.rmsprop(1e-4)
    states = {(loop, strat): train(loop, strat, rmsprop)
              for loop in ("builtin", "custom")
              for strat in ("flat", "hierarchical", "overlap")}

    failed = False
    for loop in ("builtin", "custom"):
        for strat in ("hierarchical", "overlap"):
            diff = _max_diff(states[(loop, "flat")], states[(loop, strat)])
            ok = diff <= TOL
            failed |= not ok
            print(f"{loop:>8} loop: flat-vs-{strat} max param diff after "
                  f"{STEPS} steps on (node=2, device=2): {diff:.2e} "
                  f"[{'OK' if ok else 'FAIL'} tol={TOL:g}]")
    if failed:
        return 1
    print("parity OK: hierarchical and overlap grad-reduce match flat "
          "psum on the 2x2 virtual topology for both engine loops")

    zero1 = lambda: opt_lib.zero1(opt_lib.rmsprop(1e-4), 4,
                                  axis=("node", "device"))
    z_state = train("custom", "flat", zero1)
    diff = _max_diff(states[("custom", "flat")], z_state)
    ok = diff <= TOL
    print(f"  custom loop: replicated-vs-zero1 optimizer max param diff "
          f"after {STEPS} steps: {diff:.2e} "
          f"[{'OK' if ok else 'FAIL'} tol={TOL:g}]")
    if not ok:
        return 1
    print("zero1 parity OK: sharded optimizer matches the replicated "
          "update on the 2x2 virtual topology")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
