"""Training cells: the fused Algorithm-1 step of ``train/engine.py``,
driven the way ``Engine.fit`` drives it.

One object is built in set-up — the engine's compiled step, its state
made on the device from the seed in one jitted call, and its prefetching
batch stream — and the same object runs the first steps (which the
reference follows) and then the measured window.  ``Engine.fit`` builds
a new compiled step on every call and hands losses out only through a
per-step host sync, so the loop here is ``fit``'s own loop body over
``compile_step`` and ``data_iter``: the step key of global step g is
``fold_in(step_rng, g)``, batches come through the engine's prefetcher,
and nothing waits on the device inside the window except the bound on
run-ahead (``traffic["run_ahead"]`` steps), which keeps at most that many
steps queued (the runtime may block a dispatch sooner).
"""
from __future__ import annotations

import collections
import itertools
import time

from harness import common, compare, reference

CONFIG_FIELDS = ("image_shape", "latent_dim", "gen_channels", "disc_channels",
                 "gen_steps_per_disc", "aux_ecal_weight", "aux_energy_weight",
                 "aux_angle_weight", "batch_size", "use_pallas_conv",
                 "precision", "grad_reduce")
NOISE_DTYPE = {"bf16": "bfloat16", "f32": "float32"}


def gan_config(conf: dict):
    from repro.configs.calo3dgan import GANConfig
    kw = {k: (tuple(conf[k]) if isinstance(conf[k], list) else conf[k])
          for k in CONFIG_FIELDS if k in conf}
    return GANConfig(**kw)


def reference_config(conf: dict) -> dict:
    opt = conf["optimizer"]
    return dict(
        {k: conf[k] for k in ("image_shape", "latent_dim", "gen_channels",
                              "disc_channels", "gen_steps_per_disc",
                              "aux_ecal_weight", "aux_energy_weight",
                              "aux_angle_weight")},
        rows=conf["batch_size"], lr=opt["lr"], rmsprop_decay=opt["decay"],
        rmsprop_eps=opt["eps"], noise_dtype=NOISE_DTYPE[conf["precision"]])


def data_pool(conf: dict, traffic: dict, seed: int, rows: int):
    """``pool_batches`` batches of ``rows`` showers from the stand-in for
    the Geant4 dataset, generated once on the host; every row differs."""
    from repro.data.calo import CaloSimulator, CaloSpec
    sim = CaloSimulator(CaloSpec(image_shape=tuple(conf["image_shape"])),
                        seed=seed)
    it = sim.batches(rows)
    return [next(it) for _ in range(traffic["pool_batches"])]


class Program:
    """The system under test, built once: engine, compiled step, state,
    batch stream.  ``step_fn`` may be swapped by a test to plant a fault
    in the timed path."""

    def __init__(self, conf, traffic, seed, devs):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.optim import optimizers as opt_lib
        from repro.substrate.precision import get_policy
        from repro.train import engine as engine_lib

        self.cfg = gan_config(conf)
        self.chips = len(devs)
        self.rows = self.cfg.batch_size
        mesh = make_mesh((self.chips, 1), ("data", "model"), devices=devs)
        opt = conf["optimizer"]
        if opt["name"] != "rmsprop":
            raise ValueError(f"only RMSprop is implemented, got {opt}")

        def make_opt():
            return opt_lib.rmsprop(opt["lr"], decay=opt["decay"],
                                   eps=opt["eps"])
        self.task = engine_lib.gan_task(
            self.cfg, make_opt(), make_opt(),
            policy=get_policy(self.cfg.precision))
        self.engine = engine_lib.Engine(
            mesh, conf["loop"], dp_axes=tuple(mesh.axis_names),
            grad_reduce=self.cfg.grad_reduce)
        self.conf, self.traffic = conf, traffic
        self._init = jax.jit(self.task.init,
                             out_shardings=NamedSharding(mesh, P()))
        self.reset(seed)
        self.step_fn = self.engine.compile_step(self.task, self.pool[0])

    def reset(self, seed):
        """Weights, batches and keys of ``seed``; the compiled step stays."""
        import jax
        self.pool = data_pool(self.conf, self.traffic, seed,
                              self.rows * self.chips)
        self.init_key, self.step_rng = jax.random.split(common.jax_key(seed))
        self.state = self._init(self.init_key)
        self.stream = self.engine.data_iter(itertools.cycle(self.pool),
                                            size=self.traffic["prefetch"])
        self.gstep = 0

    def step(self):
        import jax
        batch = next(self.stream)
        key = jax.random.fold_in(self.step_rng, self.gstep)
        self.state, metrics = self.step_fn(self.state, batch, key)
        self.gstep += 1
        return metrics


def params_of(state):
    import jax
    return jax.device_get({"g": state.g_params, "d": state.d_params})


def nu_of(state):
    import jax
    return jax.device_get({"g": state.g_opt["nu"], "d": state.d_opt["nu"]})


def check_steps(prog: Program, n: int) -> dict:
    """Run the first ``n`` steps, keeping what the reference checks:
    each step's losses, the optimizer's second moments after step 1 (the
    squared gradients as RMSprop got them) and the parameters before
    step 1, after step 1 and after step n."""
    import jax
    out = {"p0": params_of(prog.state), "losses": []}
    for i in range(n):
        metrics = prog.step()
        if i == 0:
            out["nu1"] = nu_of(prog.state)
            out["p1"] = params_of(prog.state)
        out["losses"].append(metrics)
    out["pn"] = params_of(prog.state)
    out["losses"] = [{k: float(v) for k, v in m.items()
                      if k in compare.LOSSES}
                     for m in jax.device_get(out["losses"])]
    return out


_REFERENCE_STEPS = {}


def reference_readings(conf, prog_pool, init_key, step_rng, chips, n,
                       precision="float32", half_batch=False,
                       replicas=None) -> dict:
    """The reference (or, with a lower ``precision`` or a fault, the
    control or a planted fault) through the same ``n`` steps on the same
    rows and keys.  Its compiled pieces are kept for the next seed."""
    import jax
    rcfg = reference_config(conf)
    key = (precision, half_batch, replicas and tuple(replicas),
           repr(sorted(rcfg.items())))
    if key not in _REFERENCE_STEPS:
        q = (reference.identity if precision == "float32"
             else reference.rounding_to(precision))
        _REFERENCE_STEPS[key] = (
            jax.jit(lambda k: reference.init_state(k, rcfg)),
            reference.make_step(rcfg, q, half_batch=half_batch,
                                replicas=replicas))
    init, step = _REFERENCE_STEPS[key]
    state = init(init_key)
    out = {"p0": jax.device_get({"g": state["g"], "d": state["d"]}),
           "losses": []}
    rows = rcfg["rows"]
    for i in range(n):
        batch = prog_pool[i % len(prog_pool)]
        per = [{k: v[r * rows:(r + 1) * rows] for k, v in batch.items()}
               for r in range(chips)]
        keys = reference.replica_keys(jax.random.fold_in(step_rng, i), chips,
                                      rcfg["gen_steps_per_disc"])
        state, losses = step(state, per, keys)
        if i == 0:
            out["nu1"] = jax.device_get({"g": state["g_nu"],
                                         "d": state["d_nu"]})
            out["p1"] = jax.device_get({"g": state["g"], "d": state["d"]})
        out["losses"].append(losses)
    out["pn"] = jax.device_get({"g": state["g"], "d": state["d"]})
    return out


def run(ctx) -> dict:
    import jax
    conf, traffic = ctx.conf, ctx.traffic
    n_check = traffic["check_steps"]
    prog = Program(conf, traffic, ctx.seed, ctx.devices)
    prog_readings = check_steps(prog, n_check)
    jax.block_until_ready(prog.state)
    wait0 = prog.stream.stats["h2d_wait_ms"]
    ctx.open_window()
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    pending = collections.deque()
    kept = []
    longest = {"dispatch_ms": 0.0, "run_ahead_wait_ms": 0.0}
    slow = []       # [seconds into the window, dispatch ms, wait ms]
    with ctx.span("bench.window"):
        while time.perf_counter() < deadline:
            t = time.perf_counter()
            with ctx.span("bench.step"):
                metrics = prog.step()
            kept.append(metrics)
            pending.append(metrics)
            t2 = time.perf_counter()
            if len(pending) > traffic["run_ahead"]:
                with ctx.span("bench.run_ahead_wait"):
                    jax.block_until_ready(pending.popleft())
            t3 = time.perf_counter()
            longest["dispatch_ms"] = max(longest["dispatch_ms"],
                                         1e3 * (t2 - t))
            longest["run_ahead_wait_ms"] = max(longest["run_ahead_wait_ms"],
                                               1e3 * (t3 - t2))
            if t3 - t > 0.25:
                slow.append([round(t - t0, 3), round(1e3 * (t2 - t), 1),
                             round(1e3 * (t3 - t2), 1)])
            ctx.tick()
        with ctx.span("bench.drain"):
            jax.block_until_ready(prog.state)
    t1 = time.perf_counter()
    ctx.close_window()
    steps = len(kept)
    h2d_wait_ms = prog.stream.stats["h2d_wait_ms"] - wait0
    window_metrics = jax.device_get(kept)
    failed = sum(1 for m in window_metrics
                 if float(m.get("nonfinite_skips", 0.0)) != 0.0
                 or not all(map(_finite, m.values())))
    ctx.read_memory()
    pool, init_key, step_rng = prog.pool, prog.init_key, prog.step_rng
    chips, rows = prog.chips, prog.rows
    del prog, kept, pending
    ctx.free()
    ref = reference_readings(conf, pool, init_key, step_rng, chips, n_check)
    readings = compare.training_readings(prog_readings, ref)
    return {
        "window_s": t1 - t0,
        "attempted": steps,
        "failed": failed,
        "train": {"steps": steps, "samples": steps * rows * chips,
                  "rows_per_chip": rows, "h2d_wait_ms": h2d_wait_ms},
        "checks": compare.checks(readings, ctx.limits),
        # readings without a limit (see ``compare.training_readings``)
        "notes": {**{k: v for k, v in readings.items()
                     if not k.startswith("_") and k not in ctx.limits},
                  "longest_step_dispatch_ms": longest["dispatch_ms"],
                  "longest_run_ahead_wait_ms": longest["run_ahead_wait_ms"],
                  "h2d_wait_ms_total": h2d_wait_ms, "steps": steps,
                  "steps_over_250ms": slow},
    }


def _finite(v) -> bool:
    import math
    return math.isfinite(float(v))


def calibrate(conf, traffic, devs, seeds, control_seeds, emit):
    """Readings of the program, the control and the planted faults, per
    seed (``bench/calibrate.py``)."""
    n = traffic["check_steps"]
    traffic = dict(traffic, pool_batches=n)    # no window: n batches do
    prog = None
    for seed in seeds:
        t = time.perf_counter()
        if prog is None:
            prog = Program(conf, traffic, seed, devs)
        else:
            prog.reset(seed)
        got = check_steps(prog, n)
        ref = reference_readings(conf, prog.pool, prog.init_key,
                                 prog.step_rng, prog.chips, n)
        emit(kind="program", seed=seed, s=time.perf_counter() - t,
             **compare.training_readings(got, ref))
    chips = len(devs)
    for seed in control_seeds:
        prog.reset(seed)
        args = (conf, prog.pool, prog.init_key, prog.step_rng, chips, n)
        ref = reference_readings(*args)
        planted = {"control": dict(precision="float8_e4m3fn"),
                   "half_batch": dict(half_batch=True)}
        if chips > 1:
            planted["one_replica"] = dict(replicas=[0])
        for kind, kw in planted.items():
            emit(kind=kind, seed=seed, **compare.training_readings(
                reference_readings(*args, **kw), ref))
