"""LM training cells: a language model's step through
``train/engine.lm_task``, driven the way ``Engine.fit`` drives it.

As in ``gan_train``: one object is built in set-up (the engine's compiled
step, the state made on the device from the seed, the prefetching batch
stream); it runs the checked steps, whose readings the float32 reference
(``harness/lm_reference.py``) follows after the window, and then the
measured window, in which nothing waits on the device but the bound on
run-ahead (``traffic["run_ahead"]`` steps).

With ``--trace 1`` the driver also reads its own trace once the window
is closed: the step's op time by named scope (``harness/lm_scopes.py``,
with the op -> op_name map of the compiled step), into ``rec["lm"]``.
"""
from __future__ import annotations

import collections
import itertools
import time

import numpy as np

from harness import common, compare, lm_compare, lm_reference, lm_scopes, trace

# what the program implements; a file that asks for anything else is refused
FIXED = {"model_type": "deepseek_v3", "hidden_act": "silu",
         "attention_bias": False, "q_lora_rank": None, "n_group": 1,
         "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
         "moe_layer_freq": 1, "num_nextn_predict_layers": 0,
         "norm_topk_prob": True, "rms_norm_eps": 1e-5}


def lm_config(conf: dict):
    """The program's ``ArchConfig`` for the configuration file."""
    from repro.configs.base import ArchConfig, MLAConfig, MoEConfig
    for k, v in FIXED.items():
        if conf[k] != v:
            raise ValueError(f"{k}={conf[k]!r}: the program implements "
                             f"{k}={v!r}")
    if conf["num_key_value_heads"] != conf["num_attention_heads"]:
        raise ValueError("latent attention has one key/value head per head")
    mla = MLAConfig(kv_lora_rank=conf["kv_lora_rank"],
                    qk_nope_head_dim=conf["qk_nope_head_dim"],
                    qk_rope_head_dim=conf["qk_rope_head_dim"],
                    v_head_dim=conf["v_head_dim"])
    moe = MoEConfig(
        n_experts=conf["n_routed_experts"],
        top_k=conf["num_experts_per_tok"],
        d_ff_expert=conf["moe_intermediate_size"], dispatch="grouped",
        router_experts=conf["router_experts"],
        expert_offset=conf["expert_offset"],
        n_shared_experts=conf["n_shared_experts"],
        routed_scale=conf["routed_scaling_factor"],
        bias_update_rate=conf["bias_update_rate"],
        seq_balance_loss=conf["seq_balance_alpha"] if conf["seq_aux"] else 0.0)
    return ArchConfig(
        arch_id=conf["arch_id"], family="moe",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        d_head=mla.qk_head_dim, rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"],
        first_k_dense=conf["first_k_dense_replace"], mla=mla, moe=moe)


def optimizer(conf: dict):
    from repro.optim import optimizers as opt_lib
    opt = conf["optimizer"]
    if opt["name"] != "adamw" or opt["clip_norm"] != 1.0:
        raise ValueError(f"the step runs AdamW with clip 1.0, got {opt}")
    return opt_lib.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                         eps=opt["eps"], weight_decay=opt["weight_decay"])


def data_pool(conf: dict, traffic: dict, seed: int, rows: int):
    """``pool_batches`` batches of ``rows`` packed sequences over the
    configuration's vocabulary (``data/tokens.PackedDocuments``), made once
    on the host."""
    from repro.data.tokens import PackedDocuments
    docs = PackedDocuments(conf["vocab_size"], traffic["seq_len"], seed=seed,
                           median=traffic["doc_median"],
                           sigma=traffic["doc_sigma"])
    n = traffic["pool_batches"]
    tokens = docs.sample(rows * n)
    return [{"tokens": tokens[i * rows:(i + 1) * rows]} for i in range(n)]


class Program:
    """The system under test, built once: engine, compiled step, state,
    batch stream.  ``step_fn`` may be swapped by a test to plant a fault
    in the timed path."""

    def __init__(self, conf, traffic, seed, devs):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.models import api
        from repro.substrate.precision import get_policy
        from repro.train import engine as engine_lib

        self.cfg = lm_config(conf)
        self.chips = len(devs)
        self.rows = traffic["batch"]
        mesh = make_mesh((self.chips, 1), ("data", "model"), devices=devs)
        self.task = engine_lib.lm_task(
            api.get_model(self.cfg), self.cfg, optimizer(conf),
            policy=get_policy(conf["precision"]))
        self.engine = engine_lib.Engine(mesh, conf["loop"],
                                        dp_axes=tuple(mesh.axis_names))
        self.conf, self.traffic = conf, traffic
        self._init = jax.jit(self.task.init,
                             out_shardings=NamedSharding(mesh, P()))
        self.state = None
        self.reset(seed)
        self.step_fn = self.engine.compile_step(self.task, self.pool[0])

    def reset(self, seed):
        """Weights, batches and keys of ``seed``; the compiled step stays."""
        import jax
        self.state = self.stream = None
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

    def compiled_text(self) -> str:
        import jax
        key = jax.random.fold_in(self.step_rng, self.gstep)
        return self.step_fn.lower(self.state, self.pool[0], key).compile() \
            .as_text()


def check_steps(prog: Program, n: int) -> dict:
    """Run the first ``n`` steps, keeping what the reference checks: the
    parameters before step 1 (``p0``, which the reference starts from),
    the norms ``lm_compare.readings`` compares, the routers' biases after
    the last step, each step's loss, rows held per MoE layer and dropped
    share."""
    import jax
    p0 = jax.device_get(prog.state.params)
    out = {"p0": p0}
    kept = []
    for i in range(n):
        kept.append(prog.step())
        if i == 0:
            out["g1"] = lm_compare.norms(prog.state.opt_state["v"],
                                         sqrt_of=True)
            out["d1"] = lm_compare.norms(prog.state.params, p0)
    out["dn"] = lm_compare.norms(prog.state.params, p0)
    out["bias"] = np.asarray(jax.device_get(
        prog.state.params["buffers"]["router_bias"]))
    kept = jax.device_get(kept)
    out["losses"] = [float(m["loss"]) for m in kept]
    out["rows"] = [np.asarray(m["moe_rows_held"]) for m in kept]
    out["drop"] = max(float(np.max(m["moe_drop_frac"])) for m in kept)
    return out


def run(ctx) -> dict:
    import jax
    conf, traffic = ctx.conf, ctx.traffic
    n_check = traffic["check_steps"]
    prog = Program(conf, traffic, ctx.seed, ctx.devices)
    prog_readings = check_steps(prog, n_check)
    names = lm_scopes.op_scopes(prog.compiled_text()) if ctx.traced else None
    jax.block_until_ready(prog.state)
    wait0 = prog.stream.stats["h2d_wait_ms"]
    ctx.open_window()
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    pending = collections.deque()
    kept = []
    longest = {"dispatch_ms": 0.0, "run_ahead_wait_ms": 0.0}
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
            ctx.tick()
        with ctx.span("bench.drain"):
            jax.block_until_ready(prog.state)
    t1 = time.perf_counter()
    ctx.close_window()
    scoped = None
    if ctx.traced:
        ex = trace.extract(ctx.trace_dir)
        scoped = lm_scopes.reduce(ex, trace.window_of(ex, "bench.traced"),
                                  names, trace.patterns())
        del ex, names
    steps = len(kept)
    h2d_wait_ms = prog.stream.stats["h2d_wait_ms"] - wait0
    window = jax.device_get(kept)
    failed = sum(1 for m in window if not np.isfinite(float(m["loss"])))
    rows = np.array([np.asarray(m["moe_rows_held"]) for m in window])
    counts = np.array([np.asarray(m["moe_tokens_per_expert"])
                       for m in window])
    drop = max([prog_readings["drop"]]
               + [float(np.max(m["moe_drop_frac"])) for m in window])
    ctx.read_memory()
    pool, chips, batch = prog.pool, prog.chips, prog.rows
    del prog, kept, pending, window
    ctx.free()
    ref = lm_reference.run_steps(conf, prog_readings.pop("p0"), pool,
                                 n_check)
    readings = dict(lm_compare.readings(prog_readings, ref),
                    moe_drop_frac=drop)
    load = counts.mean(axis=0)
    return {
        "window_s": t1 - t0,
        "attempted": steps,
        "failed": failed,
        "train": {"steps": steps, "samples": steps * batch * chips,
                  "rows_per_chip": batch, "h2d_wait_ms": h2d_wait_ms},
        "lm": {"batch": batch, "seq_len": traffic["seq_len"],
               "rows_held_by_layer": rows.mean(axis=0).tolist(),
               "tokens_per_expert": load.tolist(),
               "drop_frac": drop, "scopes": scoped},
        "checks": compare.checks(readings, ctx.limits),
        "notes": {**{k: v for k, v in readings.items()
                     if not k.startswith("_") and k not in ctx.limits},
                  "rows_program_vs_reference": [readings["_rows_program"],
                                                readings["_rows_reference"]],
                  "load_max_over_mean_by_layer": (
                      load.max(axis=-1) / load.mean(axis=-1)).tolist(),
                  "longest_step_dispatch_ms": longest["dispatch_ms"],
                  "longest_run_ahead_wait_ms": longest["run_ahead_wait_ms"],
                  "h2d_wait_ms_total": h2d_wait_ms, "steps": steps,
                  **({"scopes_ms_per_step": _scope_notes(scoped)}
                     if scoped else {})},
    }


def _scope_notes(scoped: dict) -> dict:
    """Per chip: device ms a step by scope and pass, the unscoped rest,
    the busy time, and the ``lax.cond`` ops' time with the part of it no
    other op covers."""
    out = {}
    for dev, d in scoped.items():
        per = 1e3 / max(d["steps"], 1)
        out[dev] = {
            **{s: {p: v * per for p, v in d["scopes"][s].items()}
               for s in lm_scopes.SCOPES},
            **{k: d[k] * per for k in ("unscoped_s", "busy_s", "unmapped_s",
                                       "cond_s", "cond_uncovered_s")},
            "steps": d["steps"]}
    return out


def calibrate(conf, traffic, devs, seeds, control_seeds, emit):
    """Readings of the program and of the control (the reference with
    float8 operands) per seed (``bench/calibrate.py``).  The program's
    state is freed before each reference run, which needs the chip's
    memory."""
    import gc
    import jax
    n = traffic["check_steps"]
    traffic = dict(traffic, pool_batches=n)
    prog = None
    for seed in seeds:
        t = time.perf_counter()
        if prog is None:
            prog = Program(conf, traffic, seed, devs)
        else:
            prog.reset(seed)
        got = check_steps(prog, n)
        prog.state = prog.stream = None
        gc.collect()
        ref = lm_reference.run_steps(conf, got.pop("p0"), prog.pool, n)
        emit(kind="program", seed=seed, s=time.perf_counter() - t,
             moe_drop_frac=got["drop"],
             **{k: v for k, v in lm_compare.readings(got, ref).items()
                if not k.startswith("_")})
    for seed in control_seeds:
        if prog is None:
            prog = Program(conf, traffic, seed, devs)
        else:
            prog.reset(seed)
        p0, pool = jax.device_get(prog.state.params), prog.pool
        prog.state = prog.stream = None
        gc.collect()
        ref = lm_reference.run_steps(conf, p0, pool, n)
        ctl = lm_reference.run_steps(conf, p0, pool, n,
                                     precision="float8_e4m3fn")
        emit(kind="control", seed=seed,
             **{k: v for k, v in lm_compare.readings(ctl, ref).items()
                if not k.startswith("_")})
