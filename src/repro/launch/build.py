"""Build jitted, mesh-sharded train / prefill / serve steps for any arch.

Shared by the real launchers (train.py / serve.py) and the multi-pod dry-run
(dryrun.py): the SAME code path produces either executable functions (given
real arrays) or AOT ``lowered``/``compiled`` artifacts (given only
ShapeDtypeStructs) — so what the dry-run proves is what training runs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import base as config_base
from repro.models import api
from repro.optim import optimizers as opt_lib
from repro.parallel import sharding
from repro.substrate.precision import get_policy
from repro.train import steps as steps_lib


# ---------------------------------------------------------------------------
# Sharding trees
# ---------------------------------------------------------------------------


def param_shapes(model, cfg):
    """ShapeDtypeStruct pytree of the params — no allocation."""
    return jax.eval_shape(lambda: model.init(jax.random.key(0), cfg))


def param_shardings(model, cfg, mesh: Mesh, rules: dict):
    shapes = param_shapes(model, cfg)
    return sharding.tree_shardings(model.logical_axes(cfg), shapes, mesh,
                                   rules), shapes


def opt_state_shardings(optimizer, p_shapes, p_shard, mesh: Mesh):
    """Optimizer-state shardings: moment trees mirror the params; scalars
    (step) are replicated; None slots stay None."""
    o_shapes = jax.eval_shape(optimizer.init, p_shapes)
    rep = NamedSharding(mesh, P())

    def top(entry_shapes):
        if entry_shapes is None:
            return None
        if isinstance(entry_shapes, jax.ShapeDtypeStruct):
            return rep
        return jax.tree.map(lambda _, s: s, entry_shapes, p_shard)

    return {k: top(v) for k, v in o_shapes.items()}, o_shapes


def batch_shardings(batch_shapes, mesh: Mesh, batch_dim_for: Optional[dict] = None):
    """Leading-dim (pod, data) sharding for every batch leaf.  Delegates to
    the engine's single placement rule (``positions`` carries batch on
    dim 1; non-dividing dims stay replicated)."""
    from repro.train import engine as engine_lib
    return engine_lib.Engine(mesh, "builtin").batch_shardings(
        batch_shapes, batch_dim_for)


def cache_shardings(model, cfg, mesh: Mesh, rules: dict, cache_shapes):
    return sharding.tree_shardings(model.cache_logical_axes(cfg),
                                   cache_shapes, mesh, rules)


# ---------------------------------------------------------------------------
# Step builders (train / prefill / serve), AOT-lowerable
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BuiltStep:
    fn: Any                 # the jitted function
    args: tuple             # ShapeDtypeStruct args (for .lower(*args))
    kind: str

    def lower(self):
        return self.fn.lower(*self.args)


def build_train(arch_id: str, shape_name: str, mesh: Mesh, *,
                rules_name: str = "fsdp_tp", policy_name: str = "bf16",
                optimizer_name: str = "adamw", lr: float = 3e-4,
                remat: bool = True, donate: bool = True,
                microbatches: int = 1, seq_shard: bool = True) -> BuiltStep:
    cfg = config_base.get_config(arch_id)
    shape = config_base.INPUT_SHAPES[shape_name]
    model = api.get_model(cfg)
    rules = sharding.RULE_SETS[rules_name]
    policy = get_policy(policy_name)
    optimizer = opt_lib.get_optimizer(optimizer_name, lr)

    p_shard, p_shapes = param_shardings(model, cfg, mesh, rules)
    o_shard, o_shapes = opt_state_shardings(
        optimizer, steps_lib.trainable(p_shapes),
        steps_lib.trainable(p_shard), mesh)
    b_shapes = api.train_batch_specs(cfg, shape)
    b_shard = batch_shardings(b_shapes, mesh)

    step = steps_lib.make_train_step(model, cfg, optimizer, policy, mesh=mesh,
                                     remat=remat, microbatches=microbatches,
                                     seq_shard=seq_shard)
    fn = jax.jit(
        step,
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard, None),
        donate_argnums=(0, 1) if donate else (),
    )
    return BuiltStep(fn, (p_shapes, o_shapes, b_shapes), "train")


def build_prefill(arch_id: str, shape_name: str, mesh: Mesh, *,
                  rules_name: str = "fsdp_tp",
                  policy_name: str = "bf16") -> BuiltStep:
    cfg = config_base.get_config(arch_id)
    shape = config_base.INPUT_SHAPES[shape_name]
    model = api.get_model(cfg)
    rules = sharding.RULE_SETS[rules_name]
    policy = get_policy(policy_name)
    window = api.decode_window(cfg, shape)

    p_shard, p_shapes = param_shardings(model, cfg, mesh, rules)
    b_shapes = api.prefill_specs(cfg, shape)
    b_shard = batch_shardings(b_shapes, mesh)

    step = steps_lib.make_prefill_step(model, cfg, policy, mesh=mesh,
                                       window=window)
    fn = jax.jit(step, in_shardings=(p_shard, b_shard))
    return BuiltStep(fn, (p_shapes, b_shapes), "prefill")


def build_serve(arch_id: str, shape_name: str, mesh: Mesh, *,
                rules_name: str = "fsdp_tp",
                policy_name: str = "bf16") -> BuiltStep:
    cfg = config_base.get_config(arch_id)
    shape = config_base.INPUT_SHAPES[shape_name]
    model = api.get_model(cfg)
    rules = sharding.RULE_SETS[rules_name]
    policy = get_policy(policy_name)
    window = api.decode_window(cfg, shape)

    p_shard, p_shapes = param_shardings(model, cfg, mesh, rules)
    tokens1, cache_shapes, pos, extra = api.decode_specs(cfg, shape)
    c_shard = cache_shardings(model, cfg, mesh, rules, cache_shapes)
    b = shape.global_batch
    ax = sharding.batch_axes(mesh)
    n_batch = 1
    for a in ax or ():
        n_batch *= mesh.shape[a]
    shard_batch = ax is not None and b % n_batch == 0 and b > 1
    tok_in = NamedSharding(mesh, P(ax, None) if shard_batch else P())
    tok_out = NamedSharding(mesh, P(ax) if shard_batch else P())
    extra_shard = batch_shardings(extra, mesh)

    step = steps_lib.make_serve_step(model, cfg, policy, mesh=mesh,
                                     window=window)
    fn = jax.jit(
        step,
        in_shardings=(p_shard, tok_in, c_shard,
                      NamedSharding(mesh, P()), extra_shard),
        out_shardings=(tok_out, c_shard),
        donate_argnums=(2,),
    )
    return BuiltStep(fn, (p_shapes, tokens1, cache_shapes, pos, extra),
                     "serve")


def gan_batch_shapes(cfg, n_replicas: int) -> dict:
    """ShapeDtypeStruct batch for the 3DGAN at the paper's per-replica
    batch size (global batch = batch_size x replicas, weak scaling)."""
    B = cfg.batch_size * n_replicas
    X, Y, Z = cfg.image_shape
    return {
        "image": jax.ShapeDtypeStruct((B, X, Y, Z, 1), jnp.float32),
        "e_p": jax.ShapeDtypeStruct((B,), jnp.float32),
        "theta": jax.ShapeDtypeStruct((B,), jnp.float32),
        "ecal": jax.ShapeDtypeStruct((B,), jnp.float32),
    }


def build_gan_train(mesh: Mesh, *, policy_name: Optional[str] = None,
                    reduced: bool = False, loop: str = "builtin",
                    grad_reduce: Optional[str] = None,
                    bucket_mb: Optional[float] = None) -> BuiltStep:
    """The paper's own architecture: fused Algorithm-1 step, pure DP
    (mirrored-strategy analogue — params replicated, batch sharded).

    Delegates to the unified engine: ``loop`` selects the paper's
    built-in (jit + NamedSharding) or custom (shard_map + explicit psum)
    strategy, ``grad_reduce`` the reduction schedule (flat | hierarchical
    over a (node, device) mesh).  Every mesh axis carries batch — all
    256/512 chips are replicas, per-replica BS=128 exactly as the paper
    runs it (§4).  ``policy_name``/``grad_reduce``/``bucket_mb`` default
    to the config's ``precision``/``grad_reduce``/``reduce_bucket_mb``."""
    from repro.configs import calo3dgan
    from repro.train import engine as engine_lib

    cfg = calo3dgan.reduced() if reduced else calo3dgan.config()
    task = engine_lib.gan_task(cfg, opt_lib.rmsprop(1e-4),
                               opt_lib.rmsprop(1e-4),
                               policy=get_policy(policy_name
                                                 or cfg.precision))
    eng = engine_lib.Engine(mesh, loop, dp_axes=tuple(mesh.axis_names),
                            grad_reduce=grad_reduce or cfg.grad_reduce,
                            bucket_mb=(cfg.reduce_bucket_mb
                                       if bucket_mb is None else bucket_mb))
    built = eng.build(task, gan_batch_shapes(cfg, mesh.devices.size))
    return BuiltStep(built.fn, built.args, built.kind)
