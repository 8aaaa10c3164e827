"""Topology-aware 2-level runtime: Topology factories, bucketed
hierarchical + backward-overlapped gradient reduction (parity with the
flat psum for BOTH engine loops on a virtual node×device mesh), ZeRO-1
sharded-optimizer parity, jaxpr collective accounting (per-kind bytes,
schedule exposure, per-device state bytes), and the subprocess 2x2
virtual-topology gate CI runs."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import calo3dgan
from repro.core import adversarial
from repro.data.calo import CaloSimulator, CaloSpec
from repro.launch.mesh import (TOPOLOGIES, make_node_mesh, topology)
from repro.optim import optimizers as opt_lib
from repro.parallel import collectives
from repro.parallel.jaxpr_cost import cost_of
from repro.train import engine as engine_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Topology
# ---------------------------------------------------------------------------


def test_topology_factories_cover_paper_configs():
    assert topology("v100", 8).total_devices == 64
    assert topology("v100", 8).mesh_shape == (8, 8)
    for name in ("v100x8", "v100x128", "tpu_v3-8", "tpu_v3-32"):
        assert name in TOPOLOGIES
    assert TOPOLOGIES["v100x128"].nodes == 16
    assert TOPOLOGIES["tpu_v3-32"].total_devices == 32


def test_gpu_topology_links_are_hierarchical():
    t = topology("v100", 2)
    assert t.intra_link.bandwidth > t.inter_link.bandwidth
    assert t.intra_link.latency < t.inter_link.latency
    assert t.axis_names == ("node", "device")


def test_make_node_mesh_folds_host_devices():
    mesh = make_node_mesh(1, 1)
    assert mesh.axis_names == ("node", "device")
    assert mesh.shape == {"node": 1, "device": 1}


def test_make_node_mesh_rejects_oversized_grid():
    with pytest.raises(ValueError, match="virtual topology"):
        make_node_mesh(64, 64)


# ---------------------------------------------------------------------------
# bucket planning + grad-reduce strategies
# ---------------------------------------------------------------------------


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_plan_buckets_respects_cap_and_order():
    leaves = [_sds((256,)), _sds((256,)), _sds((256,)), _sds((4096,))]
    # cap = 2 * 256 f32 leaves -> [0,1], [2], [3 alone: oversize]
    buckets = collectives.plan_buckets(leaves, bucket_bytes=2048)
    assert buckets == [[0, 1], [2], [3]]
    flat = [i for b in buckets for i in b]
    assert flat == list(range(len(leaves)))     # nothing dropped/reordered


def test_plan_buckets_never_mixes_dtypes():
    leaves = [_sds((8,)), _sds((8,), jnp.bfloat16), _sds((8,))]
    buckets = collectives.plan_buckets(leaves, bucket_bytes=1 << 20)
    assert buckets == [[0], [1], [2]]


def test_bucket_transform_is_identity():
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": jnp.ones((7,)), "c": jnp.zeros((2, 2, 2))}
    out = jax.jit(collectives.bucket_transform(bucket_bytes=32))(tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(tree[k]))


def test_make_grad_reduce_validates():
    mesh = make_node_mesh(1, 1)
    with pytest.raises(ValueError, match="grad_reduce"):
        collectives.make_grad_reduce("nope", mesh, ("node", "device"))
    with pytest.raises(ValueError, match="2-level"):
        collectives.make_grad_reduce("hierarchical", mesh, ("node",))
    fn = collectives.make_grad_reduce(lambda t: t, mesh, ("node",))
    assert fn(3) == 3                            # callables pass through


def test_builtin_loop_honors_callable_grad_reduce():
    """A user-supplied callable must reach the step in BOTH loops — a
    zeroing reduce leaves params untouched."""
    mesh = make_node_mesh(1, 1)
    sim = CaloSimulator(CaloSpec(image_shape=GAN_CFG.image_shape), seed=0)
    batch = next(sim.batches(8))
    task = engine_lib.gan_task(GAN_CFG, opt_lib.rmsprop(1e-4),
                               opt_lib.rmsprop(1e-4))
    eng = engine_lib.Engine(mesh, "builtin",
                            dp_axes=("node", "device"),
                            grad_reduce=lambda t: jax.tree.map(
                                jnp.zeros_like, t))
    state = eng.init_state(task, jax.random.key(0))
    step = eng.compile_step(task, batch)
    new_state, _ = step(state, batch, jax.random.key(1))
    before = eng.init_state(task, jax.random.key(0))   # state was donated
    for a, b in zip(jax.tree.leaves(before.g_params),
                    jax.tree.leaves(new_state.g_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_rejects_hierarchical_on_flat_mesh():
    from repro.launch.mesh import make_dev_mesh
    with pytest.raises(ValueError, match="2-level"):
        engine_lib.Engine(make_dev_mesh(), "custom", dp_axes=("data",),
                          grad_reduce="hierarchical")


# ---------------------------------------------------------------------------
# hierarchical vs flat parity (virtual node×device mesh, both loops)
# ---------------------------------------------------------------------------

GAN_CFG = calo3dgan.bench()


def _run_gan(loop, strategy, batches, mesh):
    task = engine_lib.gan_task(GAN_CFG, opt_lib.rmsprop(1e-4),
                               opt_lib.rmsprop(1e-4))
    eng = engine_lib.Engine(mesh, loop, dp_axes=("node", "device"),
                            grad_reduce=strategy, bucket_mb=0.05)
    state = eng.init_state(task, jax.random.key(0))
    step = eng.compile_step(task, batches[0])
    rng = jax.random.key(1)
    for b in batches:
        rng, k = jax.random.split(rng)
        state, metrics = step(state, b, k)
    return state, metrics


@pytest.mark.parametrize("strategy", ("hierarchical", "overlap"))
@pytest.mark.parametrize("loop", ("builtin", "custom"))
def test_strategies_match_flat_psum(loop, strategy):
    """The acceptance gate: hierarchical AND backward-overlapped
    grad_reduce are numerically interchangeable with the flat psum path
    on a node×device mesh, for both engine loops (builtin: bit-identical
    — a single replica reduces to the identity; custom: f32 tolerance.
    Multi-participant reduction order is covered by
    tools/parity_scaleout.py on 4 virtual devices)."""
    mesh = make_node_mesh(1, 1)
    sim = CaloSimulator(CaloSpec(image_shape=GAN_CFG.image_shape), seed=3)
    batches = [next(sim.batches(8)) for _ in range(2)]
    flat_state, flat_m = _run_gan(loop, "flat", batches, mesh)
    alt_state, alt_m = _run_gan(loop, strategy, batches, mesh)
    for a, b in zip(jax.tree.leaves(flat_state.g_params)
                    + jax.tree.leaves(flat_state.d_params),
                    jax.tree.leaves(alt_state.g_params)
                    + jax.tree.leaves(alt_state.d_params)):
        if loop == "builtin":
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=2e-6)
    for k in flat_m:
        assert float(flat_m[k]) == pytest.approx(float(alt_m[k]),
                                                 rel=1e-4, abs=1e-5), k


def test_lm_custom_loop_strategies_match_flat():
    """steps.make_train_step consumes the same grad_reduce hook — the
    LM path must be strategy-agnostic too (overlap included: the
    wrap_params tagging path through the custom_vjp)."""
    from repro.configs import base as config_base
    from repro.data.tokens import MarkovTokens
    from repro.models import api
    from repro.substrate.precision import get_policy

    cfg = config_base.reduced_config("qwen2-1.5b")
    model = api.get_model(cfg)
    data = MarkovTokens(cfg.vocab, seed=0)
    batches = [{"tokens": data.sample(4, 64)} for _ in range(2)]
    mesh = make_node_mesh(1, 1)
    losses = {}
    for strat in ("flat", "hierarchical", "overlap"):
        task = engine_lib.lm_task(model, cfg, opt_lib.adamw(1e-3),
                                  policy=get_policy("f32"))
        eng = engine_lib.Engine(mesh, "custom", dp_axes=("node", "device"),
                                grad_reduce=strat)
        state = eng.init_state(task, jax.random.key(0))
        step = eng.compile_step(task, batches[0])
        ls = []
        for b in batches:
            state, m = step(state, b, jax.random.key(2))
            ls.append(float(m["loss"]))
        losses[strat] = ls
    assert losses["flat"] == pytest.approx(losses["hierarchical"],
                                           rel=1e-6)
    assert losses["flat"] == pytest.approx(losses["overlap"], rel=1e-6)


# ---------------------------------------------------------------------------
# ZeRO-1 sharded optimizer
# ---------------------------------------------------------------------------


def test_zero1_matches_replicated_optimizer():
    """zero1(rmsprop) must walk the same trajectory as plain rmsprop —
    the sharded (N, L) master layout + gather is pure data movement.
    4 shards on a 1x1 mesh exercises the layout without an axis."""
    mesh = make_node_mesh(1, 1)
    sim = CaloSimulator(CaloSpec(image_shape=GAN_CFG.image_shape), seed=3)
    batches = [next(sim.batches(8)) for _ in range(2)]

    def train(make_opt):
        task = engine_lib.gan_task(GAN_CFG, make_opt(), make_opt())
        eng = engine_lib.Engine(mesh, "custom", dp_axes=("node", "device"),
                                grad_reduce="flat")
        state = eng.init_state(task, jax.random.key(0))
        step = eng.compile_step(task, batches[0])
        rng = jax.random.key(1)
        for b in batches:
            rng, k = jax.random.split(rng)
            state, _ = step(state, b, k)
        return state

    rep = train(lambda: opt_lib.rmsprop(1e-4))
    z = train(lambda: opt_lib.zero1(opt_lib.rmsprop(1e-4), 4))
    for a, b in zip(jax.tree.leaves(rep.g_params)
                    + jax.tree.leaves(rep.d_params),
                    jax.tree.leaves(z.g_params)
                    + jax.tree.leaves(z.d_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=2e-6)


def test_zero1_state_layout_and_padding():
    """The (N, L) shard-major layout: padding stays zero after updates
    (the cross-N resharding invariant) and the master row concatenation
    reconstructs the params exactly at init."""
    params = {"w": jnp.arange(10.0), "b": jnp.ones((3,))}
    opt = opt_lib.zero1(opt_lib.rmsprop(1e-2), 4)
    st = opt.init(params)
    m = np.asarray(st["zero1"]["master"])
    assert m.shape[0] == 4 and m.size >= 13
    flat = m.reshape(-1)
    np.testing.assert_allclose(flat[:3], 1.0)       # "b" flattens first
    np.testing.assert_allclose(flat[3:13], np.arange(10.0))
    assert np.all(flat[13:] == 0)                  # zero padding
    grads = jax.tree.map(jnp.ones_like, params)
    upd, st2 = opt.update(grads, st, params)
    assert np.all(np.asarray(st2["zero1"]["master"]).reshape(-1)[13:] == 0)
    new = jax.tree.map(lambda p, u: p + u, params, upd)
    # element-wise rmsprop on the flat layout == rmsprop on the tree
    ref_upd, _ = opt_lib.rmsprop(1e-2).update(
        grads, opt_lib.rmsprop(1e-2).init(params), params)
    for k in params:
        np.testing.assert_allclose(np.asarray(new[k]),
                                   np.asarray(params[k] + ref_upd[k]),
                                   rtol=1e-6)


def test_per_device_state_bytes_zero1_is_fraction_of_replicated():
    """The bench's memory columns: a zero1 state's per-device
    optimizer+master bytes must be ~1/N of the replicated equivalent."""
    from repro.parallel import jaxpr_cost

    n = 8
    task_rep = engine_lib.gan_task(GAN_CFG, opt_lib.rmsprop(1e-4),
                                   opt_lib.rmsprop(1e-4))
    task_z = engine_lib.gan_task(
        GAN_CFG, opt_lib.zero1(opt_lib.rmsprop(1e-4), n),
        opt_lib.zero1(opt_lib.rmsprop(1e-4), n))
    rep = jax.eval_shape(task_rep.init, jax.random.key(0))
    z = jax.eval_shape(task_z.init, jax.random.key(0))
    # optimizer + master: replicated masters are the f32 params
    om_rep = (jaxpr_cost.per_device_state_bytes(
        {"g": rep.g_opt, "d": rep.d_opt}, 1)
        + jaxpr_cost.per_device_state_bytes(
            {"g": rep.g_params, "d": rep.d_params}, 1))
    om_z = jaxpr_cost.per_device_state_bytes({"g": z.g_opt, "d": z.d_opt}, n)
    assert om_z <= om_rep / n * 1.10 + 65536
    # and sharding marks only the zero1 subtree
    assert jaxpr_cost.per_device_state_bytes(z, n) < \
        jaxpr_cost.per_device_state_bytes(z, 1)


# ---------------------------------------------------------------------------
# jaxpr collective accounting + reduce traffic
# ---------------------------------------------------------------------------


def test_grad_reduce_traffic_matches_param_bytes():
    from repro.core import gan
    from repro.parallel.sharding import count_params

    cfg = calo3dgan.reduced()
    traffic = adversarial.grad_reduce_traffic(cfg)
    g = gan.init_generator(jax.random.key(0), cfg)
    d = gan.init_discriminator(jax.random.key(1), cfg)
    gb, db = 4 * count_params(g), 4 * count_params(d)
    rounds = dict(traffic["rounds"])
    assert rounds["d_real"] == db and rounds["d_fake"] == db
    assert rounds["g0"] == gb
    assert traffic["bytes_per_step"] == 2 * db + cfg.gen_steps_per_disc * gb


def test_jaxpr_cost_counts_shard_map_psum_bytes():
    from jax.sharding import PartitionSpec as P

    mesh = make_node_mesh(1, 1)

    def local(x):
        return jax.lax.psum(x, ("node", "device"))

    fn = jax.shard_map(local, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    stats = cost_of(fn, jax.ShapeDtypeStruct((256, 128), jnp.float32))
    # mesh.size (=1) * result bytes
    assert stats["collective_bytes"] == 256 * 128 * 4


def test_jaxpr_cost_per_kind_collective_bytes():
    """psum / all_gather / psum_scatter land in their own byte columns
    (what separates ZeRO's reduce-scatter + all-gather from plain
    all-reduce in the bench report)."""
    from jax.sharding import PartitionSpec as P

    mesh = make_node_mesh(1, 1)

    def local(x):
        a = jax.lax.psum(x, ("node", "device"))
        g = jax.lax.all_gather(a, ("node", "device"), axis=0, tiled=False)
        s = jax.lax.psum_scatter(a.reshape(-1), ("node", "device"),
                                 tiled=True)
        return a, g, s

    fn = jax.shard_map(local, mesh=mesh, in_specs=P(),
                       out_specs=(P(), P(), P()), check_vma=False)
    stats = cost_of(fn, jax.ShapeDtypeStruct((16, 8), jnp.float32))
    nb = 16 * 8 * 4
    assert stats["psum_bytes"] == nb
    assert stats["all_gather_bytes"] == nb          # world size 1
    assert stats["reduce_scatter_bytes"] == nb
    assert stats["collective_bytes"] == 3 * nb


def test_collective_schedule_overlap_exposes_less():
    """The MEASURED overlap story: the reverse-order bucket schedule must
    leave a strictly smaller byte-fraction of its collectives exposed
    (no independent later compute) than the post-backward hierarchical
    schedule, on the real custom-loop GAN step."""
    from jax.sharding import PartitionSpec as P
    from repro.parallel import jaxpr_cost

    mesh = make_node_mesh(1, 1)
    sim = CaloSimulator(CaloSpec(image_shape=GAN_CFG.image_shape), seed=0)
    batch = next(sim.batches(8))
    fracs = {}
    for strat in ("hierarchical", "overlap"):
        task = engine_lib.gan_task(GAN_CFG, opt_lib.rmsprop(1e-4),
                                   opt_lib.rmsprop(1e-4))
        eng = engine_lib.Engine(mesh, "custom", dp_axes=("node", "device"),
                                grad_reduce=strat, bucket_mb=0.05)
        state = eng.init_state(task, jax.random.key(0))
        reduce = collectives.make_grad_reduce(strat, mesh,
                                              ("node", "device"),
                                              bucket_bytes=int(0.05 *
                                                               (1 << 20)))
        step = task.make_step(grad_reduce=reduce, mesh=None)
        smapped = jax.shard_map(step, mesh=mesh,
                                in_specs=(P(), P(), P()),
                                out_specs=(P(), P()), check_vma=False)
        sched = jaxpr_cost.schedule_of(smapped, state, batch,
                                       jax.random.key(1))
        assert sched["n_collectives"] > 0
        fracs[strat] = sched["exposed_frac"]
    assert 0.0 < fracs["overlap"] < fracs["hierarchical"] <= 1.0


def test_custom_loop_collective_bytes_cover_grad_traffic():
    """The custom GAN step's traced psums must carry at least the
    per-phase gradient payload adversarial.grad_reduce_traffic predicts
    (plus small metric reductions) — the jaxpr walk feeds the
    interconnect model with the right order of magnitude."""
    mesh = make_node_mesh(1, 1)
    task = engine_lib.gan_task(GAN_CFG, opt_lib.rmsprop(1e-4),
                               opt_lib.rmsprop(1e-4))
    eng = engine_lib.Engine(mesh, "custom", dp_axes=("node", "device"))
    sim = CaloSimulator(CaloSpec(image_shape=GAN_CFG.image_shape), seed=0)
    batch = next(sim.batches(8))
    step = task.make_step(grad_reduce=eng._grad_reduce, mesh=None)
    from jax.sharding import PartitionSpec as P
    state = eng.init_state(task, jax.random.key(0))
    smapped = jax.shard_map(step, mesh=mesh,
                            in_specs=(P(), P(), P()), out_specs=(P(), P()),
                            check_vma=False)
    stats = cost_of(smapped, state, batch, jax.random.key(1))
    expect = adversarial.grad_reduce_traffic(GAN_CFG)["bytes_per_step"]
    assert stats["collective_bytes"] >= expect
    assert stats["collective_bytes"] <= expect * 1.5 + (1 << 20)


# ---------------------------------------------------------------------------
# the 2x2 multi-participant gate (subprocess: own 4-device pool)
# ---------------------------------------------------------------------------


def test_virtual_2x2_parity_subprocess():
    """Runs tools/parity_scaleout.py — 4 virtual devices folded into
    (node=2, device=2), REAL two-participant reductions at both levels —
    and requires parity for both loops across every strategy (flat /
    hierarchical / overlap) plus the ZeRO-1 sharded-optimizer gate
    (the CI scaleout-smoke job)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parity_scaleout.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "parity OK" in r.stdout
    assert "zero1 parity OK" in r.stdout
