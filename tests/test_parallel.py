"""Distribution layer: logical-axis spec resolution, collective-traffic HLO
parsing (incl. while-loop scaling), jaxpr cost analysis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.parallel import collectives, sharding
from repro.parallel.jaxpr_cost import cost_of, jaxpr_cost


def _mesh2(data=2, model=1):
    devs = np.array(jax.devices()[:1] * (data * model)).reshape(data, model)
    return Mesh(devs, ("data", "model"))


# ---------------------------------------------------------------------------
# resolve_spec
# ---------------------------------------------------------------------------


def test_resolve_spec_basic():
    mesh = make_mesh((1, 1), ("data", "model"))
    spec = sharding.resolve_spec(("embed", "heads"), (64, 64), mesh,
                                 sharding.FSDP_TP_RULES)
    assert spec == P("data", "model")


def test_resolve_spec_drops_non_dividing_axis():
    mesh = make_mesh((1, 1), ("data", "model"))
    # dim 3 % mesh size 1 == 0 always with size-1 axes; use synthetic rules
    rules = {"x": "data"}
    spec = sharding.resolve_spec(("x",), (3,), mesh, rules)
    assert spec == P("data")        # size-1 axis always divides


def test_resolve_spec_never_reuses_axis():
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = {"a": "model", "b": "model"}
    spec = sharding.resolve_spec(("a", "b"), (8, 8), mesh, rules)
    assert spec == P("model", None)     # second use dropped


def test_resolve_spec_tuple_rule():
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = {"batch": ("pod", "data")}      # pod not in mesh -> filtered
    spec = sharding.resolve_spec(("batch", None), (8, 4), mesh, rules)
    assert spec == P("data", None)


def test_dp_rules_replicate_params():
    """Paper-faithful mirrored strategy: every param spec resolves to fully
    replicated under DP_RULES."""
    mesh = make_mesh((1, 1), ("data", "model"))
    spec = sharding.resolve_spec(("embed", "heads"), (64, 64), mesh,
                                 sharding.DP_RULES)
    assert spec == P(None, None)


def test_tree_specs_all_leaves_covered():
    from repro.configs import base as config_base
    from repro.models import api
    mesh = make_mesh((1, 1), ("data", "model"))
    for arch in ("qwen2-1.5b", "olmoe-1b-7b", "xlstm-125m", "zamba2-1.2b",
                 "whisper-base"):
        cfg = config_base.reduced_config(arch)
        model = api.get_model(cfg)
        shapes = jax.eval_shape(lambda m=model, c=cfg: m.init(
            jax.random.key(0), c))
        specs = sharding.tree_specs(model.logical_axes(cfg), shapes, mesh,
                                    sharding.FSDP_TP_RULES)
        n = len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)))
        assert n == len(jax.tree.leaves(shapes))


# ---------------------------------------------------------------------------
# collective HLO parsing
# ---------------------------------------------------------------------------

_FAKE_HLO = """\
HloModule test

%body.1 (p: (s32[], f32[16,128])) -> (s32[], f32[16,128]) {
  %p = (s32[], f32[16,128]) parameter(0)
  %ar = f32[16,128] all-reduce(%x), replica_groups={}, to_apply=%add
  ROOT %t = (s32[], f32[16,128]) tuple(%i, %ar)
}

%cond.1 (p: (s32[], f32[16,128])) -> pred[] {
  %p2 = (s32[], f32[16,128]) parameter(0)
  %iter = s32[] get-tuple-element(%p2), index=0
  %lim = s32[] constant(12)
  ROOT %cmp = pred[] compare(%iter, %lim), direction=LT
}

ENTRY %main (a: f32[16,128]) -> f32[16,128] {
  %a = f32[16,128] parameter(0)
  %ag = f32[32,128] all-gather(%a), dimensions={0}
  %w = (s32[], f32[16,128]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[16,128] get-tuple-element(%w), index=1
}
"""


def test_collective_stats_loop_scaling():
    unscaled = collectives.collective_stats(_FAKE_HLO, scale_loops=False)
    scaled = collectives.collective_stats(_FAKE_HLO)
    f32 = 4
    assert unscaled["all-gather"]["bytes"] == 32 * 128 * f32
    assert unscaled["all-reduce"]["bytes"] == 16 * 128 * f32
    # the all-reduce sits in a 12-trip while body
    assert scaled["all-reduce"]["bytes"] == 12 * 16 * 128 * f32
    assert scaled["all-gather"]["bytes"] == unscaled["all-gather"]["bytes"]
    assert scaled["all-reduce"]["count"] == 12


def test_ici_traffic_model():
    stats = {"all-reduce": {"bytes": 1000, "count": 1},
             "all-gather": {"bytes": 1000, "count": 1}}
    t = collectives.ici_traffic_bytes(stats, n_devices=4)
    # ring: AR = 2*(3/4)*b, AG = (3/4)*b
    assert abs(t - (2 * 750 + 750)) < 1e-6


# ---------------------------------------------------------------------------
# jaxpr cost
# ---------------------------------------------------------------------------


def test_jaxpr_cost_plain_matmul():
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    stats = cost_of(lambda x, y: x @ y, a, b)
    assert stats["flops"] == 2 * 128 * 256 * 64


def test_jaxpr_cost_scan_multiplies_by_length():
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def once(x):
        return x @ x

    def scanned(x):
        def body(c, _):
            return c @ c, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    f1 = cost_of(once, a)["flops"]
    f10 = cost_of(scanned, a)["flops"]
    assert f10 == 10 * f1


def test_jaxpr_cost_sees_through_remat_and_grad():
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def loss(x):
        return jnp.sum(jax.checkpoint(lambda y: jnp.tanh(y @ y))(x))

    f_fwd = cost_of(lambda x: jnp.tanh(x @ x), a)["flops"]
    f_grad = cost_of(jax.grad(loss), a)["flops"]
    # grad with remat: forward + recompute + 2 backward matmuls >= 3x fwd
    assert f_grad >= 3 * f_fwd


def test_jaxpr_cost_conv():
    x = jax.ShapeDtypeStruct((1, 8, 8, 8, 4), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 3, 3, 4, 8), jnp.float32)

    def conv(x_, w_):
        return jax.lax.conv_general_dilated(
            x_, w_, (1, 1, 1), "SAME",
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))

    stats = cost_of(conv, x, w)
    assert stats["flops"] == 2 * (8 ** 3) * 27 * 4 * 8


def test_jaxpr_cost_train_step_vs_model_flops():
    """End-to-end: jaxpr flops for a reduced train step within sane bounds
    of the 6*N*D napkin estimate (remat adds ~4/3, attention adds more)."""
    from repro.configs import base as config_base
    from repro.models import api
    from repro.optim import optimizers as opt_lib
    from repro.substrate.precision import get_policy
    from repro.train import steps as steps_lib

    cfg = config_base.reduced_config("qwen2-1.5b")
    model = api.get_model(cfg)
    opt = opt_lib.adamw(1e-3)
    step = steps_lib.make_train_step(model, cfg, opt, get_policy("f32"))
    B, S = 4, 256
    p_shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), cfg))
    o_shapes = jax.eval_shape(opt.init, p_shapes)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    stats = cost_of(step, p_shapes, o_shapes, batch)
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(p_shapes))
    model_flops = 6 * n_params * B * S
    assert model_flops < stats["flops"] < 3 * model_flops
