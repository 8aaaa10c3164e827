"""The analytic counts against XLA's own cost analysis where XLA inserts
no zeros, and against the repository's jaxpr counter where it does."""
import math

import jax
import jax.numpy as jnp
import pytest

import helpers
from harness import flops, reference

FULL = helpers.common.load_json(
    f"{helpers.BENCH}/configs/calo3dgan-train.json")


def test_full_width_counts():
    assert flops.generator_flops_per_event(FULL) == 173_555_856
    assert flops.discriminator_flops_per_event(FULL) == 57_936_384
    # D real (fwd, dw, dx but the first layer's), D fake (G fwd + the
    # same), then twice: G fwd+dw+dx (not the fc's input) and D fwd+dx
    g, d = 173_555_856, 57_936_384
    g_fc = 2 * 256 * 12544
    d_c0 = 6_838_784
    want = (3 * d - d_c0) + (g + 3 * d - d_c0) + 2 * (3 * g - g_fc + 2 * d)
    assert flops.step_flops_per_sample(FULL) == want == 1_767_732_208


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return cost["flops"]


def _layer(cfg, name):
    layers = flops.generator_layers(cfg) + flops.discriminator_layers(cfg)
    return next(layer for layer in layers if layer.name == name)


@pytest.mark.parametrize("name", ["disc_conv0", "disc_conv1", "disc_conv2",
                                  "disc_conv3", "gen_out"])
def test_convs_without_inserted_zeros_match_xla(name):
    cfg = FULL
    layer = _layer(cfg, name)
    stride = 1 if name == "gen_out" else 2
    x = jnp.zeros((2,) + layer.in_shape, jnp.float32)
    w = jnp.zeros((3, 3, 3, layer.in_shape[-1], layer.out_shape[-1]))
    got = _xla_flops(lambda x, w: jax.lax.conv_general_dilated(
        x, w, (stride,) * 3, "SAME", dimension_numbers=reference.DN), x, w)
    assert got == pytest.approx(2 * layer.flops, rel=1e-6)


def test_discriminator_forward_is_the_sum_xla_counts_for_its_layers():
    """Every layer of the discriminator through XLA's cost analysis: the
    four convs as lax convs and the three heads as one dot."""
    total = 0.0
    for layer in flops.discriminator_layers(FULL):
        x = jnp.zeros((1,) + layer.in_shape, jnp.float32)
        if layer.kind == "dense":
            w = jnp.zeros((layer.in_shape[0], layer.out_shape[0]))
            total += _xla_flops(jnp.dot, x, w)
            continue
        w = jnp.zeros((3, 3, 3, layer.in_shape[-1], layer.out_shape[-1]))
        total += _xla_flops(lambda x, w: jax.lax.conv_general_dilated(
            x, w, (2,) * 3, "SAME", dimension_numbers=reference.DN), x, w)
    assert total == pytest.approx(flops.discriminator_flops_per_event(FULL),
                                  rel=1e-6)


@pytest.mark.parametrize("name", ["gen_up0", "gen_up1", "gen_up2"])
def test_transposed_convs_count_without_the_dilation_zeros(name):
    """XLA's cost analysis skips the zeros an lhs-dilated lowering inserts
    and matches the count exactly; the repository's jaxpr counter
    (``parallel/jaxpr_cost``) multiplies every tap of the dilated input,
    zeros and padding included: 9.6x, 8.7x and 8.4x the useful work of
    the three layers."""
    from repro.parallel import jaxpr_cost
    layer = _layer(FULL, name)
    x = jnp.zeros((1,) + layer.in_shape, jnp.float32)
    w = jnp.zeros((3, 3, 3, layer.in_shape[-1], layer.out_shape[-1]))
    fn = lambda x, w: reference.conv_transpose2(x, w, reference.identity)
    assert _xla_flops(fn, x, w) == pytest.approx(layer.flops, rel=1e-6)
    every_tap = 2 * 8 * 27 * layer.in_shape[-1] * layer.out_shape[-1] * \
        math.prod(layer.in_shape[:-1])
    jc = jaxpr_cost.cost_of(fn, x, w)["flops"]
    assert jc == every_tap
    assert jc / layer.flops == pytest.approx(
        {"gen_up0": 9.62, "gen_up1": 8.76, "gen_up2": 8.37}[name], abs=0.01)
