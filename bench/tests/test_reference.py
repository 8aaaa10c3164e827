"""The float32 reference against the program's own f32 path, at
``calo3dgan.reduced()`` sizes: the two must agree to float32 rounding, so
any gap a chip run reads comes from the program's precision or a fault."""
import jax
import numpy as np
import pytest

import helpers
from harness import compare, reference

TRAIN = helpers.common.load_module(
    f"{helpers.BENCH}/drivers/gan_train.py", "drv_train_ref")


def _cfgs():
    conf = helpers.conf("calo3dgan-train", precision="f32")
    return conf, TRAIN.gan_config(conf), TRAIN.reference_config(conf)


def test_weights_from_the_same_seed_are_the_programs():
    from repro.core import gan
    _, cfg, rcfg = _cfgs()
    k = jax.random.key(3)
    for mine, theirs in ((reference.init_generator(k, rcfg),
                          gan.init_generator(k, cfg)),
                         (reference.init_discriminator(k, rcfg),
                          gan.init_discriminator(k, cfg))):
        a, b = compare._leaves(mine), compare._leaves(theirs)
        assert a.keys() == b.keys()
        for key in a:
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)


def test_forward_passes_match_core_gan():
    from repro.core import gan
    _, cfg, rcfg = _cfgs()
    kg, kd, kn = jax.random.split(jax.random.key(5), 3)
    g, d = gan.init_generator(kg, cfg), gan.init_discriminator(kd, cfg)
    noise = jax.random.normal(kn, (4, cfg.latent_dim))
    e_p = np.array([10.0, 100.0, 250.0, 500.0], np.float32)
    th = np.array([1.1, 1.4, 1.6, 2.0], np.float32)
    with jax.default_matmul_precision("highest"):
        want = gan.generate(g, noise, e_p, th, cfg)
        want_d = gan.discriminate(d, want, cfg)
    got = reference.generate(g, noise, e_p, th, rcfg)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)
    for a, b in zip(reference.discriminate(d, got, rcfg), want_d):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("chips", [1, 4])
def test_algorithm1_steps_match_the_engine(chips):
    """Three steps of the engine's custom loop in f32 against the
    reference's: losses, first gradients and updates to rounding."""
    conf, _, _ = _cfgs()
    traffic = helpers.traffic("train-steps-pool4")
    with jax.default_matmul_precision("highest"):
        prog = TRAIN.Program(conf, traffic, 11, jax.devices()[:chips])
        got = TRAIN.check_steps(prog, 3)
    ref = TRAIN.reference_readings(conf, prog.pool, prog.init_key,
                                   prog.step_rng, chips, 3)
    r = compare.training_readings(got, ref)
    assert r["loss_rel_gap"] < 1e-5
    assert r["grad_norm_gap"] < 1e-4
    assert r["update_norm_gap"] < 1e-4
    assert r["_left_out"] == []
