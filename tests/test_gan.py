"""The paper's core: 3DGAN adversarial training (Algorithm 1).

Integration tests: naive and fused loops agree where they share RNG-free
math, a short fused training run improves the discriminator/physics
metrics, and the physics validation utilities behave."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import calo3dgan
from repro.core import adversarial, gan, validation
from repro.data.calo import CaloSimulator, CaloSpec
from repro.optim import optimizers as opt_lib

CFG = calo3dgan.reduced()


@pytest.fixture(scope="module")
def sim():
    return CaloSimulator(CaloSpec(image_shape=CFG.image_shape), seed=11)


@pytest.fixture(scope="module")
def batch(sim):
    b = next(sim.batches(16))
    return {k: jnp.asarray(v) for k, v in b.items()}


@pytest.fixture(scope="module")
def opts():
    return opt_lib.rmsprop(1e-4), opt_lib.rmsprop(1e-4)


def test_generator_output_shape_and_nonnegative():
    p = gan.init_generator(jax.random.key(0), CFG)
    noise = jax.random.normal(jax.random.key(1), (4, CFG.latent_dim))
    e_p = jnp.array([50.0, 100.0, 200.0, 400.0])
    theta = jnp.full((4,), jnp.pi / 2)
    img = gan.generate(p, noise, e_p, theta, CFG)
    X, Y, Z = CFG.image_shape
    assert img.shape == (4, X, Y, Z, 1)
    assert (np.asarray(img) >= 0).all()          # softplus energies


@pytest.mark.parametrize("stride", [1, 2])
def test_single_channel_conv_matches_lax_conv(stride):
    """The discriminator's first layer (1 input channel) is computed as
    patches @ kernel; it must be the same conv, value and gradients."""
    kx, kw = jax.random.split(jax.random.key(stride))
    x = jax.random.normal(kx, (2, 11, 9, 7, 1))
    w = jax.random.normal(kw, (3, 3, 3, 1, 4))

    def ref(x, w):
        return jax.lax.conv_general_dilated(x, w, (stride,) * 3, "SAME",
                                            dimension_numbers=gan.DN)

    def loss(f):
        return lambda x, w: (f(x, w) ** 2).sum()

    out = gan._conv_layer(x, w, stride=stride, pallas=False)
    np.testing.assert_allclose(out, ref(x, w), rtol=1e-5, atol=1e-5)
    got = jax.grad(loss(lambda x, w: gan._conv_layer(
        x, w, stride=stride, pallas=False)), argnums=(0, 1))(x, w)
    want = jax.grad(loss(ref), argnums=(0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)


def test_generator_energy_conditioning():
    """Higher E_p must produce more total deposited energy (built-in
    response scaling — the physics prior the GAN starts from)."""
    p = gan.init_generator(jax.random.key(0), CFG)
    noise = jnp.zeros((2, CFG.latent_dim))
    e_p = jnp.array([50.0, 400.0])
    theta = jnp.full((2,), jnp.pi / 2)
    img = gan.generate(p, noise, e_p, theta, CFG)
    tot = np.asarray(img.sum(axis=(1, 2, 3, 4)))
    assert tot[1] > tot[0]


def test_discriminator_heads(batch):
    p = gan.init_discriminator(jax.random.key(0), CFG)
    v, e, t = gan.discriminate(p, batch["image"], CFG)
    assert v.shape == e.shape == t.shape == (16,)
    assert (np.asarray(e) >= 0).all()            # softplus energy head


def test_naive_and_fused_agree_on_d_real_loss(batch, opts):
    """The D-on-real update has no RNG: the naive (train_on_batch) and the
    fused (custom loop) implementations must produce the same loss."""
    g_opt, d_opt = opts
    state = adversarial.init_state(jax.random.key(0), CFG, g_opt, d_opt)
    naive = adversarial.NaiveStep(CFG, g_opt, d_opt, seed=1)
    fused = jax.jit(adversarial.make_fused_step(CFG, g_opt, d_opt))
    _, m_naive = naive(state, {k: np.asarray(v) for k, v in batch.items()})
    _, m_fused = fused(state, batch, jax.random.key(2))
    assert m_naive["d_loss_real"] == pytest.approx(
        float(m_fused["d_loss_real"]), rel=1e-4)


def test_fused_step_trains(sim, opts):
    """25 fused steps: losses stay finite, D accuracy on real data improves
    over the first steps, generator output remains non-degenerate."""
    g_opt, d_opt = opts
    state = adversarial.init_state(jax.random.key(0), CFG, g_opt, d_opt)
    fused = jax.jit(adversarial.make_fused_step(CFG, g_opt, d_opt),
                    donate_argnums=(0,))
    rng = jax.random.key(3)
    accs, g_losses = [], []
    it = sim.batches(16)
    for i in range(25):
        b = {k: jnp.asarray(v) for k, v in next(it).items()}
        rng, k = jax.random.split(rng)
        state, m = fused(state, b, k)
        accs.append(float(m["d_acc_real"]))
        g_losses.append(float(m["g_loss"]))
        assert np.isfinite(g_losses[-1])
    assert np.mean(accs[-5:]) > np.mean(accs[:5]) - 0.05
    noise = jax.random.normal(jax.random.key(9), (8, CFG.latent_dim))
    img = gan.generate(state.g_params, noise,
                       jnp.full((8,), 200.0), jnp.full((8,), jnp.pi / 2), CFG)
    assert np.isfinite(np.asarray(img)).all()
    assert float(img.max()) > 0


def test_gen_steps_per_disc_is_two():
    """Algorithm 1 trains G twice per D step."""
    assert CFG.gen_steps_per_disc == 2


# ---------------------------------------------------------------------------
# physics validation (Fig. 3/7 machinery)
# ---------------------------------------------------------------------------


def test_calo_simulator_profiles(sim):
    img, e_p, theta, ecal = sim.generate(128)
    # response ~ sampling fraction
    resp = ecal / e_p
    assert 0.01 < resp.mean() < 0.05
    # longitudinal profile has a single interior maximum (shower max)
    prof = validation.longitudinal_profile(img[..., None])
    peak = prof.argmax()
    assert 0 < peak < len(prof) - 1
    # transverse profile peaks near the centre
    tx = validation.transverse_profile(img[..., None], "x")
    assert abs(int(tx.argmax()) - CFG.image_shape[0] // 2) <= 2


def test_profile_divergence_sane():
    p = np.array([0.2, 0.5, 0.3])
    assert validation.profile_divergence(p, p) == pytest.approx(0.0, abs=1e-9)
    q = np.array([0.5, 0.2, 0.3])
    assert validation.profile_divergence(p, q) > 0.01


def test_validation_report_mc_self_consistency():
    """MC vs MC (different seeds) is the noise floor: divergences tiny.
    Fresh, fixed-seed simulators — independent of test execution order."""
    spec = CaloSpec(image_shape=CFG.image_shape)
    a, e_a, _, _ = CaloSimulator(spec, seed=101).generate(512)
    b, e_b, _, _ = CaloSimulator(spec, seed=202).generate(512)
    rep = validation.validation_report(a[..., None], b[..., None], e_a, e_b)
    assert rep["longitudinal_kl"] < 2e-3
    assert rep["transverse_x_kl"] < 2e-3
    assert rep["response_rel_err"] < 0.05


def test_theta_conditioning_tilts_shower(sim):
    """Off-perpendicular incidence shifts the shower centroid along x with
    depth — the angle physics the ACGAN aux head must learn."""
    spec = CaloSpec(image_shape=CFG.image_shape)
    s = CaloSimulator(spec, seed=5)
    n = 64
    e_p = np.full(n, 200.0, np.float32)
    img_tilt = []
    for theta in (np.deg2rad(70.0), np.deg2rad(110.0)):
        sim2 = CaloSimulator(spec, seed=5)
        img, *_ = sim2.generate(n)
        img_tilt.append(img)
    # centroid_x at last depth layer differs between 70 and 110 degrees
    def centroid_last_z(img):
        last = img[..., -1]
        xs = np.arange(img.shape[1])
        w = last.sum(axis=2)
        return (w * xs[None]).sum() / max(w.sum(), 1e-9)
    # same seed -> same E_p/theta draws... so instead check correlation
    # between theta and centroid within one sample set
    img, e_p, theta, _ = s.generate(256)
    cx = [(img[i].sum(axis=(1,))[:, -1] * np.arange(img.shape[1])).sum()
          / max(img[i].sum(axis=(1,))[:, -1].sum(), 1e-9)
          for i in range(256)]
    corr = np.corrcoef(theta, cx)[0, 1]
    assert abs(corr) > 0.5


def test_gan_generator_pallas_conv_path():
    """The Pallas implicit-GEMM conv path produces the same generator
    output as the lax.conv path (interpret mode, tiny config)."""
    import dataclasses
    cfg = dataclasses.replace(calo3dgan.bench(), image_shape=(8, 8, 8),
                              gen_channels=(8, 4), disc_channels=(4, 8),
                              latent_dim=16)
    p = gan.init_generator(jax.random.key(0), cfg)
    noise = jax.random.normal(jax.random.key(1), (2, cfg.latent_dim))
    e_p = jnp.array([100.0, 300.0])
    th = jnp.full((2,), jnp.pi / 2)
    ref = gan.generate(p, noise, e_p, th, cfg)
    with gan.use_pallas_conv():
        out = gan.generate(p, noise, e_p, th, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)

    dp = gan.init_discriminator(jax.random.key(2), cfg)
    v_ref, e_ref, t_ref = gan.discriminate(dp, ref, cfg)
    with gan.use_pallas_conv():
        v, e, t = gan.discriminate(dp, ref, cfg)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), atol=1e-3)


# ---------------------------------------------------------------------------
# the last transposed conv emits only the image's voxels
# ---------------------------------------------------------------------------


def _generate_crop_late(p, noise, e_p, theta, cfg):
    """The generator as written before the crop moved: every upsampling
    layer runs SAME over its whole grid, and the image is cut from the
    last one after its layernorm and LeakyReLU."""
    dn = ("NDHWC", "DHWIO", "NDHWC")
    ups = len(cfg.gen_channels) - 1
    d0 = [-(-d // 2 ** ups) for d in cfg.image_shape]
    e_n = (e_p / 100.0)[:, None]
    z = jnp.concatenate([noise, e_n, theta[:, None]], axis=-1)
    x = jax.nn.leaky_relu(z @ p["fc"]["w"] + p["fc"]["b"], 0.2)
    x = x.reshape(-1, *d0, cfg.gen_channels[0])
    for i in range(ups):
        q = p[f"up{i}"]
        x = jax.lax.conv_transpose(x, q["w"], (2, 2, 2), "SAME",
                                   dimension_numbers=dn) + q["b"]
        mean = x.mean(-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        x = (x - mean) * jax.lax.rsqrt(var + 1e-5)
        x = jax.nn.leaky_relu(x * q["gn"]["scale"] + q["gn"]["bias"], 0.2)
    X, Y, Z = cfg.image_shape
    x = x[:, :X, :Y, :Z]
    x = jax.lax.conv_general_dilated(x, p["out"]["w"], (1, 1, 1), "SAME",
                                     dimension_numbers=dn) + p["out"]["b"]
    return jax.nn.softplus(x) * (e_n[:, None, None, None] * 0.025)


@pytest.mark.parametrize("cfg", [
    calo3dgan.config(),                                   # 56x56x32 -> 51x51x25
    calo3dgan.reduced(),                                  # 14^3 -> 13^3
    calo3dgan.bench(),                                    # 10^3 -> 9^3
    dataclasses.replace(calo3dgan.bench(), image_shape=(8, 8, 8)),  # no overhang
], ids=["full", "reduced", "bench", "no_overhang"])
def test_generator_crops_before_norm_matches_crop_late(cfg):
    """Cropping inside the last transposed conv (negative high padding)
    gives the values and the parameter gradients of cropping after its
    layernorm and LeakyReLU, on the lax path, in f32."""
    cfg = dataclasses.replace(cfg, use_pallas_conv=False)
    p = gan.init_generator(jax.random.key(0), cfg)
    # nonzero biases and norm affines, so every parameter takes part
    leaves, tree = jax.tree_util.tree_flatten(p)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    p = jax.tree_util.tree_unflatten(tree, [
        v + 0.05 * jax.random.normal(k, v.shape) for v, k in zip(leaves, keys)])
    noise = jax.random.normal(jax.random.key(2), (2, cfg.latent_dim))
    e_p = jnp.array([80.0, 300.0])
    theta = jnp.array([1.2, 1.9])
    probe = jax.random.normal(jax.random.key(3), (2, *cfg.image_shape, 1))

    def scalar(f):
        return lambda p: (f(p, noise, e_p, theta, cfg) * probe).sum()

    got = jax.jit(gan.generate, static_argnums=4)(p, noise, e_p, theta, cfg)
    want = jax.jit(_generate_crop_late, static_argnums=4)(
        p, noise, e_p, theta, cfg)
    assert got.shape == (2, *cfg.image_shape, 1)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    g_got = jax.jit(jax.grad(scalar(gan.generate)))(p)
    g_want = jax.jit(jax.grad(scalar(_generate_crop_late)))(p)
    # the last layer's bias and norm gradients sum over the kept voxels in
    # another order than over the whole grid (f32 reassociation: 2.3e-6 of
    # the leaf's norm at the full config); every other leaf is bit-equal
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree_util.tree_leaves(g_want)):
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert err < 1e-5, (jax.tree_util.keystr(path), err)


def test_generator_pallas_path_crops_after_kernel():
    """With an overhang (a 7^3 image from an 8^3 grid) the Pallas route,
    which slices the kernel's output before the norm, gives the lax
    route's image (interpret mode, tiny config)."""
    cfg = dataclasses.replace(calo3dgan.bench(), image_shape=(7, 7, 7),
                              gen_channels=(8, 4), disc_channels=(4, 8),
                              latent_dim=16)
    p = gan.init_generator(jax.random.key(0), cfg)
    noise = jax.random.normal(jax.random.key(1), (2, cfg.latent_dim))
    e_p = jnp.array([100.0, 300.0])
    th = jnp.full((2,), jnp.pi / 2)
    ref = gan.generate(p, noise, e_p, th,
                       dataclasses.replace(cfg, use_pallas_conv=False))
    out = gan.generate(p, noise, e_p, th,
                       dataclasses.replace(cfg, use_pallas_conv=True))
    assert out.shape == (2, 7, 7, 7, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


def test_fused_step_never_builds_the_uncropped_grid():
    """The full-size fused step, lowered (no compile): the last upsampling
    layer's 56x56x32 grid appears nowhere, its 51x51x25x8 output does."""
    from repro.substrate.precision import get_policy
    cfg = calo3dgan.config()
    g_opt, d_opt = opt_lib.rmsprop(1e-4), opt_lib.rmsprop(1e-4)
    policy = get_policy(cfg.precision)
    state = jax.eval_shape(lambda k: adversarial.init_state(
        k, cfg, g_opt, d_opt, policy), jax.random.key(0))
    B, (X, Y, Z) = cfg.batch_size, cfg.image_shape
    batch = {"image": jax.ShapeDtypeStruct((B, X, Y, Z, 1), jnp.float32),
             "e_p": jax.ShapeDtypeStruct((B,), jnp.float32),
             "theta": jax.ShapeDtypeStruct((B,), jnp.float32),
             "ecal": jax.ShapeDtypeStruct((B,), jnp.float32)}
    step = adversarial.make_fused_step(cfg, g_opt, d_opt, policy=policy)
    text = jax.jit(step).lower(state, batch, jax.random.key(1)).as_text()
    assert "x56x56x32x" not in text
    assert f"{B}x51x51x25x8x" in text
