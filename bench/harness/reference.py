"""Plain float32 3-D conv ACGAN: the reference that decides ``correct``.

Written from the layers the configuration states (the repository's ACGAN
variant in arXiv:2111.04628's Algorithm-1 step) in straightforward
``jax.numpy`` and ``lax`` at ``Precision.HIGHEST``: no kernels, no patches
rewrite, no mixed-precision policy.  It imports nothing of the program under test and
takes nothing the program made: it draws its own weights and inputs from
the same seeds, by the same stated recipe (normal weights at 0.05,
dense heads at 0.02, zero biases, unit norm scales).

``q`` rounds the operands of every conv and dense layer; the identity
gives the reference, a rounding to a narrower type gives the control
(the same mathematics in a lower precision).
"""
from __future__ import annotations

import math
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
DN = ("NDHWC", "DHWIO", "NDHWC")
SLOPE = 0.2


def identity(x):
    return x


def rounding_to(dtype) -> Callable:
    """Operand rounding to ``dtype`` (e.g. ``jnp.float8_e4m3fn``), back in
    float32: a product of such operands accumulated in float32 is what
    hardware of that precision computes."""
    dt = jnp.dtype(dtype)
    return lambda x: x.astype(dt).astype(jnp.float32)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _start(image_shape, ups):
    return tuple(-(-d // 2 ** ups) for d in image_shape)


def init_generator(key, cfg: dict) -> dict:
    chs = list(cfg["gen_channels"])
    ups = len(chs) - 1
    d0 = _start(cfg["image_shape"], ups)
    z = cfg["latent_dim"] + 2
    ks = jax.random.split(key, len(chs) + 2)
    p = {"fc": {"w": 0.05 * jax.random.normal(ks[0], (z, math.prod(d0) * chs[0])),
                "b": jnp.zeros((math.prod(d0) * chs[0],))}}
    for i in range(ups):
        p[f"up{i}"] = {
            "w": 0.05 * jax.random.normal(ks[i + 1], (3, 3, 3, chs[i], chs[i + 1])),
            "b": jnp.zeros((chs[i + 1],)),
            "gn": {"scale": jnp.ones((chs[i + 1],)),
                   "bias": jnp.zeros((chs[i + 1],))}}
    p["out"] = {"w": 0.05 * jax.random.normal(ks[-1], (3, 3, 3, chs[-1], 1)),
                "b": jnp.zeros((1,))}
    return p


def init_discriminator(key, cfg: dict) -> dict:
    chs = list(cfg["disc_channels"])
    ks = jax.random.split(key, len(chs) + 3)
    p, c_in = {}, 1
    for i, c in enumerate(chs):
        p[f"conv{i}"] = {"w": 0.05 * jax.random.normal(ks[i], (3, 3, 3, c_in, c)),
                         "b": jnp.zeros((c,)),
                         "ln": {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}}
        c_in = c
    f = 2 ** len(chs)
    flat = math.prod(-(-d // f) for d in cfg["image_shape"]) * chs[-1]
    for j, head in enumerate(("validity", "energy", "angle")):
        p[head] = {"w": 0.02 * jax.random.normal(ks[len(chs) + j], (flat, 1)),
                   "b": jnp.zeros((1,))}
    return p


def init_state(key, cfg: dict) -> dict:
    kg, kd = jax.random.split(key)
    g, d = init_generator(kg, cfg), init_discriminator(kd, cfg)
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    return {"g": g, "d": d, "g_nu": zeros(g), "d_nu": zeros(d)}


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def conv(x, w, stride, q):
    """3x3x3 conv, SAME padding.  A conv of a 1-channel input is written
    as its defining sum over the 27 taps: elementwise and exact in
    float32, and its weight gradient compiles in seconds, where the v5e
    compiler takes many minutes over the conv form's."""
    if x.shape[-1] == 1:
        return _tap_sum_conv(q(x), q(w), stride)
    return jax.lax.conv_general_dilated(q(x), q(w), (stride,) * 3, "SAME",
                                        dimension_numbers=DN, precision=HI)


def _tap_sum_conv(x, w, stride):
    """out[o, c] = sum over taps a of x_padded[stride * o + a] * w[a, c]."""
    spatial = x.shape[1:4]
    out = [-(-n // stride) for n in spatial]
    pads = []
    for n, m in zip(spatial, out):
        total = max((m - 1) * stride + 3 - n, 0)
        pads.append((total // 2, total - total // 2))
    xp = jnp.pad(x[..., 0], [(0, 0)] + pads)
    acc = 0.0
    for a in range(3):
        for b in range(3):
            for c in range(3):
                tap = jax.lax.slice(
                    xp, (0, a, b, c),
                    (xp.shape[0], a + stride * (out[0] - 1) + 1,
                     b + stride * (out[1] - 1) + 1,
                     c + stride * (out[2] - 1) + 1),
                    (1, stride, stride, stride))
                acc = acc + tap[..., None] * w[a, b, c, 0]
    return acc


def conv_transpose2(x, w, q):
    """Stride-2 transposed conv, SAME: each input voxel scatters its 3x3x3
    footprint onto the doubled grid.  Written as the conv of the
    2x-dilated input with padding (2, 1) per side, which places output
    voxel o at the sum over taps a of x[(o + a - 2) / 2] w[a]."""
    return jax.lax.conv_general_dilated(
        q(x), q(w), (1, 1, 1), [(2, 1)] * 3, lhs_dilation=(2, 2, 2),
        dimension_numbers=DN, precision=HI)


def dense(x, p, q):
    return jnp.dot(q(x), q(p["w"]), precision=HI) + p["b"]


def layernorm(x, p, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def leaky(x):
    return jnp.where(x >= 0, x, SLOPE * x)


def softplus(x):
    return jnp.logaddexp(x, 0.0)


# ---------------------------------------------------------------------------
# networks
# ---------------------------------------------------------------------------


def generate(p, noise, e_p, theta, cfg: dict, q=identity):
    chs = list(cfg["gen_channels"])
    ups = len(chs) - 1
    d0 = _start(cfg["image_shape"], ups)
    e_n = (e_p / 100.0)[:, None]
    z = jnp.concatenate([noise, e_n, theta[:, None]], axis=-1)
    x = leaky(dense(z, p["fc"], q)).reshape(-1, *d0, chs[0])
    for i in range(ups):
        x = conv_transpose2(x, p[f"up{i}"]["w"], q) + p[f"up{i}"]["b"]
        x = leaky(layernorm(x, p[f"up{i}"]["gn"]))
    X, Y, Z = cfg["image_shape"]
    x = x[:, :X, :Y, :Z]
    x = softplus(conv(x, p["out"]["w"], 1, q) + p["out"]["b"])
    return x * (e_n * 0.025)[:, :, None, None, None]


def discriminate(p, img, cfg: dict, q=identity):
    x = jnp.log1p(img * 50.0)
    for i in range(len(cfg["disc_channels"])):
        c = p[f"conv{i}"]
        x = leaky(layernorm(conv(x, c["w"], 2, q) + c["b"], c["ln"]))
    x = x.reshape(x.shape[0], -1)
    v = dense(x, p["validity"], q)[:, 0]
    e = softplus(dense(x, p["energy"], q)[:, 0]) * 100.0
    t = dense(x, p["angle"], q)[:, 0] + jnp.pi / 2
    return v, e, t


# ---------------------------------------------------------------------------
# Algorithm 1 losses
# ---------------------------------------------------------------------------


def _bce(logit, target):
    return jnp.mean(jnp.maximum(logit, 0) - logit * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def _mape(pred, true):
    return jnp.mean(jnp.abs(pred - true) / jnp.maximum(jnp.abs(true), 1e-3))


def _acgan_loss(v, e, t, img, labels, target, cfg):
    e_p, theta, ecal = labels
    ecal_img = jnp.sum(img, axis=(1, 2, 3, 4))
    return (_bce(v, target) + cfg["aux_energy_weight"] * _mape(e, e_p) / 10.0
            + cfg["aux_angle_weight"] * jnp.mean(jnp.abs(t - theta))
            + cfg["aux_ecal_weight"] * _mape(ecal_img, ecal))


def disc_loss(d, img, labels, cfg, real: bool, q=identity):
    v, e, t = discriminate(d, img, cfg, q)
    return _acgan_loss(v, e, t, img, labels, 1.0 if real else 0.0, cfg)


def gen_loss(g, d, noise, labels, cfg, q=identity):
    img = generate(g, noise, labels[0], labels[1], cfg, q)
    v, e, t = discriminate(d, img, cfg, q)
    return _acgan_loss(v, e, t, img, labels, 1.0, cfg)


# ---------------------------------------------------------------------------
# one Algorithm-1 step over data-parallel replicas
# ---------------------------------------------------------------------------


def rmsprop(params, nu, grads, lr, decay, eps):
    nu = jax.tree.map(lambda n, g: decay * n + (1 - decay) * g * g, nu, grads)
    params = jax.tree.map(lambda p, g, n: p - lr * g / (jnp.sqrt(n) + eps),
                          params, grads, nu)
    return params, nu


def sample_inputs(key, rows: int, cfg: dict):
    """A replica's generator inputs: noise drawn in the compute dtype the
    configuration states, labels uniform over the training range."""
    k1, k2, k3 = jax.random.split(key, 3)
    noise = jax.random.normal(k1, (rows, cfg["latent_dim"]),
                              jnp.dtype(cfg["noise_dtype"])).astype(jnp.float32)
    e_p = jax.random.uniform(k2, (rows,), jnp.float32, 10.0, 500.0)
    theta = jax.random.uniform(k3, (rows,), jnp.float32,
                               jnp.deg2rad(60.0), jnp.deg2rad(120.0))
    return noise, e_p, theta


def replica_keys(step_key, n_replicas: int, gen_steps: int):
    """Per replica: (D-fake key, [G keys]) — each replica folds its index
    into the step key, then splits once for every phase that samples."""
    out = []
    for r in range(n_replicas):
        ks = jax.random.split(jax.random.fold_in(step_key, r), 1 + gen_steps)
        out.append((ks[0], [ks[1 + j] for j in range(gen_steps)]))
    return out


def make_step(cfg: dict, q=identity, *, replicas: Sequence[int] = None,
              half_batch: bool = False):
    """``step(state, batches, keys) -> (state, losses)``.

    ``batches``: one real batch per replica; ``keys``: ``replica_keys``.
    Gradients are averaged over ``replicas`` (all of them by default), as
    a psum-mean over the data axes does.  ``replicas`` and ``half_batch``
    exist to plant the faults the comparison must catch: a reduction that
    leaves the other chips out, and half of each batch left out.
    """
    lr, decay, eps = cfg["lr"], cfg["rmsprop_decay"], cfg["rmsprop_eps"]
    gen_steps = int(cfg["gen_steps_per_disc"])

    rows = cfg["rows"] // 2 if half_batch else cfg["rows"]

    def cut(x):
        return x[:rows]

    def _vg(fn):
        return jax.jit(jax.value_and_grad(fn))

    d_real = _vg(lambda d, b: disc_loss(
        d, cut(b["image"]), (cut(b["e_p"]), cut(b["theta"]), cut(b["ecal"])),
        cfg, True, q))

    def _d_fake(d, g, k, frac):
        noise, e_p, theta = sample_inputs(k, rows, cfg)
        fake = jax.lax.stop_gradient(generate(g, noise, e_p, theta, cfg, q))
        return disc_loss(d, fake, (e_p, theta, e_p * frac), cfg, False, q)
    d_fake = _vg(_d_fake)

    def _g(g, d, k, frac):
        noise, e_p, theta = sample_inputs(k, rows, cfg)
        return gen_loss(g, d, noise, (e_p, theta, e_p * frac), cfg, q)
    g_vg = _vg(_g)

    def mean_over(fn, args_per_replica):
        use = range(len(args_per_replica)) if replicas is None else replicas
        outs = [fn(*args_per_replica[r]) for r in use]
        loss = sum(float(o[0]) for o in outs) / len(outs)
        grads = jax.tree.map(lambda *g: sum(g) / len(g), *[o[1] for o in outs])
        return loss, grads

    def step(state, batches: List[dict], keys):
        n = len(batches)
        fracs = [jnp.mean(cut(b["ecal"]) / cut(b["e_p"])) for b in batches]
        losses = {}
        loss, gr = mean_over(d_real, [(state["d"], b) for b in batches])
        d, d_nu = rmsprop(state["d"], state["d_nu"], gr, lr, decay, eps)
        losses["d_loss_real"] = loss
        loss, gr = mean_over(d_fake, [(d, state["g"], keys[r][0], fracs[r])
                                      for r in range(n)])
        d, d_nu = rmsprop(d, d_nu, gr, lr, decay, eps)
        losses["d_loss_fake"] = loss
        g, g_nu, g_losses = state["g"], state["g_nu"], []
        for j in range(gen_steps):
            loss, gr = mean_over(g_vg, [(g, d, keys[r][1][j], fracs[r])
                                        for r in range(n)])
            g, g_nu = rmsprop(g, g_nu, gr, lr, decay, eps)
            g_losses.append(loss)
        losses["g_loss"] = float(np.mean(g_losses))
        return {"g": g, "d": d, "g_nu": g_nu, "d_nu": d_nu}, losses

    return step
