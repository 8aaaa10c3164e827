"""3DGAN — three-dimensional convolutional ACGAN for calorimeter simulation.

Generator: (latent ⊕ E_p ⊕ theta) -> dense -> stack of stride-2 3-D
transposed convolutions, the last emitting only the image's voxels ->
softplus (energies are non-negative).

Discriminator: stride-2 3-D convolutions -> heads:
  - validity logit (real/fake),
  - E_p regression (ACGAN auxiliary),
  - theta regression (ACGAN auxiliary).
The total-deposit E_CAL constraint is computed analytically from the image
(as in 3DGAN) and compared to the label in the loss.

All convs run in NDHWC / DHWIO layout (TPU-native).  The hot-spot conv3d has
a Pallas implicit-GEMM kernel under kernels/conv3d (used when enabled; the
lax.conv path is the reference).
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.substrate import layers

DN = ("NDHWC", "DHWIO", "NDHWC")

# Pallas fused implicit-GEMM conv path (kernels/conv3d).  Resolution order:
#   1. cfg.use_pallas_conv when not None (per-model config),
#   2. the process-wide setting (set_pallas_conv / use_pallas_conv ctx),
#   3. the REPRO_PALLAS_CONV environment variable (default: off — the CPU
#      stand-in runs the kernels in interpret mode, which is slow; flip on
#      for the TPU target where the MXU-tiled GEMM is the point).
_PALLAS_CONV: list = [None]


def _env_pallas_conv() -> bool:
    return os.environ.get("REPRO_PALLAS_CONV", "0").lower() \
        not in ("", "0", "false", "no")


def pallas_conv_enabled(cfg=None) -> bool:
    """Resolve the Pallas-conv toggle: config > global setter > env."""
    if cfg is not None and getattr(cfg, "use_pallas_conv", None) is not None:
        return bool(cfg.use_pallas_conv)
    if _PALLAS_CONV[0] is not None:
        return bool(_PALLAS_CONV[0])
    return _env_pallas_conv()


def set_pallas_conv(on: Optional[bool]):
    """Set the process-wide toggle (None reverts to the env default).
    Returns the previous value for save/restore."""
    prev = _PALLAS_CONV[0]
    _PALLAS_CONV[0] = on
    return prev


class use_pallas_conv:
    """Scoped toggle (kept for interactive use; config/env are the
    jit-friendly routes — they resolve BEFORE tracing)."""

    def __init__(self, on: bool = True):
        self.on = on

    def __enter__(self):
        self.prev = set_pallas_conv(self.on)

    def __exit__(self, *a):
        set_pallas_conv(self.prev)


def _conv_layer(x, w, b=None, stride=1, *, activation="none", slope=0.2,
                transpose=False, pallas=None, out_dims=None):
    """One conv layer; on the Pallas path conv+bias+activation are ONE
    fused kernel launch, on the lax path the same math is left to XLA.

    ``out_dims`` (transposed convs only): keep only the first ``out_dims``
    of SAME's ``stride * n`` outputs per spatial axis.  The lax path never
    computes the rest; the Pallas kernel has no window, so its output is
    sliced right after the launch (bias and activation are elementwise)."""
    if pallas is None:
        pallas = pallas_conv_enabled()
    if pallas:
        from repro.kernels.conv3d import (conv3d_bias_act,
                                          conv3d_transpose_bias_act)
        op = conv3d_transpose_bias_act if transpose else conv3d_bias_act
        bias = b if b is not None else jnp.zeros((w.shape[-1],), x.dtype)
        # w stays in param dtype: the kernel casts for compute, the custom
        # vjp hands back dw in param dtype (bf16 policy safe)
        out = op(x, w, bias, stride, activation, slope, None)
        if out_dims is not None:
            out = out[:, :out_dims[0], :out_dims[1], :out_dims[2]]
        return out
    if transpose:
        pads = ("SAME" if out_dims is None else
                [_transpose_padding(k, stride, n, m) for k, n, m
                 in zip(w.shape[:3], x.shape[1:4], out_dims)])
        out = jax.lax.conv_transpose(x, w.astype(x.dtype), (stride,) * 3,
                                     pads, dimension_numbers=DN)
    elif x.shape[-1] == 1:
        out = _single_channel_conv(x, w, stride)
    else:
        out = jax.lax.conv_general_dilated(x, w.astype(x.dtype), (stride,) * 3,
                                           "SAME", dimension_numbers=DN)
    if b is not None:
        out = out + b.astype(out.dtype)
    if activation == "leaky_relu":
        out = jax.nn.leaky_relu(out, slope)
    elif activation == "softplus":
        out = jax.nn.softplus(out)
    return out


def _transpose_padding(k: int, stride: int, n: int, m: int):
    """(low, high) padding of a transposed conv (kernel ``k``, ``stride``,
    ``n`` inputs) that gives the first ``m`` of SAME's ``stride * n``
    outputs: SAME's pair, its high side cut by the overhang."""
    over = stride * n - m
    assert 0 <= over < stride * n, (n, m)
    pad_len = k + stride - 2
    lo = k - 1 if stride > k - 1 else -(-pad_len // 2)
    return lo, pad_len - lo - over


def _single_channel_conv(x, w, stride):
    """A conv over a 1-channel input as patches @ kernel: the same sum, but
    its weight gradient is one matmul.  Written as a conv, that gradient
    alone (the discriminator's first layer at 51x51x25, batch 128) takes
    the v5e compiler ~48 s; this form ~2 s."""
    patches = jax.lax.conv_general_dilated_patches(
        x, w.shape[:3], (stride,) * 3, "SAME", dimension_numbers=DN)
    return patches @ w.reshape(-1, w.shape[-1]).astype(x.dtype)


def _start_dims(image_shape, ups: int) -> Tuple[int, int, int]:
    f = 2 ** ups
    return tuple(-(-d // f) for d in image_shape)


# ---------------------------------------------------------------------------
# Generator
# ---------------------------------------------------------------------------


def init_generator(key, cfg):
    chs = cfg.gen_channels
    ups = len(chs) - 1
    d0 = _start_dims(cfg.image_shape, ups)
    in_dim = cfg.latent_dim + 2
    ks = jax.random.split(key, len(chs) + 2)
    p = {"fc": layers.init_dense(ks[0], in_dim,
                                 d0[0] * d0[1] * d0[2] * chs[0], bias=True,
                                 scale=0.05)}
    for i in range(ups):
        p[f"up{i}"] = {
            "w": layers.normal_init(ks[i + 1], (3, 3, 3, chs[i], chs[i + 1]), 0.05),
            "b": jnp.zeros((chs[i + 1],), jnp.float32),
            "gn": layers.init_norm(chs[i + 1], "layernorm"),
        }
    p["out"] = {"w": layers.normal_init(ks[-1], (3, 3, 3, chs[-1], 1), 0.05),
                "b": jnp.zeros((1,), jnp.float32)}
    return p


def generator_axes(cfg):
    chs = cfg.gen_channels
    ups = len(chs) - 1
    p = {"fc": layers.dense_axes("embed", "mlp", bias=True)}
    for i in range(ups):
        p[f"up{i}"] = {"w": (None, None, None, None, None), "b": (None,),
                       "gn": layers.norm_axes("layernorm")}
    p["out"] = {"w": (None, None, None, None, None), "b": (None,)}
    return p


def generate(p, noise, e_p, theta, cfg):
    """noise: (B, latent); e_p/theta raw units -> image (B, X, Y, Z, 1)."""
    chs = cfg.gen_channels
    ups = len(chs) - 1
    d0 = _start_dims(cfg.image_shape, ups)
    pallas = pallas_conv_enabled(cfg)
    e_n = (e_p / 100.0)[:, None].astype(noise.dtype)
    t_n = theta[:, None].astype(noise.dtype)
    z = jnp.concatenate([noise, e_n, t_n], axis=-1)
    x = layers.apply_dense(p["fc"], z)
    x = jax.nn.leaky_relu(x, 0.2)
    x = x.reshape(-1, *d0, chs[0])
    for i in range(ups):
        # bias folds into the kernel epilogue; the activation cannot (a
        # layernorm sits between), so it stays outside.  The last layer
        # emits only the image's voxels: the norm is per voxel and the
        # activation elementwise, so cropping here equals cropping after
        # them, without normalising the overhang it would throw away
        x = _conv_layer(x, p[f"up{i}"]["w"], p[f"up{i}"]["b"], 2,
                        transpose=True, pallas=pallas,
                        out_dims=cfg.image_shape if i == ups - 1 else None)
        x = layers.apply_norm(p[f"up{i}"]["gn"], x, "layernorm")
        x = jax.nn.leaky_relu(x, 0.2)
    # softplus keeps cell energies non-negative (fused into the conv
    # epilogue on the Pallas path); scale with E_p so the generator does
    # not have to learn the dynamic range from scratch
    x = _conv_layer(x, p["out"]["w"], p["out"]["b"], 1,
                    activation="softplus", pallas=pallas)
    return x * (e_n[:, None, None, None] * 0.025)


# ---------------------------------------------------------------------------
# Discriminator
# ---------------------------------------------------------------------------


def init_discriminator(key, cfg):
    chs = cfg.disc_channels
    ks = jax.random.split(key, len(chs) + 3)
    p = {}
    c_in = 1
    for i, c in enumerate(chs):
        p[f"conv{i}"] = {
            "w": layers.normal_init(ks[i], (3, 3, 3, c_in, c), 0.05),
            "b": jnp.zeros((c,), jnp.float32),
            "ln": layers.init_norm(c, "layernorm"),
        }
        c_in = c
    X, Y, Z = cfg.image_shape
    f = 2 ** len(chs)
    flat = (-(-X // f)) * (-(-Y // f)) * (-(-Z // f)) * chs[-1]
    p["validity"] = layers.init_dense(ks[-3], flat, 1, bias=True)
    p["energy"] = layers.init_dense(ks[-2], flat, 1, bias=True)
    p["angle"] = layers.init_dense(ks[-1], flat, 1, bias=True)
    return p


def discriminator_axes(cfg):
    p = {}
    for i in range(len(cfg.disc_channels)):
        p[f"conv{i}"] = {"w": (None, None, None, None, None), "b": (None,),
                         "ln": layers.norm_axes("layernorm")}
    for head in ("validity", "energy", "angle"):
        p[head] = layers.dense_axes("embed", None, bias=True)
    return p


def discriminate(p, img, cfg):
    """img: (B, X, Y, Z, 1) -> (validity_logit, e_p_pred, theta_pred)."""
    x = jnp.log1p(img * 50.0)          # compress the energy dynamic range
    n = len(cfg.disc_channels)
    pallas = pallas_conv_enabled(cfg)
    for i in range(n):
        x = _conv_layer(x, p[f"conv{i}"]["w"], p[f"conv{i}"]["b"], 2,
                        pallas=pallas)
        x = layers.apply_norm(p[f"conv{i}"]["ln"], x, "layernorm")
        x = jax.nn.leaky_relu(x, 0.2)
    x = x.reshape(x.shape[0], -1)
    validity = layers.apply_dense(p["validity"], x)[:, 0]
    e_pred = jax.nn.softplus(layers.apply_dense(p["energy"], x)[:, 0]) * 100.0
    t_pred = layers.apply_dense(p["angle"], x)[:, 0] + jnp.pi / 2
    return validity, e_pred, t_pred


# ---------------------------------------------------------------------------
# Losses (ACGAN with physics constraints, 3DGAN-style)
# ---------------------------------------------------------------------------


def bce_logits(logit, target):
    return jnp.mean(jnp.maximum(logit, 0) - logit * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def mape(pred, true):
    return jnp.mean(jnp.abs(pred - true) / jnp.maximum(jnp.abs(true), 1e-3))


def disc_loss(d_params, g_out_or_real, labels, cfg, real: bool):
    e_p, theta, ecal = labels
    v, e_pred, t_pred = discriminate(d_params, g_out_or_real, cfg)
    # loss math in f32 regardless of compute dtype (bf16 policy)
    v, e_pred, t_pred = (t.astype(jnp.float32) for t in (v, e_pred, t_pred))
    target = 1.0 if real else 0.0
    l_bce = bce_logits(v, target)
    l_e = mape(e_pred, e_p)
    l_t = jnp.mean(jnp.abs(t_pred - theta))
    ecal_img = jnp.sum(g_out_or_real.astype(jnp.float32), axis=(1, 2, 3, 4))
    l_ecal = mape(ecal_img, ecal)
    total = (l_bce + cfg.aux_energy_weight * l_e / 10.0
             + cfg.aux_angle_weight * l_t + cfg.aux_ecal_weight * l_ecal)
    acc = jnp.mean(((v > 0) == (target > 0.5)).astype(jnp.float32))
    return total, {"bce": l_bce, "e": l_e, "t": l_t, "ecal": l_ecal, "acc": acc}


def gen_loss(g_params, d_params, noise, labels, cfg):
    e_p, theta, ecal = labels
    img = generate(g_params, noise, e_p, theta, cfg)
    v, e_pred, t_pred = discriminate(d_params, img, cfg)
    v, e_pred, t_pred = (t.astype(jnp.float32) for t in (v, e_pred, t_pred))
    l_bce = bce_logits(v, 1.0)         # want D to call fakes real
    l_e = mape(e_pred, e_p)
    l_t = jnp.mean(jnp.abs(t_pred - theta))
    ecal_img = jnp.sum(img.astype(jnp.float32), axis=(1, 2, 3, 4))
    l_ecal = mape(ecal_img, ecal)
    total = (l_bce + cfg.aux_energy_weight * l_e / 10.0
             + cfg.aux_angle_weight * l_t + cfg.aux_ecal_weight * l_ecal)
    return total, {"bce": l_bce, "e": l_e, "t": l_t, "ecal": l_ecal}
