"""Useful work of the 3DGAN, counted analytically from shapes.

Every count here is what the layer's mathematics needs, whatever
implements it:

- a 3x3x3 conv with SAME padding: for every output voxel, the taps that
  fall on an input voxel (not on the padding) x c_in x c_out
  multiply-adds;
- a stride-2 transposed conv: every input voxel's 27 taps that land on
  the output grid, x c_in x c_out.  An lhs-dilated lowering also
  multiplies the zeros it inserts between input voxels (about 8x this for
  a 3-D stride-2 layer); those are not useful work and are not counted;
- a dense layer: d_in x d_out multiply-adds per row.

A multiply-add is 2 FLOPs.  The backward pass of a layer is two
products of the forward's size: dx (the input gradient) and dw (the
weight gradient); each is counted only where Algorithm 1 needs it.
Bytes are the least a layer must move through HBM: each operand read
once and the result written once, activations in the compute dtype.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

KERNEL = 27          # 3x3x3 taps


@dataclasses.dataclass(frozen=True)
class Layer:
    """One conv or dense layer of a network, per event (batch row)."""
    name: str
    kind: str                    # "conv" | "tconv" | "dense"
    in_shape: Tuple[int, ...]    # spatial dims + channels, or (d_in,)
    out_shape: Tuple[int, ...]
    weights: int                 # weight elements

    @property
    def macs(self) -> int:
        if self.kind == "dense":
            return self.in_shape[0] * self.out_shape[0]
        taps = math.prod(
            _taps_1d(n, m, self.kind)
            for n, m in zip(self.in_shape[:-1], self.out_shape[:-1]))
        return taps * self.in_shape[-1] * self.out_shape[-1]

    @property
    def flops(self) -> int:
        return 2 * self.macs


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _taps_1d(n: int, m: int, kind: str) -> int:
    """Along one axis of input length ``n`` and output length ``m``: the
    (output position, tap) pairs of a 3-tap kernel that read an input
    element.  A SAME conv of stride s = ceil(n/m) pads (m-1)s + 3 - n, the
    lower half first; the transposed conv is the conv of the input dilated
    by 2 and padded (2, 1), whose inserted zeros read nothing."""
    if kind == "tconv":
        return sum(1 for o in range(m) for a in range(3)
                   if (o + a - 2) >= 0 and (o + a - 2) % 2 == 0
                   and (o + a - 2) // 2 < n)
    s = _ceil_div(n, m) if m > 1 else 1
    lo = max((m - 1) * s + 3 - n, 0) // 2
    return sum(1 for o in range(m) for a in range(3)
               if 0 <= o * s + a - lo < n)


def generator_layers(cfg: dict) -> List[Layer]:
    """fc -> stride-2 transposed convs -> crop -> stride-1 out conv."""
    chs = list(cfg["gen_channels"])
    ups = len(chs) - 1
    image = tuple(cfg["image_shape"])
    d = tuple(_ceil_div(s, 2 ** ups) for s in image)
    z = cfg["latent_dim"] + 2
    out = [Layer("gen_fc", "dense", (z,), (math.prod(d) * chs[0],),
                 z * math.prod(d) * chs[0])]
    for i in range(ups):
        d2 = tuple(2 * s for s in d)
        out.append(Layer(f"gen_up{i}", "tconv", d + (chs[i],),
                         d2 + (chs[i + 1],), KERNEL * chs[i] * chs[i + 1]))
        d = d2
    out.append(Layer("gen_out", "conv", image + (chs[-1],), image + (1,),
                     KERNEL * chs[-1]))
    return out


def discriminator_layers(cfg: dict) -> List[Layer]:
    """stride-2 convs -> three dense heads of one output each."""
    d = tuple(cfg["image_shape"])
    c = 1
    out = []
    for i, c2 in enumerate(cfg["disc_channels"]):
        d2 = tuple(_ceil_div(s, 2) for s in d)
        out.append(Layer(f"disc_conv{i}", "conv", d + (c,), d2 + (c2,),
                         KERNEL * c * c2))
        d, c = d2, c2
    flat = math.prod(d) * c
    out.append(Layer("disc_heads", "dense", (flat,), (3,), flat * 3))
    return out


# (network, pass) entries of one Algorithm-1 step, per batch row.  ``dx``
# of a network's first layer is skipped where its input is data (the
# real image, the noise); ``dw`` only where that network is trained.
def _passes(net: str, fwd: bool, dx: bool, dw: bool, dx_first: bool):
    out = []
    if fwd:
        out.append((net, "fwd", True))
    if dx:
        out.append((net, "dx", dx_first))
    if dw:
        out.append((net, "dw", True))
    return out


def step_passes(cfg: dict) -> List[Tuple[str, str, bool]]:
    """Algorithm 1: D on real, D on fake (G forward, no gradient into G),
    then ``gen_steps_per_disc`` G updates through a fixed D."""
    d_real = _passes("D", True, True, True, dx_first=False)
    d_fake = _passes("G", True, False, False, False) + d_real
    g_step = (_passes("G", True, True, True, dx_first=False)
              + _passes("D", True, True, False, dx_first=True))
    return d_real + d_fake + g_step * int(cfg["gen_steps_per_disc"])


def _layer_bytes(layer: Layer, pass_: str, act_bytes: int) -> int:
    a_in = math.prod(layer.in_shape) * act_bytes
    a_out = math.prod(layer.out_shape) * act_bytes
    w = layer.weights * act_bytes
    if pass_ == "fwd":          # read x, w; write y
        return a_in + w + a_out
    if pass_ == "dx":           # read dy, w; write dx
        return a_out + w + a_in
    return a_in + a_out + w     # dw: read x, dy; write dw


def works(cfg: dict, passes, act_bytes: int = 2):
    """Per-row (layer, pass, flops, bytes) of the given passes."""
    nets = {"G": generator_layers(cfg), "D": discriminator_layers(cfg)}
    out = []
    for net, pass_, first_too in passes:
        layers = nets[net]
        for j, layer in enumerate(layers):
            if pass_ == "dx" and j == 0 and not first_too:
                continue
            out.append((layer, pass_, layer.flops,
                        _layer_bytes(layer, pass_, act_bytes)))
    return out


def step_flops_per_sample(cfg: dict) -> int:
    """Useful FLOPs of one Algorithm-1 step per real sample."""
    return sum(w[2] for w in works(cfg, step_passes(cfg)))


def generator_flops_per_event(cfg: dict) -> int:
    return sum(layer.flops for layer in generator_layers(cfg))


def discriminator_flops_per_event(cfg: dict) -> int:
    return sum(layer.flops for layer in discriminator_layers(cfg))


def conv_roofline_seconds(cfg: dict, passes, rows: int, peak_flops: float,
                          hbm_bytes_per_s: float) -> Dict[str, float]:
    """Least device time of the conv work (dense layers excluded) of
    ``passes`` over ``rows`` batch rows: per layer and pass, the larger of
    FLOPs over peak and bytes over HBM bandwidth, summed.  Returns the
    total and how much of it each bound set."""
    tot = {"seconds": 0.0, "compute_bound_s": 0.0, "memory_bound_s": 0.0}
    for layer, _, fl, by in works(cfg, passes):
        if layer.kind == "dense":
            continue
        tc, tm = rows * fl / peak_flops, rows * by / hbm_bytes_per_s
        tot["seconds"] += max(tc, tm)
        tot["compute_bound_s" if tc >= tm else "memory_bound_s"] += max(tc, tm)
    return tot
