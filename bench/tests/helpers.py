"""A run's context without the chip: small configurations on the CPU."""
from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import common  # noqa: E402

# calo3dgan.reduced(): every kind of layer, at sizes the CPU runs in seconds
SMALL = {"image_shape": [13, 13, 13], "latent_dim": 62, "gen_channels": [16, 8],
         "disc_channels": [8, 16], "batch_size": 8}


def conf(name: str, **over) -> dict:
    c = common.load_json(os.path.join(BENCH, "configs", f"{name}.json"))
    c.update(SMALL)
    c.update(over)
    return c


class CpuContext:
    """The hooks ``run.Context`` gives a driver, on CPU devices."""

    def __init__(self, conf, traffic, seed=7, seconds=1.0, chips=1,
                 limits=None):
        import jax
        self.conf, self.traffic, self.seed = conf, traffic, seed
        self.seconds, self.traced, self.chips = seconds, False, chips
        self.devices = jax.devices()[:chips]
        self.limits = limits or {}
        self.compiles = common.CompileCounter()
        self.memory_peak_bytes = None

    def span(self, name):
        return common.span(name, False)

    def open_window(self):
        self._c0 = self.compiles.count

    def tick(self):
        pass

    def close_window(self):
        self.compiles_in_window = self.compiles.count - self._c0

    def read_memory(self):
        self.memory_peak_bytes = 0

    def free(self):
        pass


def traffic(name: str, **over) -> dict:
    t = common.traffic(name)
    t.update(over)
    return t


def dumps(x) -> str:
    return json.dumps(x, default=str)
