"""Structured metric logging (the Grafana/Prometheus stand-in) and the
device-side windowed accumulator behind the engine's async-dispatch loop."""
from __future__ import annotations

import json
import time
from typing import Optional

import jax
import jax.numpy as jnp


class MetricAccumulator:
    """Windowed metric accumulation WITHOUT per-step host syncs.

    ``update`` folds one step's (device-resident) scalar metrics into
    running device-side sums — that is an async dispatch, so the host
    keeps issuing work while the device computes.  ``means`` does ONE
    ``jax.device_get`` for the whole window and returns host floats;
    call it once per logging window, not per step.

    Sums accumulate in f32 even when the step emits bf16/fp16 metrics
    (a bf16 running sum stops moving once the sum outgrows the
    increment's 8-bit mantissa — a 100-step window of ~1.0 losses would
    drift visibly).  The cast happens AT ``add`` time, not at drain:
    every increment lands at full precision.
    """

    def __init__(self):
        self.sums = None
        self.count = 0

    @staticmethod
    def _f32(metrics) -> dict:
        """The step's scalars; array counters (per layer, per expert) are
        the caller's to read from the step's metrics."""
        return {k: jnp.asarray(v).astype(jnp.float32)
                for k, v in dict(metrics).items() if jnp.ndim(v) == 0}

    def update(self, metrics) -> None:
        self.count += 1
        if self.sums is None:
            self.sums = self._f32(metrics)
        else:
            m = self._f32(metrics)
            self.sums = {k: jnp.add(self.sums[k], m[k])
                         for k in self.sums}

    def means(self) -> dict:
        """Host-side means of the current window (one device transfer)."""
        if not self.count:
            return {}
        host = jax.device_get(self.sums)
        return {k: float(v) / self.count for k, v in host.items()}

    def reset(self) -> None:
        self.sums = None
        self.count = 0


class MetricLog:
    def __init__(self, path: Optional[str] = None, print_every: int = 10):
        self.path = path
        self.print_every = print_every
        self.rows = []
        self._t0 = time.time()

    def log(self, step: int, **metrics):
        row = {"step": step, "t": round(time.time() - self._t0, 3)}
        row.update({k: float(v) for k, v in metrics.items()})
        self.rows.append(row)
        if self.path:
            with open(self.path, "a") as f:
                f.write(json.dumps(row) + "\n")
        if self.print_every and step % self.print_every == 0:
            parts = " ".join(f"{k}={v:.4g}" for k, v in row.items()
                             if k not in ("step", "t"))
            print(f"[step {step:6d} t={row['t']:8.1f}s] {parts}", flush=True)

    def series(self, key: str):
        return [r[key] for r in self.rows if key in r]
