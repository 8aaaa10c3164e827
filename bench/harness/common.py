"""What every cell shares: finding its files, the chip check, the compile
cache, the compile counter and host spans."""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


class NoDevice(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_names(bench: dict, workload: str, traced: bool):
    """The cell's end-to-end metrics (untraced) or per-layer metrics
    (traced): every metric whose ``workloads`` lists the cell, or that
    has no such list."""
    group = bench["per_layer" if traced else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def require_program():
    """The system under test lives in the checkout's ``src``; without it
    there is nothing to measure."""
    src = os.path.join(CHECKOUT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"no program under {src}: nothing to measure")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_compile_cache():
    """JAX's persistent cache at the fixed ``<checkout>/.jax_cache``;
    every program of set-up is cached however fast it compiled, so a
    second run of a cell compiles nothing there (``run.Context`` stops the
    caching for the window and restarts it for the reference)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def devices(chips: int):
    """The first ``chips`` TPU devices; exit non-zero without them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform}; this "
                       "benchmark measures only the chip")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """XLA backend compiles (not persistent-cache hits), from JAX's
    monitoring events, with the seconds they took."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


class GcPauses:
    """Python's garbage collections while open: when each began (seconds
    after opening), how long it held the interpreter, and its generation."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.events = []
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.events.append((self._t - self.t0,
                                time.perf_counter() - self._t,
                                info["generation"]))
            self._t = None

    def close(self) -> dict:
        gc.callbacks.remove(self._on)
        ms = [1e3 * d for _, d, _ in self.events]
        return {"gc_collections": len(ms), "gc_total_ms": sum(ms),
                "gc_longest_ms": max(ms, default=0.0),
                "gc_gen2_collections": sum(g == 2 for *_, g in self.events),
                "gc_over_100ms": [[round(t, 3), round(1e3 * d, 1), g]
                                  for t, d, g in self.events if d > 0.1]}


@contextlib.contextmanager
def span(name: str, on: bool):
    """A host span in the profiler's trace, when tracing."""
    if not on:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def memory_peak_bytes(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def jax_key(seed: int):
    """A key from a seed of any size: the low 32 bits seed it, the rest
    are folded in (``jax.random.key`` keeps only 32 bits of a large
    seed)."""
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
