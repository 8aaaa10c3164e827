#!/usr/bin/env python3
"""On-chip benchmark of the 3DGAN training job.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data, found by name: the cell in
``BENCHMARK.json`` names its configuration (``bench/configs/*.json``, whose
``kind`` picks the driver ``bench/drivers/<kind>.py``) and its traffic
(``bench/traffic/<traffic>.json``); its limits for ``correct`` are
``bench/limits/<cell>.json``; each metric is read from the run's record by
``bench/metrics/<metric>.py``.  One process sets up, measures for
``--seconds``, checks the timed path's output against the plain float32
reference (``bench/harness/reference.py``), and prints one JSON line last.
With ``--trace 1`` the window is profiled and the per-layer metrics are
reported instead of the end-to-end ones.

It runs only on a TPU: without one, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import common  # noqa: E402


class Context:
    """What a driver is given: the cell's data, the chips, the clock, and
    the hooks that open and close the measured window."""

    def __init__(self, args, bench, cell_entry):
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.traced = bool(args.trace)
        self.chips = int(cell_entry["chips"])
        entry = common.config_entry(bench, cell_entry["config"])
        self.conf = common.load_json(os.path.join(common.CHECKOUT,
                                                  entry["file"]))
        self.traffic = common.traffic(cell_entry["traffic"])
        self.limits = common.load_json(os.path.join(
            common.BENCH, "limits", f"{args.workload}.json"))
        self.devices = None
        self.compiles = None
        self.setup_s = None
        self.memory_peak_bytes = None
        self.compiles_in_window = None
        self.trace_dir = None
        self._trace_cm = None
        self._trace_span = None
        self.window_notes = {}

    def span(self, name):
        return common.span(name, self.traced)

    def open_window(self):
        """Set-up ends here.  Programs compiled from now on are not written
        to the persistent cache, so every run starts from the same cache;
        with ``--trace 1`` the profiler starts."""
        import jax
        self.setup_s = time.perf_counter() - T0
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
        self._compiles0 = self.compiles.count
        self._compile_s0 = self.compiles.seconds
        self._gc = common.GcPauses()
        self.window_t0 = time.perf_counter()
        if self.traced:
            from harness import trace
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            self._trace_cm = trace.capture(self.trace_dir)
            self._trace_cm.__enter__()
            self._trace_span = jax.profiler.TraceAnnotation("bench.traced")
            self._trace_span.__enter__()

    def tick(self):
        """Stop the profiler once the traced part of the window is over
        (``traffic["trace_seconds"]``): a trace of the whole window would
        be too large to read back within a run's time."""
        if self._trace_cm is not None and time.perf_counter() >= \
                self.window_t0 + self.traffic["trace_seconds"]:
            self._stop_trace()

    def _stop_trace(self):
        self._trace_span.__exit__(None, None, None)
        self._trace_cm.__exit__(None, None, None)
        self._trace_cm = None

    def close_window(self):
        if self._trace_cm is not None:
            self._stop_trace()
        self.compiles_in_window = self.compiles.count - self._compiles0
        self.window_notes = dict(
            self._gc.close(), compiles_in_window=self.compiles_in_window,
            compile_s_in_window=self.compiles.seconds - self._compile_s0)

    def read_memory(self):
        self.memory_peak_bytes = common.memory_peak_bytes(self.devices)

    def free(self):
        """The program is done with; the reference programs that follow
        are cached again, so only a cell's first run compiles them."""
        import jax
        gc.collect()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = common.benchmark()
    cell_entry = common.cell(bench, args.workload)
    common.require_program()
    ctx = Context(args, bench, cell_entry)

    common.enable_compile_cache()
    ctx.devices = common.devices(ctx.chips)
    ctx.compiles = common.CompileCounter()
    driver = common.load_module(
        os.path.join(common.BENCH, "drivers", f"{ctx.conf['kind']}.py"),
        f"bench_driver_{ctx.conf['kind']}")
    record = driver.run(ctx)
    dev = ctx.devices[0]
    record.update(conf=ctx.conf, traffic=ctx.traffic, chips=ctx.chips,
                  setup_s=ctx.setup_s,
                  compiles_in_window=ctx.compiles_in_window,
                  device_kind=dev.device_kind)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": ctx.chips,
                      "memory_peak_bytes": ctx.memory_peak_bytes}}
    if ctx.traced:
        from harness import trace
        try:
            ex = trace.extract(ctx.trace_dir)
        finally:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        summary = trace.reduce(ex, trace.window_of(ex, "bench.traced"),
                               trace.patterns())
        record["trace"] = summary
        busy = [d["busy_s"] for d in summary["devices"].values()]
        out["device"].update(busy_s=sum(busy) / len(busy),
                             window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    metrics = {}
    for m in common.metric_names(bench, args.workload, ctx.traced):
        reader = common.load_module(
            os.path.join(common.BENCH, "metrics", f"{m['name']}.py"),
            "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = record["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks)
    line = {"correct": correct, "attempted": record["attempted"],
            "failed": record["failed"], "metrics": metrics}
    line.update(out)
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    notes = dict(ctx.window_notes, **record.get("notes", {}))
    for k, v in sorted(notes.items()):
        print(f"note {k}: {v}", file=sys.stderr)
    for c in checks:
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {c['name']}: {c['value']!r} against limit "
              f"{c['limit']!r}: {verdict}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
