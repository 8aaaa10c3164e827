"""From the profiler's trace to device busy time, op classes and idle gaps.

``capture`` records a window with JAX's profiler (no Python tracer: its
per-call events would swamp the host thread it measures).  ``extract``
reads the ``.xplane.pb`` into plain lists:

- per device (plane ``/device:TPU:<n>``), line ``XLA Ops``: one event per
  executed HLO op, named by its HLO text (``%fusion.12 = bf16[...]
  fusion(...), kind=kOutput, calls=...``), on the host's clock;
- per device, line ``XLA Modules``: one event per program execution;
- host spans whose name starts with ``bench.``: the harness's own
  ``TraceAnnotation`` spans around each call into the program.

``reduce`` turns those lists into the numbers the per-layer readers use.
It is plain Python over lists, so ``tests/test_trace.py`` checks it on a
small recorded trace.  Op classes come from ``bench/ops/<class>/*.json``
(one regular expression per file, matched against the HLO text), so a
later kernel adds a pattern file, not code.
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re

from harness import common

OPS_DIR = os.path.join(common.BENCH, "ops")
CLASSES = ("container", "collective", "convolution")   # first match wins
SPAN_PREFIX = "bench."


@contextlib.contextmanager
def capture(log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def extract(log_dir: str) -> dict:
    import jax
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}: {files}")
    pd = jax.profiler.ProfileData.from_file(files[0])
    out = {"devices": {}, "modules": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    key = "devices" if line.name == "XLA Ops" else "modules"
                    out[key][plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out["host"].append([e.name, float(e.start_ns),
                                            float(e.duration_ns)])
    return out


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def patterns(ops_dir: str = OPS_DIR) -> dict:
    out = {}
    for cls in CLASSES:
        regs = [common.load_json(p)["regex"] for p in
                sorted(glob.glob(os.path.join(ops_dir, cls, "*.json")))]
        out[cls] = re.compile("|".join(f"(?:{r})" for r in regs)) if regs \
            else None
    return out


def classify(name: str, pats: dict) -> str:
    for cls in CLASSES:
        if pats[cls] is not None and pats[cls].search(name):
            return cls
    return "other"


def _union(intervals):
    """Merge [start, end) intervals; returns the sorted merged list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _minus(a, b):
    """Length of the merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _op_label(name: str) -> str:
    """``%fusion.12 = bf16[8,4]{...} fusion(...), kind=kLoop`` ->
    ``fusion.12 bf16[8,4] kLoop``: the op, its result, its fusion kind."""
    m = re.match(r"%([^ ]+) = (\(?[a-z0-9]+\[[0-9,]*\])", name)
    kind = re.search(r"kind=(k\w+)", name)
    if not m:
        return name[:80]
    return " ".join([m.group(1), m.group(2)] + ([kind.group(1)] if kind
                                                 else []))


def window_of(extracted: dict, span: str):
    """[start, end) in ns of the (first) host span named ``span``."""
    for name, s, d in extracted["host"]:
        if name == span:
            return s, s + d
    raise RuntimeError(f"no host span {span!r} in the trace")


def reduce(extracted: dict, window, pats: dict, top: int = 10) -> dict:
    """Per-device busy/idle, class times, exposed collective time, module
    executions, the top device ops and the longest idle gaps by host span,
    all inside ``window`` (ns).  Times in seconds."""
    lo, hi = window
    per_dev = {}
    op_time = {}
    gaps_by_span = {}
    host = sorted(([s, s + d, n] for n, s, d in extracted["host"]),
                  key=lambda x: x[0])
    for dev, events in sorted(extracted["devices"].items()):
        by_cls = {c: [] for c in CLASSES + ("other",)}
        for name, s, d in events:
            cls = classify(name, pats)
            by_cls[cls].append([s, s + d])
            if cls != "container":
                for cs, ce in _clip([[s, s + d]], lo, hi):
                    lab = _op_label(name)
                    op_time[lab] = op_time.get(lab, 0.0) + (ce - cs) / 1e9
        work = _union(_clip(by_cls["convolution"] + by_cls["collective"]
                            + by_cls["other"], lo, hi))
        coll = _union(_clip(by_cls["collective"], lo, hi))
        non_coll = _union(_clip(by_cls["convolution"] + by_cls["other"],
                                lo, hi))
        busy = _length(work)
        per_dev[dev] = {
            "busy_s": busy / 1e9,
            "idle_share": 1.0 - busy / (hi - lo),
            "convolution_s": _length(_union(_clip(by_cls["convolution"],
                                                  lo, hi))) / 1e9,
            "collective_s": _length(coll) / 1e9,
            "exposed_collective_s": _minus(coll, non_coll) / 1e9,
        }
        if dev == min(extracted["devices"]):
            edges = [lo] + [x for iv in work for x in iv] + [hi]
            for gs, ge in zip(edges[0::2], edges[1::2]):
                if ge <= gs:
                    continue
                name = _span_at(host, (gs + ge) / 2)
                gaps_by_span[name] = gaps_by_span.get(name, 0.0) + \
                    (ge - gs) / 1e9
    modules = {}
    for dev, events in extracted["modules"].items():
        runs = {}
        for name, s, d in events:
            if lo <= s + d <= hi:
                n, t = runs.get(name, (0, 0.0))
                runs[name] = (n + 1, t + d / 1e9)
        modules[dev] = runs
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": per_dev,
        "modules": modules,
        "device_ops": sorted(([k, v] for k, v in op_time.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in gaps_by_span.items()),
                            key=lambda x: -x[1])[:top],
    }


def _span_at(host, t):
    """The innermost (latest-starting) host span covering ``t``."""
    best = None
    for s, e, n in host:
        if s > t:
            break
        if e >= t:
            best = n
    return best or "(no bench span)"


def step_executions(summary: dict) -> float:
    """Executions per device of the program that took the most time —
    the step — inside the window, averaged over devices."""
    counts = [max(mods.values(), key=lambda nt: nt[1])[0]
              for mods in summary["modules"].values() if mods]
    return sum(counts) / len(counts) if counts else 0.0
