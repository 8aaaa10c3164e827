#!/usr/bin/env python3
"""Device time of each Algorithm-1 phase of a training cell, on the chip.

    python3 bench/trace_phases.py --workload <cell> --seed <n> --steps 20 \
        [--record <path>.json.gz]

Builds the cell's program as a run does (``drivers/gan_train.Program``),
runs its checked steps, then traces ``--steps`` more in a window of their
own and splits the step program's op time by phase (``d_real``,
``d_fake``, ``g``) and pass (``fwd``, ``bwd``, ``update``) with the op ->
op_name map of the compiled step (``harness/scopes.py``).  Prints one JSON
line: per step, each phase's ms, the unscoped rest, the idle time inside
and between programs, and each program's device time; the shares of the
step's op time that found no instruction in the map and no phase.
``--record`` writes the trace and the map, as the recorded traces under
``tests/data``.

It runs only on a TPU, like a run of the cell.
"""
import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import common, scopes, trace  # noqa: E402


def record(prog, steps: int) -> tuple:
    """Trace ``steps`` steps of ``prog`` (already warm) in a window
    ``bench.traced`` that ends once the device is done; returns the
    extracted trace, with the program's ``repro.`` host spans, and the
    compiled text of the step that ran."""
    import jax
    log_dir = tempfile.mkdtemp(prefix="bench_phases_")
    try:
        with trace.capture(log_dir):
            with jax.profiler.TraceAnnotation("bench.traced"):
                for _ in range(steps):
                    with jax.profiler.TraceAnnotation("bench.step"):
                        prog.step()
                jax.block_until_ready(prog.state)
        ex = trace.extract(log_dir)
        ex["host"] += scopes.host_spans(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    key = jax.random.fold_in(prog.step_rng, prog.gstep)
    text = prog.step_fn.lower(prog.state, prog.pool[0], key).compile() \
        .as_text()
    return ex, text


def summarize(ex: dict, names: dict, raw: dict) -> dict:
    """Per-step milliseconds on the first chip, from the first step's
    start to the last one's end: the split by phase with ``names`` (op_names
    that ``scopes.inherit`` filled in), the share left unscoped with
    ``raw`` (op_names as XLA kept them), idle gaps, and each program's
    executions and device ms."""
    pats = trace.patterns()
    dev = min(ex["devices"])
    mods = ex["modules"][dev]
    traced = trace.window_of(ex, "bench.traced")
    runs = scopes._runs(mods, scopes.step_program(mods, traced))
    window = (runs[0][0], runs[-1][1])
    ph = scopes.reduce(ex, window, names, pats)[dev]
    by_meta = scopes.reduce(ex, window, raw, pats)[dev]
    n = ph["steps"]
    gaps = scopes.idle_gaps(ex, window, names, pats)
    programs = trace.reduce(ex, window, pats)["modules"][dev]
    per_step = {p: {q: 1e3 * s / n for q, s in passes.items()}
                for p, passes in ph["phases"].items()}
    scoped = sum(sum(v.values()) for v in ph["phases"].values())
    return {
        "device": dev, "program": ph["program"], "steps": n,
        "window_ms": (window[1] - window[0]) / 1e6,
        "step_busy_ms": 1e3 * ph["busy_s"] / n,
        "phase_ms": {p: sum(v.values()) for p, v in per_step.items()},
        "phase_pass_ms": per_step,
        "unscoped_ms": 1e3 * ph["unscoped_s"] / n,
        "scoped_plus_unscoped_over_busy": (scoped + ph["unscoped_s"])
        / ph["busy_s"],
        "unscoped_share": ph["unscoped_s"] / ph["busy_s"],
        "unscoped_share_by_metadata": by_meta["unscoped_s"]
        / by_meta["busy_s"],
        "unmapped_share": ph["unmapped_s"] / ph["busy_s"],
        "idle_ms_in_program": 1e3 * sum(
            v for k, v in gaps.items() if k.startswith("in:")) / n,
        "idle_ms_between_programs": 1e3 * sum(
            v for k, v in gaps.items() if not k.startswith("in:")) / n,
        "idle_gaps_ms": {k: 1e3 * v / n for k, v in sorted(gaps.items())},
        "program_runs_ms": {k: [c, 1e3 * t / c]
                            for k, (c, t) in programs.items()},
        "prefetch_wait_spans": sum(
            1 for name, s, d in ex["host"] if name == "repro.prefetch.wait"
            and traced[0] <= s <= traced[1]),
    }


def _short(name: str) -> str:
    return name if len(name) <= 240 else name[:120] + "..." + name[-120:]


def fixture(ex: dict, raw: dict, names: dict, source: str) -> dict:
    """The trace as the tests read it: op names shortened to their first
    and last 120 characters; ``op_names`` as XLA kept them and
    ``inherited`` (what ``scopes.inherit`` filled in) for the
    instructions it holds."""
    seen = {scopes.instruction(n) for evs in ex["devices"].values()
            for n, _, _ in evs}
    return {"devices": {d: [[_short(n), s, t] for n, s, t in evs]
                        for d, evs in ex["devices"].items()},
            "modules": ex["modules"], "host": ex["host"],
            "op_names": {k: v for k, v in raw.items() if k in seen},
            "inherited": {k: v for k, v in names.items()
                          if k in seen and v != raw[k]},
            "source": source}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)

    bench = common.benchmark()
    cell = common.cell(bench, args.workload)
    entry = common.config_entry(bench, cell["config"])
    conf = common.load_json(os.path.join(common.CHECKOUT, entry["file"]))
    traffic = common.traffic(cell["traffic"])
    common.require_program()
    common.enable_compile_cache()
    devs = common.devices(int(cell["chips"]))
    drv = common.load_module(
        os.path.join(common.BENCH, "drivers", f"{conf['kind']}.py"),
        "bench_driver")
    prog = drv.Program(conf, traffic, args.seed, devs)
    drv.check_steps(prog, traffic["check_steps"])
    ex, text = record(prog, args.steps)
    raw = scopes.op_names(text)
    names = scopes.inherit(text, raw)
    out = summarize(ex, names, raw)
    out.update(workload=args.workload, seed=args.seed,
               device_kind=devs[0].device_kind)
    if args.record:
        os.makedirs(os.path.dirname(os.path.abspath(args.record)),
                    exist_ok=True)
        src = (f"{devs[0].device_kind}, one chip: {out['steps']} steps of "
               f"{args.workload} (seed {args.seed}) after its checked "
               "steps, bench/trace_phases.py (JAX profiler, XLA Ops and "
               "XLA Modules lines; bench. and repro. host spans); op names "
               "shortened to their first and last 120 characters; op_names "
               "from the compiled step's text, inherited from scopes.inherit")
        with gzip.open(args.record, "wt") as f:
            json.dump(fixture(ex, raw, names, src), f)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
