#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6]

For every seed, in one process with one compiled program:

- ``program``: the timed path's first steps against the float32
  reference, as a run reads them;
- ``control`` (control seeds): the reference computed with float8 (e4m3)
  operands put in the program's place — the precision one step below the
  configuration's bf16 — against the float32 reference;
- ``half_batch`` and, on more than one chip, ``one_replica`` (control
  seeds): the reference with half of each batch left out, and with
  the gradient of one replica in place of the mean over all.

Each reading is one JSON line on stdout and in
``chiprun_out/calibrate/<cell>.jsonl``.  It takes the chip like a run does.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import common  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]

    bench = common.benchmark()
    cell = common.cell(bench, args.workload)
    entry = common.config_entry(bench, cell["config"])
    conf = common.load_json(os.path.join(common.CHECKOUT, entry["file"]))
    traffic = common.traffic(cell["traffic"])
    common.require_program()
    common.enable_compile_cache()
    devs = common.devices(int(cell["chips"]))
    out_dir = os.path.join(common.CHECKOUT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, f"{args.workload}.jsonl"), "a")

    def emit(**kw):
        line = json.dumps(dict(kw, workload=args.workload))
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    drv = common.load_module(
        os.path.join(common.BENCH, "drivers", f"{conf['kind']}.py"),
        "bench_driver")
    drv.calibrate(conf, traffic, devs, seeds, control, emit)
    sink.close()


if __name__ == "__main__":
    t = time.time()
    main()
    print(f"calibrate: {time.time() - t:.1f}s", file=sys.stderr)
