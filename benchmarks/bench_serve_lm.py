"""Serving benchmark: the LM continuous-batching hot path.

Measures the two halves of the flash-decode serving PR on one reduced LM
config:

- **prefill**: wall-clock to ingest ``slots`` prompts of ``--prompt-len``
  tokens through the legacy SEQUENTIAL path (prompt_len global decode
  steps per slot, snapshot/merge around each) vs the CHUNKED batched
  path (ceil(prompt_len / chunk) ``prefill_chunk`` launches total, all
  slots riding each launch).  ``prefill_speedup`` is the machine-
  normalized ratio gated in CI via ``bench_compare --relative-only``.
- **decode**: steady-state tokens/s over a full continuous-batching run
  plus p50/p99 per-request latency (submit -> finalize).

Kernel routing follows the launcher default (the pure-JAX path until the
Pallas attention kernels compile for v5e; ``--pallas-attn`` /
REPRO_PALLAS_ATTN override).
On the CPU stand-in the numbers measure the reference/interpret path —
labeled via the ``backend`` / ``interpret`` fields — and become
meaningful on TPU; the SHAPE of the comparison (chunked vs sequential
launch counts) transfers.

Writes machine-readable results to results/BENCH_serve_lm.json.

  PYTHONPATH=src python -m benchmarks.bench_serve_lm \
      [--arch qwen2-1.5b] [--slots 4] [--prompt-len 128] [--chunk 64] \
      [--max-new 32] [--max-len 256]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro.configs import base as config_base
from repro.kernels import autotune as autotune_lib
from repro.models import api
from repro.serve.engine import Request, ServeEngine

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(HERE, "results", "BENCH_serve_lm.json")


def _requests(cfg, n, prompt_len, max_new, seed=1):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def _build_engine(cfg, params, args, mode):
    return ServeEngine(cfg, params, slots=args.slots, max_len=args.max_len,
                       prefill=mode, prefill_chunk=args.chunk)


def _warmup(eng, cfg, args):
    """Compile the prefill + decode programs outside the timed window."""
    eng.submit(_requests(cfg, 1, args.prompt_len, 2, seed=7)[0])
    eng.run()


def _time_prefill(eng, cfg, args):
    """Time ONLY prompt ingestion: submit a full slot batch, then time the
    _fill_slots call that prefills every slot (first sampled token
    included — that is where chunked and sequential converge)."""
    for r in _requests(cfg, args.slots, args.prompt_len, 1):
        eng.submit(r)
    t0 = time.perf_counter()
    eng._fill_slots()
    jax.block_until_ready(eng.cache)
    dt = time.perf_counter() - t0
    eng.run()            # drain so the engine ends idle
    return dt


def _time_decode(eng, cfg, args):
    """Steady-state continuous batching: tokens/s + per-request latency."""
    reqs = _requests(cfg, args.slots, args.prompt_len, args.max_new)
    for r in reqs:
        eng.submit(r)
    lat, seen = {}, 0
    t0 = time.perf_counter()
    for _ in range(100_000):
        eng._sweep_slot_deadlines()
        eng._fill_slots()
        if all(r is None for r in eng.slot_req):
            break
        eng._step()
        while seen < len(eng._finished):
            lat[eng._finished[seen].rid] = time.perf_counter() - t0
            seen += 1
    dt = time.perf_counter() - t0
    total = sum(len(r.tokens) for r in eng._finished)
    lats = sorted(lat.values())

    def pct(q):
        return 1e3 * lats[min(len(lats) - 1, int(len(lats) * q))] if lats \
            else 0.0

    return {"tok_per_s": total / dt, "p50_ms": pct(0.50),
            "p99_ms": pct(0.99), "total_tokens": total}


def run(args):
    cfg = config_base.reduced_config(args.arch)
    attn = (args.pallas_attn if args.pallas_attn is not None
            else autotune_lib.default_use_pallas("REPRO_PALLAS_ATTN"))
    cfg = dataclasses.replace(cfg, use_pallas_attn=attn)
    model = api.get_model(cfg)
    params = model.init(jax.random.key(args.seed), cfg)

    times = {}
    for mode in ("sequential", "chunked"):
        eng = _build_engine(cfg, params, args, mode)
        _warmup(eng, cfg, args)
        times[mode] = _time_prefill(eng, cfg, args)
        print(f"  {mode} prefill ({args.slots}x{args.prompt_len} tokens): "
              f"{times[mode]:.3f}s")

    eng = _build_engine(cfg, params, args, "chunked")
    _warmup(eng, cfg, args)
    dec = _time_decode(eng, cfg, args)
    print(f"  decode: {dec['tok_per_s']:.1f} tok/s "
          f"p50={dec['p50_ms']:.0f}ms p99={dec['p99_ms']:.0f}ms")

    rows = [
        {"case": "prefill", "prompt_len": args.prompt_len,
         "slots": args.slots, "chunk": args.chunk,
         "sequential_prefill_s": times["sequential"],
         "chunked_prefill_s": times["chunked"],
         "prefill_speedup": times["sequential"] / times["chunked"]},
        {"case": "decode", "slots": args.slots, "max_new": args.max_new,
         **dec},
    ]
    return rows, {"arch": args.arch, "pallas_attn": bool(attn),
                  "max_len": args.max_len}


def write_json(rows, path=OUT_PATH, **meta):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    payload = {"benchmark": "serve_lm",
               "backend": jax.default_backend(),
               "interpret": jax.default_backend() != "tpu", **meta,
               "rows": rows}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=str)
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pallas-attn", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="kernel routing (default: off; env "
                         "REPRO_PALLAS_ATTN overrides)")
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args(argv)
    print(f"bench_serve_lm: {args.arch} (reduced), slots={args.slots}, "
          f"prompt={args.prompt_len}, chunk={args.chunk}, "
          f"backend={jax.default_backend()})")
    rows, meta = run(args)
    sp = rows[0]["prefill_speedup"]
    print(f"  prefill_speedup (chunked over sequential): {sp:.1f}x")
    path = write_json(rows, args.out, **meta)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
