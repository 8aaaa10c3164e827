"""§Roofline: three-term roofline per (arch x shape x mesh) from the
dry-run's compiled artifacts (results/dryrun_baseline.json).

    compute term    = FLOPs / (chips * 197 TFLOP/s bf16)
    memory term     = bytes / (chips * 819 GB/s HBM)
    collective term = per-chip ICI traffic / 50 GB/s/link

FLOPs/bytes come from the jaxpr walk (exact, scan-aware — XLA's own
cost_analysis counts while bodies once; both are recorded).  Collective
traffic comes from the optimized per-device HLO with while-trip scaling,
converted to ring-algorithm per-chip link bytes.

MODEL_FLOPS uses the assigned formula: 6*N*D for training (N_active for
MoE), 2*N*D for prefill, 2*N*B for decode — the ratio MODEL_FLOPS/FLOPs
exposes remat/attention/redundancy overhead.
"""
from __future__ import annotations

import argparse
import json
import math
import os
os.environ["JAX_PLATFORMS"] = "cpu"       # shapes and jaxprs only, never a chip

from repro.cloud import costs as cost_lib
from repro.configs import base as config_base
from repro.launch.mesh import HARDWARE

PEAK = HARDWARE["peak_flops_bf16"]
HBM = HARDWARE["hbm_bw"]
ICI = HARDWARE["ici_bw"]
HBM_CAP = 16e9                      # v5e HBM per chip


def model_flops(arch: str, shape_name: str) -> float:
    if arch == "calo3dgan":
        # convs reuse weights across voxels, so 6*N*D does not apply; the
        # intrinsic work is the forward conv FLOPs (from the jaxpr) times
        # Algorithm 1's step structure: D on real + D on fake (fwd+bwd =
        # 3x fwd each), one fake generation, and 2 G updates (G+D fwd+bwd).
        import jax
        import jax.numpy as jnp
        from repro.configs import calo3dgan
        from repro.core import gan as gan_lib
        from repro.parallel.jaxpr_cost import cost_of
        cfg = calo3dgan.config()
        B = cfg.batch_size * 256
        X, Y, Z = cfg.image_shape
        gp = jax.eval_shape(lambda: gan_lib.init_generator(
            jax.random.key(0), cfg))
        dp = jax.eval_shape(lambda: gan_lib.init_discriminator(
            jax.random.key(0), cfg))
        noise = jax.ShapeDtypeStruct((B, cfg.latent_dim), jnp.float32)
        lab = jax.ShapeDtypeStruct((B,), jnp.float32)
        img = jax.ShapeDtypeStruct((B, X, Y, Z, 1), jnp.float32)
        gen_fwd = cost_of(
            lambda p, n, e, t: gan_lib.generate(p, n, e, t, cfg),
            gp, noise, lab, lab)["flops"]
        disc_fwd = cost_of(
            lambda p, im: gan_lib.discriminate(p, im, cfg), dp, img)["flops"]
        g_steps = cfg.gen_steps_per_disc
        return (2 * 3 * disc_fwd            # D on real + D on fake
                + gen_fwd                   # fake generation
                + g_steps * 3 * (gen_fwd + disc_fwd))
    cfg = config_base.get_config(arch)
    shape = config_base.INPUT_SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch           # decode: one token


def ici_per_chip_bytes(coll: dict, devices: int) -> float:
    """Ring-algorithm per-chip traffic from per-device HLO result bytes."""
    f = (devices - 1) / max(devices, 1)
    total = 0.0
    for op, v in coll.items():
        b = v["bytes"]
        if op == "all-reduce":
            total += 2 * f * b
        elif op in ("all-gather", "reduce-scatter", "all-to-all"):
            total += f * b
        else:                                     # collective-permute
            total += b
    return total


def analyse(rec: dict) -> dict:
    if rec.get("status") != "ok":
        return rec
    dev = rec["devices"]
    flops = rec.get("jaxpr_flops") or rec["flops"]
    # memory term: post-fusion HLO bytes, scaled by the scan-trip ratio
    # (XLA counts while bodies once; the dominant loop carries both the
    # flops and the bytes, so the flops ratio is the right multiplier)
    scan_ratio = max(1.0, flops / rec["flops"]) if rec.get("flops") else 1.0
    byts = rec["bytes_accessed"] * scan_ratio
    compute_s = flops / (dev * PEAK)
    memory_s = byts / (dev * HBM)
    ici_b = ici_per_chip_bytes(rec.get("collectives", {}), dev)
    coll_s = ici_b / ICI
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"])
    bound_s = max(terms.values())
    out = dict(rec)
    out.update({
        "compute_s": compute_s, "memory_s": memory_s, "collective_s": coll_s,
        "dominant": dominant,
        "model_flops": mf,
        "useful_flop_ratio": mf / flops if flops else 0.0,
        "bound_s": bound_s,
        "mfu_upper_bound": (mf / (dev * PEAK)) / bound_s if bound_s else 0.0,
        "fits_hbm": rec["peak_bytes_per_device"] <= HBM_CAP,
    })
    return out


_HINTS = {
    "compute": ("compute-bound: larger per-chip batch / more chips, or cut "
                "remat recompute (the 6ND->8ND overhead) to move it down"),
    "memory": ("memory-bound: raise arithmetic intensity — fuse elementwise "
               "chains, widen matmul tiles, cast activations to bf16, or "
               "re-shard so weights stream fewer bytes per chip"),
    "collective": ("collective-bound: re-shard to cut cross-chip traffic "
                   "(FSDP gather batching, TP only where mlp/heads divide, "
                   "avoid resharding between ops) or overlap collectives "
                   "with compute"),
}


def hint(rec: dict) -> str:
    return _HINTS[rec["dominant"]]


def markdown_table(rows, mesh_filter="16x16") -> str:
    lines = [
        "| arch | shape | kind | compute_s | memory_s | collective_s | "
        "dominant | MODEL_FLOPs | useful/HLO | fits 16G |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r.get("status") == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | "
                         f"skipped ({r['reason'][:40]}) | — | — | — |")
            continue
        if r.get("status") != "ok" or r["mesh"] != mesh_filter:
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['kind']} "
            f"| {r['compute_s']:.2e} | {r['memory_s']:.2e} "
            f"| {r['collective_s']:.2e} | **{r['dominant']}** "
            f"| {r['model_flops']:.2e} | {r['useful_flop_ratio']:.2f} "
            f"| {'y' if r['fits_hbm'] else 'NO'} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--in", dest="inp",
                    default="results/dryrun_baseline.json")
    ap.add_argument("--out", default="results/roofline.json")
    ap.add_argument("--md", default="results/roofline.md")
    args = ap.parse_args()

    with open(args.inp) as f:
        recs = json.load(f)
    rows = [analyse(r) for r in recs]
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)

    md = ["# Roofline (single-pod 16x16 = 256 chips)", "",
          markdown_table(rows, "16x16"), "",
          "# Multi-pod check (2x16x16 = 512 chips)", "",
          markdown_table(rows, "2x16x16"), ""]
    ok_rows = [r for r in rows if r.get("status") == "ok"]
    md.append("## Dominant-term hints\n")
    seen = set()
    for r in ok_rows:
        key = (r["arch"], r["shape"])
        if r["mesh"] != "16x16" or key in seen:
            continue
        seen.add(key)
        md.append(f"- **{r['arch']} / {r['shape']}** ({r['dominant']}): "
                  f"{hint(r)}")
    with open(args.md, "w") as f:
        f.write("\n".join(md))
    print(f"wrote {args.out} and {args.md} ({len(ok_rows)} analysed rows)")
    # console summary
    for r in ok_rows:
        if r["mesh"] != "16x16":
            continue
        print(f"{r['arch']:16s} {r['shape']:12s} dom={r['dominant']:10s} "
              f"bound={r['bound_s']:.2e}s useful={r['useful_flop_ratio']:.2f} "
              f"fits={'y' if r['fits_hbm'] else 'NO'}")


if __name__ == "__main__":
    main()
