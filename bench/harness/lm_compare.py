"""The comparisons that decide ``correct`` in an LM training cell.

Both sides run the same checked steps from the same weights on the same
rows: the program (its first steps, as its timed path runs them) and the
reference (``lm_reference.run_steps``), or the control or a planted
fault in the program's place.  Leaves are compared layer by layer, and
an expert's weights expert by expert, so that one layer or one expert
that goes wrong reads as a whole leaf that went wrong.

- ``first_loss_rel_gap``: relative gap of the step-1 loss.
- ``grad_median_leaf_gap``: per leaf, the gap between the norms of the
  first gradient as AdamW got it (the square root of its second moment
  after step 1) over that leaf's reference norm; the median leaf.
- ``first_update_leaf_gap``: per leaf, the gap between the norms of its
  change over step 1, over its own reference change; the worst leaf.
  AdamW's first step moves every element by about lr whatever its
  gradient's size, so a leaf left unmoved reads 1.
- ``update_norm_gap``: the worst leaf's gap between the norms of its
  change over all checked steps, over the larger of its reference norm
  and the median leaf's.
- ``held_rows_rel_gap``: the worst relative gap, over steps and MoE
  layers, between the rows the program computed for its held experts
  (``moe_rows_held``) and the rows the reference routed to them.  A
  dropped pair reads as rows missing; routing flipped by rounding at a
  near tie moves a few rows either way.
- ``bias_gap``: the share of the routers' correction biases (MoE layers
  x all routed experts) that differ from the reference's after the
  checked steps by more than half of gamma.  The update is an exact sign
  step, so a sound run differs only at experts whose load sat within a
  near-tie flip of the mean; a bias never updated, moved the wrong way or
  by another gamma differs nearly everywhere.

Leaves whose reference gradient is below ``NEGLIGIBLE_GRAD`` of the
median leaf's are left out of the gradient and update gaps.  Each side is
summarised as it runs (``norms``): per leaf, the norms of the first
gradient (``g1``), of the step-1 change (``d1``) and of the change over
all checked steps (``dn``), read from the device a leaf at a time, so that
no side keeps a second copy of the weights on the host.
"""
from __future__ import annotations

import numpy as np

NEGLIGIBLE_GRAD = 1e-3
EXPERT_LEAVES = ("w_gate", "w_in", "w_out")
STACKS = ("blocks", "dense_blocks")


def _split(path, x):
    """(name, per-split values) of one leaf: a stacked layer's leaf split
    by layer, a held expert's weights by layer and expert, each part
    flattened; ``buffers`` give nothing."""
    import jax
    keys = [getattr(e, "key", None) for e in path]
    if keys[0] == "buffers":
        return None
    name = jax.tree_util.keystr(path)
    if keys[0] not in STACKS:
        return [(name, x.reshape(1, -1))]
    if keys[-1] in EXPERT_LEAVES and "moe" in keys:
        L, E = x.shape[:2]
        return [(f"{name}[{i}][{e}]", x[i, e].reshape(1, -1))
                for i in range(L) for e in range(E)]
    return [(f"{name}[{i}]", x[i].reshape(1, -1)) for i in range(x.shape[0])]


def norms(tree, base=None, sqrt_of=False) -> dict:
    """Per split leaf, the norm of ``tree - base`` (``base`` a host tree of
    the same structure), or with ``sqrt_of`` the norm of ``sqrt(tree)``;
    each leaf fetched from the device on its own."""
    import jax
    out = {}
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    bases = (jax.tree.leaves(base) if base is not None
             else [None] * len(flat))
    for (path, v), b in zip(flat, bases):
        x = np.asarray(jax.device_get(v), np.float64)
        if b is not None:
            x = x - np.asarray(b, np.float64)
        for name, part in _split(path, x) or ():
            out[name] = float(np.sqrt(np.sum(part))) if sqrt_of else \
                float(np.linalg.norm(part))
    return out


def _gaps(prog, ref, keep, floor=0.0):
    return {k: abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
            for k in keep}


def readings(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"g1", "d1", "dn", "losses", "rows", "bias"} of
    the same steps (``rows``: per step, rows held per MoE layer; ``bias``:
    the routers' biases after the last step); ``ref`` also gives
    ``bias_step``, gamma."""
    if prog["g1"].keys() != ref["g1"].keys():
        raise ValueError(f"parameter trees differ: {sorted(prog['g1'])} vs "
                         f"{sorted(ref['g1'])}")
    gnorm = ref["g1"]
    med = float(np.median(list(gnorm.values())))
    keep = sorted(k for k, v in gnorm.items() if v >= NEGLIGIBLE_GRAD * med)
    grad = _gaps(prog["g1"], ref["g1"], keep)
    first = _gaps(prog["d1"], ref["d1"], keep)
    upd = _gaps(prog["dn"], ref["dn"], keep,
                floor=float(np.median([ref["dn"][k] for k in keep])))
    rows_p = np.asarray(prog["rows"], np.float64)
    rows_r = np.asarray(ref["rows"], np.float64)
    rows_gap = np.abs(rows_p - rows_r) / np.maximum(rows_r, 1.0)
    bias_off = np.abs(np.asarray(prog["bias"], np.float64)
                      - np.asarray(ref["bias"], np.float64)) \
        > 0.5 * ref["bias_step"]
    worst = lambda gaps: max(gaps, key=gaps.get)
    return {
        "first_loss_rel_gap": abs(prog["losses"][0] - ref["losses"][0])
        / max(abs(ref["losses"][0]), 1e-12),
        "grad_median_leaf_gap": float(np.median(list(grad.values()))),
        "first_update_leaf_gap": first[worst(first)],
        "update_norm_gap": upd[worst(upd)],
        "held_rows_rel_gap": float(np.max(rows_gap)),
        "bias_gap": float(np.mean(bias_off)),
        "loss_rel_gaps": [abs(p - r) / max(abs(r), 1e-12)
                          for p, r in zip(prog["losses"], ref["losses"])],
        "_rows_program": rows_p.tolist(), "_rows_reference": rows_r.tolist(),
        "_grad_leaf": worst(grad), "_first_update_leaf": worst(first),
        "_update_leaf": worst(upd), "_left_out": sorted(set(gnorm) - set(keep)),
    }
