"""The comparisons that decide ``correct``, and their readings.

Each reading is a gap between the program (or the control, or a planted
fault) and the plain float32 reference; each has a limit of its own,
kept per cell in ``bench/limits/<cell>.json``.  A check is
``{"name", "value", "limit"}`` and passes when ``value <= limit``.
"""
from __future__ import annotations

import numpy as np

LOSSES = ("d_loss_real", "d_loss_fake", "g_loss")
# a leaf whose reference gradient is below this share of the median
# leaf's moves by rounding alone (RMSprop divides its gradient by its
# own root-mean-square), so its change is left out
NEGLIGIBLE_GRAD = 1e-3


def _leaves(tree) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
            for p, v in flat}


def loss_gap(prog_losses, ref_losses) -> float:
    """Largest relative gap of any phase's loss at any checked step."""
    gaps = [abs(p[k] - r[k]) / max(abs(r[k]), 1e-12)
            for p, r in zip(prog_losses, ref_losses) for k in LOSSES]
    return float(max(gaps))


def leaf_gaps(prog: dict, ref: dict, keep, own: bool = False) -> dict:
    """Per leaf: the gap between the program's and the reference's norms,
    over the larger of that leaf's reference norm and the median leaf's
    (``own``: over that leaf's reference norm alone)."""
    refn = {k: float(np.linalg.norm(ref[k])) for k in keep}
    med = 0.0 if own else float(np.median(list(refn.values())))
    return {k: abs(float(np.linalg.norm(prog[k])) - refn[k])
            / max(refn[k], med, 1e-30) for k in keep}


def training_readings(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"p0", "p1", "pn", "nu1", "losses"} of the same
    steps.  The first gradient, as the optimizer got it, is the square root
    of RMSprop's second moment after step 1; the change is ``pn - p0``.

    ``first_update_leaf_gap`` measures each leaf's first change
    (``p1 - p0``) against that leaf's own reference change, so a leaf left
    unmoved, or moved twice, reads 1 however small it is.  RMSprop's first
    step moves every element by lr / sqrt(1 - decay) whatever the size or
    sign of its gradient, so sound runs read rounding there; over three
    steps a one-value leaf whose gradient changes sign can all but cancel,
    which makes the later change no steady measure of a small leaf.

    ``grad_median_leaf_gap`` is the median leaf's first-gradient gap
    against that leaf's own reference norm.  The worst leaf's gap swings
    with the accuracy heads: their L1 gradients are sums of signs over
    the batch that all but cancel, so rounding that flips a few of them
    moves the whole leaf."""
    nu_p, nu_r = _leaves(prog["nu1"]), _leaves(ref["nu1"])
    if nu_p.keys() != nu_r.keys():
        raise ValueError(f"parameter trees differ: {sorted(nu_p)} vs "
                         f"{sorted(nu_r)}")
    g_p = {k: np.sqrt(v) for k, v in nu_p.items()}
    g_r = {k: np.sqrt(v) for k, v in nu_r.items()}
    gnorm = {k: float(np.linalg.norm(v)) for k, v in g_r.items()}
    med = float(np.median(list(gnorm.values())))
    keep = sorted(k for k, v in gnorm.items() if v >= NEGLIGIBLE_GRAD * med)
    p0p, pnp = _leaves(prog["p0"]), _leaves(prog["pn"])
    p0r, pnr = _leaves(ref["p0"]), _leaves(ref["pn"])
    p1p, p1r = _leaves(prog["p1"]), _leaves(ref["p1"])
    d_p = {k: pnp[k] - p0p[k] for k in keep}
    d_r = {k: pnr[k] - p0r[k] for k in keep}
    grad = leaf_gaps(g_p, g_r, keep)
    grad_own = leaf_gaps(g_p, g_r, keep, own=True)
    upd = leaf_gaps(d_p, d_r, keep)
    own = leaf_gaps({k: p1p[k] - p0p[k] for k in keep},
                    {k: p1r[k] - p0r[k] for k in keep}, keep, own=True)
    worst = lambda gaps: max(gaps, key=gaps.get)
    return {"loss_rel_gap": loss_gap(prog["losses"], ref["losses"]),
            "first_loss_rel_gap": loss_gap(prog["losses"][:1],
                                           ref["losses"][:1]),
            "grad_norm_gap": grad[worst(grad)],
            "grad_median_leaf_gap": float(np.median(list(grad_own.values()))),
            "update_norm_gap": upd[worst(upd)],
            "first_update_leaf_gap": own[worst(own)],
            "_loss_gaps": [{k: abs(p[k] - r[k]) / max(abs(r[k]), 1e-12)
                            for k in LOSSES}
                           for p, r in zip(prog["losses"], ref["losses"])],
            "_grad_leaf": worst(grad), "_update_leaf": worst(upd),
            "_first_update_leaf": worst(own),
            "_first_update_gaps": own,
            "_left_out": sorted(set(gnorm) - set(keep))}


def checks(readings: dict, limits: dict) -> list:
    return [{"name": k, "value": float(readings[k]), "limit": float(limits[k])}
            for k in limits]
