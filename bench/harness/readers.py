"""Pieces the per-layer metric readers share."""
from __future__ import annotations

from harness import flops, peaks, trace


def idle_share_pct(rec):
    """Largest share of the traced window in which no op ran, over the
    chips used."""
    tr = rec.get("trace")
    if not tr:
        return None
    return 100.0 * max(d["idle_share"] for d in tr["devices"].values())


def peak(rec) -> dict:
    return peaks.for_kind(rec["device_kind"])


def train_steps_traced(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    n = trace.step_executions(tr)
    return n or None


def train_flops_per_step_per_chip(rec) -> float:
    return (flops.step_flops_per_sample(rec["conf"])
            * rec["train"]["rows_per_chip"])
