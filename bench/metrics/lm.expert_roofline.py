"""The held experts' grouped matmuls at their least time a step
(``harness/lm_flops.expert_least_seconds`` at the rows the program held)
over the device time a step of the ops under ``moe/experts``
(``harness/lm_scopes.py``: the grouped matmuls' custom calls and the
gating between them, forward and backward), in %, averaged over chips."""
from harness import lm_flops, readers


def read(rec):
    lm = rec.get("lm") or {}
    scoped = lm.get("scopes")
    if not scoped:
        return None
    per_step = [sum(d["scopes"]["moe/experts"].values()) / d["steps"]
                for d in scoped.values() if d["steps"]]
    if not per_step or min(per_step) <= 0:
        return None
    pk = readers.peak(rec)
    least = lm_flops.expert_least_seconds(
        rec["conf"], lm["rows_held_by_layer"], pk["bf16_flops_per_s"],
        pk["hbm_bytes_per_s"])
    return 100.0 * least * len(per_step) / sum(per_step)
