"""Useful FLOPs of the LM step (``harness/lm_flops.py``: matmuls and
causal attention forward and backward, the routed experts at the rows the
program held, no recompute) times the step executions in the traced
window, over the window and each chip's bf16 peak, in %."""
from harness import lm_flops, readers


def read(rec):
    steps = readers.train_steps_traced(rec)
    lm = rec.get("lm")
    if not steps or not lm:
        return None
    flops = lm_flops.step_flops(rec["conf"], lm["batch"], lm["seq_len"],
                                sum(lm["rows_held_by_layer"]))["total"]
    return 100.0 * flops * steps / rec["trace"]["window_s"] / \
        readers.peak(rec)["bf16_flops_per_s"]
