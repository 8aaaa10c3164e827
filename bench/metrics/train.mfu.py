"""Useful FLOPs of the Algorithm-1 step (``harness/flops.py``) times the
step executions in the traced window, over the window and each chip's
bf16 peak (``data/peaks.json``), in %."""
from harness import readers


def read(rec):
    steps = readers.train_steps_traced(rec)
    if not steps:
        return None
    per_chip = readers.train_flops_per_step_per_chip(rec) * steps
    return 100.0 * per_chip / rec["trace"]["window_s"] / \
        readers.peak(rec)["bf16_flops_per_s"]
