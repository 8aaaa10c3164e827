"""Real samples in the training steps completed in the window, over the
window (opened and closed by ``block_until_ready``; no sync per step)."""


def read(rec):
    return rec["train"]["samples"] / rec["window_s"]
