"""The conv layers' least device time per step — for every layer and pass
(fwd, dx, dw) the larger of FLOPs over peak and bytes over HBM bandwidth,
counted from shapes (``harness/flops.py``) — over the device time of the
conv-classified ops (``bench/ops/convolution``) in the traced window, in
%, averaged over chips."""
from harness import flops, readers


def read(rec):
    steps = readers.train_steps_traced(rec)
    if not steps:
        return None
    conv_s = [d["convolution_s"] for d in rec["trace"]["devices"].values()]
    conv_s = sum(conv_s) / len(conv_s)
    if conv_s <= 0:
        return None
    pk = readers.peak(rec)
    least = flops.conv_roofline_seconds(
        rec["conf"], flops.step_passes(rec["conf"]),
        rec["train"]["rows_per_chip"], pk["bf16_flops_per_s"],
        pk["hbm_bytes_per_s"])["seconds"]
    return 100.0 * least * steps / conv_s
