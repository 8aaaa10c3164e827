"""The trace reduction, on a hand-made trace and on a recorded one."""
import os

import pytest

import helpers
from harness import readers, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PATS = trace.patterns()


def _op(name, kind, start, dur):
    return [f"%{name} = bf16[8]{{0}} fusion(bf16[8] %x), kind={kind}, "
            f"calls=%c", start, dur]


def _hand_made():
    """Two devices over [0, 100) ns.  Device 0: a while loop over 10-60
    holding a conv 10-30 and an elementwise op 40-60; an all-reduce 55-80
    of which 60-80 runs alone.  Device 1: one conv 0-50."""
    d0 = [["%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b",
           10, 50],
          _op("convolution_add_fusion.1", "kOutput", 10, 20),
          _op("fusion.2", "kLoop", 40, 20),
          ["%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %g), "
           "replica_groups={{0,1}}", 55, 25]]
    d1 = [_op("fusion.7", "kOutput", 0, 50)]
    return {"devices": {"/device:TPU:0": d0, "/device:TPU:1": d1},
            "modules": {"/device:TPU:0": [["jit_step(1)", 10, 70],
                                          ["jit_tiny(2)", 85, 1]],
                        "/device:TPU:1": [["jit_step(1)", 0, 50]]},
            "host": [["bench.traced", 0, 100], ["bench.step", 0, 35],
                     ["bench.wait", 80, 20]]}


def test_classes():
    names = {n: trace.classify(n, PATS)
             for n, _, _ in _hand_made()["devices"]["/device:TPU:0"]}
    assert sorted(names.values()) == ["collective", "container",
                                      "convolution", "other"]


def test_hand_made_trace_reduces_to_its_intervals():
    r = trace.reduce(_hand_made(), (0, 100), PATS)
    d0, d1 = r["devices"]["/device:TPU:0"], r["devices"]["/device:TPU:1"]
    # busy: 10-30, 40-80 (the while itself is not an op that ran)
    assert d0["busy_s"] == pytest.approx(60e-9)
    assert d0["idle_share"] == pytest.approx(0.4)
    assert d0["convolution_s"] == pytest.approx(20e-9)
    assert d0["collective_s"] == pytest.approx(25e-9)
    assert d0["exposed_collective_s"] == pytest.approx(20e-9)
    assert d1["idle_share"] == pytest.approx(0.5)
    # device 0's gaps: 0-10 and 30-40 under bench.step (35 ends inside the
    # second gap, whose middle is at 35 -> the step span), 80-100 waiting
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.step": 20e-9, "bench.wait": 20e-9})
    assert trace.step_executions(r) == 1.0
    # summed over the chips, the container left out
    assert [(n.split()[0], t) for n, t in r["device_ops"]] == [
        ("fusion.7", pytest.approx(50e-9)), ("all-reduce.3", pytest.approx(25e-9)),
        ("convolution_add_fusion.1", pytest.approx(20e-9)),
        ("fusion.2", pytest.approx(20e-9))]


def test_idle_share_reader_takes_the_idlest_chip():
    rec = {"trace": trace.reduce(_hand_made(), (0, 100), PATS)}
    assert readers.idle_share_pct(rec) == pytest.approx(50.0)


def test_recorded_v5e_trace():
    """Five steps of the full-width step on one v5e chip: the device ran
    ops nearly all the time, output fusions (convs, their gradients, the
    dense layers' dots) about half of it, and every step's program once."""
    ex = trace.load(os.path.join(DATA, "train_1chip_5steps.json.gz"))
    r = trace.reduce(ex, trace.window_of(ex, "bench.traced"), PATS)
    (d,) = r["devices"].values()
    assert r["window_s"] == pytest.approx(0.19163, rel=1e-4)
    assert d["busy_s"] == pytest.approx(0.187867, rel=1e-4)
    assert d["convolution_s"] == pytest.approx(0.0995335, rel=1e-4)
    assert d["collective_s"] == 0.0
    assert trace.step_executions(r) == 5
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) <= 10
    rec = {"trace": r, "conf": helpers.conf("calo3dgan-train",
                                            **_full_width()),
           "train": {"rows_per_chip": 128}, "device_kind": "TPU v5 lite"}
    mfu = helpers.common.load_module(
        os.path.join(helpers.BENCH, "metrics", "train.mfu.py"), "m1").read(rec)
    roof = helpers.common.load_module(
        os.path.join(helpers.BENCH, "metrics", "train.conv_roofline.py"),
        "m2").read(rec)
    # 5 x 128 x 1.768 GFLOP in 0.1916 s against 197 TFLOP/s; 5 x 6.51 ms
    # of least conv time against 99.5 ms of conv ops
    assert mfu == pytest.approx(100 * 5 * 128 * 1.767732208e9 / 0.19163
                                / 197e12, rel=1e-3)
    assert roof == pytest.approx(100 * 5 * 6.5118e-3 / 0.0995335, rel=1e-3)


def _full_width():
    return {k: v for k, v in helpers.common.load_json(
        os.path.join(helpers.BENCH, "configs", "calo3dgan-train.json")).items()
        if k in ("image_shape", "latent_dim", "gen_channels",
                 "disc_channels", "batch_size")}
