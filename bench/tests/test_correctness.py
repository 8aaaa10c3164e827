"""``correct`` comes out false when it should: for the control (the
reference in float8 put in the program's place) and for each fault a cell
can have, planted under the timed path of an otherwise whole run.  On the
CPU at ``calo3dgan.reduced()`` sizes, against each cell's own limits; the
program runs in float32 here, so that a sound run reads rounding alone and
only the planted fault can fail it."""
import jax
import numpy as np
import pytest

import helpers
from harness import common, compare

TRAIN = common.load_module(f"{helpers.BENCH}/drivers/gan_train.py",
                           "drv_train_c")


def limits(cell):
    return common.load_json(f"{helpers.BENCH}/limits/{cell}.json")


def failed(checks):
    return [c["name"] for c in checks if c["value"] > c["limit"]]


def train_ctx(chips):
    """The training cell's limits hold the same numbers on any chip count:
    four CPU devices stand in for the data-parallel path."""
    cell = "gan-train-custom-1chip"
    return helpers.CpuContext(helpers.conf("calo3dgan-train", precision="f32"),
                              helpers.traffic("train-steps-pool4"),
                              seed=2147483651, seconds=0.5, chips=chips,
                              limits=limits(cell))


def test_sound_training_run_is_correct():
    rec = TRAIN.run(train_ctx(1))
    assert failed(rec["checks"]) == []
    assert rec["attempted"] > 0 and rec["failed"] == 0


@pytest.mark.parametrize("chips", [1, 4])
def test_training_control_fails(chips):
    ctx = train_ctx(chips)
    prog = TRAIN.Program(ctx.conf, ctx.traffic, 5, ctx.devices)
    args = (ctx.conf, prog.pool, prog.init_key, prog.step_rng, chips, 3)
    ref = TRAIN.reference_readings(*args)
    ctl = TRAIN.reference_readings(*args, precision="float8_e4m3fn")
    assert failed(compare.checks(compare.training_readings(ctl, ref),
                                 ctx.limits))


class _Faulty(TRAIN.Program):
    """The program with a fault planted in its compiled step."""
    fault = None

    def __init__(self, conf, traffic, seed, devs):
        super().__init__(conf, traffic, seed, devs)
        eng, task = self.engine, self.task
        if self.fault == "state_unchanged":
            eng.donate = False
            real = eng.compile_step(task, self.pool[0])
            self.step_fn = lambda s, b, k: (s, real(s, b, k)[1])
        elif self.fault == "leaf_unchanged":
            # one small leaf, the energy head's bias, never updated
            eng.donate = False
            real = eng.compile_step(task, self.pool[0])

            def step(s, b, k):
                new, metrics = real(s, b, k)
                d = dict(new.d_params)
                d["energy"] = dict(d["energy"], b=s.d_params["energy"]["b"])
                return new._replace(d_params=d), metrics
            self.step_fn = step
        elif self.fault == "half_batch":
            rows, chips = self.rows, self.chips
            cut = {k: v.reshape(chips, rows, *v.shape[1:])[:, :rows // 2]
                   .reshape(chips * rows // 2, *v.shape[1:])
                   for k, v in self.pool[0].items()}
            half = eng.compile_step(task, cut)

            def step(s, b, k):
                b = {n: jax.device_put(np.asarray(v).reshape(
                    chips, rows, *v.shape[1:])[:, :rows // 2].reshape(
                    chips * rows // 2, *v.shape[1:])) for n, v in b.items()}
                return half(s, b, k)
            self.step_fn = step
        elif self.fault == "no_exchange":
            from repro.train import engine as engine_lib
            alone = engine_lib.Engine(eng.mesh, "custom", dp_axes=eng.axes,
                                      grad_reduce=lambda g: g)
            self.step_fn = alone.compile_step(task, self.pool[0])


@pytest.mark.parametrize("chips,fault", [
    (1, "state_unchanged"), (1, "half_batch"), (1, "leaf_unchanged"),
    (4, "state_unchanged"), (4, "half_batch"), (4, "no_exchange")])
def test_training_fault_fails(monkeypatch, chips, fault):
    monkeypatch.setattr(_Faulty, "fault", fault)
    monkeypatch.setattr(TRAIN, "Program", _Faulty)
    rec = TRAIN.run(train_ctx(chips))
    assert failed(rec["checks"]), rec["checks"]
    if fault == "leaf_unchanged":
        # the contract's measure scales a leaf below the median by the
        # median leaf, so only the measure against its own norm sees it
        assert failed(rec["checks"]) == ["first_update_leaf_gap"], \
            rec["checks"]
