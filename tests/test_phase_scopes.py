"""Algorithm 1's phases as named scopes in the fused GAN step, and the
prefetcher's wait as a host span.

The scopes only change metadata: every conv and dot of the compiled step
that keeps an ``op_name`` carries exactly one phase in it, each phase has an ``update``
part, the G steps' ``while`` body carries ``g``, and the step computes
bit for bit what it computes without them."""
import contextlib
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.configs import calo3dgan
from repro.core import adversarial
from repro.data.calo import CaloSimulator, CaloSpec
from repro.data.pipeline import prefetch
from repro.launch.mesh import make_dev_mesh
from repro.optim import optimizers as opt_lib
from repro.substrate.precision import get_policy
from repro.train import engine as engine_lib

CFG = calo3dgan.reduced()
_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def _batch():
    sim = CaloSimulator(CaloSpec(image_shape=CFG.image_shape), seed=3)
    return next(sim.batches(CFG.batch_size))


def _custom_step(precision):
    task = engine_lib.gan_task(CFG, opt_lib.rmsprop(1e-4),
                               opt_lib.rmsprop(1e-4),
                               policy=get_policy(precision))
    eng = engine_lib.Engine(make_dev_mesh(), "custom", donate=False)
    batch = _batch()
    return task, eng.compile_step(task, batch), batch


def _computations(text):
    """Computation name -> [(instruction, opcode, op_name)]."""
    comps, cur = {}, None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h and " = " not in line:
            cur = comps.setdefault(h.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            op = _OP_NAME.search(line)
            cur.append((m.group(1), m.group(2), op.group(1) if op else ""))
    return comps


def _phases_in(op_name):
    parts = op_name.split("/")
    return [p for p in adversarial.PHASES if p in parts]


@pytest.fixture(scope="module", params=["f32", "bf16"])
def compiled(request):
    """The custom loop's step, compiled, as text: f32 runs the plain
    update, bf16 the loss-scale guard around it."""
    task, step, batch = _custom_step(request.param)
    state = jax.jit(task.init)(jax.random.key(0))
    return step.lower(state, batch, jax.random.key(1)).compile().as_text()


def test_every_conv_and_dot_is_in_exactly_one_phase(compiled):
    """Every conv and dot of the compiled step that keeps its op_name sits
    in one phase, and nearly all keep one (an instruction that a compiler
    pass made, such as the CPU backend's rewrite of a dilated window, may
    have none)."""
    ops = [(n, op) for instrs in _computations(compiled).values()
           for n, code, op in instrs if code in ("convolution", "dot")]
    kept = [(n, op) for n, op in ops if op]
    assert len(kept) > 20 and len(kept) >= 0.9 * len(ops)
    for name, op_name in kept:
        assert len(_phases_in(op_name)) == 1, (name, op_name)
    assert {_phases_in(op)[0] for _, op in kept} == set(adversarial.PHASES)


def test_each_phase_has_an_update(compiled):
    names = _OP_NAME.findall(compiled)
    for phase in adversarial.PHASES:
        assert any(_phases_in(n) == [phase]
                   and adversarial.UPDATE in n.split("/") for n in names), \
            phase


def test_g_while_body_carries_g(compiled):
    """The G steps run in a ``lax.scan``; every conv, dot and fusion of
    the compiled ``while`` body that has an op_name is under ``g``."""
    comps = _computations(compiled)
    bodies = [re.search(r"body=%?([\w.\-]+)", line).group(1)
              for line in compiled.splitlines()
              if re.search(r"\bwhile\(", line) and "/g/" in line]
    assert bodies
    body_ops = [op for b in bodies for _, code, op in comps[b]
                if code in ("convolution", "dot", "fusion")]
    convs = [op for b in bodies for _, code, op in comps[b]
             if code in ("convolution", "dot")]
    assert convs, "no conv or dot in the G loop's body"
    for op in body_ops:
        if op:
            assert _phases_in(op) == ["g"], op


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_scopes_change_nothing_computed(precision, monkeypatch):
    """Two steps with and without the scopes (``jax.named_scope`` made a
    null context): losses and new state bit-identical."""
    def run():
        task, step, batch = _custom_step(precision)
        state = jax.jit(task.init)(jax.random.key(0))
        out = []
        for i in range(2):
            state, metrics = step(state, batch, jax.random.key(10 + i))
            out.append(jax.device_get(metrics))
        return jax.device_get(state), out

    scoped = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = run()
    for a, b in zip(jax.tree.leaves(scoped), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _host_events(log_dir, name):
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [e for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name == name]


def test_prefetch_wait_is_one_span_per_batch(tmp_path):
    """Under the profiler, N batches give N ``repro.prefetch.wait`` host
    spans, and the counter still adds up to the time waited."""
    n = 5
    batches = [{"x": np.full((4,), i, np.float32)} for i in range(n)]
    pf = prefetch(iter(batches), size=2)
    with jax.profiler.trace(str(tmp_path)):
        got = [next(pf) for _ in range(n)]
    assert len(got) == n and pf.stats["batches"] == n
    spans = _host_events(str(tmp_path), "repro.prefetch.wait")
    assert len(spans) == n
    # each span holds the interval the counter timed
    assert pf.stats["h2d_wait_ms"] <= sum(e.duration_ns
                                          for e in spans) / 1e6 + 1e-6


def test_fit_wait_windows_add_up_with_spans(tmp_path):
    """``Engine.fit`` under the profiler: one span per step's batch, and
    the per-window waits still sum to the total."""

    class _Log:
        def log(self, *a, **kw):
            pass

    sim = CaloSimulator(CaloSpec(image_shape=CFG.image_shape), seed=3)
    batches = [next(sim.batches(CFG.batch_size)) for _ in range(4)]
    task = engine_lib.gan_task(CFG, opt_lib.rmsprop(1e-4),
                               opt_lib.rmsprop(1e-4))
    eng = engine_lib.Engine(make_dev_mesh(), "builtin")
    with jax.profiler.trace(str(tmp_path)):
        eng.fit(task, iter(batches), 4, rng=jax.random.key(0), log=_Log(),
                log_every=2)
    stats = eng.last_fit_stats
    assert stats["steps"] == 4 and "h2d_put_ms" not in stats
    assert stats["h2d_wait_ms"] == pytest.approx(
        sum(stats["h2d_wait_ms_windows"]), abs=1e-6)
    assert len(_host_events(str(tmp_path), "repro.prefetch.wait")) == 4
