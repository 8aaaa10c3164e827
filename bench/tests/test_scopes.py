"""Algorithm 1's phases in the trace: the op -> op_name map, the split of
the step program's op time, and where idle gaps lie."""
import os

import pytest

import helpers
from harness import common, scopes, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PATS = trace.patterns()
STEP = "jit_local_step(1)"
OP = "jit(local_step)/"


def _op(name, kind, start, dur):
    return [f"%{name} = bf16[8]{{0}} fusion(bf16[8] %x), kind={kind}, "
            f"calls=%c", start, dur]


def _hand_made():
    """One device over [0, 100) ns.  Two runs of the step program, 0-40
    and 60-100, with a 5 ns fold-in program at 50-55 between them.  Run
    one: D-real forward 0-10, its backward 10-20, its update 20-25, idle
    25-30, D-fake forward 30-40.  Run two: G forward 60-70, a while loop
    over 60-90 holding it, G backward 75-85, an op of the step that no
    phase holds 85-90, an op that is not in the map 90-100."""
    dev = [_op("fusion.1", "kOutput", 0, 10), _op("fusion.2", "kOutput", 10, 10),
           _op("fusion.3", "kLoop", 20, 5), _op("fusion.4", "kOutput", 30, 10),
           _op("fold.1", "kLoop", 50, 5),
           ["%while.5 = (s32[]) while((s32[]) %t), condition=%c, body=%b",
            60, 30],
           _op("fusion.6", "kOutput", 60, 10), _op("fusion.7", "kOutput", 75, 10),
           _op("fusion.8", "kLoop", 85, 5), _op("fusion.99", "kLoop", 90, 10)]
    names = {"fusion.1": OP + "d_real/jvp()/conv_general_dilated",
             "fusion.2": OP + "d_real/transpose(jvp())/conv_general_dilated",
             "fusion.3": OP + "d_real/update/mul",
             "fusion.4": OP + "d_fake/jvp(jit(_uniform))/add",
             "fusion.6": OP + "g/while/body/closed_call/jvp()/dot_general",
             "fusion.7": OP + "g/while/body/closed_call/transpose(jvp())/mul",
             "fusion.8": OP + "convert_element_type",
             "fold.1": "jit(_threefry_fold_in)/add", "while.5": OP + "g/while"}
    ex = {"devices": {"/device:TPU:0": dev},
          "modules": {"/device:TPU:0": [[STEP, 0, 40],
                                        ["jit__threefry_fold_in(2)", 50, 5],
                                        [STEP, 60, 40]]},
          "host": [["bench.traced", 0, 100], ["bench.step", 0, 20],
                   ["bench.step", 45, 15], ["repro.prefetch.wait", 45, 4],
                   ["other.span", 40, 10]]}
    return ex, names


def test_op_names_from_compiled_text():
    text = "\n".join([
        "HloModule jit_local_step, entry_computation_layout={()}",
        "%fused_computation.1 (p: f32[8]) -> f32[8] {",
        '  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="mul"}',
        "}",
        "ENTRY %main.9 (a: f32[8]) -> f32[8] {",
        "  %a = f32[8]{0} parameter(0)",
        '  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls='
        '%fused_computation.1, metadata={op_name="jit(f)/d_real/update/mul"'
        ' source_file="x.py" source_line=3}',
        '  ROOT %copy-start.2 = f32[8]{0} copy(%fusion.1), metadata={op_name='
        '"jit(f)/batch[\\"image\\"]"}',
        "}"])
    assert scopes.op_names(text) == {
        "m": "mul", "a": "", "fusion.1": "jit(f)/d_real/update/mul",
        "copy-start.2": 'jit(f)/batch[\\"image\\"]'}
    assert scopes.instruction(
        "%fusion.1 = f32[8]{0:T(128)} fusion(%a), kind=kLoop") == "fusion.1"


def test_inherit_fills_ops_xla_added_from_the_op_they_serve():
    """A ``reverse`` and an async copy that XLA put in have no op_name:
    each takes that of the op using its result; an op whose result goes
    nowhere with a name takes that of its operand; one with neither keeps
    none."""
    text = "\n".join([
        "ENTRY %main (p: bf16[8]) -> bf16[8] {",
        '  %p = bf16[8]{0} parameter(0), metadata={op_name="state.w"}',
        "  %copy-start.1 = (bf16[8]{0:S(1)}, bf16[8]{0}, u32[]{:S(2)}) "
        "copy-start(%p)",
        "  %copy-done.1 = bf16[8]{0:S(1)} copy-done(%copy-start.1)",
        '  %fusion.2 = bf16[8]{0:T(128)} fusion(%copy-done.1), kind=kLoop, '
        'calls=%f, metadata={op_name="jit(s)/g/update/mul"}',
        "  %reverse.3 = bf16[8]{0} reverse(%fusion.2), dimensions={0}",
        "  %fusion.4 = bf16[8]{0} fusion(%reverse.3, /*index=1*/%fusion.2), "
        'kind=kOutput, calls=%f, metadata={op_name='
        '"jit(s)/g/transpose(jvp())/conv_general_dilated"}',
        "  %copy.5 = bf16[8]{0} copy(%fusion.4)",
        "  %constant.6 = bf16[] constant(0)",
        "  ROOT %tuple.7 = (bf16[8]{0}) tuple(%copy.5)",
        "}"])
    raw = scopes.op_names(text)
    assert raw["reverse.3"] == raw["copy-start.1"] == raw["copy.5"] == ""
    got = scopes.inherit(text, raw)
    assert got["copy-start.1"] == got["copy-done.1"] == "jit(s)/g/update/mul"
    assert got["reverse.3"] == got["copy.5"] == got["tuple.7"] == \
        "jit(s)/g/transpose(jvp())/conv_general_dilated"
    assert got["constant.6"] == ""
    assert {k: v for k, v in got.items() if raw[k]} == \
        {k: v for k, v in raw.items() if v}


@pytest.mark.parametrize("op_name,want", [
    (OP + "d_real/jvp()/conv_general_dilated", ("d_real", "fwd")),
    (OP + "d_fake/transpose(jvp())/dot_general", ("d_fake", "bwd")),
    (OP + "d_fake/update/jit(_where)/select_n", ("d_fake", "update")),
    (OP + "g/while/body/closed_call/update/add", ("g", "update")),
    (OP + "g/convert_element_type", ("g", "fwd")),
    (OP + "jit(_threefry_split)/g_keys/add", (None, None)),
    (OP + "dynamic_update_slice", (None, None)),
    ("", (None, None))])
def test_phase_of(op_name, want):
    assert scopes.phase_of(op_name) == want


def test_hand_made_trace_splits_by_phase():
    ex, names = _hand_made()
    r = scopes.reduce(ex, (0, 100), names, PATS)
    d = r["/device:TPU:0"]
    assert d["program"] == STEP and d["steps"] == 2
    # the fold-in program is not the step's; the while is a container
    assert d["busy_s"] == pytest.approx(70e-9)
    assert d["phases"] == {
        "d_real": pytest.approx({"fwd": 10e-9, "bwd": 10e-9, "update": 5e-9}),
        "d_fake": pytest.approx({"fwd": 10e-9, "bwd": 0.0, "update": 0.0}),
        "g": pytest.approx({"fwd": 10e-9, "bwd": 10e-9, "update": 0.0})}
    assert d["unscoped_s"] == pytest.approx(15e-9)
    assert d["unmapped_s"] == pytest.approx(10e-9)
    assert scopes.phase_ms_per_step(r, "d_real") == pytest.approx(12.5e-6)
    assert scopes.phase_ms_per_step(r, "g") == pytest.approx(10e-6)


def test_gaps_inside_the_step_go_to_the_phase_that_ends_them():
    """25-30 lies inside run one and ends at D-fake's op, 70-75 inside run
    two and ends at G's backward; 40-50 and 55-60 lie between programs.
    The middle of 40-50 is under the prefetch wait (``other.span`` has no
    prefix the breakdown takes), that of 55-60 under ``bench.step``."""
    ex, names = _hand_made()
    gaps = scopes.idle_gaps(ex, (0, 100), names, PATS)
    assert gaps == pytest.approx({"in:d_fake": 5e-9, "in:g": 5e-9,
                                  "repro.prefetch.wait": 10e-9,
                                  "bench.step": 5e-9})


def test_host_spans_are_the_programs(tmp_path):
    import jax
    with jax.profiler.trace(str(tmp_path)):
        for name in ("repro.a", "bench.b", "other.c", "repro.a"):
            with jax.profiler.TraceAnnotation(name):
                pass
    assert [n for n, _, _ in scopes.host_spans(str(tmp_path))] == \
        ["repro.a", "repro.a"]


def test_trace_phases_records_spans_and_the_step_text():
    """``trace_phases.record`` on the CPU at small sizes: the profiler
    sees one ``bench.step`` and one ``repro.prefetch.wait`` per step, and
    the compiled text it returns names every phase (the device lines are
    the TPU's, so a CPU trace has none)."""
    import jax

    import trace_phases
    drv = common.load_module(os.path.join(helpers.BENCH, "drivers",
                                          "gan_train.py"), "drv_phases")
    prog = drv.Program(helpers.conf("calo3dgan-train"),
                       helpers.traffic("train-steps-pool4"), 11,
                       jax.devices()[:1])
    drv.check_steps(prog, 1)
    ex, text = trace_phases.record(prog, 2)
    spans = [n for n, _, _ in ex["host"]]
    assert spans.count("bench.step") == 2
    assert spans.count("repro.prefetch.wait") == 2
    names = scopes.inherit(text, scopes.op_names(text))
    assert {scopes.phase_of(v)[0] for v in names.values()} >= \
        set(scopes.phases())
    from repro.core import adversarial
    assert scopes.UPDATE == adversarial.UPDATE


def test_existing_readers_read_the_recorded_trace_as_before():
    """The per-layer readers that were there before the phases, on the
    recorded 5-step trace, give the values they gave then; the breakdown's
    idle gaps keep their host spans."""
    import test_trace
    ex = trace.load(os.path.join(DATA, "train_1chip_5steps.json.gz"))
    r = trace.reduce(ex, trace.window_of(ex, "bench.traced"), PATS)
    rec = {"trace": r, "device_kind": "TPU v5 lite",
           "conf": helpers.conf("calo3dgan-train", **test_trace._full_width()),
           "train": {"rows_per_chip": 128, "steps": 5, "h2d_wait_ms": 0.25}}
    want = {"train.device_idle_share": 1.9651644779534405,
            "train.mfu": 2.9968138541971734,
            "train.conv_roofline": 32.71143265106102,
            "train.h2d_wait_ms": 0.05}
    for m, v in want.items():
        reader = common.load_module(
            os.path.join(helpers.BENCH, "metrics", f"{m}.py"),
            "pinned_" + m.replace(".", "_"))
        assert reader.read(rec) == pytest.approx(v, rel=1e-12), m
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.step": 0.003278411999999882,
         "bench.traced": 0.00048749299999999195}, rel=1e-12)


def _recorded():
    """Three steps of the scoped step on one v5e chip
    (``bench/trace_phases.py --record``) with the op -> op_name map of
    the instructions in it, and what ``scopes.inherit`` filled in."""
    ex = trace.load(os.path.join(DATA, "train_1chip_3steps_phases.json.gz"))
    return ex, dict(ex["op_names"], **ex["inherited"])


def test_recorded_phases_add_up_to_the_step():
    """One prefetch wait per step, and idle gaps inside each phase.  Every
    op of the step program finds its instruction; phases plus the
    unscoped rest are its busy time within 0.1%; under 5% is unscoped once
    the ops XLA added inherit their op_name (by metadata alone, XLA's
    ``reverse`` and async copies leave more)."""
    ex, names = _recorded()
    w = trace.window_of(ex, "bench.traced")
    (d,) = scopes.reduce(ex, w, names, PATS).values()
    scoped = sum(sum(p.values()) for p in d["phases"].values())
    assert scoped + d["unscoped_s"] == pytest.approx(d["busy_s"], rel=1e-3)
    assert d["unmapped_s"] <= 1e-3 * d["busy_s"]
    assert d["unscoped_s"] < 0.05 * d["busy_s"]
    (raw,) = scopes.reduce(ex, w, ex["op_names"], PATS).values()
    assert raw["unscoped_s"] > d["unscoped_s"]
    assert all(d["phases"][p]["update"] > 0 for p in scopes.phases())
    waits = [s for n, s, _ in ex["host"]
             if n == "repro.prefetch.wait" and w[0] <= s <= w[1]]
    assert d["steps"] == 3 and len(waits) == 3
    assert {f"in:{p}" for p in scopes.phases()} <= \
        set(scopes.idle_gaps(ex, w, names, PATS))


def test_recorded_phase_ms_match_a_hand_sum():
    """Per step, each phase's ms is the plain sum of its op events' times
    inside the step program's runs (ops on the line do not overlap)."""
    ex, names = _recorded()
    w = trace.window_of(ex, "bench.traced")
    r = scopes.reduce(ex, w, names, PATS)
    (dev,) = ex["devices"]
    runs = [(s, s + t) for n, s, t in ex["modules"][dev]
            if n == r[dev]["program"]]
    hand = {}
    for ev, s, t in ex["devices"][dev]:
        if trace.classify(ev, PATS) == "container" or \
                not any(a <= s and s + t <= b for a, b in runs):
            continue
        phase, _ = scopes.phase_of(names.get(ev.split(" ")[0][1:], ""))
        hand[phase] = hand.get(phase, 0.0) + t / 1e6
    for phase in scopes.phases():
        assert scopes.phase_ms_per_step(r, phase) == pytest.approx(
            hand[phase] / len(runs), rel=1e-9), phase
