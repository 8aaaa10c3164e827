"""The LM training cell on the CPU at a small size: ``correct`` holds for a
sound run and fails for the float8 control and for each planted fault,
against the cell's own limits; the FLOP count by hand; the scope
reduction over a compiled step's text.  The program runs in float32
here, so a sound run reads rounding alone."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import helpers
from harness import common, lm_compare, lm_flops, lm_reference, lm_scopes, \
    trace

CELL = "moonlight-train-8k-ep8-1chip"
LM = common.load_module(f"{helpers.BENCH}/drivers/lm_train.py", "drv_lm_c")
# every kind of layer of the configuration at CPU sizes: a dense layer and
# two MoE layers, 4 of 16 experts held, 6 per token, 2 shared
SMALL = {"hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "moe_intermediate_size": 32, "n_routed_experts": 4,
         "router_experts": 16, "num_hidden_layers": 3, "vocab_size": 256,
         "precision": "f32"}
TRAFFIC = {"batch": 2, "seq_len": 96, "doc_median": 24, "pool_batches": 2,
           "run_ahead": 2}


def conf(**over):
    c = common.load_json(f"{helpers.BENCH}/configs/moonlight-16b-a3b-ep8.json")
    return dict(c, **dict(SMALL, **over))


def limits():
    return common.load_json(f"{helpers.BENCH}/limits/{CELL}.json")


def failed(checks):
    return [c["name"] for c in checks if c["value"] > c["limit"]]


def ctx(seed=2147483653):
    return helpers.CpuContext(conf(), helpers.traffic("lm-train-packed-8k",
                                                      **TRAFFIC),
                              seed=seed, seconds=0.5, limits=limits())


def test_config_file_is_the_programs_share_of_the_published_model():
    """The file's configuration is ``moonlight_16b_a3b.config`` at the
    cut it states, and keeps every published width."""
    from repro.configs import moonlight_16b_a3b as ml
    c = common.load_json(f"{helpers.BENCH}/configs/moonlight-16b-a3b-ep8.json")
    got = LM.lm_config(c)
    want = ml.config(n_layers=5, n_held=8, vocab=20480)
    assert dataclasses.replace(got, source=want.source,
                               decode_supported=False) == want
    assert c["published"] == {"num_hidden_layers": 27, "n_routed_experts": 64,
                              "vocab_size": 163840}


def test_sound_run_is_correct():
    rec = LM.run(ctx())
    assert failed(rec["checks"]) == [], rec["checks"]
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert rec["lm"]["drop_frac"] == 0.0


def test_control_fails():
    c = ctx()
    prog = LM.Program(c.conf, c.traffic, 5, c.devices)
    p0 = jax.device_get(prog.state.params)
    ref = lm_reference.run_steps(c.conf, p0, prog.pool, 3)
    ctl = lm_reference.run_steps(c.conf, p0, prog.pool, 3,
                                 precision="float8_e4m3fn")
    from harness import compare
    got = dict(lm_compare.readings(ctl, ref), moe_drop_frac=0.0)
    assert failed(compare.checks(got, c.limits))


def _capacity_groups(real):
    """The old dispatch at capacity factor 1.0: past T*K/n_router pairs an
    expert's pairs are dropped."""
    def groups(ids, cfg):
        g = real(ids, cfg)
        m = cfg.moe
        cap = ids.size // m.n_router
        onehot = jax.nn.one_hot(g, m.n_experts + 1, dtype=jnp.int32)
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, -1)
        return jnp.where(rank < cap, g, m.n_experts)
    return groups


class _OneExpertFrozen(LM.Program):
    """The program with held expert 0 of every MoE layer never updated."""

    def __init__(self, *a):
        super().__init__(*a)
        self.engine.donate = False
        real = self.engine.compile_step(self.task, self.pool[0])

        def step(s, b, k):
            new, metrics = real(s, b, k)
            blocks = dict(new.params["blocks"])
            moe = dict(blocks["moe"])
            for w in ("w_gate", "w_in", "w_out"):
                moe[w] = moe[w].at[:, 0].set(s.params["blocks"]["moe"][w][:, 0])
            blocks["moe"] = moe
            return new._replace(params=dict(new.params, blocks=blocks)), \
                metrics
        self.step_fn = step


class _HalfBatch(LM.Program):
    """The program's step over the first half of each batch's rows."""

    def __init__(self, *a):
        super().__init__(*a)
        half = self.rows // 2
        cut = {k: v[:half] for k, v in self.pool[0].items()}
        step = self.engine.compile_step(self.task, cut)

        def halved(s, b, k):
            return step(s, {n: jax.device_put(np.asarray(v)[:half])
                            for n, v in b.items()}, k)
        self.step_fn = halved


@pytest.mark.parametrize("fault", ["capacity_dispatch", "shared_left_out",
                                   "one_expert_frozen", "bias_never_updated",
                                   "half_batch"])
def test_planted_fault_fails(monkeypatch, fault):
    from repro.substrate import moe as moe_lib
    if fault == "capacity_dispatch":
        monkeypatch.setattr(moe_lib, "_assign_groups",
                            _capacity_groups(moe_lib._assign_groups))
    elif fault == "shared_left_out":
        monkeypatch.setattr(moe_lib, "shared_experts",
                            lambda p, x: jnp.zeros_like(x))
    elif fault == "bias_never_updated":
        monkeypatch.setattr(moe_lib, "update_bias",
                            lambda bias, counts, rate: bias)
    elif fault == "half_batch":
        monkeypatch.setattr(LM, "Program", _HalfBatch)
    else:
        monkeypatch.setattr(LM, "Program", _OneExpertFrozen)
    rec = LM.run(ctx())
    bad = failed(rec["checks"])
    assert bad, rec["checks"]
    if fault == "capacity_dispatch":
        assert "held_rows_rel_gap" in bad and "moe_drop_frac" in bad
    if fault == "one_expert_frozen":
        # the expert's own change reads 1 against its reference change
        assert "first_update_leaf_gap" in bad, rec["checks"]
    if fault == "bias_never_updated":
        assert "bias_gap" in bad, rec["checks"]
    if fault == "half_batch":
        # half the tokens route half the rows
        assert "held_rows_rel_gap" in bad, rec["checks"]


def test_expert_least_time_by_hand():
    """At SMALL (d 64, expert width 32, 4 held): 100 rows in one layer,
    3 products x (forward + 2 backward), each compute-bound at 1 FLOP/s
    and 1e9 B/s; then bandwidth-bound at 1e12 FLOP/s and 1 B/s."""
    c = conf()
    flops = 2 * 100 * 64 * 32
    assert lm_flops.expert_least_seconds(c, [100], 1.0, 1e9) == 9 * flops
    bytes_ = 2 * (100 * 64 + 4 * 64 * 32 + 100 * 32)
    assert lm_flops.expert_least_seconds(c, [100, 0], 1e12, 1.0) == \
        pytest.approx(9 * bytes_ + 9 * 2 * 4 * 64 * 32)


def test_step_flops_by_hand():
    """At SMALL: d 64, 4 heads, qk 24, v 16, latent 32, rope 8, dense width
    96, expert width 32, 16 routed and 2 shared, 3 layers (1 dense), vocab
    256; 2 sequences of 96 tokens, 500 held rows over the MoE layers."""
    c = conf()
    mla = 64 * 4 * 24 + 64 * (32 + 8) + 32 * 4 * (16 + 16) + 4 * 16 * 64
    assert mla == 6144 + 2560 + 4096 + 4096
    per_token = (3 * mla + 3 * 64 * 96 + 2 * 64 * 16 + 2 * 3 * 64 * 2 * 32
                 + 64 * 256)
    attention = 2 * 3 * 4 * (96 * 97 // 2) * (24 + 16)
    experts = 500 * 3 * 64 * 32
    want = 6 * (2 * 96 * per_token + attention + experts)
    assert lm_flops.step_flops(c, 2, 96, 500)["total"] == want


def test_scope_reduction_covers_the_step():
    """Over a compiled step's text, every instruction as one op of 1 ns in
    one execution of the step: each scope gets time, every op finds its
    instruction in the map, and the scopes plus the unscoped rest are the
    step's busy time."""
    c = ctx()
    prog = LM.Program(c.conf, dict(c.traffic, seq_len=1280), 5, c.devices)
    text = prog.compiled_text()
    names = lm_scopes.op_scopes(text)
    instrs = sorted(names)
    dev = [[f"%{n} = f32[1]{{0}} fusion(f32[1] %x), kind=kLoop", float(i), 1.0]
           for i, n in enumerate(instrs)]
    n = float(len(dev))
    ex = {"devices": {"/device:TPU:0": dev},
          "modules": {"/device:TPU:0": [["jit_local_step(1)", 0.0, n]]},
          "host": []}
    out = lm_scopes.reduce(ex, (0.0, n), names, trace.patterns())
    d = out["/device:TPU:0"]
    assert d["steps"] == 1 and d["unmapped_s"] == 0.0
    scoped = sum(sum(v.values()) for v in d["scopes"].values())
    assert abs(scoped + d["unscoped_s"] - d["busy_s"]) < 1e-15
    for s in lm_scopes.SCOPES:
        assert d["scopes"][s]["fwd"] > 0 and d["scopes"][s]["bwd"] > 0, s
    assert d["unscoped_s"] < 0.5 * d["busy_s"]


def test_scope_of_reads_wrapped_names():
    J = "jit(local_step)/"
    assert lm_scopes.scope_of(J + "jvp(lm_head)/dot") == ("lm_head", "fwd")
    assert lm_scopes.scope_of(J + "transpose(jvp())/while/body/checkpoint/"
                              "moe/cond/branch_1_fun/checkpoint/"
                              "rematted_computation/experts/ragged_dot") == \
        ("moe/experts", "bwd")
    assert lm_scopes.scope_of(J + "jvp()/while/body/moe/add") == \
        ("moe/combine", "fwd")
    assert lm_scopes.scope_of(J + "jvp()/while/body/mla/checkpoint/dot") == \
        ("mla", "fwd")
    assert lm_scopes.scope_of(J + "add") == (None, None)


# a v5e step's grouped matmul as XLA lowers it: custom calls named
# ``ragged-dot-*`` with no name stack, between ops of the ``experts`` and
# ``combine`` scopes, forward and in the backward pass
_RAGGED_TEXT = """\
%gather.1 = bf16[8,4]{1,0} fusion(bf16[8,4]{1,0} %x), kind=kCustom, metadata={op_name="jit(s)/jvp()/while/body/moe/experts/cond/branch_1_fun/checkpoint/dispatch/gather"}
%ragged-dot-metadata.1 = (s32[9]{0}) custom-call(s32[8]{0} %sizes), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
%ragged-dot-none.1 = bf16[8,4]{1,0} custom-call(%ragged-dot-metadata.1, %gather.1, bf16[2,4,4]{2,1,0} %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
%select.1 = f32[8,4]{1,0} fusion(%ragged-dot-none.1), kind=kLoop, metadata={op_name="jit(s)/jvp()/while/body/moe/experts/cond/branch_1_fun/checkpoint/combine/select_n"}
%ragged-dot-none.2 = bf16[2,4,4]{2,1,0} custom-call(%ragged-dot-metadata.1, %gather.1, %select.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
%add.1 = f32[2,4,4]{2,1,0} add(%ragged-dot-none.2, %ragged-dot-none.2), metadata={op_name="jit(s)/transpose(jvp())/while/body/add_any"}
"""


def test_grouped_matmul_calls_are_under_experts():
    names = lm_scopes.op_scopes(_RAGGED_TEXT)
    assert lm_scopes.scope_of(names["ragged-dot-none.1"]) == \
        ("moe/experts", "fwd")
    assert lm_scopes.scope_of(names["ragged-dot-metadata.1"]) == \
        ("moe/experts", "fwd")
    assert lm_scopes.scope_of(names["ragged-dot-none.2"]) == \
        ("moe/experts", "bwd")
    assert lm_scopes.scope_of(names["select.1"]) == ("moe/combine", "fwd")


@pytest.mark.parametrize("branch_traced", [True, False])
def test_cond_time_counted_once(branch_traced):
    """A ``lax.cond`` of 10 ns whose branch's ops take 6 of them: if the
    trace shows those ops, the cond adds only its 4 uncovered ns; if it
    does not, its 10 ns count under its own scope.  Either way scoped plus
    unscoped is the busy time."""
    J = "jit(s)/jvp()/while/body/moe/"
    names = {"cond.1": J + "experts/cond",
             "fusion.1": J + "experts/cond/branch_1_fun/dispatch/gather",
             "ragged-dot-none.1": J + "experts/cond/branch_1_fun/experts/"
             "ragged_dot",
             "fusion.2": "jit(s)/jvp()/while/body/mla/dot"}
    dev = [["%cond.1 = (f32[8]{0}) conditional(pred[] %p, %a, %b), "
            "branch_computations={%r1, %r2}", 10.0, 10.0],
           ["%fusion.2 = f32[8]{0} fusion(f32[8] %x), kind=kLoop", 0.0, 10.0]]
    if branch_traced:
        dev += [["%fusion.1 = f32[8]{0} fusion(f32[8] %x), kind=kLoop",
                 11.0, 2.0],
                ["%ragged-dot-none.1 = f32[8]{0} custom-call(f32[8] %x)",
                 14.0, 4.0]]
    ex = {"devices": {"/device:TPU:0": dev},
          "modules": {"/device:TPU:0": [["jit_s(1)", 0.0, 20.0]]},
          "host": []}
    d = lm_scopes.reduce(ex, (0.0, 20.0), names, trace.patterns())[
        "/device:TPU:0"]
    sc = {k: sum(v.values()) * 1e9 for k, v in d["scopes"].items()}
    assert d["busy_s"] * 1e9 == pytest.approx(20.0)
    assert d["cond_s"] * 1e9 == pytest.approx(10.0)
    assert sc["mla"] == pytest.approx(10.0)
    if branch_traced:
        assert d["cond_uncovered_s"] * 1e9 == pytest.approx(4.0)
        assert sc["moe/dispatch"] == pytest.approx(2.0)
        assert sc["moe/experts"] == pytest.approx(4.0 + 4.0)
    else:
        assert d["cond_uncovered_s"] * 1e9 == pytest.approx(10.0)
        assert sc["moe/experts"] == pytest.approx(10.0)
    assert sum(sc.values()) * 1e-9 + d["unscoped_s"] == \
        pytest.approx(d["busy_s"])
