"""moonlight-16b-a3b at ``reduced()`` size on seeded random weights: the
program against a plain float32 reference written here from the
published equations (DeepSeek-V2 arXiv:2405.04434 for latent attention,
DeepSeek-V3 arXiv:2412.19437 for routing), latent attention against a
per-head naive attention, dropless dispatch, the expert-parallel shares,
the correction bias, and the launcher."""
import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as config_base
from repro.configs import moonlight_16b_a3b as ml
from repro.models import api, lm
from repro.optim import optimizers as opt_lib
from repro.substrate import attention as attn_lib
from repro.substrate import moe as moe_lib
from repro.substrate.precision import get_policy
from repro.train import engine as engine_lib
from repro.train import steps as steps_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = get_policy("f32")
HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Plain float32 reference
# ---------------------------------------------------------------------------


def _rms(x, scale, eps=1e-5):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rot(x, pos, theta):
    """RoPE on the last axis, rotating its two halves: x (S, D)."""
    half = x.shape[-1] // 2
    freq = theta ** (-np.arange(half) / half)
    ang = pos[:, None] * freq[None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def ref_mla(p, x, cfg):
    """One sequence x (S, d), each head on its own."""
    a, H = cfg.mla, cfg.n_heads
    S = x.shape[0]
    pos = np.arange(S, dtype=np.float64)
    q = jnp.dot(x, p["wq"]["w"], precision=HI).reshape(S, H, a.qk_head_dim)
    kv_a = jnp.dot(x, p["wkv_a"]["w"], precision=HI)
    c_kv = _rms(kv_a[:, :a.kv_lora_rank], p["kv_norm"]["scale"])
    k_pe = _rot(kv_a[:, a.kv_lora_rank:], pos, cfg.rope_theta)
    kv = jnp.dot(c_kv, p["wkv_b"]["w"], precision=HI).reshape(
        S, H, a.qk_nope_head_dim + a.v_head_dim)
    mask = np.tril(np.ones((S, S), bool))
    heads = []
    for h in range(H):
        q_nope = q[:, h, :a.qk_nope_head_dim]
        q_pe = _rot(q[:, h, a.qk_nope_head_dim:], pos, cfg.rope_theta)
        k_nope = kv[:, h, :a.qk_nope_head_dim]
        v = kv[:, h, a.qk_nope_head_dim:]
        s = (jnp.dot(q_nope, k_nope.T, precision=HI)
             + jnp.dot(q_pe, k_pe.T, precision=HI)) / np.sqrt(a.qk_head_dim)
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        heads.append(jnp.dot(w, v, precision=HI))
    return jnp.dot(jnp.concatenate(heads, axis=-1), p["wo"]["w"], precision=HI)


def _swiglu(p, x):
    g = jnp.dot(x, p["w_gate"], precision=HI)
    return jnp.dot(g / (1 + jnp.exp(-g)) * jnp.dot(x, p["w_in"], precision=HI),
                   p["w_out"], precision=HI)


def ref_moe(p, x, bias, cfg):
    """One sequence x (S, d): (held experts' part + shared, balance loss,
    rows routed to held experts)."""
    m = cfg.moe
    S = x.shape[0]
    scores = 1 / (1 + jnp.exp(-jnp.dot(x, p["router"], precision=HI)))
    _, ids = jax.lax.top_k(scores + bias, m.top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    gates = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * m.routed_scale
    y = jnp.zeros_like(x)
    rows = 0
    for j in range(m.n_experts):
        e = m.expert_offset + j
        w = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)
        y = y + w[:, None] * _swiglu({k: p[k][j] for k in
                                      ("w_gate", "w_in", "w_out")}, x)
        rows += int(jnp.sum(ids == e))
    y = y + _swiglu(p["shared"], x)
    R, K = m.n_router, m.top_k
    f = R / (K * S) * np.sum(np.eye(R)[np.asarray(ids)], axis=(0, 1))
    P = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    return y, m.seq_balance_loss * jnp.sum(f * P), rows


def ref_forward(params, tokens, cfg):
    """Logits (B, S, V), mean next-token loss + balance loss, and the
    rows routed to held experts per MoE layer."""
    dense = dataclasses.replace(cfg, moe=None)
    layers = ([(dense, jax.tree.map(lambda a: a[i], params["dense_blocks"]),
                None) for i in range(cfg.first_k_dense)]
              + [(cfg, jax.tree.map(lambda a: a[i], params["blocks"]),
                  params["buffers"]["router_bias"][i])
                 for i in range(cfg.n_layers - cfg.first_k_dense)])
    logits, ce, bal = [], 0.0, 0.0
    rows = np.zeros(cfg.n_layers - cfg.first_k_dense, np.int64)
    for b in range(tokens.shape[0]):
        x = params["embed"]["emb"][tokens[b]]
        for li, (c, p, bias) in enumerate(layers):
            x = x + ref_mla(p["attn"], _rms(x, p["ln1"]["scale"]), c)
            h = _rms(x, p["ln2"]["scale"])
            if c.moe is None:
                x = x + _swiglu(p["ffn"], h)
            else:
                y, lb, r = ref_moe(p["moe"], h, bias, c)
                x, bal = x + y, bal + lb
                rows[li - cfg.first_k_dense] += r
        lg = jnp.dot(_rms(x, params["ln_f"]["scale"]), params["head"]["w"],
                     precision=HI)
        logits.append(lg)
        lp = jax.nn.log_softmax(lg[:-1], axis=-1)
        ce = ce + jnp.sum(-jnp.take_along_axis(
            lp, tokens[b, 1:, None], axis=-1))
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    return jnp.stack(logits), ce / n + bal / tokens.shape[0], rows


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def _setup(cfg=None, seed=0, B=2, S=64):
    cfg = cfg or ml.reduced()
    params = lm.init(jax.random.key(seed), cfg)
    # a non-zero bias, so selection by score + bias is exercised
    params["buffers"]["router_bias"] = 0.05 * jax.random.normal(
        jax.random.key(seed + 1), params["buffers"]["router_bias"].shape)
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab, (B, S)), jnp.int32)
    return cfg, params, toks


def _program_logits(params, toks, cfg):
    h, _, _, cp = lm.forward(params, toks, cfg, policy=F32)
    return jnp.dot(h, cp["head"]["w"], precision=HI)


# ---------------------------------------------------------------------------
# Tests
# ---------------------------------------------------------------------------


def test_published_config():
    cfg = config_base.get_config("moonlight-16b-a3b")
    a, m = cfg.mla, cfg.moe
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab,
            cfg.first_k_dense, cfg.rope_theta) == (
        27, 2048, 16, 11264, 163840, 1, 50000.0)
    assert (a.kv_lora_rank, a.qk_nope_head_dim, a.qk_rope_head_dim,
            a.v_head_dim) == (512, 128, 64, 128)
    assert (m.n_router, m.n_experts, m.top_k, m.d_ff_expert,
            m.n_shared_experts, m.routed_scale) == (64, 64, 6, 1408, 2, 2.446)
    # the cell's share: the hand count of the configuration file
    share = ml.config(n_layers=5, n_held=8, vocab=20480)
    assert abs(share.param_count() - 568.48e6) < 0.01e6
    params = jax.eval_shape(lambda: lm.init(jax.random.key(0), share))
    real = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        steps_lib.trainable(params)))
    assert real == share.param_count() + share.d_model    # + ln_f


def test_logits_loss_and_gradients_match_float32_reference():
    """Tolerances: program and reference both run float32 (matmuls at
    HIGHEST), so only summation order differs — 1e-4 relative on logits
    and loss; gradients within 1e-3 of each leaf's norm (the backward
    sums over more terms).  Routing is the same selection in both: a
    flipped choice would move a logit by far more."""
    cfg, params, toks = _setup()
    model = api.get_model(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, metrics), g = jax.value_and_grad(
            lambda p: model.loss_fn(dict(p, buffers=params["buffers"]),
                                    {"tokens": toks}, cfg, policy=F32),
            has_aux=True)(steps_lib.trainable(params))
        logits = _program_logits(params, toks, cfg)
    ref_logits, ref_loss, rows = ref_forward(params, toks, cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-4, atol=1e-4 * float(
                                   jnp.max(jnp.abs(ref_logits))))
    assert abs(float(loss) - float(ref_loss)) <= 1e-4 * abs(float(ref_loss))
    np.testing.assert_array_equal(np.asarray(metrics["moe_rows_held"]), rows)
    g_ref = jax.grad(lambda p: ref_forward(
        dict(p, buffers=params["buffers"]), toks, cfg)[1])(
            steps_lib.trainable(params))
    flat, _ = jax.tree_util.tree_flatten_with_path(g)
    ref = dict(jax.tree_util.tree_flatten_with_path(g_ref)[0])
    for path, leaf in flat:
        r = np.asarray(ref[path])
        err = np.linalg.norm(np.asarray(leaf) - r)
        assert err <= 1e-3 * max(np.linalg.norm(r), 1e-12), (
            jax.tree_util.keystr(path), err, np.linalg.norm(r))


@pytest.mark.parametrize("S", [64, 1280])
def test_mla_matches_per_head_naive_attention(S):
    """S=64 takes the dense path, S=1280 the blockwise path with its
    custom VJP.  Tolerance 1e-4 relative: float32 both sides, online
    softmax against one softmax."""
    cfg = ml.reduced()
    p = attn_lib.init_mla(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (1, S, cfg.d_model))
    pos = jnp.arange(S)[None]
    cos, sin = attn_lib.rope_cos_sin(pos, cfg.mla.qk_rope_head_dim,
                                     cfg.rope_theta)
    with jax.default_matmul_precision("highest"):
        got = attn_lib.apply_mla(p, x, cos, sin, cfg)
        # through a gradient too: the blockwise path's own backward
        g = jax.grad(lambda xx: jnp.sum(
            attn_lib.apply_mla(p, xx, cos, sin, cfg) ** 2))(x)
    want = ref_mla(p, x[0], cfg)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-4 * scale)
    g_ref = jax.grad(lambda xx: jnp.sum(ref_mla(p, xx, cfg) ** 2))(x[0])
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(g_ref),
                               atol=1e-4 * float(jnp.max(jnp.abs(g_ref))))


_HLO_OPCODE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][\w-]*)\(")


def test_mla_gradient_ops_land_under_mla_in_the_backward_pass():
    """The blockwise path's custom VJP inside the layer's checkpoint, as
    ``models/lm._mla_stack`` runs it, compiled at S=1280: through the
    benchmark's ``lm_scopes.scope_of``, every op of the backward pass (the
    rematerialised forward and the VJP's tile loops) reads ``mla``/``bwd``,
    every op of an attention loop (a ``while`` body) reads ``mla``, and the
    backward's tile matmuls are among them."""
    bench = os.path.join(REPO, "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import lm_scopes

    cfg = ml.reduced()
    S = 1280
    p = attn_lib.init_mla(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (1, S, cfg.d_model))
    cos, sin = attn_lib.rope_cos_sin(jnp.arange(S)[None],
                                     cfg.mla.qk_rope_head_dim, cfg.rope_theta)
    layer = jax.checkpoint(lambda p, x: attn_lib.apply_mla(p, x, cos, sin,
                                                           cfg))
    grad = jax.jit(jax.grad(lambda p, x: jnp.sum(layer(p, x) ** 2),
                            argnums=(0, 1)))
    text = grad.lower(p, x).compile().as_text()
    names = lm_scopes.op_scopes(text)
    opcode = {m.group(1): m.group(2) for m in map(_HLO_OPCODE.match,
                                                  text.splitlines()) if m}
    bwd = {n: op for n, op in names.items() if "rematted_computation" in op}
    assert bwd
    assert {op for op in bwd.values()
            if lm_scopes.scope_of(op) != ("mla", "bwd")} == set()
    loops = {op for op in names.values() if "/while/" in op}
    assert loops
    assert {op for op in loops if lm_scopes.scope_of(op)[0] != "mla"} == set()
    tile_dots = [n for n, op in bwd.items()
                 if opcode.get(n) == "dot" and "/while/" in op]
    assert len(tile_dots) >= 5          # s, dV, dP, dK, dQ of a tile


def _chunked_cfg():
    """32 routed experts, 8 held: the held pairs take two chunks of
    dispatch rows when every pair goes to a held expert."""
    base = ml.reduced()
    return dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, router_experts=32, n_experts=8, expert_offset=8))


def test_no_pair_dropped_when_routing_forces_every_pair_onto_held_experts():
    """Every token's 6 picks forced onto the 8 held experts (a large bias
    on them): every pair is computed, across both dispatch chunks, and
    the layer equals the reference's dense loop.  Tolerance 1e-4
    relative: float32 both sides."""
    cfg = _chunked_cfg()
    m = cfg.moe
    p = moe_lib.init_grouped(jax.random.key(5), cfg)
    x = jax.random.normal(jax.random.key(6), (1, 96, cfg.d_model))
    bias = jnp.zeros((m.n_router,)).at[8:16].set(100.0)
    with jax.default_matmul_precision("highest"):
        y, _, stats = moe_lib.apply_grouped(p, x, bias, cfg)
    assert int(stats["moe_rows_held"]) == 96 * m.top_k
    assert float(stats["moe_drop_frac"]) == 0.0
    want, _, rows = ref_moe(p, x[0], bias, cfg)
    assert rows == 96 * m.top_k
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               atol=1e-4 * float(jnp.max(jnp.abs(want))))


def test_capacity_dispatch_drops_where_grouped_dispatch_does_not():
    """The old path at capacity factor 1.0 drops pairs on the same
    skewed routing that the grouped path computes in full."""
    cfg = dataclasses.replace(ml.reduced(), moe=config_base.MoEConfig(
        n_experts=8, top_k=6, d_ff_expert=128, capacity_factor=1.0))
    p = moe_lib.init_moe(jax.random.key(5), cfg)
    p["router"] = p["router"].at[:, 0].add(1.0)
    x = jax.random.normal(jax.random.key(6), (1, 96, cfg.d_model))
    _, _, stats = moe_lib.apply_moe(p, x, cfg)
    assert float(stats["moe_drop_frac"]) > 0


def test_shares_of_held_experts_sum_to_the_uncut_layer():
    """model-configs §4: the routed parts of 4 disjoint shares of 2
    experts, with the shared experts counted once, equal the layer that
    holds all 8.  Tolerance 1e-5 relative: the same float32 matmuls,
    added in another order."""
    whole = ml.reduced(n_held=8)
    p = moe_lib.init_grouped(jax.random.key(7), whole)
    x = jax.random.normal(jax.random.key(8), (2, 48, whole.d_model))
    bias = 0.05 * jax.random.normal(jax.random.key(9), (8,))
    y_all, bal_all, st_all = moe_lib.apply_grouped(p, x, bias, whole)
    shared = lm.layers.apply_ffn(p["shared"], x, "swiglu")
    total, rows = shared, 0
    for i in range(4):
        cut = ml.reduced(n_held=2, offset=2 * i)
        sp = dict(p, **{k: p[k][2 * i:2 * i + 2]
                        for k in ("w_gate", "w_in", "w_out")})
        y, bal, st = moe_lib.apply_grouped(sp, x, bias, cut)
        total = total + (y - shared)
        rows += int(st["moe_rows_held"])
        assert float(bal) == float(bal_all)     # the router is whole on each
        np.testing.assert_array_equal(np.asarray(st["moe_tokens_per_expert"]),
                                      np.asarray(st_all["moe_tokens_per_expert"]))
    assert rows == int(st_all["moe_rows_held"]) == 2 * 48 * 6
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_all),
                               atol=1e-5 * float(jnp.max(jnp.abs(y_all))))


def test_correction_bias_update_and_its_absence_from_adamw():
    """b_i += gamma * sign(mean load - load_i) after each step, from the
    step's own counts; AdamW holds no moments for it and no gradient
    reaches it."""
    counts = jnp.array([[3, 1, 2, 2]])
    np.testing.assert_allclose(
        np.asarray(moe_lib.update_bias(jnp.zeros((1, 4)), counts, 1e-3)),
        [[-1e-3, 1e-3, 0.0, 0.0]])
    cfg, params, toks = _setup()
    model = api.get_model(cfg)
    task = engine_lib.lm_task(model, cfg, opt_lib.adamw(ml.LR),
                              policy=F32)
    state = task.init(jax.random.key(0))
    assert "buffers" in state.params
    for moments in ("m", "v"):
        assert "buffers" not in state.opt_state[moments]
    state = engine_lib.LMState(params, opt_lib.adamw(ml.LR).init(
        steps_lib.trainable(params)))
    step = jax.jit(task.make_step())
    new, metrics = step(state, {"tokens": toks}, jax.random.key(0))
    want = moe_lib.update_bias(params["buffers"]["router_bias"],
                               metrics["moe_tokens_per_expert"],
                               cfg.moe.bias_update_rate)
    np.testing.assert_allclose(np.asarray(new.params["buffers"]["router_bias"]),
                               np.asarray(want))
    moved = np.abs(np.asarray(new.params["buffers"]["router_bias"]
                              - params["buffers"]["router_bias"]))
    assert np.all((np.isclose(moved, 1e-3)) | (moved == 0))
    g = jax.grad(lambda b: model.loss_fn(
        dict(params, buffers={"router_bias": b}), {"tokens": toks}, cfg,
        policy=F32)[0])(params["buffers"]["router_bias"])
    assert float(jnp.max(jnp.abs(g))) == 0.0


def test_launcher_trains_moonlight_through_the_engine(tmp_path):
    """``launch/train.py --arch moonlight-16b-a3b --reduced``: the custom
    loop of ``train/engine.lm_task``, with a falling loss."""
    log = tmp_path / "log.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "moonlight-16b-a3b", "--reduced", "--steps", "12", "--batch", "4",
         "--seq", "64", "--lr", "3e-3", "--loop", "custom", "--log",
         str(log)], env=env, cwd=REPO, capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    losses = [json.loads(line)["loss"] for line in log.read_text().splitlines()]
    assert len(losses) == 12 and all(np.isfinite(losses))
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) - 0.1, losses
