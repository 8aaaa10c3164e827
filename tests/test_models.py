"""Per-architecture smoke tests (deliverable f): every assigned arch
instantiates a REDUCED variant of the same family (<=2 layers, d_model<=512,
<=4 experts) and runs one forward/train step + one decode step on CPU,
asserting output shapes and finiteness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as config_base
from repro.models import api
from repro.optim import optimizers as opt_lib
from repro.substrate.precision import get_policy
from repro.train import steps as steps_lib

POLICY = get_policy("f32")
ARCHS = [a for a in config_base.ARCH_IDS if a != "calo3dgan"]
# archs with a serving path (latent attention has no KV cache yet)
DECODE_ARCHS = [a for a in ARCHS if config_base.get_config(a).decode_supported]
B, S = 2, 128


def _train_batch(cfg):
    rng = np.random.default_rng(0)
    if cfg.family == "audio":
        return {"audio_emb": jnp.asarray(
                    rng.normal(0, 1, (B, S, cfg.d_model)), jnp.float32),
                "tokens": jnp.asarray(
                    rng.integers(0, cfg.vocab, (B, 64)), jnp.int32)}
    if cfg.family == "vlm":
        n_patch = 16
        pos = np.broadcast_to(np.arange(S, dtype=np.int32), (3, B, S))
        return {"tokens": jnp.asarray(
                    rng.integers(0, cfg.vocab, (B, S - n_patch)), jnp.int32),
                "embeds": jnp.asarray(
                    rng.normal(0, 1, (B, n_patch, cfg.d_model)), jnp.float32),
                "positions": jnp.asarray(pos)}
    return {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                  jnp.int32)}


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_config_is_reduced(arch):
    cfg = config_base.reduced_config(arch)
    assert cfg.n_layers <= 2
    assert cfg.d_model <= 512
    if cfg.moe is not None:
        assert cfg.moe.n_experts <= 4


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The registered full config must carry the exact assigned shape."""
    expect = {
        "whisper-base": (6, 512, 8, 8, 2048, 51_865),
        "dbrx-132b": (40, 6144, 48, 8, 10_752, 100_352),
        "qwen2-vl-72b": (80, 8192, 64, 8, 29_568, 152_064),
        "granite-20b": (52, 6144, 48, 1, 24_576, 49_152),
        "nemotron-4-15b": (32, 6144, 48, 8, 24_576, 256_000),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32_000),
        "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50_304),
        "xlstm-125m": (12, 768, 4, 4, 0, 50_304),
        "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151_936),
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200_064),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11_264, 163_840),
    }[arch]
    cfg = config_base.get_config(arch)
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab)
    assert got == expect, (got, expect)
    assert cfg.source        # citation required


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = config_base.reduced_config(arch)
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    # logical axes tree must mirror the params tree exactly
    axes = model.logical_axes(cfg)
    from repro.parallel.sharding import _is_axes_leaf
    n_axes = len(jax.tree.leaves(axes, is_leaf=_is_axes_leaf))
    n_params = len(jax.tree.leaves(params))
    assert n_axes == n_params, (n_axes, n_params)

    opt = opt_lib.adamw(1e-3)
    step = jax.jit(steps_lib.make_train_step(model, cfg, opt, POLICY))
    p2, o2, metrics = step(params, opt.init(steps_lib.trainable(params)),
                           _train_batch(cfg))
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    # params actually changed
    delta = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         params, p2)
    assert max(jax.tree.leaves(delta)) > 0


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_smoke_decode_step(arch):
    cfg = config_base.reduced_config(arch)
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    cache = model.init_cache(cfg, B, 64, jnp.bfloat16)
    extra = {}
    if cfg.mrope:
        extra["positions"] = jnp.zeros((3, B, 1), jnp.int32)
    serve = jax.jit(steps_lib.make_serve_step(model, cfg, POLICY))
    tok = jnp.ones((B, 1), jnp.int32)
    nxt, cache2 = serve(params, tok, cache, jnp.int32(3), extra)
    assert nxt.shape == (B,)
    assert nxt.dtype == jnp.int32
    assert (np.asarray(nxt) >= 0).all() and (np.asarray(nxt) < cfg.vocab).all()
    # cache updated in place structure-wise
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


def test_two_decode_steps_differ_from_one():
    """The cache must actually carry state between steps."""
    cfg = config_base.reduced_config("qwen2-1.5b")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    serve = jax.jit(steps_lib.make_serve_step(model, cfg, POLICY))
    cache0 = model.init_cache(cfg, 1, 16, jnp.bfloat16)
    t = jnp.array([[5]], jnp.int32)
    n1, c1 = serve(params, t, cache0, jnp.int32(0), {})
    # same token at pos 1 with different history in cache
    n2a, _ = serve(params, t, c1, jnp.int32(1), {})
    cache0b = model.init_cache(cfg, 1, 16, jnp.bfloat16)
    n2b, _ = serve(params, jnp.array([[9]], jnp.int32), cache0b,
                   jnp.int32(0), {})
    _, c1b = serve(params, jnp.array([[9]], jnp.int32), cache0b,
                   jnp.int32(0), {})
    n2c, _ = serve(params, t, c1b, jnp.int32(1), {})
    # logits after [5, 5] vs after [9, 5] must differ
    assert int(n2a[0]) != int(n2c[0]) or True   # argmax may coincide...
    # ...so compare the caches' K content instead
    k1 = np.asarray(jax.tree.leaves(c1)[0], np.float32)
    k1b = np.asarray(jax.tree.leaves(c1b)[0], np.float32)
    assert not np.allclose(k1, k1b)


def test_vlm_embeds_prefix_changes_loss():
    cfg = config_base.reduced_config("qwen2-vl-72b")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    batch = _train_batch(cfg)
    l1, _ = model.loss_fn(params, batch, cfg, policy=POLICY)
    batch2 = dict(batch, embeds=batch["embeds"] + 1.0)
    l2, _ = model.loss_fn(params, batch2, cfg, policy=POLICY)
    assert float(l1) != float(l2)


def test_whisper_encoder_memory_feeds_decoder():
    cfg = config_base.reduced_config("whisper-base")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    batch = _train_batch(cfg)
    l1, _ = model.loss_fn(params, batch, cfg, policy=POLICY)
    batch2 = dict(batch, audio_emb=batch["audio_emb"] * 2.0 + 1.0)
    l2, _ = model.loss_fn(params, batch2, cfg, policy=POLICY)
    assert float(l1) != float(l2)


def test_param_counts_match_analytic_estimate():
    """Analytic param_count() within 25% of the real reduced-model count
    (rough head/norm terms tolerated)."""
    for arch in ("qwen2-1.5b", "phi4-mini-3.8b", "olmoe-1b-7b"):
        cfg = config_base.reduced_config(arch)
        model = api.get_model(cfg)
        params = model.init(jax.random.key(0), cfg)
        real = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        est = cfg.param_count()
        assert abs(est - real) / real < 0.25, (arch, est, real)


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "zamba2-1.2b", "xlstm-125m"])
def test_decode_matches_prefill(arch):
    """3 decode steps from a prefilled cache == prefill of the longer
    prompt (the §Perf zamba ring-buffer regression test)."""
    cfg = config_base.reduced_config(arch)
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    logits, cache = model.prefill(params, toks, cfg, policy=POLICY,
                                  max_len=32)
    cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    allt = toks
    for i in range(3):
        allt = jnp.concatenate([allt, cur], axis=1)
        l, cache = model.decode_step(params, cur, cache, jnp.int32(16 + i),
                                     cfg, policy=POLICY)
        cur = jnp.argmax(l[:, -1], -1).astype(jnp.int32)[:, None]
    lb, _ = model.prefill(params, allt, cfg, policy=POLICY, max_len=32)
    err = float(jnp.max(jnp.abs(l[:, -1] - lb[:, -1])))
    assert err < 5e-3, err


def test_microbatched_step_matches_full_batch():
    """Gradient accumulation (§Perf H6) must be numerically equivalent to
    the full-batch step (same grads up to reduction order)."""
    cfg = config_base.reduced_config("qwen2-1.5b")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    opt = opt_lib.adamw(1e-3)
    rng = np.random.default_rng(1)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 64)),
                                   jnp.int32)}
    s1 = jax.jit(steps_lib.make_train_step(model, cfg, opt, POLICY,
                                           microbatches=1))
    s4 = jax.jit(steps_lib.make_train_step(model, cfg, opt, POLICY,
                                           microbatches=4))
    p1, _, m1 = s1(params, opt.init(params), batch)
    p4, _, m4 = s4(params, opt.init(params), batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 5e-3
    d = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p4)))
    assert d < 5e-3, d


def test_whisper_decode_matches_incremental():
    """encdec: two decode steps with the self/cross cache equal the
    teacher-forced decoder run on the same prefix."""
    cfg = config_base.reduced_config("whisper-base")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    audio = jnp.asarray(rng.normal(0, 1, (2, 24, cfg.d_model)), jnp.float32)
    logits0, cache = model.prefill(params, audio, cfg, policy=POLICY)
    # prefill returns (memory, cache) for encdec — adapt
    memory, cache = logits0 if isinstance(logits0, tuple) else (logits0, cache)
    from repro.models import encdec
    cparams = POLICY.cast_to_compute(params)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 3)), jnp.int32)
    # teacher-forced reference over 3 tokens
    mem = encdec.encode(cparams, audio, cfg)
    h = encdec.decode(cparams, toks, mem, cfg)
    ref = (h[:, -1] @ cparams["embed"]["emb"].T).astype(jnp.float32)
    # incremental decode of the same 3 tokens
    l = None
    for i in range(3):
        l, cache = model.decode_step(params, toks[:, i:i + 1], cache,
                                     jnp.int32(i), cfg, policy=POLICY)
    np.testing.assert_allclose(np.asarray(l[:, -1]), np.asarray(ref),
                               atol=5e-3, rtol=5e-2)


def test_moe_topk_all_experts_close_to_dense_average():
    """With top_k == n_experts and uniform router, MoE output equals the
    average of all experts' FFNs (dispatch/combine math sanity)."""
    from repro.configs.base import ArchConfig, MoEConfig
    from repro.substrate import moe as moe_lib
    cfg = ArchConfig(
        arch_id="t", family="moe", n_layers=1, d_model=32, n_heads=4,
        n_kv_heads=4, d_ff=64, vocab=128, ffn_type="swiglu",
        moe=MoEConfig(n_experts=4, top_k=4, d_ff_expert=64,
                      capacity_factor=8.0))
    p = moe_lib.init_moe(jax.random.key(0), cfg)
    p = dict(p, router=jnp.zeros_like(p["router"]))
    x = jnp.asarray(np.random.default_rng(0).normal(0, 0.5, (1, 16, 32)),
                    jnp.float32)
    y, _, stats = moe_lib.apply_moe(p, x, cfg)
    assert float(stats["moe_drop_frac"]) == 0.0
    # manual expert average
    h_all = []
    for e in range(4):
        h = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_in"][e])
        h_all.append(h @ p["w_out"][e])
    ref = sum(h_all) / 4.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
