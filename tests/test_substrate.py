"""Substrate unit tests: attention math, RoPE, MoE dispatch, norms, FFN."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.substrate import attention as attn_lib
from repro.substrate import layers
from repro.substrate import moe as moe_lib
from repro.substrate.precision import get_policy

RNG = np.random.default_rng(1)


def _randn(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(0, scale, shape), dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,H,KH,D,window", [
    (256, 4, 2, 32, 0), (256, 4, 4, 32, 0), (300, 8, 1, 16, 0),
    (256, 4, 2, 32, 64),
])
def test_blockwise_equals_dot_attention(S, H, KH, D, window):
    q = _randn((2, S, H, D))
    k = _randn((2, S, KH, D))
    v = _randn((2, S, KH, D))
    blk = attn_lib.blockwise_attention(q, k, v, causal=True, window=window,
                                       q_chunk=64, kv_chunk=64)
    ref = attn_lib.dot_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal,q_offset", [(False, 0), (True, 37)])
def test_blockwise_leaves_kv_padding_out(causal, q_offset):
    """T=300 pads the last kv block of 64 with 20 zero keys; queries that
    may see past T (no causal mask, or positions past 300) must still not
    count them.  A Whisper encoder's 1500 frames take this path."""
    S = 300
    q = _randn((2, S, 4, 32))
    k = _randn((2, S, 2, 32))
    v = _randn((2, S, 2, 32))
    qpos = jnp.broadcast_to(q_offset + jnp.arange(S)[None], (2, S))
    blk = attn_lib.blockwise_attention(q, k, v, causal=causal, q_chunk=64,
                                       kv_chunk=64, q_offset=q_offset)
    ref = attn_lib.dot_attention(q, k, v, causal=causal, q_positions=qpos)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,H,KH,D,Dv,window,causal,q_offset", [
    # the four cases of test_blockwise_equals_dot_attention
    (256, 4, 2, 32, 32, 0, True, 0), (256, 4, 4, 32, 32, 0, True, 0),
    (300, 8, 1, 16, 16, 0, True, 0), (256, 4, 2, 32, 32, 64, True, 0),
    # latent attention's head widths: q/k 192, v 128
    (256, 4, 4, 192, 128, 0, True, 0),
    (256, 4, 2, 32, 32, 0, False, 0),
    # last q and kv blocks partial (300 = 4 x 64 + 44)
    (300, 4, 2, 32, 32, 0, True, 0),
    # queries start at 100 (a chunk after a cached prefix)
    (256, 4, 2, 32, 32, 0, True, 100),
])
def test_blockwise_grad_equals_dot_attention(S, H, KH, D, Dv, window, causal,
                                             q_offset):
    """dq, dk, dv of the blockwise path's custom VJP (probabilities rebuilt
    from the saved log-sum-exp) against autodiff of one softmax, float32,
    at the forward test's tolerance."""
    q = _randn((2, S, H, D))
    k = _randn((2, S, KH, D))
    v = _randn((2, S, KH, Dv))
    w = _randn((2, S, H, Dv))
    qpos = jnp.broadcast_to(q_offset + jnp.arange(S)[None], (2, S))

    def blk(q, k, v):
        return jnp.sum(w * attn_lib.blockwise_attention(
            q, k, v, causal=causal, window=window, q_chunk=64, kv_chunk=64,
            q_offset=q_offset))

    def ref(q, k, v):
        return jnp.sum(w * attn_lib.dot_attention(
            q, k, v, causal=causal, window=window, q_positions=qpos))

    got = jax.grad(blk, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for name, g, r in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-5,
                                   rtol=2e-5, err_msg=f"d{name}")


def test_dot_attention_kv_len_masks_cache_tail():
    """Decode semantics: keys beyond kv_len must not contribute."""
    q = _randn((2, 1, 4, 16))
    k = _randn((2, 32, 2, 16))
    v = _randn((2, 32, 2, 16))
    kv_len = jnp.array([8, 16])
    out = attn_lib.dot_attention(q, k, v, causal=False, kv_len=kv_len)
    k2 = k.at[0, 8:].set(99.0).at[1, 16:].set(-99.0)
    v2 = v.at[0, 8:].set(99.0).at[1, 16:].set(-99.0)
    out2 = attn_lib.dot_attention(q, k2, v2, causal=False, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-6)


def test_rope_preserves_norm_and_relative_phase():
    pos = jnp.broadcast_to(jnp.arange(16)[None], (1, 16))
    cos, sin = attn_lib.rope_cos_sin(pos, 32, 10_000.0)
    x = _randn((1, 16, 2, 32))
    r = attn_lib.apply_rope(x, cos, sin)
    # rotation preserves per-pair norm
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(r, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)
    # q.k after rope depends only on relative distance
    q = _randn((1, 1, 1, 32))
    k = _randn((1, 1, 1, 32))
    def dot_at(pq, pk):
        pqv = jnp.full((1, 1), pq)
        pkv = jnp.full((1, 1), pk)
        cq, sq = attn_lib.rope_cos_sin(pqv, 32, 10_000.0)
        ck, sk = attn_lib.rope_cos_sin(pkv, 32, 10_000.0)
        qr = attn_lib.apply_rope(q, cq, sq)
        kr = attn_lib.apply_rope(k, ck, sk)
        return float(jnp.sum(qr * kr))
    assert abs(dot_at(5, 3) - dot_at(12, 10)) < 1e-4
    assert abs(dot_at(5, 3) - dot_at(6, 3)) > 1e-6   # but not absolute


def test_mrope_reduces_to_rope_when_positions_equal():
    """If t/h/w positions coincide, M-RoPE == plain RoPE."""
    B, S, D = 1, 8, 32
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    pos3 = jnp.broadcast_to(pos[None], (3, B, S))
    c1, s1 = attn_lib.rope_cos_sin(pos, D, 10_000.0)
    c3, s3 = attn_lib.mrope_cos_sin(pos3, D, 10_000.0, (4, 6, 6))
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c3), atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s3), atol=1e-6)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


class _MoECfg:
    def __init__(self, **kw):
        from repro.configs.base import ArchConfig, MoEConfig
        self.cfg = ArchConfig(
            arch_id="t", family="moe", n_layers=1, d_model=kw.get("d", 32),
            n_heads=4, n_kv_heads=4, d_ff=64, vocab=128, ffn_type="swiglu",
            moe=MoEConfig(n_experts=kw.get("E", 8), top_k=kw.get("K", 2),
                          d_ff_expert=64,
                          capacity_factor=kw.get("cap", 2.0)))


def test_moe_output_shape_and_finite():
    cfg = _MoECfg().cfg
    p = moe_lib.init_moe(jax.random.key(0), cfg)
    x = _randn((2, 64, cfg.d_model), scale=0.5)
    y, aux, stats = moe_lib.apply_moe(p, x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) >= 0.0
    assert 0.0 <= float(stats["moe_drop_frac"]) <= 1.0


def test_moe_respects_capacity():
    """With capacity_factor ~0, nearly all tokens are dropped -> y ~ 0."""
    cfg = _MoECfg(cap=1e-6).cfg
    p = moe_lib.init_moe(jax.random.key(0), cfg)
    x = _randn((1, 64, cfg.d_model), scale=0.5)
    y, _, stats = moe_lib.apply_moe(p, x, cfg)
    # capacity floor is top_k slots per expert, so a few tokens survive
    assert float(stats["moe_drop_frac"]) > 0.5


def test_moe_uniform_router_balance():
    """With identical tokens every expert sees the same router prob."""
    cfg = _MoECfg(E=4, K=1).cfg
    p = moe_lib.init_moe(jax.random.key(0), cfg)
    p = dict(p, router=jnp.zeros_like(p["router"]))     # uniform router
    x = jnp.ones((1, 64, cfg.d_model)) * 0.1
    _, _, stats = moe_lib.apply_moe(p, x, cfg)
    # load-balance loss at uniform routing equals 1.0 (its minimum)
    assert abs(float(stats["moe_load_balance"]) - 1.0) < 0.05


# ---------------------------------------------------------------------------
# layers / precision
# ---------------------------------------------------------------------------


def test_rmsnorm_unit_scale():
    p = layers.init_norm(64, "rmsnorm")
    x = _randn((4, 64), scale=10.0)
    y = layers.apply_norm(p, x, "rmsnorm")
    rms = np.asarray(jnp.sqrt(jnp.mean(jnp.square(y), axis=-1)))
    np.testing.assert_allclose(rms, 1.0, atol=1e-3)


def test_layernorm_zero_mean_unit_var():
    p = layers.init_norm(64, "layernorm")
    x = _randn((4, 64), scale=3.0) + 5.0
    y = np.asarray(layers.apply_norm(p, x, "layernorm"))
    np.testing.assert_allclose(y.mean(-1), 0.0, atol=1e-4)
    np.testing.assert_allclose(y.std(-1), 1.0, atol=1e-2)


def test_norm_statistics_in_f32_for_bf16_inputs():
    p = layers.init_norm(512, "rmsnorm")
    x = _randn((2, 512), jnp.bfloat16, scale=100.0)
    y = layers.apply_norm(p, x, "rmsnorm")
    assert y.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(y, np.float32)).all()


def test_policy_casts():
    pol = get_policy("bf16")
    tree = {"w": jnp.ones((4,), jnp.float32), "i": jnp.ones((4,), jnp.int32)}
    c = pol.cast_to_compute(tree)
    assert c["w"].dtype == jnp.bfloat16
    assert c["i"].dtype == jnp.int32          # ints untouched
    back = pol.cast_to_param(c)
    assert back["w"].dtype == jnp.float32


@pytest.mark.parametrize("ffn_type", ["swiglu", "gelu", "relu2"])
def test_ffn_types(ffn_type):
    p = layers.init_ffn(jax.random.key(0), 32, 64, ffn_type)
    x = _randn((2, 8, 32))
    y = layers.apply_ffn(p, x, ffn_type)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
