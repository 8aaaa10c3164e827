"""Attention substrate: GQA projections, RoPE / M-RoPE, blockwise
(flash-style) attention in pure JAX, sliding-window variant, decode step.

The blockwise path is the memory-safe reference used inside jitted train /
prefill steps; kernels/flash_attention provides the Pallas TPU version with
the same semantics (validated against this module's math via ref.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.substrate import layers

# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def rope_cos_sin(positions, d_head: int, theta: float, dtype=jnp.float32):
    """positions: (..., S) int -> cos,sin (..., S, d_head//2)."""
    half = d_head // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def mrope_cos_sin(positions, d_head: int, theta: float, sections, dtype=jnp.float32):
    """M-RoPE (qwen2-vl): positions (3, B, S) for (t, h, w) axes.

    The rotary half-dim is partitioned into ``sections``; frequencies in
    section j rotate by the j-th position axis.
    """
    half = d_head // 2
    assert sum(sections) == half, (sections, half)
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    # section id per frequency index
    sec_id = jnp.concatenate(
        [jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)])
    # choose position row per frequency: (B, S, half)
    pos = positions.astype(jnp.float32)[sec_id, :, :].transpose(1, 2, 0)
    ang = pos * inv_freq
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x, cos, sin):
    """x: (B, S, H, D); cos/sin: (B, S, D//2) -> rotated x (half-split)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------


def init_attn(key, cfg, cross: bool = False):
    ks = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": layers.init_dense(ks[0], d, qd, bias=cfg.qkv_bias),
        "wk": layers.init_dense(ks[1], d, kvd, bias=cfg.qkv_bias),
        "wv": layers.init_dense(ks[2], d, kvd, bias=cfg.qkv_bias),
        "wo": layers.init_dense(ks[3], qd, d, bias=False,
                                scale=0.02 / max(cfg.n_layers, 1) ** 0.5),
    }
    return p


def attn_axes(cfg):
    b = cfg.qkv_bias
    return {
        "wq": layers.dense_axes("embed", "heads", bias=b),
        "wk": layers.dense_axes("embed", "kv_heads", bias=b),
        "wv": layers.dense_axes("embed", "kv_heads", bias=b),
        "wo": layers.dense_axes("heads", "embed"),
    }


def project_qkv(p, x, cfg):
    B, S, _ = x.shape
    q = layers.apply_dense(p["wq"], x).reshape(B, S, cfg.n_heads, cfg.d_head)
    k = layers.apply_dense(p["wk"], x).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    v = layers.apply_dense(p["wv"], x).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    return q, k, v


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention — pure JAX
# ---------------------------------------------------------------------------


def _attend_block(q, k, v, q_off, k_off, causal, window, scale):
    """One (q-block, kv-block) tile with f32 score math.

    q: (B, qc, KH, G, D)  k/v: (B, kc, KH, D) -> out (unnormalised), m, l.
    """
    s = jnp.einsum("bqkgd,bckd->bkgqc", q, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = q_off + jnp.arange(q.shape[1])
    kpos = k_off + jnp.arange(k.shape[1])
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)                                   # (B,KH,G,qc)
    # guard fully-masked rows
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(s - m_safe[..., None])
    e = jnp.where(mask[None, None, None], e, 0.0)
    l = jnp.sum(e, axis=-1)
    o = jnp.einsum("bkgqc,bckd->bqkgd", e.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m_safe, l


def blockwise_attention(q, k, v, *, causal=True, window=0,
                        q_chunk=512, kv_chunk=512, q_offset=0,
                        remat_blocks=False):
    """Memory-bounded attention with online softmax.

    q: (B, S, H, D); k/v: (B, T, KH, D). GQA via head grouping.
    Python loop over q blocks (static causal kv extent -> exact FLOPs),
    lax.scan over kv blocks (O(1) HLO in T).  ``remat_blocks``
    rematerialises each q block, so a backward pass holds one block's
    scores at a time instead of every block's.
    """
    B, S, H, D = q.shape
    T, KH = k.shape[1], k.shape[2]
    Dv = v.shape[-1]            # latent attention: v heads narrower than q/k
    G = H // KH
    scale = 1.0 / (D ** 0.5)
    q = q.reshape(B, S, KH, G, D)
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    # pad kv to a block multiple so dynamic_slice never clamps (the valid
    # mask below zeroes the padded tail)
    t_pad = (-T) % kv_chunk
    if t_pad:
        k = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    n_q = -(-S // q_chunk)
    outs = []
    for qi in range(n_q):
        q_off = q_offset + qi * q_chunk
        qlen = min(q_chunk, S - qi * q_chunk)
        qb = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, qlen, axis=1)
        # causal: kv blocks beyond the end of this q block contribute nothing
        if causal:
            k_hi = min(T, q_off + qlen)
        else:
            k_hi = T
        if window and causal:
            k_lo = max(0, (q_off - window + 1) // kv_chunk * kv_chunk)
        else:
            k_lo = 0
        n_kv = max(1, -(-(k_hi - k_lo) // kv_chunk))

        def q_block(qb, k, v, q_off=q_off, k_lo=k_lo, qlen=qlen,
                    n_kv=n_kv):
            def body(carry, ki):
                acc, m, l = carry
                k_off = k_lo + ki * kv_chunk
                kb = jax.lax.dynamic_slice_in_dim(k, k_off, kv_chunk, axis=1)
                vb = jax.lax.dynamic_slice_in_dim(v, k_off, kv_chunk, axis=1)
                # mask out positions past T (dynamic_slice clamps, so re-mask)
                kpos = k_off + jnp.arange(kv_chunk)
                valid = kpos < T
                o_b, m_b, l_b = _attend_block(
                    qb, jnp.where(valid[None, :, None, None], kb, 0),
                    jnp.where(valid[None, :, None, None], vb, 0),
                    q_off, k_off, causal, window, scale)
                l_b = jnp.where(valid.any(), l_b, 0.0)
                m_new = jnp.maximum(m, m_b)
                a1 = jnp.exp(m - m_new)
                a2 = jnp.exp(m_b - m_new)
                acc = (acc * a1[..., None]
                       + o_b.transpose(0, 2, 3, 1, 4) * a2[..., None])
                l = l * a1 + l_b * a2
                return (acc, m_new, l), None

            acc0 = jnp.zeros((B, KH, G, qlen, Dv), jnp.float32)
            # m must start finite for exp(m - m_new): large negative, not -inf
            m0 = jnp.full((B, KH, G, qlen), -1e30)
            l0 = jnp.zeros((B, KH, G, qlen), jnp.float32)
            (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                          jnp.arange(n_kv))
            return acc / jnp.maximum(l[..., None], 1e-30)

        if remat_blocks:
            q_block = jax.checkpoint(q_block)
        o = q_block(qb, k, v)
        outs.append(o.transpose(0, 3, 1, 2, 4).reshape(B, qlen, H, Dv))
    return jnp.concatenate(outs, axis=1).astype(v.dtype) if len(outs) > 1 \
        else outs[0].astype(v.dtype)


def attend(q, k, v, *, causal=True, window=0, use_pallas=False,
           seq_len=None, kv_len=None, q_offset=None):
    """Training/prefill/decode attention router shared by the model zoo.

    ``use_pallas=True`` routes to the flash-attention Pallas kernels
    (forward AND backward; block sizes from the shared autotune
    registry).  The pure-JAX fallback picks ``dot_attention`` for short
    sequences and ``blockwise_attention`` beyond 1k, as before.

    A non-None ``kv_len`` (per-row (B,) live cache lengths) selects the
    SERVING branch: single-query calls (S=1, no ``q_offset``) hit the
    split-KV flash-decode kernel; chunked prefill calls pass ``q_offset``
    (per-row (B,) absolute position of the chunk's first query) and hit
    the offset-aware chunk kernel.  The pure-JAX serving fallback is
    ``dot_attention`` with the matching ragged masks.
    """
    if kv_len is not None:
        if use_pallas:
            if q.shape[1] == 1 and q_offset is None:
                from repro.kernels.flash_attention.decode import flash_decode
                return flash_decode(q, k, v, kv_len, window=window)
            from repro.kernels.flash_attention.flash_attention import (
                flash_attention_chunk)
            off = q_offset if q_offset is not None \
                else jnp.maximum(kv_len - 1, 0)
            return flash_attention_chunk(q, k, v, off, kv_len, window=window)
        if q_offset is None:
            return dot_attention(q, k, v, causal=False, window=window,
                                 kv_len=kv_len)
        qpos = q_offset[:, None] + jnp.arange(q.shape[1])[None]
        return dot_attention(q, k, v, causal=causal, window=window,
                             kv_len=kv_len, q_positions=qpos)
    S = q.shape[1] if seq_len is None else seq_len
    if use_pallas:
        from repro.kernels.flash_attention.ops import flash_attention
        return flash_attention(q, k, v, causal, window)
    if S <= 1024:
        return dot_attention(q, k, v, causal=causal, window=window)
    return blockwise_attention(q, k, v, causal=causal, window=window)


def dot_attention(q, k, v, *, causal=True, window=0, kv_len=None, q_positions=None):
    """Plain O(S*T)-memory attention for short sequences / decode.

    kv_len: (B,) valid cache lengths (decode); q_positions: (B,S) absolute
    positions of queries (for causal masking against a cache).
    """
    B, S, H, D = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    qg = q.reshape(B, S, KH, G, D)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k,
                   preferred_element_type=jnp.float32) / (D ** 0.5)
    kpos = jnp.arange(T)
    mask = jnp.ones((B, S, T), bool)
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if causal:
        mask &= q_positions[:, :, None] >= kpos[None, None, :]
    if window:
        mask &= kpos[None, None, :] > q_positions[:, :, None] - window
    if kv_len is not None:
        mask &= kpos[None, None, :] < kv_len[:, None, None]
    s = jnp.where(mask[:, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqt,btkd->bqkgd", w, v, preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, v.shape[-1]).astype(v.dtype)


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), no q-LoRA
# ---------------------------------------------------------------------------


def init_mla(key, cfg):
    a, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": layers.init_dense(ks[0], d, H * a.qk_head_dim),
        "wkv_a": layers.init_dense(ks[1], d,
                                   a.kv_lora_rank + a.qk_rope_head_dim),
        "kv_norm": layers.init_norm(a.kv_lora_rank),
        "wkv_b": layers.init_dense(
            ks[2], a.kv_lora_rank, H * (a.qk_nope_head_dim + a.v_head_dim)),
        "wo": layers.init_dense(ks[3], H * a.v_head_dim, d,
                                scale=0.02 / max(cfg.n_layers, 1) ** 0.5),
    }


def mla_axes(cfg):
    return {
        "wq": layers.dense_axes("embed", "heads"),
        "wkv_a": layers.dense_axes("embed", None),
        "kv_norm": layers.norm_axes(),
        "wkv_b": layers.dense_axes(None, "heads"),
        "wo": layers.dense_axes("heads", "embed"),
    }


def apply_mla(p, x, cos, sin, cfg):
    """x: (B, S, d) -> (B, S, d), causal.

    q = x W_q splits per head into q_nope and q_pe; [c_kv, k_pe] =
    x W_kv_a with c_kv RMS-normed; [k_nope, v] per head = c_kv W_kv_b.
    RoPE (``cos``/``sin`` of width qk_rope_head_dim / 2) turns q_pe and
    the one k_pe that every head shares.  Scores are
    (q_nope.k_nope + q_pe.k_pe) / sqrt(qk_head_dim).  RoPE here rotates
    the two halves of the rope dims (``apply_rope``) where DeepSeek's code
    rotates interleaved pairs: the same map up to a fixed permutation of
    W_q's and W_kv_a's rope columns."""
    a, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    with jax.named_scope("mla"):
        q = layers.apply_dense(p["wq"], x).reshape(B, S, H, a.qk_head_dim)
        q_nope, q_pe = jnp.split(q, [a.qk_nope_head_dim], axis=-1)
        kv_a = layers.apply_dense(p["wkv_a"], x)
        c_kv, k_pe = jnp.split(kv_a, [a.kv_lora_rank], axis=-1)
        c_kv = layers.apply_norm(p["kv_norm"], c_kv)
        kv = layers.apply_dense(p["wkv_b"], c_kv).reshape(
            B, S, H, a.qk_nope_head_dim + a.v_head_dim)
        k_nope, v = jnp.split(kv, [a.qk_nope_head_dim], axis=-1)
        q_pe = apply_rope(q_pe, cos, sin)
        k_pe = apply_rope(k_pe[:, :, None, :], cos, sin)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (B, S, H, a.qk_rope_head_dim))],
            axis=-1)
        if S <= 1024:
            o = dot_attention(q, k, v, causal=True)
        else:
            o = blockwise_attention(q, k, v, causal=True, remat_blocks=True)
        return layers.apply_dense(p["wo"], o.reshape(B, S, H * a.v_head_dim))
