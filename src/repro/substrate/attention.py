"""Attention substrate: GQA projections, RoPE / M-RoPE, blockwise
(flash-style) attention in pure JAX, sliding-window variant, decode step.

The blockwise path is the memory-safe reference used inside jitted train /
prefill steps; kernels/flash_attention provides the Pallas TPU version with
the same semantics (validated against this module's math via ref.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

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


def _attend_block(q, k, v, mask, scale):
    """One (q-block, kv-block) tile with f32 score math.

    q: (B, qc, KH, G, D)  k/v: (B, kc, KH, D)  mask: (qc, kc) -> out
    (unnormalised), m, l.
    """
    s = jnp.einsum("bqkgd,bckd->bkgqc", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)                                   # (B,KH,G,qc)
    # guard fully-masked rows
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(s - m_safe[..., None])
    e = jnp.where(mask, e, 0.0)
    l = jnp.sum(e, axis=-1)
    o = jnp.einsum("bkgqc,bckd->bqkgd", e.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m_safe, l


def _tile_mask(qpos, kpos, T, causal, window):
    """(len(qpos), len(kpos)) bool of one tile's counted pairs (keys past T
    are the kv padding)."""
    qpos, kpos = qpos[:, None], kpos[None, :]
    mask = jnp.broadcast_to(kpos < T, (qpos.shape[0], kpos.shape[1]))
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= kpos > qpos - window
    return mask


class _Tiling(NamedTuple):
    """Static arguments of the blockwise path: chunks already cut to S, T."""
    causal: bool
    window: int
    q_chunk: int
    kv_chunk: int
    q_offset: int


def blockwise_attention(q, k, v, *, causal=True, window=0,
                        q_chunk=512, kv_chunk=512, q_offset=0):
    """Memory-bounded attention with online softmax.

    q: (B, S, H, D); k/v: (B, T, KH, D); v may be narrower than q/k.
    GQA via head grouping.  Forward: a Python loop over q blocks (static
    causal kv extent -> exact FLOPs), lax.scan over kv blocks (O(1) HLO
    in T).  Backward (a custom VJP): each tile's probabilities are rebuilt
    from the forward's per-row log-sum-exp, so residuals are O(S) and no
    score tile is saved; dK/dV accumulate per kv block over the q blocks
    that see it (scanned), dQ in place in its q block.
    """
    B, S, H, D = q.shape
    T, KH = k.shape[1], k.shape[2]
    t = _Tiling(bool(causal), int(window), min(q_chunk, S),
                min(kv_chunk, T), int(q_offset))
    o = _flash(q.reshape(B, S, KH, H // KH, D), k, v, t)
    return o.reshape(B, S, H, v.shape[-1])


def _pad_axis(x, axis, n, value=0):
    """``x`` with ``n`` entries of ``value`` appended along ``axis``."""
    if not n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n)
    return jnp.pad(x, pad, constant_values=value)


def _flash_forward(q, k, v, t: _Tiling):
    """q: (B, S, KH, G, D); k/v: (B, T, KH, D[v]) -> out (B, S, KH, G, Dv)
    f32 and the log-sum-exp of each row's scaled scores (B, KH, G, S) f32,
    +inf on a row that sees no key (its probabilities then rebuild as 0)."""
    B, S, KH, G, D = q.shape
    T, Dv = k.shape[1], v.shape[-1]
    scale = 1.0 / (D ** 0.5)
    # pad kv to a block multiple so dynamic_slice never clamps (the tile
    # mask leaves the padded tail out)
    k = _pad_axis(k, 1, (-T) % t.kv_chunk)
    v = _pad_axis(v, 1, (-T) % t.kv_chunk)
    # positions built outside the kv scans, so that no loop-invariant op
    # is hoisted out of the caller's named scope
    kidx = jnp.arange(t.kv_chunk)
    outs, lses = [], []
    for qi in range(-(-S // t.q_chunk)):
        q_off = t.q_offset + qi * t.q_chunk
        qlen = min(t.q_chunk, S - qi * t.q_chunk)
        qb = jax.lax.dynamic_slice_in_dim(q, qi * t.q_chunk, qlen, axis=1)
        # causal: kv blocks beyond the end of this q block contribute nothing
        k_hi = min(T, q_off + qlen) if t.causal else T
        k_lo = max(0, (q_off - t.window + 1) // t.kv_chunk * t.kv_chunk) \
            if t.window and t.causal else 0
        n_kv = max(1, -(-(k_hi - k_lo) // t.kv_chunk))
        qpos = q_off + jnp.arange(qlen)

        def body(carry, ki, qb=qb, qpos=qpos, k_lo=k_lo):
            acc, m, l = carry
            k_off = k_lo + ki * t.kv_chunk
            o_b, m_b, l_b = _attend_block(
                qb, jax.lax.dynamic_slice_in_dim(k, k_off, t.kv_chunk, 1),
                jax.lax.dynamic_slice_in_dim(v, k_off, t.kv_chunk, 1),
                _tile_mask(qpos, k_off + kidx, T, t.causal, t.window),
                scale)
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
        outs.append((acc / jnp.maximum(l[..., None], 1e-30))
                    .transpose(0, 3, 1, 2, 4))
        lses.append(jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                              jnp.inf))
    return jnp.concatenate(outs, axis=1), jnp.concatenate(lses, axis=-1)


def _q_runs(j, S, T, t: _Tiling):
    """The q blocks whose tiles with kv block ``j`` hold a counted pair, as
    runs [lo, hi) with whether the tile needs a mask (it crosses the
    diagonal or the window edge, or holds kv padding).  A masked tile is a
    run of its own; the tiles between need none."""
    ka = j * t.kv_chunk
    kz = min(T, ka + t.kv_chunk) - 1                  # last key held
    runs = []
    for i in range(-(-S // t.q_chunk)):
        qa = t.q_offset + i * t.q_chunk
        qz = t.q_offset + min(S, (i + 1) * t.q_chunk) - 1
        if (t.causal and ka > qz) or (t.window and kz <= qa - t.window):
            continue
        masked = (ka + t.kv_chunk > T or (t.causal and qa < kz)
                  or bool(t.window and ka <= qz - t.window))
        if runs and not masked and not runs[-1][2] and runs[-1][1] == i:
            runs[-1][1] = i + 1
        else:
            runs.append([i, i + 1, masked])
    return runs


def _tile_grads(qb, dob, lse, dr, kb, vb, mask, scale):
    """One tile's gradients from its rebuilt probabilities; f32 math,
    operands of every matmul in the inputs' dtype.

    qb: (B, qc, KH, G, D), dob: (B, qc, KH, G, Dv), lse/dr: (B, KH, G, qc),
    kb/vb: (B, kc, KH, D[v]) -> dq (B, qc, KH, G, D), dk, dv (B, kc, ...)
    in f32."""
    f32 = jnp.float32
    s = jnp.einsum("bqkgd,bckd->bkgqc", qb, kb, preferred_element_type=f32)
    p = jnp.exp(s * scale - lse[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dv = jnp.einsum("bkgqc,bqkgd->bckd", p.astype(vb.dtype), dob,
                    preferred_element_type=f32)
    dp = jnp.einsum("bqkgd,bckd->bkgqc", dob, vb, preferred_element_type=f32)
    ds = (p * (dp - dr[..., None]) * scale).astype(qb.dtype)
    dk = jnp.einsum("bkgqc,bqkgd->bckd", ds, qb, preferred_element_type=f32)
    dq = jnp.einsum("bkgqc,bckd->bqkgd", ds, kb, preferred_element_type=f32)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, t: _Tiling):
    return _flash_forward(q, k, v, t)[0].astype(v.dtype)


def _flash_fwd(q, k, v, t: _Tiling):
    o, lse = _flash_forward(q, k, v, t)
    return o.astype(v.dtype), (q, k, v, o, lse)


def _flash_bwd(t: _Tiling, res, do):
    """dQ, dK, dV.  Outer loop over kv blocks, each with a kv-block-sized
    f32 dK/dV carry over the q blocks that see it (written once); dQ is one
    f32 buffer that each tile adds into its own q block."""
    q, k, v, o, lse = res
    B, S, KH, G, D = q.shape
    T = k.shape[1]
    qc, kc = t.q_chunk, t.kv_chunk
    n_q, n_k = -(-S // qc), -(-T // kc)
    scale = 1.0 / (D ** 0.5)
    dr = jnp.einsum("bqkgd,bqkgd->bkgq", do.astype(jnp.float32), o)
    # q rows past S: lse +inf rebuilds their probabilities as 0
    sp = n_q * qc - S
    qp, dop = _pad_axis(q, 1, sp), _pad_axis(do, 1, sp)
    lse, dr = _pad_axis(lse, 3, sp, jnp.inf), _pad_axis(dr, 3, sp)
    kp = _pad_axis(k, 1, n_k * kc - T)
    vp = _pad_axis(v, 1, n_k * kc - T)

    def tile(carry, i, kb, vb, mask):
        dq, dk, dv = carry
        a = i * qc
        dq_b, dk_b, dv_b = _tile_grads(
            jax.lax.dynamic_slice_in_dim(qp, a, qc, axis=1),
            jax.lax.dynamic_slice_in_dim(dop, a, qc, axis=1),
            jax.lax.dynamic_slice_in_dim(lse, a, qc, axis=3),
            jax.lax.dynamic_slice_in_dim(dr, a, qc, axis=3),
            kb, vb, mask, scale)
        dq = jax.lax.dynamic_update_slice_in_dim(
            dq, jax.lax.dynamic_slice_in_dim(dq, a, qc, axis=1) + dq_b, a,
            axis=1)
        return dq, dk + dk_b, dv + dv_b

    dq = jnp.zeros(qp.shape, jnp.float32)
    dks, dvs = [], []
    for j in range(n_k):
        kb = kp[:, j * kc:(j + 1) * kc]
        vb = vp[:, j * kc:(j + 1) * kc]
        carry = (dq, jnp.zeros(kb.shape, jnp.float32),
                 jnp.zeros(vb.shape, jnp.float32))
        for lo, hi, masked in _q_runs(j, S, T, t):
            if masked:
                mask = _tile_mask(t.q_offset + lo * qc + jnp.arange(qc),
                                  j * kc + jnp.arange(kc), T, t.causal,
                                  t.window)
                carry = tile(carry, lo, kb, vb, mask)
            else:
                carry, _ = jax.lax.scan(
                    lambda c, i, kb=kb, vb=vb: (tile(c, i, kb, vb, None),
                                                None),
                    carry, jnp.arange(lo, hi))
        dq, dk, dv = carry
        dks.append(dk)
        dvs.append(dv)
    dk = jnp.concatenate(dks, axis=1)[:, :T]
    dv = jnp.concatenate(dvs, axis=1)[:, :T]
    return (dq[:, :S].astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


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
            o = blockwise_attention(q, k, v, causal=True)
        return layers.apply_dense(p["wo"], o.reshape(B, S, H * a.v_head_dim))
