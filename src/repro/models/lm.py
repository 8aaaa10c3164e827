"""Decoder-only language model covering the dense, MoE and VLM families.

- dense:  qwen2-1.5b, phi4-mini-3.8b, granite-20b (MQA), nemotron-4-15b
- moe:    dbrx-132b, olmoe-1b-7b  (block FFN -> substrate.moe)
- vlm:    qwen2-vl-72b (M-RoPE positions; vision frontend stubbed — the
          model can consume precomputed patch embeddings via ``embeds``)

Layers are STACKED and applied with lax.scan so HLO size is O(1) in depth
(80-layer dry-runs compile quickly); each block is rematerialised.
The LM head + cross-entropy are computed in sequence chunks so the full
(B, S, vocab) logits tensor is never materialised.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.parallel import sharding
from repro.substrate import attention as attn_lib
from repro.substrate import layers, moe as moe_lib

# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------


def _grouped(cfg) -> bool:
    return cfg.moe is not None and cfg.moe.dispatch == "grouped"


def _dense_cfg(cfg):
    """The leading dense layers' config: the same block with a dense FFN."""
    return dataclasses.replace(cfg, moe=None)


def init_block(key, cfg):
    ks = jax.random.split(key, 3)
    p = {
        "ln1": layers.init_norm(cfg.d_model, cfg.norm_type),
        "attn": (attn_lib.init_mla(ks[0], cfg) if cfg.mla is not None
                 else attn_lib.init_attn(ks[0], cfg)),
        "ln2": layers.init_norm(cfg.d_model, cfg.norm_type),
    }
    if _grouped(cfg):
        p["moe"] = moe_lib.init_grouped(ks[1], cfg)
    elif cfg.moe is not None:
        p["moe"] = moe_lib.init_moe(ks[1], cfg)
    else:
        p["ffn"] = layers.init_ffn(ks[1], cfg.d_model, cfg.d_ff, cfg.ffn_type)
    return p


def block_axes(cfg):
    p = {
        "ln1": layers.norm_axes(cfg.norm_type),
        "attn": (attn_lib.mla_axes(cfg) if cfg.mla is not None
                 else attn_lib.attn_axes(cfg)),
        "ln2": layers.norm_axes(cfg.norm_type),
    }
    if _grouped(cfg):
        p["moe"] = moe_lib.grouped_axes(cfg)
    elif cfg.moe is not None:
        p["moe"] = moe_lib.moe_axes(cfg)
    else:
        p["ffn"] = layers.ffn_axes(cfg.ffn_type)
    return p


def _attend(q, k, v, *, causal, window, seq_len, use_pallas=False):
    return attn_lib.attend(q, k, v, causal=causal, window=window,
                           use_pallas=use_pallas, seq_len=seq_len)


def _apply_mla_block(p, x, cos, sin, cfg, bias):
    """Latent-attention block (DeepSeek-V2/V3); x: (B, S, d) ->
    (x', aux, stats), stats empty in a dense layer."""
    h = layers.apply_norm(p["ln1"], x, cfg.norm_type)
    x = x + attn_lib.apply_mla(p["attn"], h, cos, sin, cfg)
    h = layers.apply_norm(p["ln2"], x, cfg.norm_type)
    if cfg.moe is not None:
        f, aux, stats = moe_lib.apply_grouped(p["moe"], h, bias, cfg)
    else:
        with jax.named_scope("dense_ffn"):
            f = layers.apply_ffn(p["ffn"], h, cfg.ffn_type)
        aux, stats = jnp.zeros((), jnp.float32), {}
    return x + f, aux, stats


def apply_block(p, x, cos, sin, cfg, *, window=0, mesh=None):
    """x: (B, S, d) -> (x', aux)."""
    B, S, _ = x.shape
    # H2 (§Perf): force TP-only sharding on the per-layer weight slice so
    # FSDP storage shards are ALL-GATHERED here (small) instead of XLA
    # all-reducing activation-sized partial contractions (huge).
    if mesh is not None:
        p = sharding.constrain_tree(p, block_axes(cfg), mesh,
                                    sharding.TP_RULES)
    h = layers.apply_norm(p["ln1"], x, cfg.norm_type)
    q, k, v = attn_lib.project_qkv(p["attn"], h, cfg)
    q = attn_lib.apply_rope(q, cos, sin) if cos is not None else q
    k = attn_lib.apply_rope(k, cos, sin) if cos is not None else k
    # H5 (§Perf): head-sharded, full-seq activations inside the block —
    # ONLY when heads divide the model axis; otherwise the constraint
    # would force full replication (it cost phi4 3x peak memory).
    h5 = (mesh is not None
          and cfg.n_heads % sharding.mesh_axis_size(mesh, "model") == 0
          and cfg.n_kv_heads > 1)   # MQA: replicated K/V resharding loses
    if h5:
        q = sharding.constrain_act(q, mesh, ("batch", None, "heads", None))
        k = sharding.constrain_act(k, mesh, ("batch", None, "kv_heads", None))
        v = sharding.constrain_act(v, mesh, ("batch", None, "kv_heads", None))
    o = _attend(q, k, v, causal=True, window=window, seq_len=S,
                use_pallas=cfg.use_pallas_attn)
    if h5:
        o = sharding.constrain_act(o, mesh, ("batch", None, "heads", None))
    o = layers.apply_dense(p["attn"]["wo"], o.reshape(B, S, cfg.q_dim))
    x = x + o
    x = sharding.constrain_batch(x, mesh, seq_dim=1)
    h = layers.apply_norm(p["ln2"], x, cfg.norm_type)
    if cfg.moe is not None:
        f, aux, _ = moe_lib.apply_moe(p["moe"], h, cfg)
    else:
        f, aux = layers.apply_ffn(p["ffn"], h, cfg.ffn_type), jnp.zeros((), jnp.float32)
    x = x + f
    return sharding.constrain_batch(x, mesh, seq_dim=1), aux


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------


def init(rng, cfg):
    """Params: ``blocks`` stacks the layers after the leading dense ones,
    ``dense_blocks`` stacks those (``first_k_dense``), and a grouped-MoE
    model keeps its routers' correction biases under ``buffers`` — state
    that the train step updates outside the optimizer
    (``train/steps.BUFFERS``)."""
    k_emb, k_blocks, k_head = jax.random.split(rng, 3)
    block_keys = jax.random.split(k_blocks, cfg.n_layers - cfg.first_k_dense)
    p = {
        "embed": layers.init_embed(k_emb, cfg.vocab, cfg.d_model),
        "blocks": jax.vmap(lambda k: init_block(k, cfg))(block_keys),
        "ln_f": layers.init_norm(cfg.d_model, cfg.norm_type),
    }
    if cfg.first_k_dense:
        dense = _dense_cfg(cfg)
        p["dense_blocks"] = jax.vmap(lambda k: init_block(k, dense))(
            jax.random.split(jax.random.fold_in(k_blocks, 1),
                             cfg.first_k_dense))
    if not cfg.tie_embeddings:
        p["head"] = {"w": layers.normal_init(k_head, (cfg.d_model, cfg.vocab))}
    if _grouped(cfg):
        p["buffers"] = {"router_bias": jnp.zeros(
            (cfg.n_layers - cfg.first_k_dense, cfg.moe.n_router),
            jnp.float32)}
    return p


def logical_axes(cfg):
    p = {
        "embed": layers.embed_axes(),
        "blocks": sharding.stacked(block_axes(cfg)),
        "ln_f": layers.norm_axes(cfg.norm_type),
    }
    if cfg.first_k_dense:
        p["dense_blocks"] = sharding.stacked(block_axes(_dense_cfg(cfg)))
    if not cfg.tie_embeddings:
        p["head"] = {"w": ("embed", "vocab")}
    if _grouped(cfg):
        p["buffers"] = {"router_bias": (None, None)}
    return p


def update_buffers(buffers, metrics, cfg):
    """After a step: each router's correction bias moves toward balance
    by the tokens each expert got in that step (DeepSeek-V3)."""
    return {"router_bias": moe_lib.update_bias(
        buffers["router_bias"], metrics["moe_tokens_per_expert"],
        cfg.moe.bias_update_rate)}


def _rope_for(cfg, positions, dtype):
    if cfg.rope_theta <= 0:
        return None, None
    if cfg.mrope:
        return attn_lib.mrope_cos_sin(positions, cfg.d_head, cfg.rope_theta,
                                      cfg.mrope_sections, dtype)
    dim = cfg.mla.qk_rope_head_dim if cfg.mla is not None else cfg.d_head
    return attn_lib.rope_cos_sin(positions, dim, cfg.rope_theta, dtype)


def _mla_stack(blocks, x, cos, sin, cfg, bias, remat):
    """Scan a stack of latent-attention blocks; per-layer MoE stats are
    stacked on a leading layer axis."""
    def body(carry, xs):
        h, aux = carry
        block_p, b = xs
        h, a, stats = _apply_mla_block(block_p, h, cos, sin, cfg, b)
        return (h, aux + a), stats

    if remat:
        body = jax.checkpoint(body)
    n = jax.tree.leaves(blocks)[0].shape[0]
    if bias is None:
        bias = jnp.zeros((n, 0), jnp.float32)
    (x, aux), stats = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   (blocks, bias))
    return x, aux, stats


def backbone(params, x, cfg, *, positions, mesh=None, remat=True, window=0):
    """x: (B, S, d) embedded input -> (hidden (B,S,d), aux, stats)."""
    cos, sin = _rope_for(cfg, positions, x.dtype)
    if cfg.mla is not None:
        aux = jnp.zeros((), jnp.float32)
        if cfg.first_k_dense:
            x, aux, _ = _mla_stack(params["dense_blocks"], x, cos, sin,
                                   _dense_cfg(cfg), None, remat)
        bias = params.get("buffers", {}).get("router_bias")
        x, a, stats = _mla_stack(params["blocks"], x, cos, sin, cfg, bias,
                                 remat)
        x = layers.apply_norm(params["ln_f"], x, cfg.norm_type)
        return x, aux + a, stats

    def body(carry, block_p):
        h, aux = carry
        h, a = apply_block(block_p, h, cos, sin, cfg, window=window, mesh=mesh)
        return (h, aux + a), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    x = layers.apply_norm(params["ln_f"], x, cfg.norm_type)
    return x, aux, {}


def _head_matrix(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"]["emb"].T
    return params["head"]["w"]


def forward(params, tokens, cfg, *, policy, positions=None, embeds=None,
            mesh=None, remat=True, window=0):
    """Returns final hidden states (NOT logits — see chunked loss)."""
    cparams = policy.cast_to_compute(params)
    if "buffers" in params:     # routing state stays in float32
        cparams = dict(cparams, buffers=params["buffers"])
    if embeds is not None:
        x = embeds.astype(policy.compute_dtype)
        if tokens is not None:      # VLM: patch embeds replace a token prefix
            tok_emb = layers.apply_embed(cparams["embed"], tokens,
                                         policy.compute_dtype)
            x = jnp.concatenate([x, tok_emb], axis=1)
    else:
        x = layers.apply_embed(cparams["embed"], tokens, policy.compute_dtype)
    B, S, _ = x.shape
    if positions is None:
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        positions = jnp.broadcast_to(pos[None], (3, B, S)) if cfg.mrope else pos
    x = sharding.constrain_batch(x, mesh, seq_dim=1)
    h, aux, stats = backbone(cparams, x, cfg, positions=positions, mesh=mesh,
                             remat=remat, window=window)
    return h, aux, stats, cparams


def chunked_softmax_xent(h, head_w, targets, valid, chunk=512):
    """Cross-entropy over vocab without materialising (B, S, V).

    h: (B,S,d) hidden; head_w: (d,V); targets: (B,S) int; valid: (B,S) bool.
    """
    B, S, d = h.shape
    chunk = min(chunk, S)
    n = S // chunk

    # rematerialised: a backward pass holds one chunk's logits, not all
    @jax.checkpoint
    def body(carry, i):
        loss_sum, cnt = carry
        hs = jax.lax.dynamic_slice_in_dim(h, i * chunk, chunk, axis=1)
        ts = jax.lax.dynamic_slice_in_dim(targets, i * chunk, chunk, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(valid, i * chunk, chunk, axis=1)
        logits = (hs @ head_w.astype(hs.dtype)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ts[..., None], axis=-1)[..., 0]
        nll = (lse - tgt) * vs
        return (loss_sum + jnp.sum(nll), cnt + jnp.sum(vs)), None

    (loss_sum, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        jnp.arange(n))
    rem = S - n * chunk
    if rem:
        logits = (h[:, n * chunk:] @ head_w.astype(h.dtype)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, targets[:, n * chunk:, None], axis=-1)[..., 0]
        nll = (lse - tgt) * valid[:, n * chunk:]
        loss_sum += jnp.sum(nll)
        cnt += jnp.sum(valid[:, n * chunk:])
    return loss_sum / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Serving: KV-cache prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg, batch, max_len, dtype=jnp.bfloat16):
    """KV cache pytree. For sliding-window serving, max_len = window and the
    cache is a ring buffer (rope is applied to k at write time, so ring order
    does not matter)."""
    if cfg.mla is not None:
        raise NotImplementedError(
            f"{cfg.arch_id}: latent attention has no KV cache for serving")
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes(cfg):
    # seq dim -> 'model' (cache_seq rule): a 32k-long KV cache is by far the
    # biggest decode-time tensor; kv_heads rarely divide the model axis (GQA
    # kv<=8 vs model=16) so the sequence axis carries the model parallelism.
    return {"k": (None, "batch", "cache_seq", "kv_heads", None),
            "v": (None, "batch", "cache_seq", "kv_heads", None)}


def prefill(params, tokens, cfg, *, policy, positions=None, embeds=None,
            mesh=None, window=0, max_len=None):
    """Run the full prompt, return (last-token logits, cache).

    ``max_len``: serving capacity — the returned cache is right-padded so
    decode_step can append (decode writes at absolute position; for
    windowed serving pass max_len=window and the last `window` entries are
    stored position-aligned, matching decode's ``pos % window`` ring)."""
    cparams = policy.cast_to_compute(params)
    if embeds is not None:
        x = embeds.astype(policy.compute_dtype)
    else:
        x = layers.apply_embed(cparams["embed"], tokens, policy.compute_dtype)
    B, S, _ = x.shape
    if positions is None:
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        positions = jnp.broadcast_to(pos[None], (3, B, S)) if cfg.mrope else pos
    cos, sin = _rope_for(cfg, positions, x.dtype)
    x = sharding.constrain_batch(x, mesh, seq_dim=1)

    def body(h, block_p):
        if mesh is not None:                      # H2: see apply_block
            block_p = sharding.constrain_tree(block_p, block_axes(cfg),
                                              mesh, sharding.TP_RULES)
        hn = layers.apply_norm(block_p["ln1"], h, cfg.norm_type)
        q, k, v = attn_lib.project_qkv(block_p["attn"], hn, cfg)
        q = attn_lib.apply_rope(q, cos, sin) if cos is not None else q
        k = attn_lib.apply_rope(k, cos, sin) if cos is not None else k
        o = _attend(q, k, v, causal=True, window=window, seq_len=S,
                    use_pallas=cfg.use_pallas_attn)
        o = layers.apply_dense(block_p["attn"]["wo"], o.reshape(B, S, cfg.q_dim))
        h = h + o
        hn = layers.apply_norm(block_p["ln2"], h, cfg.norm_type)
        if cfg.moe is not None:
            f, _, _ = moe_lib.apply_moe(block_p["moe"], hn, cfg)
        else:
            f = layers.apply_ffn(block_p["ffn"], hn, cfg.ffn_type)
        h = sharding.constrain_batch(h + f, mesh, seq_dim=1)
        return h, (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))

    body = jax.checkpoint(body)
    h, (ks, vs) = jax.lax.scan(body, x, cparams["blocks"])
    h = layers.apply_norm(cparams["ln_f"], h, cfg.norm_type)
    logits = (h[:, -1:] @ _head_matrix(cparams, cfg).astype(h.dtype))
    if max_len is not None:
        cap = min(max_len, window) if window else max_len
        if S >= cap:        # keep last `cap`, position-aligned ring slots
            ks = jnp.roll(ks[:, :, S - cap:], S % cap, axis=2)
            vs = jnp.roll(vs[:, :, S - cap:], S % cap, axis=2)
        else:
            pad = ((0, 0), (0, 0), (0, cap - S), (0, 0), (0, 0))
            ks, vs = jnp.pad(ks, pad), jnp.pad(vs, pad)
    return logits.astype(jnp.float32), {"k": ks, "v": vs}


def decode_step(params, tokens1, cache, pos, cfg, *, policy, positions=None,
                mesh=None, window=0):
    """One decode step.  tokens1: (B, 1); pos: scalar int OR (B,) int
    vector of per-sequence absolute positions (ragged continuous
    batching: every slot decodes at its own depth); cache: {"k","v"}
    (L, B, T, KH, D).  Returns (logits, cache)."""
    cparams = policy.cast_to_compute(params)
    x = layers.apply_embed(cparams["embed"], tokens1, policy.compute_dtype)
    B = x.shape[0]
    T = cache["k"].shape[2]
    pos_vec = jnp.broadcast_to(jnp.asarray(pos), (B,))       # (B,)
    if positions is None:
        pos_b = pos_vec[:, None]
        positions = (jnp.broadcast_to(pos_b[None], (3, B, 1))
                     if cfg.mrope else pos_b)
    cos, sin = _rope_for(cfg, positions, x.dtype)
    write_idx = pos_vec % T if window else pos_vec           # (B,)
    kv_len = jnp.minimum(pos_vec + 1, T)
    x = sharding.constrain_batch(x, mesh, seq_dim=1)

    def _write(c, new):
        """Per-row cache write at each sequence's own position."""
        return jax.vmap(
            lambda cb, nb, i: jax.lax.dynamic_update_slice_in_dim(
                cb, nb, i, axis=0))(c, new.astype(c.dtype), write_idx)

    def body(h, xs):
        block_p, kc, vc = xs
        hn = layers.apply_norm(block_p["ln1"], h, cfg.norm_type)
        q, k, v = attn_lib.project_qkv(block_p["attn"], hn, cfg)
        q = attn_lib.apply_rope(q, cos, sin) if cos is not None else q
        k = attn_lib.apply_rope(k, cos, sin) if cos is not None else k
        kc = _write(kc, k)
        vc = _write(vc, v)
        o = attn_lib.attend(
            q, kc.astype(q.dtype), vc.astype(q.dtype), causal=False,
            kv_len=kv_len, use_pallas=cfg.use_pallas_attn)
        o = layers.apply_dense(block_p["attn"]["wo"], o.reshape(B, 1, cfg.q_dim))
        h = h + o
        hn = layers.apply_norm(block_p["ln2"], h, cfg.norm_type)
        if cfg.moe is not None:
            f, _, _ = moe_lib.apply_moe(block_p["moe"], hn, cfg)
        else:
            f = layers.apply_ffn(block_p["ffn"], hn, cfg.ffn_type)
        return h + f, (kc, vc)

    h, (ks, vs) = jax.lax.scan(body, x, (cparams["blocks"],
                                         cache["k"], cache["v"]))
    h = layers.apply_norm(cparams["ln_f"], h, cfg.norm_type)
    logits = h @ _head_matrix(cparams, cfg).astype(h.dtype)
    return logits.astype(jnp.float32), {"k": ks, "v": vs}


def prefill_chunk(params, tokens, cache, pos, lens, cfg, *, policy,
                  positions=None, mesh=None, window=0):
    """Batched chunked prefill: run C prompt positions for every active
    slot in ONE launch, writing K/V straight into each slot's cache region.

    tokens: (B, C) prompt chunk per slot; pos: (B,) absolute cache
    position of each slot's chunk start; lens: (B,) valid tokens of this
    chunk per slot (0 = slot not prefilling — its cache row and logits
    are left untouched / unused).  Requires pos + lens <= T (the engine
    caps prompts at the cache capacity, so chunk writes never wrap the
    ring).  Returns (last-valid-token logits (B, 1, V), cache).
    """
    cparams = policy.cast_to_compute(params)
    x = layers.apply_embed(cparams["embed"], tokens, policy.compute_dtype)
    B, C, _ = x.shape
    T = cache["k"].shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    kv_len = pos + lens                                      # (B,)
    qpos = pos[:, None] + jnp.arange(C)[None]                # (B, C)
    if positions is None:
        positions = (jnp.broadcast_to(qpos[None], (3, B, C))
                     if cfg.mrope else qpos)
    cos, sin = _rope_for(cfg, positions, x.dtype)
    x = sharding.constrain_batch(x, mesh, seq_dim=1)

    t = jnp.arange(T)
    write_mask = (t[None] >= pos[:, None]) & (t[None] < kv_len[:, None])
    gather_idx = jnp.clip(t[None] - pos[:, None], 0, C - 1)  # (B, T)

    def _write(c, new):
        """Masked scatter of the chunk into [pos, pos+lens) per row — a
        gather + where rather than dynamic_update_slice, so rows whose
        chunk tail is padding (i >= lens) never touch the cache and
        inactive rows (lens = 0) are bit-identical no-ops."""
        g = jnp.take_along_axis(new.astype(c.dtype),
                                gather_idx[:, :, None, None], axis=1)
        return jnp.where(write_mask[:, :, None, None], g, c)

    def body(h, xs):
        block_p, kc, vc = xs
        if mesh is not None:                      # H2: see apply_block
            block_p = sharding.constrain_tree(block_p, block_axes(cfg),
                                              mesh, sharding.TP_RULES)
        hn = layers.apply_norm(block_p["ln1"], h, cfg.norm_type)
        q, k, v = attn_lib.project_qkv(block_p["attn"], hn, cfg)
        q = attn_lib.apply_rope(q, cos, sin) if cos is not None else q
        k = attn_lib.apply_rope(k, cos, sin) if cos is not None else k
        kc = _write(kc, k)
        vc = _write(vc, v)
        o = attn_lib.attend(
            q, kc.astype(q.dtype), vc.astype(q.dtype), causal=True,
            kv_len=kv_len, q_offset=pos, use_pallas=cfg.use_pallas_attn)
        o = layers.apply_dense(block_p["attn"]["wo"], o.reshape(B, C, cfg.q_dim))
        h = h + o
        hn = layers.apply_norm(block_p["ln2"], h, cfg.norm_type)
        if cfg.moe is not None:
            f, _, _ = moe_lib.apply_moe(block_p["moe"], hn, cfg)
        else:
            f = layers.apply_ffn(block_p["ffn"], hn, cfg.ffn_type)
        return h + f, (kc, vc)

    h, (ks, vs) = jax.lax.scan(body, x, (cparams["blocks"],
                                         cache["k"], cache["v"]))
    h = layers.apply_norm(cparams["ln_f"], h, cfg.norm_type)
    last = jnp.clip(lens - 1, 0, C - 1)                      # (B,)
    h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)  # (B,1,d)
    logits = h_last @ _head_matrix(cparams, cfg).astype(h.dtype)
    return logits.astype(jnp.float32), {"k": ks, "v": vs}


def loss_fn(params, batch, cfg, *, policy, mesh=None, remat=True):
    tokens = batch["tokens"]
    positions = batch.get("positions")
    embeds = batch.get("embeds")
    h, aux, stats, cparams = forward(params, tokens, cfg, policy=policy,
                                     positions=positions, embeds=embeds,
                                     mesh=mesh, remat=remat)
    # next-token prediction over the token region (embeds prefix has no labels)
    if embeds is not None:
        h = h[:, embeds.shape[1]:]
    targets = tokens[:, 1:]
    hh = h[:, :-1]
    valid = jnp.ones_like(targets, jnp.float32)
    head_w = _head_matrix(cparams, cfg)
    with jax.named_scope("lm_head"):
        ce = chunked_softmax_xent(hh, head_w, targets, valid)
    return ce + aux, dict(stats, ce=ce, aux=aux)
