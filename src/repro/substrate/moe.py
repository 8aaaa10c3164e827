"""Mixture-of-Experts substrate: top-k router + grouped capacity dispatch.

GShard/Mesh-TF formulation, adapted for TPU + GSPMD:

- tokens are split into GROUPS along the (data-sharded) token axis; each
  group computes its own capacity-bounded dispatch one-hot, keeping dispatch
  memory O(group * E * cap) instead of O(T * E * cap_global);
- per-expert buffers are built with einsums (lowering to all-to-all across
  the ``expert``->``model`` mesh axis under GSPMD);
- experts run as one (G, E)-batched matmul, sharded over groups (data) and
  experts (model) simultaneously.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.substrate import layers


def init_moe(key, cfg):
    m = cfg.moe
    ks = jax.random.split(key, 4)
    d, dff, E = cfg.d_model, m.d_ff_expert, m.n_experts
    p = {
        "router": layers.normal_init(ks[0], (d, E), 0.02),
        "w_in": layers.normal_init(ks[1], (E, d, dff)),
        "w_out": layers.normal_init(ks[2], (E, dff, d)),
    }
    if cfg.ffn_type == "swiglu":
        p["w_gate"] = layers.normal_init(ks[3], (E, d, dff))
    return p


def moe_axes(cfg):
    p = {
        "router": ("embed", None),
        "w_in": ("expert", "embed", "mlp"),
        "w_out": ("expert", "mlp", "embed"),
    }
    if cfg.ffn_type == "swiglu":
        p["w_gate"] = ("expert", "embed", "mlp")
    return p


def _pick_groups(T: int, target: int = 1024) -> int:
    """Largest group count G dividing T with group size <= target."""
    G = max(1, T // target)
    while T % G:
        G += 1
    return G


def apply_moe(p, x, cfg, group_target: int = 1024):
    """x: (B, S, d) -> (y, aux_loss, stats)."""
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.n_experts, m.top_k
    T = B * S
    G = _pick_groups(T, group_target)
    gs = T // G
    xg = x.reshape(G, gs, d)

    logits = (xg @ p["router"].astype(x.dtype)).astype(jnp.float32)   # (G,gs,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)                   # (G,gs,K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    cap = max(int(m.capacity_factor * gs * K / E), K)

    # position of each (token, k) slot inside its expert buffer (per group)
    onehot_e = jax.nn.one_hot(expert_ids, E, dtype=jnp.int32)         # (G,gs,K,E)
    flat = onehot_e.reshape(G, gs * K, E)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(G, gs, K, E)
    pos = jnp.sum(pos * onehot_e, axis=-1)                            # (G,gs,K)
    keep = (pos < cap).astype(jnp.float32)

    onehot_c = jax.nn.one_hot(pos, cap, dtype=jnp.float32)            # (G,gs,K,cap)
    oe = onehot_e.astype(jnp.float32)

    # dispatch: (G, gs, E, cap)
    disp = jnp.einsum("gske,gskc->gsec", oe, onehot_c * keep[..., None])
    buf = jnp.einsum("gsec,gsd->gecd", disp.astype(x.dtype), xg)      # (G,E,cap,d)

    # expert computation — batched over (G, E)
    if cfg.ffn_type == "swiglu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(x.dtype)))
        h = h * jnp.einsum("gecd,edf->gecf", buf, p["w_in"].astype(x.dtype))
    else:
        h = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", buf, p["w_in"].astype(x.dtype)))
    out = jnp.einsum("gecf,efd->gecd", h, p["w_out"].astype(x.dtype))

    # combine with gate weights: (G, gs, E, cap) weighted
    wdisp = jnp.einsum("gske,gskc,gsk->gsec", oe, onehot_c,
                       gate_vals * keep)
    y = jnp.einsum("gsec,gecd->gsd", wdisp.astype(x.dtype), out)
    y = y.reshape(B, S, d)

    # aux losses (Switch-style load balance + router z-loss)
    me = jnp.mean(probs, axis=1)                                      # (G,E)
    frac = jnp.mean(oe, axis=(1, 2))                                  # (G,E)
    load_balance = E * jnp.mean(jnp.sum(frac * me, axis=-1))
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = m.load_balance_loss * load_balance + m.router_z_loss * z_loss
    stats = {"moe_load_balance": load_balance, "moe_z": z_loss,
             "moe_drop_frac": 1.0 - jnp.mean(keep)}
    return y.astype(x.dtype), aux, stats


# ---------------------------------------------------------------------------
# Grouped dispatch: one device's share of an expert-parallel layer
# ---------------------------------------------------------------------------


def init_grouped(key, cfg):
    """Router over all ``n_router`` experts, the ``n_experts`` held here,
    and the shared experts as one SwiGLU of ``n_shared * d_ff_expert``."""
    m = cfg.moe
    ks = jax.random.split(key, 5)
    d, dff, E = cfg.d_model, m.d_ff_expert, m.n_experts
    p = {
        "router": layers.normal_init(ks[0], (d, m.n_router), 0.02),
        "w_gate": layers.normal_init(ks[1], (E, d, dff)),
        "w_in": layers.normal_init(ks[2], (E, d, dff)),
        "w_out": layers.normal_init(ks[3], (E, dff, d)),
    }
    if m.n_shared_experts:
        p["shared"] = layers.init_ffn(ks[4], d, m.n_shared_experts * dff,
                                      "swiglu")
    return p


def grouped_axes(cfg):
    p = {"router": ("embed", None),
         "w_gate": ("expert", "embed", "mlp"),
         "w_in": ("expert", "embed", "mlp"),
         "w_out": ("expert", "mlp", "embed")}
    if cfg.moe.n_shared_experts:
        p["shared"] = layers.ffn_axes("swiglu")
    return p


def route(p, x, bias, cfg):
    """Sigmoid scores over every expert, selection by top-k of score +
    ``bias`` (DeepSeek-V3's ``noaux_tc`` with one group), gates from the
    unbiased scores, normalised and scaled by ``routed_scale``.  x: (B, S, d) -> ids (T, K), gates (T, K) f32, per-expert
    token counts (n_router,) int32, the sequence-wise balance loss."""
    m = cfg.moe
    B, S, d = x.shape
    R, K = m.n_router, m.top_k
    xt = x.reshape(B * S, d)
    scores = jax.nn.sigmoid(jnp.dot(xt.astype(jnp.float32),
                                    p["router"].astype(jnp.float32)))
    _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), K)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    gates = gates * m.routed_scale
    counts = jnp.zeros((R,), jnp.int32).at[ids.reshape(-1)].add(1)
    balance = jnp.zeros((), jnp.float32)
    if m.seq_balance_loss:
        # L = alpha * sum_i f_i P_i per sequence, f_i = R/(K S) * tokens
        # that chose i, P_i = mean over the sequence of i's normalised score
        chose = jnp.zeros((B * S, R), jnp.float32).at[
            jnp.arange(B * S)[:, None], ids].set(1.0)
        f = (R / (K * S)) * jnp.sum(chose.reshape(B, S, R), axis=1)
        pn = scores / jnp.sum(scores, axis=-1, keepdims=True)
        P = jnp.mean(pn.reshape(B, S, R), axis=1)
        balance = m.seq_balance_loss * jnp.mean(jnp.sum(f * P, axis=-1))
    return ids, gates, counts, balance


def _assign_groups(ids, cfg):
    """Group of each (token, k) pair: its expert's index among those held
    here, or ``n_experts`` for a pair whose expert another device holds."""
    m = cfg.moe
    local = ids.reshape(-1) - m.expert_offset
    held = (local >= 0) & (local < m.n_experts)
    return jnp.where(held, local, m.n_experts)


def _held_chunk(p, xt, order, starts, ends, gates, n_rows, lo, rows):
    """Pairs ``[lo, lo + rows)`` of ``order`` (held pairs first, sorted by
    expert; group g holds ``[starts[g], ends[g])``) through their experts
    as grouped matmuls; returns their gated outputs added up per token,
    (T, d) float32, and the rows the grouped matmuls were given."""
    K = gates.shape[-1]
    T, d = xt.shape
    with jax.named_scope("dispatch"):
        pairs = jax.lax.dynamic_slice_in_dim(order, lo, rows)
        tok = pairs // K
        live = lo + jnp.arange(rows) < n_rows
        # rows past the groups are left unwritten by the TPU's grouped
        # matmul, forward and backward: select them away on both sides
        xs = jnp.where(live[:, None], xt[tok], 0)
        sizes = jnp.clip(jnp.minimum(ends, lo + rows)
                         - jnp.maximum(starts, lo), 0).astype(jnp.int32)
    with jax.named_scope("experts"):
        dt = xt.dtype
        h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"].astype(dt), sizes))
        h = h * jax.lax.ragged_dot(xs, p["w_in"].astype(dt), sizes)
        ys = jax.lax.ragged_dot(h, p["w_out"].astype(dt), sizes)
    with jax.named_scope("combine"):
        w = jnp.where(live, gates.reshape(-1)[pairs], 0.0)
        ys = jnp.where(live[:, None], ys.astype(jnp.float32) * w[:, None],
                       0.0)
        return jnp.zeros((T, d), jnp.float32).at[tok].add(ys), \
            jnp.sum(sizes)


def shared_experts(p, x):
    """The shared experts, which every token goes through."""
    return layers.apply_ffn(p["shared"], x, "swiglu")


def apply_grouped(p, x, bias, cfg):
    """x: (B, S, d) -> (y, aux, stats): the held experts' part of the
    layer plus the shared experts.  Routes over all ``n_router`` experts
    and drops no pair: the held pairs, sorted by expert, go through the
    experts in chunks of twice the even share of rows, as many chunks as
    they fill (each rematerialised, so a backward pass holds one)."""
    m = cfg.moe
    B, S, d = x.shape
    T, K, E = B * S, m.top_k, m.n_experts
    xt = x.reshape(T, d)
    with jax.named_scope("moe"):
        with jax.named_scope("route"):
            ids, gates, counts, balance = route(p, x, bias, cfg)
        with jax.named_scope("dispatch"):
            groups = _assign_groups(ids, cfg)
            order = jnp.argsort(groups, stable=True)
            sizes = jnp.zeros((E + 1,), jnp.int32).at[groups].add(1)[:E]
            ends = jnp.cumsum(sizes)
            starts = ends - sizes
            n_rows = ends[-1]
            routed = jnp.sum((ids >= m.expert_offset)
                             & (ids < m.expert_offset + E))
        full = T * min(K, E)            # every pair that can be held here
        rows = min(full, -(-2 * T * K * E // m.n_router // 128) * 128)
        n_chunks = -(-full // rows)
        order = jnp.pad(order[:full], (0, n_chunks * rows - full))
        chunk = jax.checkpoint(functools.partial(
            _held_chunk, p, xt, order, starts, ends, gates, n_rows,
            rows=rows))

        def skip(lo):
            return jnp.zeros((T, d), jnp.float32), jnp.zeros((), jnp.int32)
        y, computed = jnp.zeros((T, d), jnp.float32), jnp.zeros((), jnp.int32)
        for lo in range(0, n_chunks * rows, rows):
            # the cond's op carries ``experts``, its branch's ops their part
            with jax.named_scope("experts"):
                y_lo, n_lo = jax.lax.cond(lo < n_rows, chunk, skip, lo)
            y, computed = y + y_lo, computed + n_lo
        if m.n_shared_experts:
            with jax.named_scope("shared"):
                y = y + shared_experts(p, x).reshape(T, d).astype(
                    jnp.float32)
    # rows held: what the grouped matmuls computed; dropped: held pairs
    # routed (counted from the picks) that no chunk computed
    stats = {"moe_rows_held": computed, "moe_tokens_per_expert": counts,
             "moe_drop_frac": (routed - computed) / jnp.maximum(routed, 1)}
    return y.reshape(B, S, d).astype(x.dtype), balance, stats


def update_bias(bias, counts, rate):
    """DeepSeek-V3's auxiliary-loss-free balancing: each expert's bias
    moves by ``rate`` toward the mean load, b_i += rate * sign(mean - load_i).
    bias, counts: (..., n_router)."""
    load = counts.astype(jnp.float32)
    mean = jnp.mean(load, axis=-1, keepdims=True)
    return bias + rate * jnp.sign(mean - load)
