"""Plain float32 reference of the LM training step (moonlight-16b-a3b).

Written from the published equations, independently of ``repro``:
DeepSeek-V2 (arXiv:2405.04434) for latent attention, DeepSeek-V3
(arXiv:2412.19437) for sigmoid routing with a correction bias, its update
and the sequence-wise balance loss.  Plain ``jax.numpy`` in float32 at
``jax.default_matmul_precision("highest")``: no scan, no grouped matmul,
no kernels.  Each held expert is a dense loop over the sequence's tokens,
weighted by its gate, which is 0 for a token that did not choose it (the
same sum as a loop over the tokens that chose it, with no shape that
depends on routing).  Computed one sequence at a time, each layer, each
block of 1024 queries and each block of the head rematerialised; AdamW's
moments wait on the host while the gradients are computed, so that the
step fits a chip once the program's state is freed.

A step: gradients of the mean next-token loss plus the balance loss,
clipped to global norm ``clip_norm``, then AdamW (decoupled weight decay
on every leaf, bias-corrected moments), then each router's bias moves by
gamma * sign(mean load - load) from the step's counts.  ``q`` rounds
every matmul operand (``reference.rounding_to``: the float8 control).

Parameters are the program's tree (``repro.models.lm.init``): stacked
``dense_blocks`` and ``blocks``, ``buffers.router_bias``.
"""
from __future__ import annotations

import functools

import numpy as np

QUERY_BLOCK = 1024
HEAD_BLOCK = 1024


def config(conf: dict) -> dict:
    opt = conf["optimizer"]
    return dict(
        d=conf["hidden_size"], H=conf["num_attention_heads"],
        nope=conf["qk_nope_head_dim"], rope=conf["qk_rope_head_dim"],
        v=conf["v_head_dim"], c=conf["kv_lora_rank"],
        theta=float(conf["rope_theta"]), eps=conf["rms_norm_eps"],
        L=conf["num_hidden_layers"], dense=conf["first_k_dense_replace"],
        E=conf["n_routed_experts"], R=conf["router_experts"],
        offset=conf["expert_offset"], K=conf["num_experts_per_tok"],
        scale=conf["routed_scaling_factor"],
        alpha=conf["seq_balance_alpha"], gamma=conf["bias_update_rate"],
        lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], adam_eps=opt["eps"],
        wd=opt["weight_decay"], clip=opt["clip_norm"])


def _mm(q, a, b):
    import jax.numpy as jnp
    return jnp.matmul(q(a), q(b))


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate the two halves of the last axis; x (S, ..., D)."""
    import jax.numpy as jnp
    S, half = x.shape[0], x.shape[-1] // 2
    ang = (np.arange(S)[:, None] * theta ** (-np.arange(half) / half))
    shape = (S,) + (1,) * (x.ndim - 2) + (half,)
    c = jnp.asarray(np.cos(ang), jnp.float32).reshape(shape)
    s = jnp.asarray(np.sin(ang), jnp.float32).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _swiglu(q, p, x):
    import jax
    g = _mm(q, x, p["w_gate"])
    return _mm(q, jax.nn.silu(g) * _mm(q, x, p["w_in"]), p["w_out"])


def _attention(q, c, qs, ks, vs):
    """Causal softmax attention of queries ``qs`` (S, H, qk) over keys
    ``ks`` and values ``vs``, a block of queries at a time."""
    import jax
    import jax.numpy as jnp
    S = qs.shape[0]
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(S, lo + QUERY_BLOCK)

        @jax.checkpoint
        def block(qb, kb, vb, lo=lo, hi=hi):
            s = jnp.einsum("qhd,khd->hqk", q(qb), q(kb)) / np.sqrt(
                c["nope"] + c["rope"])
            mask = np.arange(lo, hi)[:, None] >= np.arange(hi)[None, :]
            w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", q(w), q(vb))
        out.append(block(qs[lo:hi], ks[:hi], vs[:hi]))
    return jnp.concatenate(out, axis=0)


def _mla(q, c, p, x):
    import jax.numpy as jnp
    S, H = x.shape[0], c["H"]
    qh = _mm(q, x, p["wq"]["w"]).reshape(S, H, c["nope"] + c["rope"])
    kv_a = _mm(q, x, p["wkv_a"]["w"])
    c_kv = _rms(kv_a[:, :c["c"]], p["kv_norm"]["scale"], c["eps"])
    k_pe = _rope(kv_a[:, c["c"]:], c["theta"])
    kv = _mm(q, c_kv, p["wkv_b"]["w"]).reshape(S, H, c["nope"] + c["v"])
    qs = jnp.concatenate([qh[..., :c["nope"]],
                          _rope(qh[..., c["nope"]:], c["theta"])], axis=-1)
    ks = jnp.concatenate([kv[..., :c["nope"]],
                          jnp.broadcast_to(k_pe[:, None, :],
                                           (S, H, c["rope"]))], axis=-1)
    o = _attention(q, c, qs, ks, kv[..., c["nope"]:])
    return _mm(q, o.reshape(S, H * c["v"]), p["wo"]["w"])


def _moe(q, c, p, x, bias):
    """(held experts' part + shared experts, balance loss, rows routed to
    held experts, tokens per expert) of one sequence."""
    import jax
    import jax.numpy as jnp
    S, R, K = x.shape[0], c["R"], c["K"]
    scores = jax.nn.sigmoid(_mm(q, x, p["router"]))
    _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), K)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    gates = picked / jnp.sum(picked, axis=-1, keepdims=True) * c["scale"]
    chose = jnp.sum(jax.nn.one_hot(ids, R, dtype=jnp.float32), axis=1)
    y = _swiglu(q, p["shared"], x)
    rows = jnp.zeros((), jnp.int32)
    for j in range(c["E"]):
        e = c["offset"] + j
        w = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)
        expert = {k: p[k][j] for k in ("w_gate", "w_in", "w_out")}
        y = y + w[:, None] * _swiglu(q, expert, x)
        rows = rows + jnp.sum(ids == e)
    f = R / (K * S) * jnp.sum(chose, axis=0)
    P = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    balance = c["alpha"] * jnp.sum(f * P)
    return y, balance, rows, jnp.sum(chose, axis=0).astype(jnp.int32)


def _layers(params, c):
    import jax
    out = [(jax.tree.map(lambda a, i=i: a[i], params["dense_blocks"]), None)
           for i in range(c["dense"])]
    out += [(jax.tree.map(lambda a, i=i: a[i], params["blocks"]),
             params["buffers"]["router_bias"][i])
            for i in range(c["L"] - c["dense"])]
    return out


def sequence_loss(params, tokens, c, q):
    """Summed next-token loss of one sequence and its balance loss; aux:
    rows routed to held experts and tokens per expert, per MoE layer."""
    import jax
    import jax.numpy as jnp

    def dense_layer(p, x):
        x = x + _mla(q, c, p["attn"], _rms(x, p["ln1"]["scale"], c["eps"]))
        return x + _swiglu(q, p["ffn"], _rms(x, p["ln2"]["scale"], c["eps"]))

    def moe_layer(p, bias, x):
        x = x + _mla(q, c, p["attn"], _rms(x, p["ln1"]["scale"], c["eps"]))
        y, bal, rows, counts = _moe(q, c, p["moe"],
                                    _rms(x, p["ln2"]["scale"], c["eps"]),
                                    bias)
        return x + y, bal, rows, counts

    x = params["embed"]["emb"][tokens]
    balance, rows, counts = 0.0, [], []
    for p, bias in _layers(params, c):
        if bias is None:
            x = jax.checkpoint(dense_layer)(p, x)
        else:
            x, bal, r, n = jax.checkpoint(moe_layer)(p, bias, x)
            balance, rows, counts = balance + bal, rows + [r], counts + [n]
    h = _rms(x, params["ln_f"]["scale"], c["eps"])
    ce = 0.0
    S = tokens.shape[0]
    for lo in range(0, S - 1, HEAD_BLOCK):
        hi = min(S - 1, lo + HEAD_BLOCK)

        @jax.checkpoint
        def head(hb, tb):
            logits = _mm(q, hb, params["head"]["w"])
            lse = jax.nn.logsumexp(logits, axis=-1)
            return jnp.sum(lse - jnp.take_along_axis(
                logits, tb[:, None], axis=-1)[:, 0])
        ce = ce + head(h[lo:hi], tokens[lo + 1:hi + 1])
    return ce, (balance, jnp.stack(rows), jnp.stack(counts))


def _trainable(params):
    return {k: v for k, v in params.items() if k != "buffers"}


@functools.lru_cache(maxsize=None)
def _compiled(frozen, precision):
    """The step's two programs for one configuration and operand
    precision: gradients of a batch, and the update."""
    import jax
    import jax.numpy as jnp
    from harness import reference

    c = dict(frozen)
    q = (reference.identity if precision == "float32"
         else reference.rounding_to(precision))

    def batch_loss(tr, buffers, tokens):
        B, S = tokens.shape
        params = dict(tr, buffers=buffers)
        loss, rows, counts = 0.0, 0, 0
        for b in range(B):
            ce, (bal, r, n) = sequence_loss(params, tokens[b], c, q)
            loss = loss + ce / (B * (S - 1)) + bal / B
            rows, counts = rows + r, counts + n
        return loss, (rows, counts)

    def grads(tr, buffers, tokens):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(batch_loss, has_aux=True)(
                tr, buffers, tokens)

    def update(tr, m, v, g, buffers, counts, step):
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, c["clip"] / gn), g)
        m = jax.tree.map(lambda a, x: c["b1"] * a + (1 - c["b1"]) * x, m, g)
        v = jax.tree.map(lambda a, x: c["b2"] * a + (1 - c["b2"]) * x * x,
                         v, g)
        bc1, bc2 = 1 - c["b1"] ** step, 1 - c["b2"] ** step
        tr = jax.tree.map(
            lambda p, a, b: p - c["lr"] * ((a / bc1) / (jnp.sqrt(b / bc2)
                                                       + c["adam_eps"])
                                           + c["wd"] * p), tr, m, v)
        load = counts.astype(jnp.float32)
        bias = buffers["router_bias"] + c["gamma"] * jnp.sign(
            jnp.mean(load, axis=-1, keepdims=True) - load)
        return tr, m, v, {"router_bias": bias}, gn

    return (jax.jit(grads),
            jax.jit(update, donate_argnums=(0, 1, 2), static_argnums=6))


def run_steps(conf, params0, batches, n, precision="float32"):
    """``n`` reference steps from ``params0`` (the program's initial tree,
    on the host) over ``batches``; returns what ``lm_compare.readings``
    reads: each step's loss and rows held per layer, the norms of the
    first gradient, the step-1 change and the change over all steps, and
    the routers' biases after the last step with gamma, their step."""
    import jax
    from harness import lm_compare
    c = config(conf)
    grads, update = _compiled(tuple(sorted(c.items())), precision)
    tr = jax.device_put(_trainable(params0))
    buffers = jax.device_put(params0["buffers"])
    m = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), tr)
    v = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), tr)
    out = {"losses": [], "rows": []}
    for i in range(n):
        tokens = jax.device_put(batches[i % len(batches)]["tokens"])
        (loss, (rows, counts)), g = grads(tr, buffers, tokens)
        out["losses"].append(float(loss))
        out["rows"].append(np.asarray(jax.device_get(rows)))
        m, v = jax.device_put((m, v))
        tr, m, v, buffers, _ = update(tr, m, v, g, buffers, counts, i + 1)
        del g
        if i == 0:
            out["g1"] = lm_compare.norms(v, sqrt_of=True)
            out["d1"] = lm_compare.norms(dict(tr, buffers=buffers), params0)
        m, v = jax.device_get((m, v))
    out["dn"] = lm_compare.norms(dict(tr, buffers=buffers), params0)
    out["bias"] = np.asarray(jax.device_get(buffers["router_bias"]))
    out["bias_step"] = c["gamma"]
    return out
