"""jit-able train / serve step factories shared by every architecture.

This is the paper's "custom training loop" discipline applied framework-wide:
the ENTIRE step (loss, backward, clip, optimizer, any RNG) lives in one
compiled program, so nothing sequential is left on the host (paper §3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.optim import optimizers as opt_lib

# top-level params key of state that a step updates outside the optimizer
# (a model's ``update_buffers(buffers, metrics, cfg)``): no gradient flows
# into it and the optimizer holds no moments for it
BUFFERS = "buffers"


def split_buffers(params):
    """(trainable params, buffers or None)."""
    if isinstance(params, dict) and BUFFERS in params:
        return ({k: v for k, v in params.items() if k != BUFFERS},
                params[BUFFERS])
    return params, None


def trainable(params):
    """What the optimizer sees: ``params`` without its buffers."""
    return split_buffers(params)[0]


def _sum_counts_mean_rest(stacked):
    """Per-microbatch metrics -> per-step: integer counters add up, the
    rest average."""
    return jax.tree.map(
        lambda x: (jnp.sum(x, axis=0)
                   if jnp.issubdtype(x.dtype, jnp.integer)
                   else jnp.mean(x, axis=0)), stacked)


def _split_microbatches(batch, n: int):
    """Reshape every batch leaf to a leading microbatch axis.

    The batch dim is dim 0 for every leaf except mrope ``positions``
    (3, B, S), whose batch dim is 1."""
    b0 = batch[next(k for k in ("tokens", "image", "audio_emb")
                    if k in batch)].shape[0]

    def leaf(k, x):
        if x.shape[0] == b0:
            return x.reshape(n, b0 // n, *x.shape[1:])
        assert x.ndim >= 2 and x.shape[1] == b0, (k, x.shape)
        y = x.reshape(x.shape[0], n, b0 // n, *x.shape[2:])
        return jnp.moveaxis(y, 1, 0)

    return {k: leaf(k, v) for k, v in batch.items()}


def make_train_step(model, cfg, optimizer, policy, mesh=None,
                    clip_norm: float = 1.0, remat: bool = True,
                    microbatches: int = 1, seq_shard: bool = True,
                    grad_reduce=None):
    """One fully-compiled train step (the paper's fused-loop discipline).

    ``microbatches`` > 1 runs gradient accumulation INSIDE the step via
    lax.scan — §Perf H6: live activation footprint shrinks by the
    microbatch factor while total compute/collective bytes are unchanged
    (the grad accumulator is param-sized and stays sharded like params).

    ``seq_shard``: residual-stream sequence sharding is ON for training by
    default (remat-saved activations shrink by the model-axis factor) and
    OFF for prefill/serve (§Perf: it only buys gathers there).  The flag
    is applied at TRACE time so it holds wherever the step is jitted.

    ``grad_reduce``: applied to the (accumulated) gradients before
    clipping and the optimizer update.  The data-parallel engine's
    custom loop passes an explicit psum-mean here (the step then runs as
    a per-device program under shard_map); leave ``None`` under jit,
    where GSPMD inserts the gradient all-reduce itself.  A reducer
    exposing ``wrap_params`` (``collectives.OverlapReduce``,
    ``grad_reduce="overlap"``) is applied to the params INSIDE the loss
    instead, so each bucket's collective issues mid-backward; the
    post-hoc call is then the identity.
    """
    from repro.parallel import sharding as sharding_lib

    wrap_params = getattr(grad_reduce, "wrap_params", None)

    def grad_of(params, buffers, mb):
        def loss(p):
            if wrap_params is not None:
                p = wrap_params(p)
            if buffers is not None:
                p = dict(p, **{BUFFERS: jax.lax.stop_gradient(buffers)})
            with sharding_lib.seq_sharding(seq_shard):
                return model.loss_fn(p, mb, cfg, policy=policy, mesh=mesh,
                                     remat=remat)
        return jax.value_and_grad(loss, has_aux=True)(params)

    def train_step(params, opt_state, batch):
        params, buffers = split_buffers(params)
        if microbatches > 1:
            mbs = _split_microbatches(batch, microbatches)

            def body(acc, mb):
                g_acc, l_acc = acc
                (l, m), g = grad_of(params, buffers, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l), m

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, l), ms = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            l = l / microbatches
            metrics = _sum_counts_mean_rest(ms)
        else:
            (l, metrics), grads = grad_of(params, buffers, batch)
        if grad_reduce is not None:
            grads = grad_reduce(grads)
        if clip_norm:
            grads, gnorm = opt_lib.clip_by_global_norm(grads, clip_norm)
        else:
            gnorm = opt_lib.global_norm(grads)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = opt_lib.apply_updates(params, updates)
        if buffers is not None:
            # replicas must agree on the new buffers: the custom loop's
            # reducer means each replica's counts over all of them
            seen = metrics
            if wrap_params is not None:
                raise NotImplementedError(
                    "the overlap reducer reduces gradients inside the "
                    "backward pass only; buffers need a flat or "
                    "hierarchical grad_reduce")
            if grad_reduce is not None:
                seen = grad_reduce(jax.tree.map(
                    lambda x: x.astype(jnp.float32), metrics))
            params = dict(params, **{
                BUFFERS: model.update_buffers(buffers, seen, cfg)})
        metrics = dict(metrics, loss=l, grad_norm=gnorm)
        return params, opt_state, metrics

    return train_step


def grad_reduce_traffic(model, cfg, bucket_bytes=None) -> dict:
    """LM analogue of ``adversarial.grad_reduce_traffic``: one gradient
    reduction per step, param-tree-sized.  Feeds cloud/interconnect.
    ``bucket_bytes`` adds the overlap reducer's per-round tail-bucket
    bytes (see ``adversarial.grad_reduce_traffic``)."""
    import numpy as np
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), cfg))
    leaves = jax.tree.leaves(shapes)
    nbytes = int(sum(np.prod(s.shape) * s.dtype.itemsize for s in leaves))
    out = {"rounds": [("step", nbytes)], "bytes_per_step": nbytes,
           "largest_round_bytes": nbytes}
    if bucket_bytes is not None:
        from repro.parallel import collectives
        out["tail_bytes"] = {"step": max(
            int(sum(np.prod(leaves[i].shape) * leaves[i].dtype.itemsize
                    for i in bucket))
            for bucket in collectives.plan_buckets(leaves, bucket_bytes))}
    return out


def make_serve_step(model, cfg, policy, mesh=None, window: int = 0):
    def serve_step(params, tokens1, cache, pos, extra):
        logits, cache = model.decode_step(
            params, tokens1, cache, pos, cfg, policy=policy, mesh=mesh,
            window=window, positions=extra.get("positions"))
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, cache

    return serve_step


def make_prefill_chunk_step(model, cfg, policy, mesh=None, window: int = 0):
    """Chunked batched serving prefill: one launch ingests a (B, C)
    prompt chunk per slot (ragged ``lens``; 0 = inactive slot) and
    returns each active slot's next token sampled from its last valid
    prompt position.  Only built for archs exporting ``prefill_chunk``."""
    def prefill_chunk_step(params, tokens, cache, pos, lens, extra):
        logits, cache = model.prefill_chunk(
            params, tokens, cache, pos, lens, cfg, policy=policy, mesh=mesh,
            window=window, positions=extra.get("positions"))
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, cache

    return prefill_chunk_step


def make_prefill_step(model, cfg, policy, mesh=None, window: int = 0):
    def prefill_step(params, batch):
        main = batch.get("audio_emb", batch.get("tokens"))
        return model.prefill(params, main, cfg, policy=policy, mesh=mesh,
                             window=window, positions=batch.get("positions"))

    return prefill_step
