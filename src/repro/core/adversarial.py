"""The paper's contribution: accelerating the adversarial training process.

Two implementations of Algorithm 1 (§3):

``naive_step`` — the ``keras.train_on_batch`` baseline.  The generator-input
initialisation (latent sampling + label concat) and the fake-image round trip
run SEQUENTIALLY ON THE HOST between separately-compiled device calls.  With
N replicas the host work grows with the global batch => the linear bottleneck
of Fig. 1.

``fused_step`` — the custom-training-loop rewrite.  The ENTIRE Algorithm-1
body is one compiled function: on-device RNG (jax.random), fake generation,
both discriminator updates and both generator updates.  Nothing sequential
remains on the host; under pjit the per-replica noise is generated on each
device's own batch shard, which is exactly the paper's "tf.function includes
all previously sequential steps".

Both follow Algorithm 1 faithfully: D on real, D on fake, then G twice.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gan
from repro.optim import optimizers as opt_lib
from repro.substrate import precision as precision_lib

# Algorithm 1's phases, as ``jax.named_scope`` names inside the fused step:
# every op of a phase carries its name in the compiled program's
# ``op_name`` metadata, so a device trace can be split by phase.  Within a
# phase, the gradient reduction and the optimizer update sit under the
# nested scope ``UPDATE``; forward and backward ops are told apart by the
# ``jvp(``/``transpose(jvp(`` markers JAX itself puts in ``op_name``.
PHASES = ("d_real", "d_fake", "g")
UPDATE = "update"


def _freeze_pallas_conv(cfg):
    """Pin the Pallas fused-conv decision into the config at STEP
    CONSTRUCTION time.  The toggle is otherwise ambient (global setter /
    env var); resolving it here means the traced program is deterministic
    no matter when jit recompiles the step."""
    resolved = gan.pallas_conv_enabled(cfg)
    if getattr(cfg, "use_pallas_conv", resolved) == resolved:
        return cfg
    try:
        return dataclasses.replace(cfg, use_pallas_conv=resolved)
    except TypeError:
        return cfg                      # config without the field


def grad_reduce_traffic(cfg, bucket_bytes: Optional[int] = None) -> dict:
    """Per-step gradient-reduction payload of the fused Algorithm-1 step.

    Each phase reduces its OWN gradients before its optimizer update —
    D params twice (D-real, D-fake), G params ``gen_steps_per_disc``
    times — so the cross-node interconnect model (cloud/interconnect.py)
    prices the step as a SEQUENCE of smaller all-reduces, not one big
    one.  Returns {"rounds": [(name, bytes), ...], "bytes_per_step",
    "largest_round_bytes"}; shapes only, nothing is allocated.

    With ``bucket_bytes`` set, also returns ``"tail_bytes"`` — per round,
    the bytes of the reverse-order overlap reducer's reduction that stay
    EXPOSED no matter how early buckets are issued
    (``collectives.OverlapReduce`` granularity is whole
    ``plan_buckets`` buckets):

    - D rounds map to 0: the following generator-phase compute (the
      generator forward making the next fakes) is independent of the D
      gradients, so their reductions hide under it.
    - G rounds map to their LARGEST bucket: the fused step runs the
      ``gen_steps_per_disc`` G updates back-to-back in a scan whose next
      iteration immediately consumes the updated params, and the last
      one ends the step — there is no independent compute left for the
      slowest bucket (with an oversize first layer, nearly the whole
      round) to hide under.

    Feeding this real plan to ``interconnect.exposed_comm_s`` is what
    makes the modeled overlap term track the measured schedule
    (``jaxpr_cost.collective_schedule``) instead of assuming a uniform
    ``bytes / n_buckets`` tail.
    """
    from repro.parallel import collectives

    g_shapes = jax.eval_shape(
        lambda: gan.init_generator(jax.random.key(0), cfg))
    d_shapes = jax.eval_shape(
        lambda: gan.init_discriminator(jax.random.key(0), cfg))

    def tree_bytes(t):
        return int(sum(np.prod(s.shape) * s.dtype.itemsize
                       for s in jax.tree.leaves(t)))

    def largest_bucket_bytes(t):
        leaves = jax.tree.leaves(t)
        return max(
            int(sum(np.prod(leaves[i].shape) * leaves[i].dtype.itemsize
                    for i in bucket))
            for bucket in collectives.plan_buckets(leaves, bucket_bytes))

    gb, db = tree_bytes(g_shapes), tree_bytes(d_shapes)
    rounds = [("d_real", db), ("d_fake", db)]
    rounds += [(f"g{i}", gb) for i in range(cfg.gen_steps_per_disc)]
    out = {"rounds": rounds,
           "bytes_per_step": sum(b for _, b in rounds),
           "largest_round_bytes": max(b for _, b in rounds)}
    if bucket_bytes is not None:
        gt = largest_bucket_bytes(g_shapes)
        out["tail_bytes"] = {name: (0 if name.startswith("d_") else gt)
                             for name, _ in rounds}
    return out


class GANState(NamedTuple):
    g_params: dict
    d_params: dict
    g_opt: dict
    d_opt: dict
    step: jax.Array
    # dynamic loss-scale state (precision_lib.LossScaleState) when the
    # policy enables it; None keeps the pytree identical to the pre-policy
    # layout, so old checkpoints and f32 runs are untouched
    loss_scale: Any = None


def init_state(rng, cfg, g_optimizer, d_optimizer, policy=None) -> GANState:
    """Master params + optimizer state are ALWAYS f32; ``policy`` only
    adds the loss-scale state its scaling mode needs."""
    kg, kd = jax.random.split(rng)
    g_params = gan.init_generator(kg, cfg)
    d_params = gan.init_discriminator(kd, cfg)
    return GANState(g_params, d_params, g_optimizer.init(g_params),
                    d_optimizer.init(d_params), jnp.zeros((), jnp.int32),
                    precision_lib.init_loss_scale(policy))


# ---------------------------------------------------------------------------
# Naive (keras.train_on_batch analogue)
# ---------------------------------------------------------------------------


class NaiveStep:
    """Host-orchestrated adversarial step with per-call compiled pieces.

    The host work (`_host_generator_inputs`) and device round trips between
    the pieces are intentional — they ARE the measured baseline.
    """

    def __init__(self, cfg, g_optimizer, d_optimizer, seed=0):
        cfg = _freeze_pallas_conv(cfg)
        self.cfg = cfg
        self.g_opt_lib = g_optimizer
        self.d_opt_lib = d_optimizer
        self.np_rng = np.random.default_rng(seed)

        @jax.jit
        def d_update(d_params, d_opt, img, e_p, theta, ecal, real_flag):
            def loss(dp):
                return gan.disc_loss(dp, img, (e_p, theta, ecal), cfg,
                                     real=True)[0] * real_flag + \
                       gan.disc_loss(dp, img, (e_p, theta, ecal), cfg,
                                     real=False)[0] * (1 - real_flag)
            l, grads = jax.value_and_grad(loss)(d_params)
            upd, d_opt = d_optimizer.update(grads, d_opt, d_params)
            return opt_lib.apply_updates(d_params, upd), d_opt, l

        @jax.jit
        def g_update(g_params, g_opt, d_params, noise, e_p, theta, ecal):
            def loss(gp):
                return gan.gen_loss(gp, d_params, noise,
                                    (e_p, theta, ecal), cfg)[0]
            l, grads = jax.value_and_grad(loss)(g_params)
            upd, g_opt = g_optimizer.update(grads, g_opt, g_params)
            return opt_lib.apply_updates(g_params, upd), g_opt, l

        @jax.jit
        def predict(g_params, noise, e_p, theta):
            return gan.generate(g_params, noise, e_p, theta, cfg)

        self._d_update, self._g_update, self._predict = d_update, g_update, predict

    def host_generator_inputs(self, batch_size):
        """The sequential host-side init the paper identifies as the
        bottleneck: numpy RNG + label concat, once per replica batch."""
        cfg = self.cfg
        noise = self.np_rng.normal(0, 1, (batch_size, cfg.latent_dim)) \
            .astype(np.float32)
        e_p = self.np_rng.uniform(10.0, 500.0, batch_size).astype(np.float32)
        theta = self.np_rng.uniform(np.deg2rad(60), np.deg2rad(120),
                                    batch_size).astype(np.float32)
        return noise, e_p, theta

    def __call__(self, state: GANState, batch) -> tuple:
        cfg = self.cfg
        img, e_p, theta, ecal = (batch["image"], batch["e_p"],
                                 batch["theta"], batch["ecal"])
        bs = img.shape[0]
        ecal_frac = float(np.mean(np.asarray(ecal) / np.asarray(e_p)))

        # -- generator input init: HOST, sequential --------------------
        noise, f_ep, f_th = self.host_generator_inputs(bs)
        fake_ecal = f_ep * ecal_frac
        # -- generate fakes; round-trip through host (train_on_batch) --
        fake = np.asarray(self._predict(state.g_params, noise, f_ep, f_th))
        # -- D on real, D on fake --------------------------------------
        d_params, d_opt, d_lr = self._d_update(
            state.d_params, state.d_opt, img, e_p, theta, ecal,
            jnp.float32(1.0))
        d_params, d_opt, d_lf = self._d_update(
            d_params, d_opt, fake, f_ep, f_th, fake_ecal, jnp.float32(0.0))
        # -- G twice (fresh host-side inputs each time: Algorithm 1) ---
        g_params, g_opt = state.g_params, state.g_opt
        g_ls = []
        for _ in range(cfg.gen_steps_per_disc):
            noise, f_ep, f_th = self.host_generator_inputs(bs)
            g_params, g_opt, g_l = self._g_update(
                g_params, g_opt, d_params, noise, f_ep, f_th,
                f_ep * ecal_frac)
            g_ls.append(float(g_l))
        new = GANState(g_params, d_params, g_opt, d_opt, state.step + 1)
        return new, {"d_loss_real": float(d_lr), "d_loss_fake": float(d_lf),
                     "g_loss": float(np.mean(g_ls))}


# ---------------------------------------------------------------------------
# Fused custom loop (the paper's optimisation)
# ---------------------------------------------------------------------------


def make_fused_step(cfg, g_optimizer, d_optimizer, mesh=None, policy=None,
                    grad_reduce=None, microbatches=1):
    """One compiled program for the full Algorithm-1 body.

    ``mesh``: when given, the on-device generator inputs (noise + labels)
    are sharding-constrained over ALL mesh axes — each replica samples its
    own shard (the paper's "every replica initialises its own inputs"),
    and GSPMD keeps the whole fake-image path batch-sharded.  The engine's
    custom loop passes ``mesh=None`` instead: there the step body is a
    per-device program under shard_map and ``batch`` is already local.

    ``policy``: mixed-precision policy (paper §4: bf16 on the MXU).  The
    batch AND both networks' params are cast to ``policy.compute_dtype``
    at phase entry, so every conv (Pallas kernels included — they keep
    their f32 VMEM accumulators) and every norm runs at compute precision;
    losses, gradients, master params and optimizer state stay f32 (§Perf
    G1: halves the memory-bound term).  When ``policy.loss_scale`` is
    nonzero, each phase's loss is scaled before the backward pass, its
    UNSCALED reduced gradients are checked for finiteness, and a
    nonfinite phase SKIPS its optimizer update (params/opt state carried
    through unchanged) while halving the dynamic scale — the state rides
    in ``GANState.loss_scale`` (see `substrate/precision.py`).

    ``grad_reduce``: applied to the gradients of EVERY phase (D-real,
    D-fake, each G step) before its optimizer update — the engine's
    custom loop passes an explicit psum-mean over the data axes here,
    keeping params replicated without GSPMD's help.  A reducer exposing
    ``wrap_params`` (``collectives.OverlapReduce``) is routed through the
    loss instead: the params are tagged before differentiation so each
    bucket's collective issues inside the backward pass, and the post-hoc
    call becomes the identity.

    ``microbatches``: gradient accumulation INSIDE each phase.  The batch
    (and the fake-input sampling) is split into this many microbatches;
    each phase averages its gradients over them via lax.scan before the
    single optimizer update, so Algorithm 1's update order is preserved
    while the live activation footprint shrinks by the microbatch factor.
    """
    cfg = _freeze_pallas_conv(cfg)      # kernel route fixed at trace time
    M = int(microbatches)
    assert M >= 1, microbatches
    reduce_grads = grad_reduce if grad_reduce is not None else (lambda g: g)
    wrap_params = getattr(reduce_grads, "wrap_params", None)
    compute_dtype = policy.compute_dtype if policy is not None else None
    to_compute = (policy.cast_to_compute if compute_dtype is not None
                  else (lambda t: t))
    scaling = policy is not None and bool(policy.loss_scale)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        _axes = tuple(mesh.axis_names)

        def _shard_batchdim(x):
            spec = P(_axes, *([None] * (x.ndim - 1)))
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
    else:
        def _shard_batchdim(x):
            return x

    def fused_step(state: GANState, batch, rng):
        img, e_p, theta, ecal = (batch["image"], batch["e_p"],
                                 batch["theta"], batch["ecal"])
        if compute_dtype is not None:
            img = img.astype(compute_dtype)      # G1: bf16 conv stacks
        bs = img.shape[0]
        assert bs % M == 0, (bs, M)
        mb = bs // M
        ecal_frac = jnp.mean(ecal / e_p)
        keys = jax.random.split(rng, (1 + cfg.gen_steps_per_disc) * M)
        d_keys = keys[:M]
        g_keys = keys[M:].reshape(cfg.gen_steps_per_disc, M)

        def sample_inputs(k):
            k1, k2, k3 = jax.random.split(k, 3)
            noise = jax.random.normal(k1, (mb, cfg.latent_dim),
                                      compute_dtype or jnp.float32)
            f_ep = jax.random.uniform(k2, (mb,), jnp.float32, 10.0, 500.0)
            f_th = jax.random.uniform(k3, (mb,), jnp.float32,
                                      jnp.deg2rad(60.0), jnp.deg2rad(120.0))
            return (_shard_batchdim(noise), _shard_batchdim(f_ep),
                    _shard_batchdim(f_th))

        def accum(loss_fn, params, xs):
            """Mean (loss, aux, grads) of ``loss_fn(params, x)`` over the
            leading microbatch axis of ``xs`` (lax.scan when M > 1)."""
            vg = jax.value_and_grad(loss_fn, has_aux=True)
            x0 = jax.tree.map(lambda v: v[0], xs)
            if M == 1:
                (l, aux), g = vg(params, x0)
                return l, aux, g
            sds = jax.eval_shape(vg, params, x0)
            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), sds)

            def body(acc, x):
                return jax.tree.map(jnp.add, acc, vg(params, x)), None

            ((l, aux), g), _ = jax.lax.scan(body, zeros, xs)
            return (l / M, jax.tree.map(lambda v: v / M, aux),
                    jax.tree.map(lambda v: v / M, g))

        real = jax.tree.map(
            lambda x: x.reshape(M, mb, *x.shape[1:]),
            {"image": img, "e_p": e_p, "theta": theta, "ecal": ecal})

        # scaling is only live when the state actually carries the
        # LossScaleState (a trace-time structure fact), so a state built
        # without the policy keeps the exact pre-policy program
        ls = state.loss_scale if scaling else None

        def phase(loss_fn, params, xs, opt_state, optimizer, ls):
            """One Algorithm-1 phase: accumulate grads, reduce, update.

            Under a scaling policy the loss is multiplied by the dynamic
            scale before the backward pass; the reduced UNSCALED grads
            are checked for finiteness (after the psum, so every replica
            agrees) and a nonfinite phase skips its update entirely.
            Returns (loss, aux, params, opt_state, ls, finite).
            """
            if wrap_params is not None:
                # overlap: each bucket's collective fires mid-backward;
                # psum is linear so reducing the SCALED grads then
                # unscaling matches the post-hoc order within rounding
                base_loss = loss_fn
                loss_fn = lambda p, x: base_loss(wrap_params(p), x)
            if ls is None:
                l, aux, g = accum(loss_fn, params, xs)
                with jax.named_scope(UPDATE):
                    upd, new_opt = optimizer.update(reduce_grads(g),
                                                    opt_state, params)
                    new_params = opt_lib.apply_updates(params, upd)
                return (l, aux, new_params, new_opt, None, jnp.float32(1.0))

            def scaled(p, x):
                l_, aux_ = loss_fn(p, x)
                return l_ * ls.scale, aux_

            l, aux, g = accum(scaled, params, xs)
            with jax.named_scope(UPDATE):
                g = reduce_grads(precision_lib.unscale(ls, g))
                finite = precision_lib.all_finite(g)
                upd, new_opt = optimizer.update(g, opt_state, params)
                new_params = precision_lib.select_finite(
                    finite, opt_lib.apply_updates(params, upd), params)
                new_opt = precision_lib.select_finite(finite, new_opt,
                                                      opt_state)
                ls2 = precision_lib.next_loss_scale(ls, finite,
                                                    policy.growth_interval)
            return (l / ls.scale, aux, new_params, new_opt, ls2,
                    finite.astype(jnp.float32))

        g_params_c = to_compute(state.g_params)   # fake-path G, nondiff

        # ---- D on real ------------------------------------------------
        def d_loss_real(dp, x):
            return gan.disc_loss(to_compute(dp), x["image"],
                                 (x["e_p"], x["theta"], x["ecal"]), cfg,
                                 real=True)
        with jax.named_scope("d_real"):
            d_lr, d_mr, d_params, d_opt, ls, fin_r = phase(
                d_loss_real, state.d_params, real, state.d_opt, d_optimizer,
                ls)

        # ---- D on fake (generation INSIDE the compiled program) -------
        def d_loss_fake(dp, k):
            noise, f_ep, f_th = sample_inputs(k)
            fake = gan.generate(g_params_c, noise, f_ep, f_th, cfg)
            return gan.disc_loss(to_compute(dp), jax.lax.stop_gradient(fake),
                                 (f_ep, f_th, f_ep * ecal_frac), cfg,
                                 real=False)
        with jax.named_scope("d_fake"):
            d_lf, d_mf, d_params, d_opt, ls, fin_f = phase(
                d_loss_fake, d_params, d_keys, d_opt, d_optimizer, ls)

        # ---- G twice ---------------------------------------------------
        def one_g(carry, ks):
            g_params, g_opt, ls = carry

            def loss(gp, k):
                noise, f_ep, f_th = sample_inputs(k)
                return gan.gen_loss(to_compute(gp), d_params_c, noise,
                                    (f_ep, f_th, f_ep * ecal_frac), cfg)
            g_l, _, g_params, g_opt, ls, fin = phase(
                loss, g_params, ks, g_opt, g_optimizer, ls)
            return (g_params, g_opt, ls), (g_l, fin)

        with jax.named_scope("g"):
            d_params_c = to_compute(d_params)     # G-phase D, nondiff
            (g_params, g_opt, ls), (g_ls, g_fins) = jax.lax.scan(
                one_g, (state.g_params, state.g_opt, ls), g_keys)

        new = GANState(g_params, d_params, g_opt, d_opt, state.step + 1,
                       ls if scaling else state.loss_scale)
        metrics = {"d_loss_real": d_lr, "d_loss_fake": d_lf,
                   "g_loss": jnp.mean(g_ls), "d_acc_real": d_mr["acc"],
                   "d_acc_fake": d_mf["acc"]}
        if ls is not None:
            n_phases = 2.0 + cfg.gen_steps_per_disc
            metrics["loss_scale"] = ls.scale
            metrics["nonfinite_skips"] = (
                n_phases - (fin_r + fin_f + jnp.sum(g_fins)))
        return new, metrics

    return fused_step
