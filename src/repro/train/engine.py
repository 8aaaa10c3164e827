"""Unified data-parallel training engine: the paper's two loop strategies.

The source paper's central comparison (§3-§4, Figs. 1-2) is between
TensorFlow's *built-in* distribution strategy (``MirroredStrategy`` /
``tf.distribute`` placing per-replica batches automatically) and a
*custom* training loop that controls exactly which elements land on each
worker.  This module is the JAX-native version of that comparison, built
from the pieces the repo already had:

- ``builtin`` loop — ``jax.jit`` + ``NamedSharding`` over the mesh's data
  axes.  The step is written as a GLOBAL-batch program; the XLA GSPMD
  partitioner decides how per-device batches are placed and inserts the
  gradient all-reduce itself (the ``tf.distribute`` analogue).
- ``custom`` loop — ``shard_map`` over the same mesh.  The step body is a
  PER-DEVICE program: each replica receives an explicitly-assigned batch
  shard, folds its replica index into the RNG so it draws its own
  generator inputs (the paper's "every replica initialises its own
  inputs"), computes local gradients, and reduces them with an explicit
  ``psum``-based mean before the (replicated) optimizer update.

Both loops share the rest of the paper's optimisations: the fully-fused
Algorithm-1 step (`core/adversarial.py`), gradient accumulation via
``microbatches``, mixed-precision policies (`substrate/precision.py`),
and double-buffered host->device prefetch (`data/pipeline.py`).

Public API
----------

``Task``
    A workload the engine can train: ``init(rng) -> state`` plus a
    ``make_step(grad_reduce, mesh)`` factory returning a pure
    ``step(state, batch, rng) -> (state, metrics)``.  Two constructors
    are provided: :func:`gan_task` (the paper's 3DGAN, Algorithm 1) and
    :func:`lm_task` (any LM arch via ``train/steps.py``).

``Engine``
    Binds a mesh and a loop mode, and compiles/runs tasks::

        from repro.launch.mesh import make_dev_mesh
        from repro.optim import optimizers as opt_lib
        from repro.train import engine as engine_lib
        from repro.configs import calo3dgan

        cfg = calo3dgan.reduced()
        task = engine_lib.gan_task(cfg, opt_lib.rmsprop(1e-4),
                                   opt_lib.rmsprop(1e-4))
        eng = engine_lib.Engine(make_dev_mesh(), loop="custom")
        state, metrics = eng.fit(task, sim.batches(cfg.batch_size),
                                 steps=100, rng=jax.random.key(0))

    Lower-level pieces (``init_state`` / ``compile_step`` / ``data_iter``)
    are exposed for benchmarks, and :meth:`Engine.build` produces an
    AOT-lowerable artifact for the multi-pod dry-run / weak-scaling
    compile studies.

The engine implements PURE data parallelism — parameters and optimizer
state replicated, batch sharded — which is exactly the paper's mirrored
strategy.  Model/FSDP sharding for the big LM archs keeps living in
``launch/build.py``; the engine is the substrate the scaling PRs
(multi-host, async checkpointing, pipeline stages) plug into.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Iterable, Iterator, Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.data import pipeline
from repro.parallel import collectives, sharding
from repro.train import metrics as metrics_lib
from repro.train import steps as steps_lib

LOOPS = ("builtin", "custom")

# batch leaves whose batch dimension is not dim 0 (mrope ``positions``
# carries batch on dim 1); tasks may override via Task.batch_dims
DEFAULT_BATCH_DIMS: Mapping[str, int] = {"positions": 1}


class LMState(NamedTuple):
    """Replicated LM train state carried through the engine loop."""
    params: Any
    opt_state: Any


@dataclasses.dataclass(frozen=True)
class Task:
    """A trainable workload, decoupled from how the engine distributes it.

    ``make_step(grad_reduce, mesh)`` must return a PURE function
    ``step(state, batch, rng) -> (state, metrics)``:

    - in the builtin loop it is called with ``grad_reduce=None`` and the
      real mesh (the step may place sharding constraints; GSPMD inserts
      gradient all-reduces automatically);
    - in the custom loop it is called with ``mesh=None`` and a
      ``grad_reduce`` callable (psum-mean over the data axes) that the
      step MUST apply to gradients before every optimizer update.
    """
    name: str
    init: Callable[[jax.Array], Any]
    make_step: Callable[..., Callable]
    batch_dims: Mapping[str, int] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_BATCH_DIMS))


def gan_task(cfg, g_optimizer, d_optimizer, *, policy=None,
             microbatches: int = 1) -> Task:
    """The paper's workload: 3DGAN Algorithm 1 as a fully-fused step.

    Example::

        task = gan_task(calo3dgan.config(), opt_lib.rmsprop(1e-4),
                        opt_lib.rmsprop(1e-4), policy=get_policy("bf16"))
    """
    from repro.core import adversarial

    def init(rng):
        return adversarial.init_state(rng, cfg, g_optimizer, d_optimizer,
                                      policy=policy)

    def make_step(grad_reduce=None, mesh=None):
        return adversarial.make_fused_step(
            cfg, g_optimizer, d_optimizer, mesh=mesh, policy=policy,
            grad_reduce=grad_reduce, microbatches=microbatches)

    return Task("gan", init, make_step)


def lm_task(model, cfg, optimizer, *, policy, microbatches: int = 1,
            remat: bool = True) -> Task:
    """Any LM architecture routed through ``steps.make_train_step``.

    The engine is pure data parallelism, so residual-stream sequence
    sharding stays off and params are replicated.

    Example::

        cfg = config_base.reduced_config("qwen2-1.5b")
        task = lm_task(api.get_model(cfg), cfg, opt_lib.adamw(3e-4),
                       policy=get_policy("bf16"))
    """

    def init(rng):
        params = model.init(rng, cfg)
        return LMState(params, optimizer.init(steps_lib.trainable(params)))

    def make_step(grad_reduce=None, mesh=None):
        inner = steps_lib.make_train_step(
            model, cfg, optimizer, policy, mesh=mesh, remat=remat,
            microbatches=microbatches, seq_shard=False,
            grad_reduce=grad_reduce)

        def step(state, batch, rng):
            del rng  # LM loss is deterministic given the batch
            params, opt_state, metrics = inner(state.params,
                                               state.opt_state, batch)
            return LMState(params, opt_state), metrics

        return step

    return Task("lm", init, make_step)


@dataclasses.dataclass
class Built:
    """AOT-lowerable step artifact (mirrors launch.build.BuiltStep)."""
    fn: Any                 # the jitted step
    args: tuple             # ShapeDtypeStruct args for .lower(*args)
    kind: str

    def lower(self):
        return self.fn.lower(*self.args)


class Engine:
    """Data-parallel training engine bound to one mesh and one loop mode.

    Parameters
    ----------
    mesh
        The device mesh.  Batches are sharded over its data axes
        (``("pod", "data")`` when present), params stay replicated.
    loop
        ``"builtin"`` (jit + NamedSharding, compiler-placed batches) or
        ``"custom"`` (shard_map, explicit per-device batches + psum).
    dp_axes
        Override which mesh axes carry the batch.  The GAN dry-run path
        uses ``tuple(mesh.axis_names)`` — every chip is a pure-DP
        replica, exactly as the paper runs 3DGAN on 256/512 chips.
    donate
        Donate the input state buffers to each step (default True).
    grad_reduce
        Reduction strategy for the gradients (``"flat"`` |
        ``"hierarchical"`` | ``"overlap"`` | a callable).  In the custom
        loop ``flat`` is the classic psum-mean over all data axes,
        ``hierarchical`` is the 2-level cluster schedule (intra-node psum
        over the fast axis, bucketed psums over the slow ``node`` axis —
        see ``collectives.make_grad_reduce``), and ``overlap`` issues the
        same hierarchical buckets in reverse parameter order from INSIDE
        the backward pass (``collectives.OverlapReduce`` — each bucket's
        collective fires as soon as its gradients exist); all are
        numerically interchangeable.  In the builtin loop GSPMD owns
        reduction placement (the paper's point about built-in
        strategies), so ``hierarchical`` only regroups the gradient
        stream into buckets (``collectives.bucket_transform``) and
        ``overlap`` does the same regrouping inside the backward
        (``collectives.overlap_transform``) — identity numerics either
        way.
    bucket_mb
        Inter-node bucket size in MiB for the hierarchical and overlap
        strategies.
    """

    def __init__(self, mesh: Mesh, loop: str = "builtin", *,
                 dp_axes: Optional[tuple] = None, donate: bool = True,
                 grad_reduce="flat", bucket_mb: float = 4.0):
        if loop not in LOOPS:
            raise ValueError(f"loop must be one of {LOOPS}, got {loop!r}")
        if (isinstance(grad_reduce, str)
                and grad_reduce not in collectives.GRAD_REDUCE_STRATEGIES):
            raise ValueError(
                f"grad_reduce must be one of "
                f"{collectives.GRAD_REDUCE_STRATEGIES} or a callable, "
                f"got {grad_reduce!r}")
        self.mesh = mesh
        self.loop = loop
        self.donate = donate
        self.grad_reduce = grad_reduce
        self.bucket_bytes = int(bucket_mb * (1 << 20))
        axes = dp_axes if dp_axes is not None else sharding.batch_axes(mesh)
        self.axes: tuple = tuple(axes) if axes else ()
        if grad_reduce == "hierarchical" and loop == "custom" \
                and len(self.axes) < 2:
            raise ValueError(
                "hierarchical grad_reduce needs a 2-level mesh "
                f"(node, device); this engine's data axes are {self.axes} "
                "— build the mesh with launch.mesh.make_node_mesh")
        self.n_shards = 1
        for a in self.axes:
            self.n_shards *= mesh.shape[a]
        # filled in by fit(): dispatch + input-pipeline observability for
        # the async loop (h2d_wait_ms = consumer-side stall the prefetch
        # overlap failed to hide, per logging window and in total)
        self.last_fit_stats = {"steps": 0, "host_transfers": 0,
                               "h2d_wait_ms": 0.0, "h2d_wait_ms_windows": []}

    # -- batch placement ----------------------------------------------------

    def batch_pspecs(self, batch_like: Mapping[str, Any],
                     batch_dims: Optional[Mapping[str, int]] = None) -> dict:
        """PartitionSpec per batch leaf: data axes on the batch dim.

        In the builtin loop a leaf whose batch dim does not divide the
        data-axis size is silently replicated (GSPMD handles it); the
        custom loop requires exact divisibility — per-device batch
        assignment is the point — and raises ``ValueError`` otherwise.
        """
        dims = dict(DEFAULT_BATCH_DIMS, **(batch_dims or {}))
        out = {}
        for k, v in batch_like.items():
            bdim = dims.get(k, 0)
            entries = [None] * len(v.shape)
            divisible = self.axes and v.shape[bdim] % self.n_shards == 0
            if self.axes and not divisible and self.loop == "custom":
                raise ValueError(
                    f"custom loop requires batch dim {bdim} of {k!r} "
                    f"(= {v.shape[bdim]}) divisible by the "
                    f"{self.n_shards} data shards")
            if divisible and v.shape[bdim] > 1:
                entries[bdim] = (self.axes if len(self.axes) > 1
                                 else self.axes[0])
            out[k] = P(*entries)
        return out

    def batch_shardings(self, batch_like: Mapping[str, Any],
                        batch_dims: Optional[Mapping[str, int]] = None) -> dict:
        """NamedSharding per batch leaf — feed to ``pipeline.prefetch``."""
        return {k: NamedSharding(self.mesh, s)
                for k, s in self.batch_pspecs(batch_like, batch_dims).items()}

    def data_iter(self, batches: Iterable[dict], *, size: int = 2,
                  batch_dims: Optional[Mapping[str, int]] = None) -> Iterator[dict]:
        """Double-buffered host->device prefetch with per-mode sharding.

        Wraps ``data.pipeline.prefetch``: the producer thread issues the
        ``device_put`` for the NEXT batch (sharded over the data axes)
        while the CURRENT step runs — the paper's host/accelerator
        overlap, identical for both loops.  The returned ``Prefetcher``
        exposes ``stats["h2d_wait_ms"]`` (consumer stalls).
        """
        it = iter(batches)
        try:
            first = next(it)
        except StopIteration:
            return pipeline.prefetch(iter(()))
        shardings = self.batch_shardings(first, batch_dims)
        return pipeline.prefetch(itertools.chain([first], it), size=size,
                                 sharding=shardings)

    # -- state & step compilation -------------------------------------------

    def state_pspecs(self, state_like):
        """PartitionSpec per state leaf: replicated everywhere EXCEPT
        ZeRO-1 shard-major leaves — arrays under an optimizer's
        ``"zero1"`` subtree whose leading dim equals the data-shard count
        (`optim.optimizers.zero1`'s ``(N, L)`` layout) are sharded over
        the data axes on dim 0.  That placement is the ZeRO-1 memory
        story: each device physically holds 1/N of the master params and
        optimizer moments."""
        if not self.axes or self.n_shards <= 1:
            return jax.tree.map(lambda _: P(), state_like)
        ax = self.axes if len(self.axes) > 1 else self.axes[0]

        def spec(path, leaf):
            in_zero1 = any(getattr(e, "key", None) == "zero1" for e in path)
            if in_zero1 and getattr(leaf, "ndim", 0) >= 1 \
                    and leaf.shape[0] == self.n_shards:
                return P(ax)
            return P()

        return jax.tree_util.tree_map_with_path(spec, state_like)

    def _state_shardings(self, state_like):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.state_pspecs(state_like),
                            is_leaf=lambda x: isinstance(x, P))

    def init_state(self, task: Task, rng: jax.Array):
        """Initialise the task state: replicated over the whole mesh,
        except ZeRO-1 state shards (see :meth:`state_pspecs`)."""
        state = task.init(rng)
        return jax.device_put(state, self._state_shardings(state))

    def _grad_reduce(self, tree):
        """Explicit gradient reduction for the custom loop, per strategy:
        flat psum-mean over all data axes, or the hierarchical 2-level
        bucketed schedule (collectives.make_grad_reduce)."""
        if not self.axes:
            return tree
        fn = collectives.make_grad_reduce(self.grad_reduce, self.mesh,
                                          self.axes,
                                          bucket_bytes=self.bucket_bytes)
        return fn(tree)

    def compile_step(self, task: Task, batch_like: Mapping[str, Any]):
        """Compile ``step(state, batch, rng) -> (state, metrics)``.

        ``batch_like`` fixes the batch pytree (real arrays or
        ``ShapeDtypeStruct`` leaves are both fine — only shapes are read).
        State and metrics are replicated in both modes; the returned
        callable donates its state argument when ``donate=True``.
        """
        rep = NamedSharding(self.mesh, P())
        b_specs = self.batch_pspecs(batch_like, task.batch_dims)
        b_shard = {k: NamedSharding(self.mesh, s) for k, s in b_specs.items()}
        donate = (0,) if self.donate else ()
        # state placement: replicated except ZeRO-1 shard-major leaves
        state_shapes = jax.eval_shape(lambda: task.init(jax.random.key(0)))
        s_specs = self.state_pspecs(state_shapes)
        s_shard = self._state_shardings(state_shapes)

        if self.loop == "builtin":
            # GSPMD inserts the gradient all-reduce itself; hierarchical
            # mode only re-expresses the grads at bucket granularity.
            # A user-supplied callable is honored exactly as in the
            # custom loop.
            if callable(self.grad_reduce):
                reduce = self.grad_reduce
            elif self.grad_reduce == "hierarchical":
                reduce = collectives.bucket_transform(self.bucket_bytes)
            elif self.grad_reduce == "overlap":
                reduce = collectives.overlap_transform(self.bucket_bytes)
            else:
                reduce = None
            step = task.make_step(grad_reduce=reduce, mesh=self.mesh)
            return jax.jit(step, in_shardings=(s_shard, b_shard, rep),
                           out_shardings=(s_shard, rep),
                           donate_argnums=donate)

        # the reducer OBJECT is passed through (not a bound method) so
        # the overlap strategy's wrap_params protocol reaches the step
        reduce = (collectives.make_grad_reduce(
            self.grad_reduce, self.mesh, self.axes,
            bucket_bytes=self.bucket_bytes) if self.axes
            else (self.grad_reduce if callable(self.grad_reduce) else None))
        local = task.make_step(grad_reduce=reduce, mesh=None)
        axes, shape = self.axes, dict(self.mesh.shape)

        def local_step(state, batch, rng):
            if axes:
                # each replica draws its OWN generator inputs (paper §3)
                idx = jnp.int32(0)
                for a in axes:
                    idx = idx * shape[a] + jax.lax.axis_index(a)
                rng = jax.random.fold_in(rng, idx)
            state, metrics = local(state, batch, rng)
            if axes:    # per-replica scalars -> global means for logging
                metrics = jax.lax.pmean(metrics, axes)
            return state, metrics

        smapped = jax.shard_map(local_step, mesh=self.mesh,
                                in_specs=(s_specs, b_specs, P()),
                                out_specs=(s_specs, P()), check_vma=False)
        return jax.jit(smapped, in_shardings=(s_shard, b_shard, rep),
                       out_shardings=(s_shard, rep), donate_argnums=donate)

    def build(self, task: Task, batch_shapes: Mapping[str, Any]) -> Built:
        """AOT artifact: jitted step + ShapeDtypeStruct args for .lower().

        Used by the weak-scaling benchmark and the multi-pod dry-run to
        compile either loop for meshes far larger than this host.
        """
        fn = self.compile_step(task, batch_shapes)
        state_shapes = jax.eval_shape(lambda: task.init(jax.random.key(0)))
        rng_shape = jax.eval_shape(lambda: jax.random.key(0))
        return Built(fn, (state_shapes, batch_shapes, rng_shape),
                     f"{task.name}_{self.loop}")

    # -- the training loop ---------------------------------------------------

    def fit(self, task: Task, batches: Iterable[dict], steps: int, *,
            rng: jax.Array, state=None, log=None, log_every: int = 1,
            sync_every: Optional[int] = None, prefetch_size: int = 2,
            start_step: int = 0, hooks: tuple = ()):
        """Run ``steps`` training steps; returns (state, last_metrics).

        Composes the whole paper pipeline: replicated init, compiled
        step (builtin or custom), sharded double-buffered prefetch, and
        windowed metric logging via ``log.log(i, **window_means)``.

        The loop is ASYNC-DISPATCH: per-step metrics are folded into
        device-side sums (`metrics_lib.MetricAccumulator`) and the host
        transfer happens once every ``log_every`` steps, so with
        ``log_every > 1`` no step blocks on a device->host sync — the
        device runs ahead of the Python loop and the prefetch overlap the
        engine was built for actually materialises.  ``log_every=1``
        reproduces the old per-step logging cadence.

        ``sync_every`` is the escape hatch: force a device sync every N
        steps to bound run-ahead (keeps the dispatch queue shallow and
        device errors attributable) independently of the logging window.

        **Elastic resume.**  Per-step RNG is BIT-PINNED to the global step
        index: the fit key splits once into (init_key, step_rng) and step
        ``g`` always uses ``fold_in(step_rng, g)`` — a pure function of
        (rng, g), independent of how many fit() calls the run was chopped
        into.  A preempted run that restores checkpointed ``state`` and
        passes ``start_step=<completed steps>`` with the SAME ``rng``
        replays the exact key sequence the uninterrupted run would have
        used (`train/elastic.py` relies on this for bit-identical
        recovery).  ``hooks`` are callables ``hook(global_step, state)``
        invoked after each step's dispatch (async, non-blocking) — the
        async checkpointer's cadence hook and the fault injector's
        corrupt hook plug in here.

        ``self.last_fit_stats`` records {"steps", "host_transfers",
        "h2d_wait_ms", "h2d_wait_ms_windows"} for the most recent fit —
        the dispatch-count observability the async tests assert on, plus
        the per-window consumer stall of the device prefetcher (time a
        step had to WAIT for its batch; ~0 when the producer-side
        ``device_put`` fully overlaps compute; each wait is also a
        ``repro.prefetch.wait`` span in a profiler trace).
        """
        if log_every < 1:
            raise ValueError(f"log_every must be >= 1, got {log_every}")
        it = iter(batches)
        try:
            first = next(it)
        except StopIteration:
            raise ValueError("fit() got an empty batches iterable") from None
        step = self.compile_step(task, first)
        # init/step keys derive from ONE split of the fit key; per-step
        # keys fold in the GLOBAL step index so a resumed fit (same rng,
        # start_step = completed steps) replays the identical sequence
        init_key, step_rng = jax.random.split(rng)
        if state is None:
            state = self.init_state(task, init_key)
        stream = self.data_iter(itertools.chain([first], it),
                                size=prefetch_size,
                                batch_dims=task.batch_dims)
        metrics: dict = {}
        acc = metrics_lib.MetricAccumulator()
        transfers = 0
        last = -1
        h2d_windows: list = []
        h2d_marked = 0.0

        def _close_window():
            nonlocal h2d_marked
            waited = stream.stats["h2d_wait_ms"]
            h2d_windows.append(waited - h2d_marked)
            h2d_marked = waited

        for i, batch in zip(range(steps), stream):
            last = i
            gstep = start_step + i
            k = jax.random.fold_in(step_rng, gstep)
            state, metrics = step(state, batch, k)
            for hook in hooks:
                hook(gstep, state)
            if log is not None:
                acc.update(metrics)
                if (i + 1) % log_every == 0 or i == steps - 1:
                    log.log(gstep, **acc.means())  # ONE transfer per window
                    transfers += 1
                    acc.reset()
                    _close_window()
            if sync_every is not None and (i + 1) % sync_every == 0:
                jax.block_until_ready(metrics)
        if log is not None and acc.count:
            # the batch stream ran dry before ``steps``: flush the
            # trailing partial window so no step goes unlogged
            log.log(start_step + last, **acc.means())
            transfers += 1
            _close_window()
        self.last_fit_stats = {
            "steps": last + 1, "host_transfers": transfers,
            "h2d_wait_ms": stream.stats["h2d_wait_ms"],
            "h2d_wait_ms_windows": h2d_windows,
        }
        return state, metrics
