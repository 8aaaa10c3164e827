"""Host-side data pipeline with device prefetch (paper §3).

The paper's final optimisation converts HDF5 to a native record format and
overlaps host batching/shuffling with accelerator compute.  The JAX-native
equivalent implemented here:

- `ShardStore`: fixed-size memmapped .npy shards on disk (the "TF Records"
  analogue — sequential reads, no per-item deserialisation),
- `prefetch` / `Prefetcher`: a double-buffered device prefetcher.  The
  PRODUCER thread issues `jax.device_put` (against the target sharding
  when given) for batch N+1 while the consumer's dispatched step N runs,
  so the host->device transfer rides under compute — and because
  `device_put` is asynchronous, the producer immediately returns to
  pulling batch N+2 from the host iterator.  The consumer only ever pops
  finished device arrays off a bounded queue; the time it spends BLOCKED
  on that queue is exactly the transfer/host time the overlap failed to
  hide, surfaced as ``Prefetcher.stats["h2d_wait_ms"]`` (the engine
  re-exposes it per logging window in ``Engine.last_fit_stats``) and, in
  a profiler trace, as one ``repro.prefetch.wait`` host span per batch.
"""
from __future__ import annotations

import os
import queue
import threading
import time
from typing import Iterator, Optional

import jax
import numpy as np


class ShardStore:
    """Directory of memmapped fixed-shape .npy shards."""

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)

    def write(self, name: str, arrays: dict):
        np.savez(os.path.join(self.root, f"{name}.npz"), **arrays)

    def shard_names(self):
        return sorted(f[:-4] for f in os.listdir(self.root)
                      if f.endswith(".npz"))

    def read(self, name: str) -> dict:
        with np.load(os.path.join(self.root, f"{name}.npz")) as z:
            return {k: z[k] for k in z.files}

    def iter_epoch(self, batch: int, shuffle_seed: Optional[int] = None):
        """Yield batches covering every record exactly once per epoch."""
        names = self.shard_names()
        rng = np.random.default_rng(shuffle_seed)
        if shuffle_seed is not None:
            names = list(rng.permutation(names))
        for name in names:
            data = self.read(name)
            n = len(next(iter(data.values())))
            order = rng.permutation(n) if shuffle_seed is not None else np.arange(n)
            for i in range(0, n - batch + 1, batch):
                idx = order[i:i + batch]
                yield {k: v[idx] for k, v in data.items()}


class Prefetcher:
    """Double-buffered device prefetch: producer-side ``device_put``.

    The producer thread pulls host batches, places them on device
    (sharded when ``sharding`` is given) and parks the resulting device
    arrays in a queue bounded at ``size`` — with ``size=2`` that is
    classic double buffering: transfer of batch N+1 overlaps the step
    consuming batch N.  Iterating yields batches in input order.

    ``stats`` (host-side, cheap):

    - ``h2d_wait_ms``  — total time the CONSUMER blocked waiting for a
      batch, i.e. transfer/host time compute did not hide (0 when the
      pipeline keeps up).  The same wait is a ``repro.prefetch.wait``
      span (``jax.profiler.TraceAnnotation``) in a profiler trace, on the
      clock of the device ops;
    - ``batches``      — batches yielded so far.

    Exceptions in the source iterator are re-raised to the consumer.
    """

    _DONE = object()

    def __init__(self, it: Iterator[dict], size: int = 2, sharding=None):
        self._q: queue.Queue = queue.Queue(maxsize=max(int(size), 1))
        self._sharding = sharding
        self.stats = {"h2d_wait_ms": 0.0, "batches": 0}
        self._thread = threading.Thread(
            target=self._produce, args=(it,), daemon=True)
        self._thread.start()

    def _place(self, batch):
        if self._sharding is not None:
            return jax.tree.map(
                lambda x, s: jax.device_put(x, s), batch, self._sharding)
        return jax.tree.map(jax.device_put, batch)

    def _produce(self, it):
        try:
            for batch in it:
                self._q.put(self._place(batch))
        except BaseException as e:        # surface in the consumer
            self._q.put((self._DONE, e))
            return
        self._q.put((self._DONE, None))

    def __iter__(self):
        return self

    def __next__(self):
        with jax.profiler.TraceAnnotation("repro.prefetch.wait"):
            t0 = time.perf_counter()
            item = self._q.get()
            self.stats["h2d_wait_ms"] += 1e3 * (time.perf_counter() - t0)
        if isinstance(item, tuple) and len(item) == 2 \
                and item[0] is self._DONE:
            self._q.put(item)             # keep raising on repeat next()
            if item[1] is not None:
                raise item[1]
            raise StopIteration
        self.stats["batches"] += 1
        return item


def prefetch(it: Iterator[dict], size: int = 2,
             sharding=None) -> Prefetcher:
    """Double-buffered host->device prefetch on a background thread."""
    return Prefetcher(it, size=size, sharding=sharding)
