"""Batching / feeding pipeline.

* :class:`ClientData` — one client's shard with an infinite shuffled batch
  stream (numpy-side; device transfer happens at the jit boundary).
* :func:`federate` — dataset -> Dirichlet-partitioned list of ClientData.
* :func:`round_batches` — stack (K, H, b, ...) arrays for
  ``device_round_step`` from a sampled cohort.
* :func:`client_pool` — flatten all clients into one (N_total, ...) pool
  + per-client offsets; uploaded once, the pool-fed round step gathers
  cohort batches on device from (K, H, b) int32 indices.
* :class:`Prefetcher` — background-thread prefetch of host batches so the
  accelerator step overlaps with batch assembly (the server phase's
  Algorithm-1 subprocess 2).
* :class:`DevicePrefetcher` — double-buffered host→device transfer: the
  next batch's ``jax.device_put`` runs in a background thread while the
  current step computes.  Fallback feeding path for the server phase
  when the consolidated pool exceeds the device-memory budget, and the
  upload path of ``generate_activations``.
"""

from __future__ import annotations

import queue
import threading
from typing import List, Optional

import numpy as np

from repro.data.partition import dirichlet_partition
from repro.data.synthetic import Dataset
from repro.observability.tracer import NULL_TRACER


class ClientData:
    def __init__(self, dataset: Dataset, client_id: int, seed: int = 0):
        self.dataset = dataset
        self.client_id = client_id
        self.rng = np.random.default_rng(seed * 100003 + client_id)
        self._order = np.arange(len(dataset))
        self._cursor = len(dataset)  # force shuffle on first use

    def __len__(self):
        return len(self.dataset)

    def next_indices(self, batch_size: int) -> np.ndarray:
        """Dataset-local sample indices of the next shuffled batch."""
        n = len(self.dataset)
        take = []
        need = batch_size
        while need > 0:
            if self._cursor >= n:
                self.rng.shuffle(self._order)
                self._cursor = 0
            got = min(need, n - self._cursor)
            take.append(self._order[self._cursor:self._cursor + got])
            self._cursor += got
            need -= got
        return np.concatenate(take)

    def next_batch(self, batch_size: int) -> dict:
        idx = self.next_indices(batch_size)
        return {k: v[idx] for k, v in self.dataset.arrays.items()}

    def batch_indices(self, batch_size: int, steps: int) -> np.ndarray:
        """(steps, b) dataset-local indices — the index-only twin of
        :meth:`batches`, for feeding a device-resident sample pool."""
        return np.stack([self.next_indices(batch_size)
                         for _ in range(steps)])

    def batches(self, batch_size: int, steps: int) -> dict:
        """(steps, b, ...) stacked batches."""
        bs = [self.next_batch(batch_size) for _ in range(steps)]
        return {k: np.stack([b[k] for b in bs]) for k in bs[0]}


def federate(dataset: Dataset, num_clients: int, alpha: float,
             seed: int = 0) -> List[ClientData]:
    rng = np.random.default_rng(seed)
    parts = dirichlet_partition(dataset.labels, num_clients, alpha, rng)
    return [ClientData(dataset.subset(ix), k, seed) for k, ix in enumerate(parts)]


def client_pool(clients: List[ClientData]):
    """Concatenate every client's samples into one flat pool.

    Returns ``(pool, offsets)``: ``pool`` is a dict of (N_total, ...)
    arrays, ``offsets[k]`` is client k's first row — a client's local
    index ``i`` lives at global row ``offsets[k] + i``.  Uploaded once,
    this is the device-resident sample store that
    :func:`repro.core.steps.make_device_round_pool_step` gathers cohort
    batches from (the per-round transfer drops from the full (K, H, b,
    ...) stack to a (K, H, b) int32 index matrix).
    """
    keys = list(clients[0].dataset.arrays)
    pool = {k: np.concatenate([c.dataset.arrays[k] for c in clients])
            for k in keys}
    offsets = np.cumsum([0] + [len(c) for c in clients])[:-1]
    return pool, offsets


def pool_nbytes(pool: dict) -> int:
    return int(sum(a.nbytes for a in pool.values()))


def round_batches(clients: List[ClientData], cohort_ids, local_steps: int,
                  batch_size: int) -> dict:
    """(K, H, b, ...) stacked batches for one federated round."""
    per_client = [clients[int(c)].batches(batch_size, local_steps)
                  for c in cohort_ids]
    return {k: np.stack([pc[k] for pc in per_client])
            for k in per_client[0]}


class Prefetcher:
    """Runs ``producer()`` in a background thread, buffering up to ``depth``
    batches; iteration yields until the producer raises StopIteration."""

    _DONE = object()

    def __init__(self, producer_iter, depth: int = 2):
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.error: Optional[BaseException] = None

        def run():
            try:
                for item in producer_iter:
                    self.q.put(item)
            except BaseException as e:  # propagate to consumer
                self.error = e
            finally:
                self.q.put(self._DONE)

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()

    def __iter__(self):
        while True:
            item = self.q.get()
            if item is self._DONE:
                if self.error is not None:
                    raise self.error
                return
            yield item


class DevicePrefetcher:
    """Double-buffered host→device prefetch.

    Wraps an iterator of ``(meta, tree)`` pairs: ``tree`` (any pytree of
    numpy arrays) is moved to device with ``jax.device_put`` in a
    background thread, up to ``depth`` items ahead of the consumer, so
    the upload of batch k+1 overlaps the computation on batch k.
    ``meta`` passes through untouched (client ids, host-side slices).
    Iteration yields ``(meta, device_tree)`` in producer order.  Each
    ``device_put`` is a ``feed.put`` span of ``tracer`` on ``track``, in
    the background thread.
    """

    def __init__(self, producer_iter, depth: int = 2, *,
                 tracer=NULL_TRACER, track: str = "feed"):
        import jax

        def put(item):
            meta, tree = item
            with tracer.span("feed.put", track=track):
                return meta, jax.device_put(tree)

        self._inner = Prefetcher(map(put, producer_iter), depth=depth)

    def __iter__(self):
        return iter(self._inner)
