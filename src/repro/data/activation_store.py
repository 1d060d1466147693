"""Activation consolidation store (Ampere §3.2.3 + Algorithm 1 lines 16-19).

The server runs two asynchronous subprocesses: one *stores* incoming client
activation shards, the other *loads* batches for server-block training —
training starts as soon as the first shard lands, never waiting for the
full consolidation.

Modes:
* ``consolidated=True``  (Ampere)   — one unified pool 𝒜; batches are
  sampled across all clients' activations.
* ``consolidated=False`` (ablation) — per-client pools; the trainer holds
  K server blocks, each fed from one client's pool, aggregated like SFL
  (Fig. 11's "w/o consolidation" arm).

Backends: in-memory (CPU experiments) or disk shards
(``<dir>/client_<k>_<i>.npz``, atomic rename) with optional int8
quantization of the payload (beyond-paper, cuts the one-shot transfer 4x
vs fp32 — accounted in the comm model).

Heterogeneous cuts: each shard may carry a *cut depth* tag (the layer its
activations were produced at).  Tags live in a parallel in-memory index —
shard payloads stay byte-identical to the untagged path — and every pool
surface (``pool`` / ``num_samples`` / ``epoch_indices``) accepts
``cut=`` to address one depth bucket, so the trainer can run server
epochs with per-bucket entry points.  Disk shards do not persist tags;
``load_store`` restarts are uniform-cut only.
"""

from __future__ import annotations

import functools
import os
import queue
import threading
from typing import Dict, List, Optional

import numpy as np

from repro.observability.tracer import NULL_TRACER


class ActivationStore:
    def __init__(self, directory: Optional[str] = None,
                 consolidated: bool = True, quantize_int8: bool = False,
                 seed: int = 0, queue_depth: int = 64):
        self.dir = directory
        self.consolidated = consolidated
        self.quantize = quantize_int8
        self.rng = np.random.default_rng(seed)
        self._mem: Dict[int, List[dict]] = {}
        # cut-depth tag per shard, parallel to _mem (None = untagged)
        self._cut_tags: Dict[int, List[Optional[int]]] = {}
        self._lock = threading.Lock()
        # bounded: a producer outrunning the writer blocks on put() —
        # legacy mode exerts backpressure too, not just the ring store
        self._q: "queue.Queue" = queue.Queue(maxsize=queue_depth)
        self._writer: Optional[threading.Thread] = None
        self._closed = threading.Event()
        self.bytes_received = 0
        # bytes concatenated into whole pools by _pool: 0 while training
        # only streams epochs, which gather from the shards
        self.pool_concat_bytes = 0
        if directory:
            os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------------
    # Subprocess 1: receive & store
    # ------------------------------------------------------------------
    def start_writer(self, tracer=NULL_TRACER):
        """Start the writer thread; each shard it stores is a
        ``store.write`` span of ``tracer``."""
        # non-daemon: close()/finish() joins it, so the writer can never
        # race interpreter teardown mid-.npz-write
        if self._writer is None:
            self._writer = threading.Thread(target=self._writer_loop,
                                            args=(tracer,), daemon=False)
            self._writer.start()

    def _writer_loop(self, tracer):
        while True:
            item = self._q.get()
            if item is None:
                break
            with tracer.span("store.write", track="transfer/writer"):
                self._store(*item)

    def submit(self, client_id: int, shard: dict,
               cut: Optional[int] = None):
        """Async upload path (used with start_writer)."""
        self._q.put((client_id, shard, cut))

    def finish(self):
        if self._writer is not None:
            self._q.put(None)
            self._writer.join()
            self._writer = None
        self._closed.set()

    # close() is the lifecycle name (join the writer, release the store);
    # finish() remains the Algorithm-1 name for the same transition
    close = finish

    def add(self, client_id: int, shard: dict, cut: Optional[int] = None):
        """Synchronous upload (tests / simple drivers)."""
        self._store(client_id, shard, cut)

    @staticmethod
    def shard_nbytes(shard: dict, quantize: bool) -> int:
        """Stored bytes for ``shard`` under ``quantize`` — the analytic
        mirror of :meth:`_store`'s accounting (asserted there), used by
        the transport layer to price a shard before/without storing it."""
        acts = np.asarray(shard["acts"])
        if quantize:
            nbytes = acts.size + (acts.size // acts.shape[-1]) * 4
        else:
            nbytes = acts.size * 4
        return nbytes + sum(np.asarray(v).nbytes for k, v in shard.items()
                            if k not in ("acts", "acts_scale"))

    @staticmethod
    def prepare_shard(shard: dict, quantize: bool):
        """Normalize one shard for storage: fp32 payload or int8 + scale.

        Returns ``(prepared_shard, stored_nbytes)``; shared by the legacy
        in-RAM path and the streaming ring so both store byte-identical
        arrays.
        """
        shard = dict(shard)
        acts = np.asarray(shard["acts"])
        if quantize:
            scale = np.abs(acts).max(axis=-1, keepdims=True) / 127.0
            scale = np.maximum(scale, 1e-12)
            q = np.clip(np.round(acts / scale), -127, 127).astype(np.int8)
            shard["acts"] = q
            shard["acts_scale"] = scale.astype(np.float32)
            nbytes = q.nbytes + shard["acts_scale"].nbytes
        else:
            shard["acts"] = acts.astype(np.float32)
            nbytes = shard["acts"].nbytes
        nbytes += sum(np.asarray(v).nbytes for k, v in shard.items()
                      if k not in ("acts", "acts_scale"))
        return shard, nbytes

    def _store(self, client_id: int, shard: dict,
               cut: Optional[int] = None):
        shard, nbytes = self.prepare_shard(shard, self.quantize)
        assert nbytes == self.shard_nbytes(shard, self.quantize)
        with self._lock:
            self._mem.setdefault(int(client_id), []).append(shard)
            self._cut_tags.setdefault(int(client_id), []).append(
                None if cut is None else int(cut))
            self.bytes_received += nbytes
        if self.dir:
            i = len(self._mem[int(client_id)]) - 1
            tmp = os.path.join(self.dir, f".tmp_{client_id}_{i}.npz")
            final = os.path.join(self.dir, f"client_{client_id}_{i}.npz")
            np.savez(tmp, **shard)
            os.replace(tmp, final)

    # ------------------------------------------------------------------
    # Subprocess 2: load for training
    # ------------------------------------------------------------------
    def _shards(self, client_id: Optional[int] = None,
                cut: Optional[int] = None) -> List[dict]:
        """Snapshot of the shard list (all clients or one, optionally one
        cut bucket) under the lock — the single source for pool assembly,
        counting and sizing.  Client iteration keeps dict insertion order
        so the consolidated pool layout is unchanged by the tag index."""
        with self._lock:
            cids = list(self._mem) if client_id is None else [int(client_id)]
            out = []
            for c in cids:
                lst = self._mem.get(c, [])
                if cut is None:
                    out.extend(lst)
                    continue
                tags = self._cut_tags.get(c, [])
                out.extend(s for i, s in enumerate(lst)
                           if (tags[i] if i < len(tags) else None) == cut)
            return out

    def cut_depths(self) -> List[int]:
        """Sorted distinct cut tags present (untagged shards excluded)."""
        with self._lock:
            return sorted({t for tags in self._cut_tags.values()
                           for t in tags if t is not None})

    def _pool(self, client_id: Optional[int] = None,
              cut: Optional[int] = None) -> dict:
        shards = self._shards(client_id, cut)
        if not shards:
            return {}
        keys = shards[0].keys()
        pool = {k: np.concatenate([s[k] for s in shards]) for k in keys}
        with self._lock:
            self.pool_concat_bytes += sum(v.nbytes for v in pool.values())
        return pool

    def pool(self, client_id: Optional[int] = None,
             dequantize: bool = False, cut: Optional[int] = None) -> dict:
        """The full consolidated (or per-client / per-cut) pool as one
        dict of arrays.  With ``dequantize=False`` an int8 payload stays
        quantized (plus its ``acts_scale``) — the device-resident server
        phase uploads it as-is and dequantizes inside the jitted step."""
        p = self._pool(client_id, cut)
        return self._dequant(p) if (dequantize and p) else p

    def pool_nbytes(self, client_id: Optional[int] = None) -> int:
        """Bytes the (quantized) pool occupies — the device-memory
        admission check for the resident server phase.  Summed per shard
        (a concatenated pool has exactly the same byte count) so the
        check never copies the data."""
        return sum(np.asarray(v).nbytes
                   for s in self._shards(client_id) for v in s.values())

    def epoch_indices(self, batch_size: int,
                      client_id: Optional[int] = None,
                      cut: Optional[int] = None) -> np.ndarray:
        """(nb, batch_size) int32 gather indices for one shuffled epoch.

        Consumes exactly one ``rng.permutation`` — the same draw (and the
        same batch membership, trailing remainder dropped) as one
        :meth:`batches` epoch, so a store seeded identically yields
        bit-identical batch order on either path.  With ``cut=`` the
        indices address that bucket's pool; callers draw buckets in
        sorted-depth order so the rng stream stays deterministic."""
        n = self.num_samples(client_id, cut)
        order = self.rng.permutation(n)
        nb = n // batch_size
        return order[:nb * batch_size].reshape(nb, batch_size).astype(np.int32)

    def num_samples(self, client_id: Optional[int] = None,
                    cut: Optional[int] = None) -> int:
        return sum(len(s["acts"]) for s in self._shards(client_id, cut))

    def clients(self) -> List[int]:
        with self._lock:
            return sorted(self._mem)

    def _dequant(self, batch: dict) -> dict:
        if "acts_scale" in batch:
            batch = dict(batch)
            batch["acts"] = (batch["acts"].astype(np.float32)
                             * batch["acts_scale"])
            del batch["acts_scale"]
        return batch

    def _one_epoch(self, shards: List[dict], batch_size: int,
                   dequantize: bool):
        """One shuffled pass over the shard snapshot ``shards`` — the
        single batching loop both :meth:`batches` and
        :meth:`streaming_batches` draw from, and the rng contract
        :meth:`epoch_indices` mirrors (one permutation per epoch,
        trailing remainder dropped).

        Each batch is gathered straight from the shards it touches: a
        row of the permutation addresses the shards' concatenation
        order, the cumulative shard sizes map it to (shard, local row),
        and one fancy index per shard touched fills a fresh array per
        key.  Batches are bit-identical to indexing the concatenated
        pool, which is never built."""
        starts = np.cumsum([0] + [len(s["acts"]) for s in shards])
        n = int(starts[-1])
        order = self.rng.permutation(n)
        # per key: the dtype and row shape np.concatenate would give
        spec = {k: (functools.reduce(np.promote_types,
                                     [s[k].dtype for s in shards]),
                    shards[0][k].shape[1:]) for k in shards[0]}
        for s in range(0, n - batch_size + 1, batch_size):
            idx = order[s:s + batch_size]
            sid = np.searchsorted(starts, idx, side="right") - 1
            local = idx - starts[sid]
            pos = np.argsort(sid, kind="stable")
            bounds = np.flatnonzero(np.diff(sid[pos])) + 1
            b = {k: np.empty((batch_size,) + shape, dtype)
                 for k, (dtype, shape) in spec.items()}
            for p in np.split(pos, bounds):
                shard, rows = shards[sid[p[0]]], local[p]
                for k, v in b.items():
                    v[p] = shard[k][rows]
            yield self._dequant(b) if dequantize else b

    def batches(self, batch_size: int, epochs: int = 1,
                client_id: Optional[int] = None, dequantize: bool = True):
        """Yield shuffled batches over the (consolidated or per-client)
        pool for ``epochs`` passes, each gathered from one snapshot of
        the shard list, not from a concatenated pool."""
        shards = self._shards(None if self.consolidated and client_id is None
                              else client_id)
        if not shards:
            return
        for _ in range(epochs):
            yield from self._one_epoch(shards, batch_size, dequantize)

    def streaming_batches(self, batch_size: int, poll: float = 0.01,
                          dequantize: bool = True):
        """Train-while-receiving: yields batches from whatever has arrived
        so far, each epoch gathered from a snapshot of the shard list
        (not a concatenated pool); completes one final full epoch over
        the COMPLETE pool after ``finish()`` — shards that landed after
        the last mid-stream snapshot are guaranteed at least one
        epoch."""
        import time

        def rows(shards):
            return sum(len(s["acts"]) for s in shards)

        while not self._closed.is_set():
            shards = self._shards()
            if rows(shards) >= batch_size:
                yield from self._one_epoch(shards, batch_size, dequantize)
            else:
                time.sleep(poll)
        # finish() joins the writer before setting _closed, so this
        # snapshot is the final pool: one guaranteed full epoch over it.
        shards = self._shards()
        if rows(shards) >= batch_size:
            yield from self._one_epoch(shards, batch_size, dequantize)


def load_store(directory: str, consolidated: bool = True,
               seed: int = 0) -> ActivationStore:
    """Rebuild a store from disk shards (server restart path)."""
    st = ActivationStore(directory=None, consolidated=consolidated, seed=seed)
    for fname in sorted(os.listdir(directory)):
        if not fname.startswith("client_") or not fname.endswith(".npz"):
            continue
        client_id = int(fname.split("_")[1])
        with np.load(os.path.join(directory, fname)) as z:
            shard = {k: z[k] for k in z.files}
        with st._lock:
            st._mem.setdefault(client_id, []).append(shard)
    return st
