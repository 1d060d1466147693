"""Ring-backed activation store: the streaming drop-in for
:class:`~repro.data.activation_store.ActivationStore`.

Device actors produce prepared shards into an
:class:`~repro.streaming.ring.ActivationRing`; the learner side drains
committed segments into the same in-memory shard table the legacy store
builds, so every downstream surface (``pool`` / ``epoch_indices`` /
``batches`` / ``pool_nbytes``) is inherited *unchanged* — a streaming
run consumes the identical pool bytes in the identical order, which is
what keeps its history byte-identical to the phase-serialized run.

What changes is the data plane and the time plane:

* shards round-trip through CRC-committed ring segments (memmap
  segments stay on disk as the pool's backing storage), and
* each segment carries its simulated *arrival time* — the per-sample
  arrival array :meth:`sample_arrivals` feeds the
  :class:`~repro.streaming.overlap.OverlapAccountant` so server epochs
  can overlap the device round in accounted ``sim_time``.

In the single-process simulator the producer and consumer interleave
deterministically: ``submit`` tries a non-blocking ring put and, on
backpressure, drains a seeded :class:`~repro.streaming.overlap.
InterleaveSchedule`-sized chunk of segments itself before retrying —
occupancy and stall statistics replay exactly for a given seed.  Real
producer/consumer threads use the ring's blocking surface directly.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from repro.data.activation_store import ActivationStore
from repro.observability import NULL_OBS
from repro.streaming.overlap import InterleaveSchedule
from repro.streaming.ring import ActivationRing


class StreamingActivationStore(ActivationStore):
    """ActivationStore whose receive path is a backpressured ring."""

    def __init__(self, directory: Optional[str] = None,
                 consolidated: bool = True, quantize_int8: bool = False,
                 seed: int = 0, *, capacity_segments: int = 64,
                 low_watermark: Optional[int] = None,
                 backend: str = "memmap", drain_chunk: int = 4,
                 interleave_seed: int = 0, fault_plan=None, obs=None):
        # base gets directory=None: the ring owns all disk I/O (the
        # legacy .npz side-writes would double every shard on disk)
        super().__init__(directory=None, consolidated=consolidated,
                         quantize_int8=quantize_int8, seed=seed)
        self.obs = obs if obs is not None else NULL_OBS
        self.ring = ActivationRing(
            directory=directory, capacity_segments=capacity_segments,
            low_watermark=low_watermark, backend=backend,
            fault_plan=fault_plan, obs=self.obs, name="acts")
        self.schedule = InterleaveSchedule(seed=interleave_seed,
                                           drain_chunk=drain_chunk)
        # (n_samples, t_arrival) per stored shard, in pool order
        self.arrivals: List[Tuple[int, float]] = []
        self._next_seq = 0

    # ------------------------------------------------------------------
    # producer side (device actors)
    # ------------------------------------------------------------------
    def start_writer(self, tracer=None):
        """No writer thread: the ring IS the async boundary."""

    def submit(self, client_id: int, shard: dict, t_arrival: float = 0.0,
               cut: Optional[int] = None):
        shard, nbytes = self.prepare_shard(shard, self.quantize)
        assert nbytes == self.shard_nbytes(shard, self.quantize)
        while not self.ring.try_put(int(client_id), shard,
                                    t_arrival=t_arrival,
                                    cut=-1 if cut is None else int(cut)):
            # backpressure: the learner drains a seeded chunk of the
            # oldest committed segments, reopening the gate at the low
            # watermark — deterministic single-process interleaving
            self.drain(self.schedule.next_drain())

    def add(self, client_id: int, shard: dict, cut: Optional[int] = None):
        self.submit(client_id, shard, cut=cut)

    def finish(self):
        self.ring.close()
        self.drain()
        self._closed.set()

    close = finish

    # ------------------------------------------------------------------
    # consumer side (server learner)
    # ------------------------------------------------------------------
    def drain(self, max_segments: Optional[int] = None) -> int:
        """Move up to ``max_segments`` committed segments into the shard
        table (all of them when ``None``).  Decoded arrays are zero-copy
        views onto the segment storage — for the memmap backend the pool
        keeps streaming from disk."""
        n = 0
        while ((max_segments is None or n < max_segments)
               and self.ring.next_committed(self._next_seq)):
            meta, shard = self.ring.read(self._next_seq)
            nbytes = sum(np.asarray(v).nbytes for v in shard.values())
            with self._lock:
                self._mem.setdefault(meta.client, []).append(shard)
                self._cut_tags.setdefault(meta.client, []).append(
                    None if meta.cut < 0 else int(meta.cut))
                self.bytes_received += nbytes
                self.arrivals.append((meta.n_samples, meta.t_arrival))
            self.ring.ack(self._next_seq)
            self._next_seq += 1
            n += 1
        return n

    def sample_arrivals(self) -> np.ndarray:
        """Per-pool-row simulated arrival time, aligned with the pool's
        concatenation order (shard drain order == submit order)."""
        with self._lock:
            arr = list(self.arrivals)
        if not arr:
            return np.zeros((0,), np.float64)
        return np.repeat(np.asarray([t for _, t in arr], np.float64),
                         [n for n, _ in arr])
