"""Consolidation: ``AmpereTrainer.generate_activations``, the one-shot
handoff between the phases — every client's samples through the device
block, copied to the host and stored in a fresh ``ActivationStore``
with ``upload="serial"``, as ``AmpereSystem.run`` calls it.

Set-up makes two calls: the first warms every batch shape (each
client's tail batch has a size of its own), the second is timed to size
the window.  The window is as many complete calls as fill ``--seconds``,
each into a new store and timed alone: a job consolidates once, so
dropping the previous call's 4.92 GB store is the harness's work and
happens between the timed calls.  The check reads back the last store:
every client's sample count and labels exactly, and the activations of
a sample of rows drawn from the seed against the reference's device
block.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

SAMPLE_ROWS = 512


def _call(h):
    """One timed call into a new store; returns its seconds."""
    from repro.data.activation_store import ActivationStore

    run = h.b.spec.run
    h.store = None        # at most one pool on the host at a time
    gc.collect()
    t0 = time.perf_counter()
    store = ActivationStore(directory=None, consolidated=True,
                            quantize_int8=run.split.quantize_activations,
                            seed=run.seed)
    h.b.trainer.generate_activations(h.b.dev_state, store, upload="serial")
    dt = time.perf_counter() - t0
    h.store = store
    return dt


def setup(h):
    from harness.build import sample_keys

    h.per_call = sum(len(c) for c in h.b.clients)
    h.inp_key, h.lab_key = sample_keys(h.b.model)


def prime(h):
    _call(h)
    h.call_s = _call(h)
    _read_back(h)


def size(h, seconds):
    h.units = max(1, math.ceil(seconds / h.call_s))


def window(h):
    t0 = time.perf_counter()
    calls = [_call(h) for _ in range(h.units)]
    dt = time.perf_counter() - t0
    _read_back(h)
    return ({"consolidate_s": sum(calls) / len(calls)},
            {"attempted": h.units, "failed": 0, "window_s": dt,
             "samples": h.units * h.per_call, "units": len(calls)})


def _read_back(h):
    """What the last store holds: per-client counts and labels, and the
    sampled rows' activations (host copies, taken before the program's
    state is freed)."""
    from harness.build import derive_seed

    clients = h.b.clients
    rng = np.random.default_rng(derive_seed(h.seed, "consolidate_rows"))
    n = len(clients)
    picks = rng.integers(0, h.per_call, SAMPLE_ROWS)
    offsets = np.cumsum([0] + [len(c) for c in clients])
    counts_bad = labels_bad = 0
    h.rows, acts = [], []
    for k in range(n):
        shard = h.store.pool(client_id=clients[k].client_id)
        got = len(shard.get("acts", ()))
        want = len(clients[k])
        if got != want:
            counts_bad += 1
        elif not np.array_equal(shard[h.lab_key],
                                clients[k].dataset.arrays[h.lab_key]):
            labels_bad += 1
        mine = picks[(picks >= offsets[k]) & (picks < offsets[k + 1])]
        for r in mine:
            i = r - offsets[k]
            h.rows.append(int(r))
            acts.append(np.asarray(shard["acts"][i], np.float32)
                        if i < got else None)
    h.prog = {"acts": acts, "clients_short": counts_bad,
              "labels_wrong": labels_bad}


def free(h):
    h.store = None


def follow(h, mode="f32", fault=None):
    """The reference's answer for the sampled rows, in the form of
    ``h.prog``; ``fault`` plants a fault in it (for the calibration)."""
    import jax.numpy as jnp

    from harness.build import pool_inputs

    ref, m, split = h.ref, h.model_dict, h.cfg["split"]
    dev, _, _ = ref.init(h.b.key, m, split)
    inputs = pool_inputs(h.b.clients, h.inp_key)
    acts = np.asarray(ref.device_forward(
        dev, jnp.asarray(inputs[np.asarray(h.rows)]), m, mode), np.float32)
    out = {"acts": list(acts), "clients_short": 0, "labels_wrong": 0}
    if fault == "altered":
        out["acts"][0] = out["acts"][0] * 1.01
    return out


def numbers(prog, ref):
    """Counts and labels exactly; the sampled rows' activations: the
    worst row's relative L2 gap, ||got - ref|| / ||ref||."""
    from harness.compare import worst_row_gap

    return ({"clients_short": float(prog["clients_short"]),
             "labels_wrong": float(prog["labels_wrong"]),
             "acts_gap": worst_row_gap(prog["acts"], ref["acts"])}, {})
