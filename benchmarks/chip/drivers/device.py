"""Device phase: ``AmpereTrainer.run_device_phase``, the paper's
synchronous rounds — K sampled clients, each H local SGD steps of the
device block and the auxiliary net, vmapped over the cohort, then
weighted FedAvg, with the auxiliary evaluation after every round.

Set-up drives the first rounds through the window's own call with a tap
on the trainer's jitted round that records the rows it gathered and the
state after each round; a second call times the rounds.  The window is
one ``run_device_phase`` call of as many rounds as fill ``--seconds``.

The reference takes the rows the program drew (each client's local
batches are the program's random choice), but not its aggregation
weights: it derives them from the cell's ``aggregation`` rule and the
sampled clients, and the check counts every cohort slot whose rows do
not all lie in one client's shard, or whose client another slot has
too (``cohort_faults``, limit 0).
"""

from __future__ import annotations

import math
import time

import numpy as np

CHECK_ROUNDS = 3
TIMING_ROUNDS = 10


def setup(h):
    fed = h.b.spec.run.fed
    h.per_round = fed.clients_per_round * fed.local_steps \
        * fed.device_batch_size


def _tap(h, rec):
    import jax
    import jax.numpy as jnp

    tr = h.b.trainer
    orig = tr._device_round_pool

    def round_step(state, pool, idx, w, lr):
        state, m = orig(state, pool, idx, w, lr)
        # the next round donates this state: keep a copy
        rec.append((jax.tree.map(jnp.copy, state), np.asarray(idx),
                    m["loss"]))
        return state, m

    tr._device_round_pool = round_step
    return orig


def prime(h):
    import jax

    from harness.compare import diff_norms
    from harness.plain import lr_at

    tr, b = h.b.trainer, h.b
    rec = []
    orig = _tap(h, rec)
    try:
        state = tr.run_device_phase(b.dev_state, max_rounds=CHECK_ROUNDS)
        jax.block_until_ready(state)
    finally:
        tr._device_round_pool = orig
    if len(rec) != CHECK_ROUNDS:
        raise RuntimeError("the device phase did not take the resident "
                           "pool path that the cell measures")
    lr0 = lr_at(h.cfg["optim"], 0)      # the recipe's, not the program's
    offsets = np.cumsum([0] + [len(c) for c in b.clients])
    h.rounds, faults = [], 0
    for r in rec:
        clients, bad = cohort(r[1], offsets)
        faults += bad
        h.rounds.append((r[1], weights(h.cell["aggregation"], clients)))
    h.prog = {"losses": [float(r[2]) for r in rec],
              "grad": diff_norms(b.dev_state, rec[0][0], 1.0 / lr0),
              "change": diff_norms(rec[-1][0], b.dev_state),
              "cohort_faults": faults}
    del rec
    h.dev_state = state
    mark = len(tr.obs.tracer.events)
    h.dev_state = tr.run_device_phase(h.dev_state, max_rounds=TIMING_ROUNDS)
    jax.block_until_ready(h.dev_state)
    spans = [e.dur_wall for e in tr.obs.tracer.events[mark:]
             if e.name == "device.round"]
    h.round_est = float(np.median(spans))


def cohort(idx, offsets):
    """(the client of each cohort slot, the number of faulty slots) of a
    round's ``(K, H, b)`` pool rows; ``offsets`` bound each client's
    shard in the pool.  A slot is faulty where its rows leave one shard
    or its client is another slot's too."""
    idx = np.asarray(idx)
    first = np.searchsorted(offsets, idx.reshape(len(idx), -1)[:, 0],
                            side="right") - 1
    clients, bad = [], 0
    for k, c in enumerate(first):
        rows = idx[k]
        inside = (0 <= c < len(offsets) - 1 and np.all(rows >= offsets[c])
                  and np.all(rows < offsets[c + 1]))
        if not inside or c in clients:
            bad += 1
        clients.append(int(c))
    return clients, bad


def weights(rule, clients):
    """The aggregation weights the cell's rule gives the sampled
    clients: ``"uniform"``, every client alike, as the configuration's
    cohort (no drops, no deadline) states."""
    if rule != "uniform":
        raise ValueError(f"no aggregation rule {rule!r}")
    return np.full(len(clients), 1.0 / len(clients))


def size(h, seconds):
    h.units = max(1, math.ceil(seconds / h.round_est))


def window(h):
    import jax

    tr = h.b.trainer
    tracer = tr.obs.tracer
    mark = len(tracer.events)
    t0 = time.perf_counter()
    state = tr.run_device_phase(h.dev_state, max_rounds=h.units)
    jax.block_until_ready(state)
    t1 = time.perf_counter()
    h.dev_state = state
    ends = sorted(e.t_wall + e.dur_wall for e in tracer.events[mark:]
                  if e.name == "device.round")
    start = t0 - tracer.t0
    intervals = np.diff(np.asarray([start] + ends))
    recs = tr.history["device"][-h.units:]
    bad = sum(not math.isfinite(r["loss"]) for r in recs)
    dt = t1 - t0
    # Python's own quantiles: the 95th percentile of all round intervals
    import statistics
    p95 = statistics.quantiles(intervals.tolist(), n=20)[-1] \
        if len(intervals) > 1 else float(intervals[0])
    return ({"round_s": dt / len(ends), "round_p95_s": p95},
            {"attempted": h.units, "failed": bad, "window_s": dt,
             "samples": len(ends) * h.per_round, "units": len(ends)})


def free(h):
    h.dev_state = None


def follow(h, mode="f32", fault=None):
    """The reference follows the recorded rounds from its own weights:
    losses and per-leaf norms in the form of ``h.prog``."""
    import jax.numpy as jnp

    from harness.build import pool_inputs, sample_keys
    from harness.plain import device_rounds

    ref, m, split = h.ref, h.model_dict, h.cfg["split"]
    dev, _, aux = ref.init(h.b.key, m, split)
    inp_key, lab_key = sample_keys(h.b.model)
    inputs = pool_inputs(h.b.clients, inp_key)
    labels = pool_inputs(h.b.clients, lab_key)
    rounds = []
    for idx, w in h.rounds:
        # a row past the pool is a cohort fault already; follow a real one
        idx = np.clip(idx, 0, len(labels) - 1)
        clients = [[(jnp.asarray(inputs[idx[k, s]]),
                     jnp.asarray(labels[idx[k, s]]))
                    for s in range(idx.shape[1])] for k in range(len(idx))]
        rounds.append((clients, w))
    half = fault == "half_batch"
    out = device_rounds(
        lambda p, x, y: ref.aux_loss(p, x, y, m, split, mode, half=half),
        {"device": dev, "aux": aux}, rounds, h.cfg["optim"])
    out["cohort_faults"] = 0
    return out


def numbers(prog, ref):
    from harness.compare import training_numbers

    nums, where = training_numbers(prog, ref)
    nums["cohort_faults"] = float(prog["cohort_faults"])
    return nums, where
