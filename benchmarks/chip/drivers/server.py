"""Server phase: ``AmpereTrainer.run_server_phase`` over a pool that a
real consolidation built in set-up.

Set-up consolidates every client's samples through the (untrained)
device block into an ``ActivationStore``, as ``AmpereSystem.run`` does
between the phases.  The first epoch is driven through the window's own
call and feed, with a tap on the trainer's jitted step or epoch that
records what the check compares, the activations fed to the first steps
among them; it warms every shape.  The window is one
``run_server_phase`` call of as many epochs as fill ``--seconds``,
per-epoch merged evaluation included, ended by blocking on the state.

The program picks its path from the pool's size, and the driver follows
either:

* a pool over ``device_pool_budget_mb`` streams batches from the host
  through the per-step ``_server_step``; the tap keeps the state and
  loss of the first steps;
* a smaller pool is uploaded once per call and each epoch is one
  ``lax.scan`` of the same step (``_server_epoch``).  The tap keeps the
  first epoch's per-step losses, its rows and the state it started
  from.  The scan hides the state after each step, so the same jitted
  epoch function then runs over the first step's rows, and on from
  there over the next steps' rows, for the state after one step and
  after all of them.  The later steps of an epoch are left out: an
  unclipped recipe turns round-off into chaos over 195 steps.
"""

from __future__ import annotations

import copy
import math
import time

import numpy as np

CHECK_STEPS = 3


def setup(h):
    from harness.build import sample_keys
    from repro.data.activation_store import ActivationStore

    run = h.b.spec.run
    h.inp_key, h.lab_key = sample_keys(h.b.model)
    h.store = ActivationStore(
        directory=None, consolidated=True,
        quantize_int8=run.split.quantize_activations, seed=run.seed)
    h.b.trainer.generate_activations(h.b.dev_state, h.store,
                                     upload="serial")
    h.bs = run.fed.server_batch_size
    h.nb = h.store.num_samples() // h.bs


def _tap(h, steps, epochs):
    """Wrap the trainer's jitted step and epoch; returns the originals."""
    import jax
    import jax.numpy as jnp

    tr = h.b.trainer
    step0, epoch0 = tr._server_step, tr._server_epoch

    def step(state, batch):
        state, m = step0(state, batch)
        if len(steps) < CHECK_STEPS:
            steps.append((state["server"], m["loss"], batch[h.lab_key],
                          batch["acts"]))
        return state, m

    def epoch(state, pool, idx):
        if epochs:
            return epoch0(state, pool, idx)
        # the epoch donates its state: keep the one it starts from
        start = jax.tree.map(jnp.copy, state)
        state, losses = epoch0(state, pool, idx)
        epochs.append((start, pool, np.asarray(idx), losses))
        return state, losses

    tr._server_step, tr._server_epoch = step, epoch
    return step0, epoch0


def _scanned(epoch0, start, pool, idx):
    """The server weights after the first step of a scanned epoch and
    after CHECK_STEPS: the same jitted epoch function over the first
    step's rows, then on over the next ones'."""
    import jax
    import jax.numpy as jnp

    s1, _ = epoch0(jax.tree.map(jnp.copy, start), pool, jnp.asarray(idx[:1]))
    p1 = jax.tree.map(jnp.copy, s1["server"])
    s3, _ = epoch0(s1, pool, jnp.asarray(idx[1:CHECK_STEPS]))
    return p1, s3["server"]


def prime(h):
    """First epoch through the window's call; records what the check
    compares and the epoch's time."""
    import jax
    import jax.numpy as jnp

    from harness.build import pool_inputs
    from harness.compare import diff_norms
    from harness.plain import lr_at

    tr, b = h.b.trainer, h.b
    lr0 = lr_at(h.cfg["optim"], 0)      # the recipe's, not the program's
    steps, epochs = [], []
    # the streamed feed draws one permutation of the pool per epoch from
    # the store's seeded generator: the first is the rows of its first
    # steps (the scanned epoch hands its rows to the tap)
    order = copy.deepcopy(h.store.rng).permutation(h.store.num_samples())
    step0, epoch0 = _tap(h, steps, epochs)
    t0 = time.perf_counter()
    try:
        state = tr.run_server_phase(b.dev_state, b.srv, h.store,
                                    max_epochs=1)
        jax.block_until_ready(state)
    finally:
        tr._server_step, tr._server_epoch = step0, epoch0
    h.epoch_s = time.perf_counter() - t0
    h.srv_params = state["server"]
    pool_labels = pool_inputs(b.clients, h.lab_key)
    if epochs:
        start, pool, idx, losses = epochs.pop()
        h.rows = list(idx[:CHECK_STEPS])
        labels = np.asarray(pool[h.lab_key])[idx[:CHECK_STEPS]]
        feed = np.asarray(pool["acts"][jnp.asarray(idx[:CHECK_STEPS])])
        after = _scanned(epoch0, start, pool, idx)
        losses = np.asarray(losses)[:CHECK_STEPS]
        del start, pool
        # warm: this call's time sizes the window, without the first
        # call's loads from the compile cache
        t0 = time.perf_counter()
        state = tr.run_server_phase(b.dev_state, h.srv_params, h.store,
                                    max_epochs=1)
        jax.block_until_ready(state)
        h.epoch_s = time.perf_counter() - t0
        h.srv_params = state["server"]
    else:
        h.rows = [order[t * h.bs:(t + 1) * h.bs] for t in range(len(steps))]
        labels = [np.asarray(x[2]) for x in steps]
        feed = np.stack([np.asarray(x[3]) for x in steps])
        after = [steps[0][0], steps[-1][0]]
        losses = [float(x[1]) for x in steps]
    for rows, lab in zip(h.rows, labels):
        if not np.array_equal(pool_labels[rows], np.asarray(lab)):
            raise RuntimeError("the server feed's rows are not the ones "
                               "the check follows")
    h.prog = {"losses": [float(x) for x in losses],
              "grad": diff_norms(b.srv, after[0], 1.0 / lr0),
              "change": diff_norms(after[1], b.srv),
              "feed": feed.reshape((-1,) + feed.shape[2:])}
    del steps, after


def size(h, seconds):
    h.units = max(1, math.ceil(seconds / h.epoch_s))


def window(h):
    import jax

    tr = h.b.trainer
    t0 = time.perf_counter()
    state = tr.run_server_phase(h.b.dev_state, h.srv_params, h.store,
                                max_epochs=h.units)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    h.srv_params = state["server"]
    recs = tr.history["server"][-h.units:]
    bad = sum(not math.isfinite(r["loss"]) for r in recs)
    done = len(recs) * h.nb * h.bs
    return ({"server_samples_per_s": done / dt},
            {"attempted": h.units * h.nb, "failed": bad * h.nb,
             "window_s": dt, "samples": done, "units": len(recs)})


def free(h):
    """Drop the program's state before the reference runs."""
    h.store = None
    h.srv_params = None


def follow(h, mode="f32", fault=None):
    """The reference follows the recorded steps from its own weights:
    losses and per-leaf norms in the form of ``h.prog``.  ``mode`` and
    ``fault`` make the control and the planted faults."""
    import jax.numpy as jnp

    from harness.build import pool_inputs
    from harness.plain import server_steps

    ref, m, split = h.ref, h.model_dict, h.cfg["split"]
    dev, srv, _ = ref.init(h.b.key, m, split)
    inputs = pool_inputs(h.b.clients, h.inp_key)
    labels = pool_inputs(h.b.clients, h.lab_key)
    batches = [(ref.device_forward(dev, jnp.asarray(inputs[r]), m, mode),
                jnp.asarray(labels[r])) for r in h.rows]
    half = fault == "half_batch"
    out = server_steps(lambda p, a, y: ref.server_loss(p, a, y, m, mode,
                                                       half=half),
                       srv, batches, h.cfg["optim"])
    out["feed"] = np.concatenate([np.asarray(a, np.float32)
                                  for a, _ in batches])
    return out


def numbers(prog, ref):
    """The training check's numbers, and ``feed_gap``: the activations
    the first steps were fed against the reference's device block on the
    same rows, by the worst row's relative L2 gap."""
    from harness.compare import training_numbers, worst_row_gap

    nums, where = training_numbers(prog, ref)
    nums["feed_gap"] = worst_row_gap(prog["feed"], ref["feed"])
    return nums, where
