"""Unidirectional Inter-Block Training — the Ampere orchestrator
(paper §3.3, Algorithm 1).

Five steps (Fig. 5):
  1  initialize theta on the server
  2  split into device/server blocks, generate the auxiliary network
  3  federated device-phase rounds: cohort sampling (w/ dropout + straggler
     policy), H local-SGD iterations per client, weighted FedAvg —
     early-stopped on the auxiliary validation metric
  4  one-shot activation generation from the *converged* device block,
     uploaded asynchronously into the consolidation store
  5  centralized server-phase training on the consolidated set 𝒜, training
     begins as soon as the first shard lands (streaming mode) —
     early-stopped on merged-model validation

Fault tolerance: every phase checkpoints through
:class:`repro.runtime.checkpoint.Checkpointer` with a round journal; a
restarted run resumes from (phase, round/epoch) — exercised by the tests.

This driver runs at any scale; CPU experiments use smoke configs, the pod
launcher reuses the same jitted steps (core/steps.py) under the production
mesh.

Two device-phase drivers share the jitted round math:

* :meth:`AmpereTrainer.run_device_phase` — the paper's fixed synchronous
  cohort (``sample_cohort`` per round, device-resident pool feeding when
  it fits the budget).
* :meth:`AmpereTrainer.run_fleet_device_phase` — rounds scheduled by the
  event-driven fleet simulator (:mod:`repro.fleet`): churning N >> K
  populations, elastic cohort sizing, straggler deadlines, heartbeat
  liveness.

The cross-cutting loop machinery (checkpoint/resume, RoundJournal, early
stopping, metrics, comm/sim-time accounting) lives in the shared
:class:`repro.experiments.runner.Runner`; the full pipelines are
composed by :class:`repro.experiments.systems.AmpereSystem`, and
:meth:`AmpereTrainer.run_all` / :meth:`AmpereTrainer.run_fleet` are
deprecation shims over it — prefer
:func:`repro.experiments.run_experiment` with a declarative spec.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, auxiliary, comm_model, evaluate, splitting, steps
from repro.data.activation_store import ActivationStore
from repro.data.pipeline import (ClientData, DevicePrefetcher, client_pool,
                                 round_batches)
from repro.experiments.runner import Runner, StepOutcome
from repro.models import build_model
from repro.observability import NULL_OBS
from repro.optim import make_schedule
from repro.transport import QuorumError, cohort_exchange, required_quorum


def _phase_span(name: str, track: str):
    """Run the decorated phase entry inside one span of the trainer's
    tracer: the whole call, so its pool upload and compile work too."""
    def decorate(method):
        @functools.wraps(method)
        def traced(self, *args, **kwargs):
            with self.obs.tracer.span(name, track=track):
                return method(self, *args, **kwargs)
        return traced
    return decorate


class AmpereTrainer:
    def __init__(self, model, run_cfg, clients: List[ClientData],
                 eval_data, workdir: Optional[str] = None,
                 patience: int = 15, log_echo: bool = False,
                 consolidate: bool = True, transport=None,
                 quorum_frac: float = 1.0, obs=None, cuts=None):
        self.model = model
        self.run = run_cfg
        self.clients = clients
        self.eval_data = eval_data
        self.workdir = workdir
        self.patience = patience
        self.consolidate = consolidate
        # optional fault-injecting transport; None keeps the legacy
        # analytic accounting byte-for-byte
        self.transport = transport
        self.quorum_frac = quorum_frac
        # heterogeneous cuts: a non-uniform CutAssignment switches the
        # device phase to per-depth bucket rounds and the server phase to
        # per-bucket entry points.  Uniform assignments must be collapsed
        # onto run_cfg.split.split_point upstream (experiments.api does),
        # keeping the legacy single-cut path byte-identical.
        self.cuts = None
        if cuts is not None and not cuts.uniform:
            if cuts.depths[0] != run_cfg.split.split_point:
                raise ValueError(
                    f"run_cfg.split.split_point={run_cfg.split.split_point} "
                    f"must equal the shallowest cut {cuts.depths[0]} (the "
                    "server block is split there)")
            self.cuts = cuts
        self.obs = obs if obs is not None else NULL_OBS
        self.rng = np.random.default_rng(run_cfg.fed.seed)
        # cross-cutting loop machinery (metrics, checkpoint/journal,
        # accounting, early stop) lives in the shared Runner; the legacy
        # attribute names stay as aliases for existing callers/tests
        self.runner = Runner(workdir, patience=patience, log_echo=log_echo,
                             history={"device": [], "server": [],
                                      "comm_bytes": 0, "sim_time": 0.0},
                             fault_plan=(transport.fault_plan
                                         if transport is not None else None),
                             obs=self.obs)
        self.log = self.runner.log
        self.ckpt = self.runner.ckpt
        self.journal = self.runner.journal
        self.history = self.runner.history

        # step functions (round state is donated: callers rebind per round)
        self._device_round = jax.jit(
            steps.named("device_round",
                        steps.make_device_round_step(model, run_cfg)),
            donate_argnums=(0,))
        # pool-fed federated round: the whole population's samples live on
        # device (uploaded once), the round state is donated, and each
        # round ships only a (K, H, b) int32 index matrix
        self._device_round_pool = jax.jit(
            steps.named("device_round",
                        steps.make_device_round_pool_step(model, run_cfg)),
            donate_argnums=(0,))
        self._server_step = jax.jit(
            steps.named("server_step",
                        steps.make_server_train_step(model, run_cfg)))
        # whole-epoch server phase: device-resident pool, donated state,
        # one host sync per epoch
        self._server_epoch = jax.jit(
            steps.named("server_epoch",
                        steps.make_server_epoch_fn(model, run_cfg)),
            donate_argnums=(0,))
        self._sched = make_schedule(run_cfg.optim)

        # sizes for comm accounting
        seq = self._seq_len()
        self.sizes = comm_model.split_sizes(model, run_cfg.split, seq_len=seq)

        # per-depth run configs + sizes for the heterogeneous paths
        # (abstract eval_shape only — nothing is allocated)
        self._run_by_depth = {}
        self._sizes_by_depth = {}
        if self.cuts is not None:
            for d in self.cuts.depths:
                rc = dataclasses.replace(
                    run_cfg,
                    split=dataclasses.replace(run_cfg.split,
                                              split_point=int(d)))
                self._run_by_depth[d] = rc
                self._sizes_by_depth[d] = comm_model.split_sizes(
                    model, rc.split, seq_len=seq)

    # ------------------------------------------------------------------
    def _seq_len(self) -> int:
        if self.model.kind != "lm":
            return 0
        return int(self.clients[0].dataset.arrays["tokens"].shape[1])

    def _one_way_bytes(self, client_id) -> int:
        """Device-block + aux bytes one model exchange moves for this
        client (its own cut depth under a heterogeneous assignment)."""
        if self.cuts is None:
            return self.sizes.device + self.sizes.aux
        s = self._sizes_by_depth[self.cuts.cut_of(client_id)]
        return s.device + s.aux

    def _round_metrics(self, phase: str, clients, excluded):
        """Direction-split analytic bytes + exclusions for one round.

        Observability only — the runner already accounts the undirected
        wire total into history; this splits the *analytic* volume by
        direction for the per-phase table.  ``clients`` is the round's
        cohort id list (per-client bytes differ across cut depths).
        """
        if not self.obs.enabled:
            return
        m = self.obs.metrics
        one_way = sum(self._one_way_bytes(c) for c in clients)
        m.counter("comm_bytes", one_way, phase=phase, direction="down")
        m.counter("comm_bytes", one_way, phase=phase, direction="up")
        if excluded:
            m.counter("excluded_devices", len(excluded), phase=phase)

    def _device_prefix(self, device, d: int):
        """The ``[0, d)`` layer slice of a device tree (non-layer keys —
        the LM embedding — ride along whole).  The slices reference the
        same buffers, so per-bucket round steps must not donate them."""
        out = {k: v for k, v in device.items() if k != "layers"}
        out["layers"] = list(device["layers"][:d])
        return out

    def _init_states(self, key):
        params = self.model.init(key)
        p = self.run.split.split_point
        if self.cuts is None:
            dev, srv = splitting.split_params(self.model, params, p)
            aux = auxiliary.init_aux(self.model, jax.random.fold_in(key, 7),
                                     self.run.split)
            return dev, srv, aux
        # heterogeneous: one global device stack at the DEEPEST cut, one
        # server block split at the shallowest with a loose region through
        # p_max (every entry point lands on a loose layer), and one aux
        # net per depth (string keys — checkpoint-safe)
        p_max = self.cuts.depths[-1]
        dev, _ = splitting.split_params(self.model, params, p_max)
        _, srv = splitting.split_params(self.model, params, p,
                                        loose_until=p_max)
        aux = {f"p{d}": auxiliary.init_aux(
                   self.model, jax.random.fold_in(key, 7 + j),
                   self._run_by_depth[d].split)
               for j, d in enumerate(self.cuts.depths)}
        return dev, srv, aux

    # ------------------------------------------------------------------
    # Phase 3: federated device training
    # ------------------------------------------------------------------
    @_phase_span("device.phase", "device")
    def run_device_phase(self, dev_state, max_rounds: Optional[int] = None):
        if self.cuts is not None:
            raise ValueError(
                "heterogeneous cuts run through the fleet device phase "
                "(a per_profile CutPolicy requires a fleet trace)")
        fed = self.run.fed
        K = fed.clients_per_round
        aux_eval = self._make_aux_eval()
        dev_state, start_round = self.runner.restore("device", dev_state)

        # device-resident feeding: upload every client's samples ONCE and
        # gather each round's (K, H, b, ...) batches on device from an
        # int32 index matrix; the round state is donated.  Pools beyond
        # the budget fall back to per-round host batch uploads (size is
        # checked before any concatenation so the fallback case never
        # duplicates the dataset on host).
        total_bytes = sum(a.nbytes for c in self.clients
                          for a in c.dataset.arrays.values())
        resident = total_bytes <= self.run.device_pool_budget_mb * 2 ** 20
        with self.obs.tracer.span("device.pool_upload", track="device"):
            if resident:
                pool_np, offsets = client_pool(self.clients)
                pool_dev = {k: jnp.asarray(v) for k, v in pool_np.items()}
                del pool_np
            # both round steps donate their input state; copy once so the
            # caller's buffers survive the first donation
            dev_state = jax.tree.map(lambda a: jnp.array(a), dev_state)

        def body(state, rnd, _plan):
            cohort = aggregation.sample_cohort(self.rng, fed, rnd)
            kept, wire, extra, excluded = cohort_exchange(
                self.transport, round_key=f"ampere/device/{rnd}",
                clients=cohort["clients"],
                one_way_bytes=self.sizes.device + self.sizes.aux,
                quorum_frac=self.quorum_frac, phase="device")
            survivors = [cohort["clients"][i] for i in kept]
            weights = [cohort["weights"][i] for i in kept]
            if excluded:    # quorum-degraded round: reweight the survivors
                total = sum(weights)
                weights = [w_ / total for w_ in weights]
            ids, w = aggregation.pad_cohort(survivors, weights, K)
            lr = self._sched(rnd)
            if resident:
                idx = np.stack([
                    offsets[int(c)] + self.clients[int(c)].batch_indices(
                        fed.device_batch_size, fed.local_steps)
                    for c in ids]).astype(np.int32)
                state, metrics = self._device_round_pool(
                    state, pool_dev, jnp.asarray(idx),
                    jnp.asarray(w, jnp.float32), lr)
            else:
                batches = round_batches(self.clients, ids, fed.local_steps,
                                        fed.device_batch_size)
                batches = {k: jnp.asarray(v) for k, v in batches.items()}
                state, metrics = self._device_round(
                    state, batches, jnp.asarray(w, jnp.float32), lr)
            val = aux_eval(state)
            log = {"dropped": len(cohort["dropped"])}
            if self.transport is not None and self.transport.faulty:
                log["excluded"] = len(excluded)
            if self.transport is not None:
                log["wire"] = self.transport.delta_stats()
            self._round_metrics("device", cohort["clients"], excluded)
            return StepOutcome(
                state=state,
                record={"round": rnd, "loss": float(metrics["loss"]), **val},
                comm_bytes=wire,
                sim_time=cohort["round_time"] + extra,
                log=log)

        rounds = max_rounds if max_rounds is not None else fed.device_epochs
        return self.runner.run_phase(
            "device", dev_state, ((r, None) for r in range(start_round,
                                                           rounds)),
            body, history_key="device", monitor="val_loss",
            checkpoint_every=self.run.checkpoint_every)

    # ------------------------------------------------------------------
    # Phase 3 (fleet mode): trace-driven federated device training
    # ------------------------------------------------------------------
    def run_fleet_device_phase(self, dev_state, trace,
                               max_rounds: Optional[int] = None):
        """Device phase driven by a :class:`repro.fleet.FleetTrace`.

        Cohorts, dropouts and wall-clock come from the event-driven
        scheduler instead of ``sample_cohort``; training runs through the
        vmapped pool-fed :class:`repro.fleet.FleetEngine` (donated state,
        stateless per-round batch indices), so a run killed mid-phase
        resumes from RoundJournal + Checkpointer onto byte-identical
        batches.  Device ids in the trace index ``self.clients``.
        """
        from repro.fleet.engine import FleetEngine

        if self.cuts is not None:
            return self._run_fleet_device_phase_hetero(dev_state, trace,
                                                       max_rounds)
        engine = FleetEngine(self.model, self.run, self.clients,
                             seed=self.run.fed.seed)
        aux_eval = self._make_aux_eval()
        dev_state, start_round = self.runner.restore("fleet", dev_state)
        dev_state = jax.tree.map(lambda a: jnp.array(a), dev_state)

        def body(state, rnd, plan):
            lr = self._sched(rnd)
            kept, wire, extra, excluded = cohort_exchange(
                self.transport, round_key=f"ampere/fleet/{rnd}",
                clients=plan.clients,
                one_way_bytes=self.sizes.device + self.sizes.aux,
                quorum_frac=self.quorum_frac, phase="fleet")
            survivors = [plan.clients[i] for i in kept]
            weights = [plan.weights[i] for i in kept]
            if excluded:    # quorum-degraded round: reweight the survivors
                total = sum(weights)
                weights = [w_ / total for w_ in weights]
            state, metrics = engine.run_round(
                state, rnd, survivors, weights, lr,
                pad_to=plan.cohort_size)
            val = aux_eval(state)
            log = {"dropped": len(plan.dropped),
                   "sim_t": round(plan.t_end, 6)}
            if self.transport is not None and self.transport.faulty:
                log["excluded"] = len(excluded)
            if self.transport is not None:
                log["wire"] = self.transport.delta_stats()
            self._round_metrics("fleet", plan.clients, excluded)
            return StepOutcome(
                state=state,
                record={"round": rnd, "loss": float(metrics["loss"]),
                        "t_end": plan.t_end, "cohort": plan.cohort_size,
                        "survivors": len(survivors), **val},
                comm_bytes=wire,
                sim_time=plan.round_time + extra,
                log=log)

        plans = trace.rounds if max_rounds is None else \
            trace.rounds[:max_rounds]
        return self.runner.run_phase(
            "fleet", dev_state,
            ((p.round_idx, p) for p in plans if p.round_idx >= start_round),
            body, history_key="device", monitor="val_loss",
            checkpoint_every=self.run.checkpoint_every)

    def _run_fleet_device_phase_hetero(self, dev_state, trace,
                                       max_rounds: Optional[int] = None):
        """Fleet device phase with per-profile cut depths.

        One :class:`FleetEngine` per depth (each compiles at its own layer
        count; ``donate=False`` because the per-bucket states are slices
        referencing the global stack's buffers).  Every round's survivors
        are bucketed by assigned cut, each bucket trains the ``[0, d)``
        prefix of the global device stack with its own aux net, and
        ``aggregation.prefix_fedavg`` folds the trained buckets back over
        their overlapping prefix — layers no surviving bucket covers keep
        their current global value.
        """
        from repro.fleet.engine import FleetEngine

        cuts = self.cuts
        engines = {d: FleetEngine(self.model, self._run_by_depth[d],
                                  self.clients, seed=self.run.fed.seed,
                                  donate=False)
                   for d in cuts.depths}
        aux_eval = self._make_aux_eval()
        dev_state, start_round = self.runner.restore("fleet", dev_state)
        dev_state = jax.tree.map(lambda a: jnp.array(a), dev_state)

        def body(state, rnd, plan):
            lr = self._sched(rnd)
            kept, wire, extra, excluded = cohort_exchange(
                self.transport, round_key=f"ampere/fleet/{rnd}",
                clients=plan.clients,
                one_way_bytes=[self._one_way_bytes(c)
                               for c in plan.clients],
                quorum_frac=self.quorum_frac, phase="fleet")
            survivors = [plan.clients[i] for i in kept]
            weights = [plan.weights[i] for i in kept]
            if excluded:    # quorum-degraded round: reweight the survivors
                total = sum(weights)
                weights = [w_ / total for w_ in weights]
            buckets = {d: ([], []) for d in cuts.depths}
            for c, w_ in zip(survivors, weights):
                ids, ws = buckets[cuts.cut_of(c)]
                ids.append(c)
                ws.append(w_)
            trained, bucket_w = {}, {}
            loss_num = 0.0
            for d in cuts.depths:
                ids, ws = buckets[d]
                if not ids:
                    continue
                sub = {"device": self._device_prefix(state["device"], d),
                       "aux": state["aux"][f"p{d}"]}
                sub, metrics = engines[d].run_round(
                    sub, rnd, ids, ws, lr, pad_to=plan.cohort_size)
                trained[d] = sub
                bucket_w[d] = float(sum(ws))
                loss_num += bucket_w[d] * float(metrics["loss"])
            new_aux = dict(state["aux"])
            for d in trained:
                new_aux[f"p{d}"] = trained[d]["aux"]
            new_device = aggregation.prefix_fedavg(
                state["device"],
                {d: t["device"] for d, t in trained.items()}, bucket_w)
            state = {"device": new_device, "aux": new_aux}
            total_w = sum(bucket_w.values())
            loss = loss_num / total_w if total_w else 0.0
            val = aux_eval(state)
            log = {"dropped": len(plan.dropped),
                   "sim_t": round(plan.t_end, 6),
                   "buckets": {f"p{d}": len(buckets[d][0])
                               for d in cuts.depths}}
            if self.transport is not None and self.transport.faulty:
                log["excluded"] = len(excluded)
            if self.transport is not None:
                log["wire"] = self.transport.delta_stats()
            self._round_metrics("fleet", plan.clients, excluded)
            return StepOutcome(
                state=state,
                record={"round": rnd, "loss": loss, "t_end": plan.t_end,
                        "cohort": plan.cohort_size,
                        "survivors": len(survivors), **val},
                comm_bytes=wire,
                sim_time=plan.round_time + extra,
                log=log)

        plans = trace.rounds if max_rounds is None else \
            trace.rounds[:max_rounds]
        return self.runner.run_phase(
            "fleet", dev_state,
            ((p.round_idx, p) for p in plans if p.round_idx >= start_round),
            body, history_key="device", monitor="val_loss",
            checkpoint_every=self.run.checkpoint_every)

    def run_fleet(self, trace, key=None, max_rounds=None,
                  max_server_epochs=None,
                  store: Optional[ActivationStore] = None,
                  population=None):
        """Deprecated shim: full trace-driven Ampere pipeline via the
        unified :class:`repro.experiments.systems.AmpereSystem` adapter —
        prefer :func:`repro.experiments.run_experiment` with a spec that
        sets ``trace_path``/``fleet``.  ``population`` (the trace's
        :class:`~repro.fleet.DeviceProfile` list) prices the one-shot
        upload on each participant's own link."""
        from repro.experiments.systems import SystemContext, get_system

        ctx = SystemContext(
            model=self.model, run_cfg=self.run, clients=self.clients,
            eval_data=self.eval_data, trainer=self, trace=trace,
            population=population, max_rounds=max_rounds,
            max_server_epochs=max_server_epochs, key=key, store=store)
        return get_system("ampere")().run(ctx)

    def _make_aux_eval(self):
        model, run = self.model, self.run

        def make_step(p, split_cfg, aux_of):
            def step(dev_state, batch):
                inp = batch["tokens"] if model.kind == "lm" \
                    else batch["images"]
                acts = splitting.device_forward(model, dev_state["device"],
                                                inp, p)
                loss, m = auxiliary.aux_loss(model, aux_of(dev_state),
                                             dev_state["device"], acts,
                                             batch, split_cfg)
                return loss, m.get("acc", jnp.zeros(()))
            return jax.jit(steps.named("aux_eval_step", step))

        if self.cuts is None:
            steps_by_depth = {run.split.split_point: make_step(
                run.split.split_point, run.split, lambda s: s["aux"])}
        else:
            # one step per depth: each evaluates its own aux head on its
            # own prefix of the shared device stack; the reported metric
            # averages across depths
            steps_by_depth = {
                d: make_step(d, self._run_by_depth[d].split,
                             (lambda d=d: lambda s: s["aux"][f"p{d}"])())
                for d in self.cuts.depths}

        tracer = self.obs.tracer

        def eval_fn(dev_state, max_batches: int = 8, batch_size: int = 64):
            with tracer.span("aux_eval", track="eval") as sp:
                n = len(self.eval_data)
                ls, accs = [], []
                bs = min(batch_size, n)
                for s in range(0, min(n, max_batches * bs) - bs + 1, bs):
                    idx = np.arange(s, s + bs)
                    batch = {k: jnp.asarray(v[idx])
                             for k, v in self.eval_data.arrays.items()}
                    for step in steps_by_depth.values():
                        loss, acc = step(dev_state, batch)
                        with tracer.span("eval.wait", track="eval"):
                            ls.append(float(loss))
                            accs.append(float(acc))
                out = {"val_loss": float(np.mean(ls)),
                       "val_acc": float(np.mean(accs))}
                sp.set(**out)
            return out
        return eval_fn

    # ------------------------------------------------------------------
    # Phase 4: one-shot activation generation + upload
    # ------------------------------------------------------------------
    def generate_activations(self, dev_state, store: ActivationStore,
                             batch_size: int = 64, upload: str = "serial",
                             client_bandwidth_bps=None):
        """``upload`` prices the one-shot transfer's simulated wall clock:
        ``"serial"`` — all bytes through one shared server link (legacy
        accounting); ``"parallel"`` — each device pushes its own shard on
        its own link concurrently (fleet semantics), so the transfer
        takes as long as the slowest participating (shard, link) pair.
        Both price the *actual* stored bytes (int8 quantization
        included).  ``client_bandwidth_bps`` maps client_id -> link
        bytes/s (e.g. from :class:`~repro.fleet.DeviceProfile`
        ``bandwidth_bps``); without it parallel mode falls back to the
        paper-testbed per-device link (``BANDWIDTH_BPS``), under which
        the slowest pair is simply the largest shard."""
        with self.obs.tracer.span("consolidate", track="transfer",
                                  upload=upload) as sp:
            return self._generate_activations(dev_state, store, batch_size,
                                              upload, client_bandwidth_bps,
                                              sp)

    def _generate_activations(self, dev_state, store, batch_size, upload,
                              client_bandwidth_bps, sp):
        model, run = self.model, self.run
        p = run.split.split_point

        def make_fwd(depth):
            def fwd(device_params, inp):
                return splitting.device_forward(model, device_params, inp,
                                                depth)
            return jax.jit(steps.named("consolidate_fwd", fwd))

        if self.cuts is None:
            fwds = {None: make_fwd(p)}
            cut_of = lambda cid: None           # noqa: E731
        else:
            # each client generates at its own assigned depth from the
            # matching prefix of the global stack; shards are cut-tagged
            # so the server phase can bucket them by entry point
            fwds = {d: make_fwd(d) for d in self.cuts.depths}
            cut_of = self.cuts.cut_of

        inp_key = "tokens" if model.kind == "lm" else "images"
        lab_key = "tokens" if model.kind == "lm" else "labels"

        def host_batches():
            for client in self.clients:
                arrays = client.dataset.arrays
                n = len(client.dataset)
                for s in range(0, n, batch_size):
                    idx = np.arange(s, min(s + batch_size, n))
                    yield (client.client_id, arrays[lab_key][idx]), \
                        arrays[inp_key][idx]

        transport = self.transport
        faulty = transport is not None and transport.faulty
        wire_total = 0
        client_extra: dict = {}
        failed: set = set()
        counters: dict = {}
        pending: dict = {}
        # streaming store: each produced shard carries its simulated
        # arrival time so the server learner can price epoch overlap.
        # Serial pricing: cumulative stored bytes through the shared
        # link + fault-retry extras so far (the last arrival lands at
        # exactly t_up + extra_total, the transfer's accounted end).
        # Parallel pricing: each client's cumulative bytes on its own
        # link + its own extras.
        streams = hasattr(store, "sample_arrivals")
        bytes_cum: dict = {None: 0}

        def arrival(cid, nbytes):
            if upload == "parallel":
                bytes_cum[cid] = bytes_cum.get(cid, 0) + nbytes
                bw_c = (client_bandwidth_bps.get(cid,
                                                 comm_model.BANDWIDTH_BPS)
                        if client_bandwidth_bps is not None
                        else comm_model.BANDWIDTH_BPS)
                return (bytes_cum[cid] / bw_c
                        + client_extra.get(cid, 0.0))
            bytes_cum[None] += nbytes
            return (bytes_cum[None] / comm_model.BANDWIDTH_BPS
                    + sum(client_extra.values()))

        tracer = self.obs.tracer

        def submit(cid, shard, t_arr, cut):
            with tracer.span("consolidate.submit", track="transfer"):
                if streams:
                    store.submit(cid, shard, t_arrival=t_arr, cut=cut)
                elif cut is not None:
                    store.submit(cid, shard, cut=cut)
                else:
                    store.submit(cid, shard)

        store.start_writer(tracer=tracer)
        # double-buffered upload: batch k+1 transfers while k computes
        feed = DevicePrefetcher(
            tracer.iter_span(host_batches(), "feed.gather", "transfer/feed"),
            tracer=tracer, track="transfer/feed")
        for (cid, labels), inp in tracer.iter_span(
                feed, "consolidate.feed_wait", "transfer"):
            cut = cut_of(cid)
            dev_params = (dev_state["device"] if cut is None
                          else self._device_prefix(dev_state["device"], cut))
            with tracer.span("consolidate.fetch", track="transfer"):
                shard = {"acts": np.asarray(fwds[cut](dev_params, inp),
                                            np.float32),
                         lab_key: labels}
            if transport is not None:
                # each shard is one framed message; the idempotency key
                # (client, shard index) is stable across retries and
                # across a crash-resumed rerun of this one-shot step
                i = counters.get(cid, 0)
                counters[cid] = i + 1
                nbytes = ActivationStore.shard_nbytes(shard, store.quantize)
                bw = (client_bandwidth_bps.get(
                          cid, comm_model.BANDWIDTH_BPS)
                      if client_bandwidth_bps is not None else None)
                res = transport.transfer(f"acts/{cid}/{i}", nbytes,
                                         device=cid, bandwidth_bps=bw,
                                         phase="transfer")
                wire_total += res.wire_bytes
                client_extra[cid] = client_extra.get(cid, 0.0) \
                    + res.extra_time
                if not res.ok:
                    failed.add(cid)
                    continue
                if not res.first_delivery:
                    continue    # duplicate absorbed by the idempotency key
            t_arr = 0.0
            if streams:
                t_arr = arrival(cid, ActivationStore.shard_nbytes(
                    shard, store.quantize))
            if faulty:
                # hold shards back until the whole client verifies, so a
                # device that perma-fails mid-stream never half-lands
                pending.setdefault(cid, []).append((shard, t_arr, cut))
            else:
                submit(cid, shard, t_arr, cut)
        for cid, shards in pending.items():
            if cid in failed:
                continue
            for shard, t_arr, cut in shards:
                submit(cid, shard, t_arr, cut)
        with tracer.span("consolidate.finish", track="transfer"):
            store.finish()
        if faulty and failed:
            survivors = len(self.clients) - len(failed)
            need = required_quorum(len(self.clients), self.quorum_frac)
            if survivors < need:
                raise QuorumError(
                    f"activation upload: only {survivors}/"
                    f"{len(self.clients)} clients verified, quorum needs "
                    f"{need} (failed: {sorted(failed)})")
        if upload == "parallel":
            n = max(store.num_samples(), 1)
            bytes_per_sample = store.bytes_received / n  # actual (incl int8)
            if client_bandwidth_bps is not None:
                # per-profile links: the transfer ends when the slowest
                # (shard bytes / own link) participant finishes
                t_up = max(
                    len(c.dataset) * bytes_per_sample /
                    client_bandwidth_bps.get(c.client_id,
                                             comm_model.BANDWIDTH_BPS)
                    for c in self.clients)
            else:
                biggest = max(len(c.dataset) for c in self.clients)
                t_up = biggest * bytes_per_sample / comm_model.BANDWIDTH_BPS
        else:
            t_up = store.bytes_received / comm_model.BANDWIDTH_BPS
        extra_total = 0.0
        if client_extra:
            extra_total = (max(client_extra.values())
                           if upload == "parallel"
                           else sum(client_extra.values()))
        # the transfer's accounted end: the overlap accountant seeds its
        # frontier here so streamed server epochs never double-charge
        self._transfer_sim_s = t_up + extra_total
        # fault-free transport moves exactly the stored bytes, so this
        # stays byte-identical to the legacy analytic accounting
        self.runner.account(
            comm_bytes=wire_total if transport is not None
            else store.bytes_received,
            sim_time=t_up + extra_total,
            phase="transfer", direction="up")
        if self.obs.enabled and failed:
            self.obs.metrics.counter("excluded_devices", len(failed),
                                     phase="transfer")
        sp.set(bytes=store.bytes_received, sim_time_s=round(t_up, 9),
               excluded=len(failed))
        if streams:
            rs = store.ring.stats
            sp.set(streaming=True, ring_segments=rs["segments"],
                   ring_stalls=rs["stalls"],
                   ring_max_occupancy=rs["max_occupancy"])
            if self.obs.enabled:
                self.obs.metrics.counter("ring_backpressure_stalls",
                                         rs["stalls"], phase="transfer")
                if rs["torn_repairs"]:
                    self.obs.metrics.counter("ring_torn_repairs",
                                             rs["torn_repairs"],
                                             phase="transfer")
        if faulty:
            self.log.log(phase="transfer", bytes=store.bytes_received,
                         upload=upload, wire=wire_total,
                         excluded=len(failed))
        else:
            self.log.log(phase="transfer", bytes=store.bytes_received,
                         upload=upload)
        return store

    # ------------------------------------------------------------------
    # Phase 5: centralized server training on the consolidated set
    # ------------------------------------------------------------------
    @_phase_span("server.phase", "server")
    def run_server_phase(self, dev_state, srv_params, store: ActivationStore,
                         max_epochs: Optional[int] = None):
        """Device-bound server phase.

        The consolidated pool is uploaded ONCE (int8 payloads stay
        quantized; the jitted step dequantizes per batch) and each epoch
        runs as a single donated ``lax.scan`` over gathered batch indices
        — per-batch losses land on host once per epoch, never per step.
        Pools beyond ``run.device_pool_budget_mb`` fall back to streaming
        host batches through the double-buffered :class:`DevicePrefetcher`.
        """
        if self.cuts is not None:
            return self._run_server_phase_hetero(dev_state, srv_params,
                                                 store, max_epochs)
        run = self.run
        tracer = self.obs.tracer
        srv_state = steps.init_server_state(self.model, run, srv_params)
        srv_state, start_epoch = self.runner.restore("server", srv_state,
                                                     step_name="epoch")
        merged_model = build_model(splitting.merged_config(self.model))
        eval_step = evaluate.make_eval_step(merged_model)
        epochs = max_epochs if max_epochs is not None else run.fed.server_epochs

        bs = run.fed.server_batch_size
        if store.num_samples() < bs:
            raise ValueError(
                f"server batch {bs} exceeds the consolidated pool of "
                f"{store.num_samples()} samples: no server step would run")
        budget = run.device_pool_budget_mb * 2 ** 20
        resident = store.pool_nbytes() <= budget
        pool_dev = None
        if resident:
            with tracer.span("server.pool_upload", track="server"):
                pool_dev = {k: jnp.asarray(v) for k, v in
                            store.pool(dequantize=False).items()}
                # the epoch fn donates its input state; copy once so the
                # caller's srv_params buffers survive the first donation
                srv_state = jax.tree.map(lambda a: jnp.array(a), srv_state)

        p = run.split.split_point
        epoch_sim_time = comm_model.ampere_server_epoch_time(
            self.model, run.split, comm_model.TimeModel(),
            n_samples=store.num_samples(), seq_len=self._seq_len(),
            sizes=self.sizes)

        # streamed store: epochs start on first-shard-landed and their
        # accounted sim-time is the pipeline increment past the device
        # round's frontier instead of the full serialized epoch — the
        # compute path (same pool, same rng draw, same jitted scan) is
        # untouched, so records stay byte-identical to the serialized run
        accountant = None
        if resident and hasattr(store, "sample_arrivals"):
            from repro.streaming import OverlapAccountant
            nb = max(1, store.num_samples() // bs)
            accountant = OverlapAccountant(
                store.sample_arrivals(),
                device_end=getattr(self, "_transfer_sim_s", 0.0),
                per_batch_s=epoch_sim_time / nb)

        def body(srv_state, epoch, _plan):
            epoch_sim = epoch_sim_time
            if resident:
                idx_np = store.epoch_indices(bs)
                idx = jnp.asarray(idx_np)
                if accountant is not None:
                    with self.obs.tracer.span("stream_consume",
                                              track="streaming",
                                              epoch=epoch) as csp:
                        srv_state, losses = self._server_epoch(
                            srv_state, pool_dev, idx)
                        dt, overlapped = accountant.epoch(idx_np)
                        epoch_sim = dt
                        csp.set(sim_s=round(dt, 9),
                                overlap_s=round(overlapped, 9))
                    if self.obs.enabled:
                        self.obs.metrics.counter("overlap_s", overlapped,
                                                 phase="server")
                else:
                    srv_state, losses = self._server_epoch(srv_state,
                                                           pool_dev, idx)
                with tracer.span("server.sync", track="server"):
                    ls = np.asarray(losses, np.float64)  # ONE sync per epoch
            else:
                acc = []
                batches = store.batches(bs, epochs=1, dequantize=False)
                feed = DevicePrefetcher(
                    tracer.iter_span(((None, b) for b in batches),
                                     "feed.gather", "server/feed"),
                    tracer=tracer, track="server/feed")
                for _, batch in tracer.iter_span(feed, "server.feed_wait",
                                                 "server"):
                    srv_state, m = self._server_step(srv_state, batch)
                    acc.append(m["loss"])           # device scalar, no sync
                with tracer.span("server.sync", track="server"):
                    ls = (np.asarray(jax.device_get(acc), np.float64)
                          if acc else np.zeros((0,), np.float64))  # one sync
            merged = splitting.merge_params(self.model, dev_state["device"],
                                            srv_state["server"], p)
            with self.obs.tracer.span("merged_eval", track="eval",
                                      epoch=epoch) as esp:
                val = evaluate.evaluate(merged_model, merged, self.eval_data,
                                        eval_step=eval_step,
                                        tracer=self.obs.tracer)
                esp.set(val_loss=val["loss"], val_acc=val["acc"])
            return StepOutcome(
                state=srv_state,
                record={"epoch": epoch, "loss": float(np.mean(ls)),
                        "val_loss": val["loss"], "val_acc": val["acc"]},
                sim_time=epoch_sim)

        return self.runner.run_phase(
            "server", srv_state,
            ((e, None) for e in range(start_epoch, epochs)),
            body, history_key="server", monitor="val_loss",
            checkpoint_every=run.checkpoint_every, ckpt_offset=10_000,
            step_name="epoch")

    def merged_params(self, dev_state, server_params):
        """Full merged model parameters (device block through the server
        split + the server block).  Under a heterogeneous assignment the
        device stack is oversized — ``merge_params`` reads only its first
        ``split_point`` layers, and the overlap layers [p_min, p_max)
        come from the server block's loose region, which holds the
        server-phase-trained copy."""
        return splitting.merge_params(self.model, dev_state["device"],
                                      server_params,
                                      self.run.split.split_point)

    def _sync_overlap_from_device(self, device, server):
        """Copy the device-trained overlap layers [p_min, p_max) from the
        global device stack into the server block's loose region.  The
        server block was carved at model init; the device phase has since
        trained those layers on-device for the deeper buckets, so server
        training must start from the converged copies."""
        p_min = self.run.split.split_point
        p_max = self.cuts.depths[-1]
        key = "layers_head" if self.model.kind == "lm" else "layers"
        lst = list(server[key])
        for layer in range(p_min, p_max):
            lst[layer - p_min] = device["layers"][layer]
        out = dict(server)
        out[key] = lst
        return out

    def _run_server_phase_hetero(self, dev_state, srv_params,
                                 store: ActivationStore,
                                 max_epochs: Optional[int] = None):
        """Server phase over a heterogeneous-cut consolidated pool.

        Shards are bucketed by their cut tag; each epoch runs one donated
        scan per depth over that bucket's pool with the scan *entering*
        the server block at that depth (:func:`steps.make_server_epoch_fn`
        ``entry=``), in sorted-depth order so the store's rng stream
        stays deterministic.  Before training starts the device-trained
        overlap layers are synced into the server block's loose region.
        The pool must fit the device budget — there is no host-streaming
        fallback for per-bucket epochs.
        """
        run = self.run
        srv_params = self._sync_overlap_from_device(dev_state["device"],
                                                    srv_params)
        srv_state = steps.init_server_state(self.model, run, srv_params)
        srv_state, start_epoch = self.runner.restore("server", srv_state,
                                                     step_name="epoch")
        merged_model = build_model(splitting.merged_config(self.model))
        eval_step = evaluate.make_eval_step(merged_model)
        epochs = max_epochs if max_epochs is not None \
            else run.fed.server_epochs

        bs = run.fed.server_batch_size
        budget = run.device_pool_budget_mb * 2 ** 20
        if store.pool_nbytes() > budget:
            raise ValueError(
                f"heterogeneous-cut pool ({store.pool_nbytes()} bytes) "
                f"exceeds device_pool_budget_mb={run.device_pool_budget_mb}"
                "; per-bucket server epochs require a resident pool")
        present = [d for d in store.cut_depths()
                   if store.num_samples(cut=d) > 0]
        if not present:
            raise ValueError("heterogeneous server phase: store has no "
                             "cut-tagged activation shards")
        pools = {d: {k: jnp.asarray(v) for k, v in
                     store.pool(dequantize=False, cut=d).items()}
                 for d in present}
        epoch_fns = {d: jax.jit(
                         steps.make_server_epoch_fn(self.model, run,
                                                    entry=int(d)),
                         donate_argnums=(0,))
                     for d in present}
        # the epoch fns donate their input state; copy once so the
        # caller's srv_params buffers survive the first donation
        srv_state = jax.tree.map(lambda a: jnp.array(a), srv_state)

        # each bucket's scan prices at its own depth's layer count and
        # activation volume; the serialized epoch is their sum
        epoch_sim_time = sum(
            comm_model.ampere_server_epoch_time(
                self.model, self._run_by_depth[d].split,
                comm_model.TimeModel(),
                n_samples=store.num_samples(cut=d),
                seq_len=self._seq_len(), sizes=self._sizes_by_depth[d])
            for d in present)

        def body(srv_state, epoch, _plan):
            ls = []
            for d in present:       # sorted order: deterministic rng draws
                n_d = store.num_samples(cut=d)
                bs_d = min(bs, n_d)
                idx = jnp.asarray(store.epoch_indices(bs_d, cut=d))
                srv_state, losses = epoch_fns[d](srv_state, pools[d], idx)
                ls.append(np.asarray(losses, np.float64))
            ls = np.concatenate(ls) if ls else np.zeros((0,), np.float64)
            merged = self.merged_params(dev_state, srv_state["server"])
            with self.obs.tracer.span("merged_eval", track="eval",
                                      epoch=epoch) as esp:
                val = evaluate.evaluate(merged_model, merged, self.eval_data,
                                        eval_step=eval_step,
                                        tracer=self.obs.tracer)
                esp.set(val_loss=val["loss"], val_acc=val["acc"])
            return StepOutcome(
                state=srv_state,
                record={"epoch": epoch, "loss": float(np.mean(ls)),
                        "val_loss": val["loss"], "val_acc": val["acc"]},
                sim_time=epoch_sim_time)

        return self.runner.run_phase(
            "server", srv_state,
            ((e, None) for e in range(start_epoch, epochs)),
            body, history_key="server", monitor="val_loss",
            checkpoint_every=run.checkpoint_every, ckpt_offset=10_000,
            step_name="epoch")

    # ------------------------------------------------------------------
    def run_all(self, key=None, max_device_rounds=None, max_server_epochs=None,
                store: Optional[ActivationStore] = None):
        """Deprecated shim: the paper's fixed-cohort pipeline via the
        unified :class:`repro.experiments.systems.AmpereSystem` adapter —
        prefer :func:`repro.experiments.run_experiment`."""
        from repro.experiments.systems import SystemContext, get_system

        ctx = SystemContext(
            model=self.model, run_cfg=self.run, clients=self.clients,
            eval_data=self.eval_data, trainer=self,
            max_rounds=max_device_rounds,
            max_server_epochs=max_server_epochs, key=key, store=store)
        return get_system("ampere")().run(ctx)
