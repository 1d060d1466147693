#!/usr/bin/env python3
"""Smoke run of the Ampere training path on one TPU chip.

    python3 chip_smoke.py

Everything runs in this one process (a chip belongs to one process):

1. kernels — each Pallas kernel once, forward and backward, compiled for
   the chip at real widths (qwen3-1.7b attention and loss, mamba2-370m
   SSD chunk) with the TPU-default ``alias`` accumulation, compared with
   its ``ref.py`` oracle run at ``highest`` matmul precision;
2. models — ``run_experiment`` with ``ExperimentSpec(smoke=False,
   systems=("ampere",))`` for ViT-S and then MobileNetV3-L at published
   widths, on the paper's fleet shape (120 clients, cohort 12, H=8,
   device batch 32, server batch 256) for 2 device rounds and 2 server
   epochs over seeded synthetic data and random weights.

It prints one JSON line per kernel and per model (errors, parameter
count, compile seconds, per-phase wall seconds of this smoke run — not a
benchmark — and the first and last device-round and server-epoch
losses), then as its last line ``{"ok": true, "device": {...}}``.  It
exits non-zero, without that line, when JAX finds no TPU, a parameter of
the trained state is not on the TPU, a loss is not finite, the server
loss does not fall, a kernel misses its oracle, or anything raises.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``<repo>/.jax_cache``; a second run with a warm cache reports fewer
compile seconds.
"""

import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# max |kernel - oracle| / max |oracle|: bf16 operands and outputs
KERNEL_RTOL = 2e-2
MODELS = ("vit-s", "mobilenet-l")
TRAIN_SAMPLES = 3840          # 15 server batches of 256 per epoch
EVAL_SAMPLES = 512
DEVICE_ROUNDS = 2
SERVER_EPOCHS = 2


def fail(msg):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


class CompileClock:
    """Seconds JAX spends compiling (cache lookups included) and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def read(self):
        return self.seconds, self.cache_hits


def tpu_device():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's devices are {dev.platform!r} "
             f"({dev.device_kind})")
    return dev


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _max_err(got, ref):
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        fail(f"shape {got.shape} != oracle shape {ref.shape}")
    if not np.all(np.isfinite(got)):
        fail("kernel output is not finite")
    err = float(np.max(np.abs(got - ref)))
    return err, err / max(float(np.max(np.abs(ref))), 1e-30)


def _compare(name, pairs):
    """pairs: {label: (kernel value, oracle value)} -> one JSON line."""
    row = {"phase": "kernel", "kernel": name, "rtol": KERNEL_RTOL}
    for label, (got, ref) in pairs.items():
        err, rel = _max_err(got, ref)
        row[f"{label}_max_abs_err"] = err
        row[f"{label}_rel_err"] = rel
        if rel > KERNEL_RTOL:
            print(json.dumps(row), flush=True)
            fail(f"{name} {label}: max abs error {err} is {rel} of the "
                 f"oracle's max, over {KERNEL_RTOL}")
    print(json.dumps(row), flush=True)


def kernel_phase(fa, xent, ssd, seed=0):
    """Each kernel forward and backward vs its oracle.  ``fa``, ``xent``
    and ``ssd`` hold the widths; the backward uses the platform's
    default accumulation strategy (``alias`` on a TPU)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.ssd_chunk.ops import ssd_intra
    from repro.kernels.ssd_chunk.ref import ssd_intra_ref
    from repro.kernels.xent.ops import cross_entropy
    from repro.kernels.xent.ref import cross_entropy_ref

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, dtype=jnp.bfloat16, std=1.0):
        return (std * jax.random.normal(next(keys), shape)).astype(dtype)

    def oracle(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    # flash attention: fwd + fused bwd
    B, S, Hkv, G, hd = fa["B"], fa["S"], fa["Hkv"], fa["G"], fa["hd"]
    q = normal((B, S, Hkv, G, hd))
    k, v = normal((B, S, Hkv, hd)), normal((B, S, Hkv, hd))
    scale = hd ** -0.5

    def fa_loss(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v).astype(
            jnp.float32)))

    def fa_kernel(q, k, v):
        return flash_attention(q, k, v, True, 0, 0.0, scale)

    def fa_ref(q, k, v):
        return attention_ref(q, k, v, causal=True, scale=scale)[0].astype(
            q.dtype)

    grads = jax.grad(fa_loss(fa_kernel), argnums=(0, 1, 2))
    o, g = jax.jit(fa_kernel)(q, k, v), jax.jit(grads)(q, k, v)
    o_ref = oracle(fa_ref, q, k, v)
    g_ref = oracle(jax.grad(fa_loss(fa_ref), argnums=(0, 1, 2)), q, k, v)
    _compare("flash_attention", {"fwd": (o, o_ref), "dq": (g[0], g_ref[0]),
                                 "dk": (g[1], g_ref[1]),
                                 "dv": (g[2], g_ref[2])})
    del q, k, v, o, g, o_ref, g_ref

    # fused cross-entropy: fwd + bwd
    T, D, V = xent["T"], xent["D"], xent["V"]
    h = normal((T, D))
    w = normal((D, V), std=D ** -0.5)
    lab = jax.random.randint(next(keys), (T,), 0, V, jnp.int32)

    def xent_kernel(h, w):
        return cross_entropy(h, w, lab, impl="pallas")

    def xent_ref(h, w):
        return cross_entropy_ref(h, w, lab)

    per_tok = jax.jit(lambda h, w: xent_kernel(h, w)[1])(h, w)
    g = jax.jit(jax.grad(lambda h, w: xent_kernel(h, w)[0],
                         argnums=(0, 1)))(h, w)
    ref_tok = oracle(lambda h, w: xent_ref(h, w)[1], h, w)
    g_ref = oracle(jax.grad(lambda h, w: xent_ref(h, w)[0], argnums=(0, 1)),
                   h, w)
    _compare("xent", {"fwd": (per_tok, ref_tok), "dh": (g[0], g_ref[0]),
                      "dw": (g[1], g_ref[1])})
    del h, w, per_tok, g, ref_tok, g_ref

    # SSD intra-chunk: the Pallas kernel is forward-only; the backward is
    # the oracle's VJP (ssd_chunk/ops.py), so it runs at the oracle's
    # precision and the comparison checks the custom VJP's wiring
    Bs, nc, Q, H, P, N = (ssd[k] for k in ("B", "nc", "Q", "H", "P", "N"))
    xf = normal((Bs, nc, Q, H, P), jnp.float32)
    dtf = jnp.abs(normal((Bs, nc, Q, H), jnp.float32, std=0.1))
    A = -jnp.abs(1.0 + 0.3 * normal((H,), jnp.float32))
    a_cum = jnp.cumsum(dtf * A, axis=2)
    Bf, Cf = normal((Bs, nc, Q, N), jnp.float32), normal((Bs, nc, Q, N),
                                                         jnp.float32)
    args = (xf, dtf, a_cum, Bf, Cf)

    def ssd_loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)[0])) + jnp.sum(fn(*a)[1])

    y, s = jax.jit(ssd_intra)(*args)
    g = oracle(jax.grad(ssd_loss(ssd_intra), argnums=(0, 1)), *args)
    y_ref, s_ref = oracle(ssd_intra_ref, *args)
    g_ref = oracle(jax.grad(ssd_loss(ssd_intra_ref), argnums=(0, 1)), *args)
    _compare("ssd_intra", {"y": (y, y_ref), "state": (s, s_ref),
                           "dx": (g[0], g_ref[0]), "ddt": (g[1], g_ref[1])})


# ---------------------------------------------------------------------------
# models
# ---------------------------------------------------------------------------


def model_spec(arch):
    """The paper's fleet shape (FedConfig defaults) at published widths.

    ``grad_clip=1.0``: at ViT-S width plain SGD at the paper's lr 0.05
    oscillates in the server phase (server loss rose 2.6 -> 6.5 over a
    few epochs on a CPU run); clipping at 1.0 lets it fall."""
    from repro.configs.base import OptimConfig, RunConfig
    from repro.experiments import DataSpec, ExperimentSpec, ObservabilitySpec

    return ExperimentSpec(
        name=f"chip_smoke_{arch}", systems=("ampere",), arch=arch,
        smoke=False,
        run=RunConfig(arch=arch, optim=OptimConfig(grad_clip=1.0)),
        data=DataSpec(train_samples=TRAIN_SAMPLES,
                      eval_samples=EVAL_SAMPLES),
        max_rounds=DEVICE_ROUNDS, max_server_epochs=SERVER_EPOCHS,
        observability=ObservabilitySpec(trace_json=False, span_log=False,
                                        scheduler_events=False))


def _assert_on(device, tree, what):
    import jax

    for leaf in jax.tree.leaves(tree):
        if not isinstance(leaf, jax.Array) or leaf.devices() != {device}:
            where = (sorted(str(d) for d in leaf.devices())
                     if isinstance(leaf, jax.Array) else type(leaf).__name__)
            fail(f"{what}: a parameter leaf is on {where}, not {device}")


def model_phase(arch, device, clock):
    import jax

    from repro.experiments import run_experiment

    spec = model_spec(arch)
    c0, h0 = clock.read()
    t0 = time.perf_counter()
    out = run_experiment(spec, write_results=False)
    wall = time.perf_counter() - t0
    c1, h1 = clock.read()

    res = out["results"]["ampere"]
    hist = res["history"]
    _assert_on(device, res["device_state"], f"{arch} device state")
    _assert_on(device, res["server_state"], f"{arch} server state")
    _assert_on(device, res["merged_params"], f"{arch} merged params")
    phase_wall = {r["phase"]: r["wall_s"]
                  for r in out["summary"]["ampere"]["phases"]}
    dev_losses = [r["loss"] for r in hist["device"]]
    srv_losses = [r["loss"] for r in hist["server"]]
    row = {
        "phase": "model", "arch": arch, "smoke_run_not_benchmark": True,
        "params": int(sum(x.size for x in
                          jax.tree.leaves(res["merged_params"]))),
        "compile_s": c1 - c0, "cache_hits": h1 - h0, "wall_s": wall,
        "phase_wall_s": phase_wall,
        # data synthesis, init and activation generation: outside steps
        "outside_phase_steps_s": wall - sum(phase_wall.values()),
        "device_round_loss": [dev_losses[0], dev_losses[-1]],
        "server_epoch_loss": [srv_losses[0], srv_losses[-1]],
        "final_val_acc": hist["server"][-1]["val_acc"],
    }
    print(json.dumps(row), flush=True)
    if (len(dev_losses), len(srv_losses)) != (DEVICE_ROUNDS, SERVER_EPOCHS):
        fail(f"{arch}: ran {len(dev_losses)} device rounds and "
             f"{len(srv_losses)} server epochs, not {DEVICE_ROUNDS} and "
             f"{SERVER_EPOCHS}")
    if not all(math.isfinite(x) for x in dev_losses + srv_losses):
        fail(f"{arch}: a loss is not finite: device {dev_losses}, "
             f"server {srv_losses}")
    if not srv_losses[-1] < srv_losses[0]:
        fail(f"{arch}: server loss did not fall: {srv_losses}")


def main():
    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        fail(f"the repo's src/repro is not next to {__file__}")
    from repro.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    device = tpu_device()
    clock = CompileClock()
    t0 = time.perf_counter()
    kernel_phase(fa=dict(B=1, S=4096, Hkv=8, G=2, hd=128),
                 xent=dict(T=4096, D=2048, V=151936),
                 ssd=dict(B=1, nc=16, Q=256, H=32, P=64, N=128))
    for arch in MODELS:
        model_phase(arch, device, clock)
    compile_s, hits = clock.read()
    print(json.dumps({"phase": "total", "compile_s": compile_s,
                      "cache_hits": hits,
                      "wall_s": time.perf_counter() - t0,
                      "compile_cache_dir": cache_dir}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
