import os
# a CPU-only rehearsal: 512 virtual host devices back the production
# meshes, even on a host with a TPU (which would otherwise be the backend)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape x mesh) cell, builds the relevant
step function (Ampere server phase for training shapes, prefill/decode for
serving shapes, plus optional device-round/e2e graphs), lowers it with
abstract ShapeDtypeStruct inputs under explicit NamedShardings, compiles
it, and records ``memory_analysis()`` / ``cost_analysis()`` plus the
parsed collective schedule for the roofline (§Roofline in EXPERIMENTS.md).

512 placeholder host devices back the production meshes — the XLA_FLAGS
line above MUST run before any other import touches jax.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
      --shape train_4k --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all \
      --out results/dryrun.json
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import analysis
from repro.configs import registry
from repro.configs.base import (FedConfig, MeshConfig, OptimConfig, RunConfig,
                                SHAPES, ShardingConfig, SplitConfig, replace)
from repro.core import comm_model, splitting, steps
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.models import transformer as T
from repro.optim import make_optimizer
from repro.sharding import axis_rules, rules as shard_rules

BIG_ARCH_PARAMS = 20e9   # archs above this use bf16 optimizer moments


# ---------------------------------------------------------------------------
# Abstract inputs
# ---------------------------------------------------------------------------


def _abs(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def make_run_cfg(arch: str, shape_name: str) -> RunConfig:
    cfg = registry.get_config(arch)
    big = cfg.param_count() > BIG_ARCH_PARAMS
    # multi-layer periods (jamba) remat per layer inside the scanned body,
    # or the backward holds a whole superblock's intermediates
    remat = "nested" if cfg.pattern_period > 1 else "block"
    return RunConfig(
        arch=arch, shape=shape_name,
        split=SplitConfig(split_point=1),
        fed=FedConfig(clients_per_round=32, local_steps=8,
                      device_batch_size=8),
        optim=OptimConfig(name="adamw", lr=3e-4, schedule="warmup_cosine",
                          optimizer_state_dtype="bfloat16" if big
                          else "float32"),
        sharding=ShardingConfig(strategy="fsdp_tp", remat=remat,
                                scan_layers=True),
    )


def input_specs(arch: str, shape_name: str, step: str, run_cfg=None,
                cfg=None):
    """ShapeDtypeStruct stand-ins for every input of ``step`` — weak-type
    correct, shardable, no device allocation."""
    cfg = cfg if cfg is not None else registry.get_config(arch)
    model = build_model(cfg)
    shape = SHAPES[shape_name]
    run_cfg = run_cfg or make_run_cfg(arch, shape_name)
    B, S = shape.global_batch, shape.seq_len
    p = run_cfg.split.split_point

    if step == "server_train_step":
        params = comm_model.abstract_params(model)
        _, srv = jax.eval_shape(
            lambda pp: splitting.split_params(model, pp, p), params)
        opt = make_optimizer(run_cfg.optim)
        opt_state = jax.eval_shape(opt.init, srv)
        state = {"server": srv, "opt": opt_state,
                 "step": jax.ShapeDtypeStruct((), jnp.int32)}
        batch = {"acts": jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16),
                 "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        return {"state": state, "batch": batch}

    if step == "e2e_train_step":
        params = comm_model.abstract_params(model)
        opt = make_optimizer(run_cfg.optim)
        opt_state = jax.eval_shape(opt.init, params)
        state = {"params": params, "opt": opt_state,
                 "step": jax.ShapeDtypeStruct((), jnp.int32)}
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        return {"state": state, "batch": batch}

    if step == "device_round_step":
        params = comm_model.abstract_params(model)
        dev, _ = jax.eval_shape(
            lambda pp: splitting.split_params(model, pp, p), params)
        from repro.core import auxiliary
        aux = jax.eval_shape(
            lambda k: auxiliary.init_aux(model, k, run_cfg.split),
            jax.random.PRNGKey(0))
        K = run_cfg.fed.clients_per_round
        H = run_cfg.fed.local_steps
        b = max(1, B // K)
        state = {"device": dev, "aux": aux}
        batches = {"tokens": jax.ShapeDtypeStruct((K, H, b, S), jnp.int32)}
        return {"state": state, "batches": batches,
                "weights": jax.ShapeDtypeStruct((K,), jnp.float32),
                "lr": jax.ShapeDtypeStruct((), jnp.float32)}

    if step in ("prefill_step", "decode_step"):
        params = comm_model.abstract_params(model)
        caches = jax.eval_shape(
            lambda: T.init_caches(cfg, B, S, kv_dtype="bfloat16"))
        if step == "prefill_step":
            tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
            return {"params": params, "tokens": tokens, "caches": caches}
        token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        return {"params": params, "caches": caches, "token": token,
                "index": jax.ShapeDtypeStruct((), jnp.int32)}

    raise ValueError(f"unknown step {step!r}")


# ---------------------------------------------------------------------------
# Sharding assignment
# ---------------------------------------------------------------------------


def shardings_for(specs_tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs_tree,
        is_leaf=lambda x: isinstance(x, P))


def cell_shardings(abstract_args, step: str, mesh, shape, run_cfg):
    """NamedSharding tree matching input_specs(...) for this step."""
    multi_pod = "pod" in mesh.axis_names
    strategy = run_cfg.sharding.strategy
    dp = (tuple(mesh.axis_names) if strategy == "dp_only"
          else (("pod", "data") if multi_pod else ("data",)))
    dp_size = int(np.prod([dict(zip(mesh.axis_names,
                                    mesh.devices.shape))[a] for a in dp]))
    B = shape.global_batch
    batch_ok = B % dp_size == 0
    if not batch_ok and strategy == "dp_only":
        dp = ("pod", "data") if multi_pod else ("data",)
        dp_size = int(np.prod([dict(zip(mesh.axis_names,
                                        mesh.devices.shape))[a] for a in dp]))
        batch_ok = B % dp_size == 0

    def pspec(tree, **kw):
        return shardings_for(
            shard_rules.param_specs(tree, mesh, strategy=strategy, **kw), mesh)

    if step in ("server_train_step", "e2e_train_step"):
        key = "server" if step == "server_train_step" else "params"
        st = abstract_args["state"]
        state_sh = {key: pspec(st[key]),
                    "opt": pspec(st["opt"]),
                    "step": NamedSharding(mesh, P())}
        bsh = {}
        for k, v in abstract_args["batch"].items():
            spec = [dp] + [None] * (v.ndim - 1)
            bsh[k] = NamedSharding(mesh, P(*spec))
        return (state_sh, bsh)

    if step == "device_round_step":
        # Pure client-parallelism: the device block is tiny by Ampere's
        # design (p=1), so clients map onto EVERY mesh axis, the device
        # block + aux net are fully replicated, per-client local SGD runs
        # with zero collectives, and the round ends in one weighted psum
        # (the FedAvg).  TP on a per-client sliver would drown in
        # activation psums — measured in EXPERIMENTS.md §Dry-run.
        all_axes = tuple(mesh.axis_names)
        st = abstract_args["state"]
        repl = lambda tree: jax.tree.map(
            lambda _: NamedSharding(mesh, P()), tree)
        state_sh = {"device": repl(st["device"]), "aux": repl(st["aux"])}
        bsh = {k: NamedSharding(mesh, P(all_axes, *([None] * (v.ndim - 1))))
               for k, v in abstract_args["batches"].items()}
        return (state_sh, bsh, NamedSharding(mesh, P(all_axes)),
                NamedSharding(mesh, P()))

    if step in ("prefill_step", "decode_step"):
        kv_axes = ("model",)
        batch_axes = dp
        if not batch_ok:
            batch_axes = ()
            kv_axes = dp + ("model",)    # long-context: shard seq everywhere
        params_sh = pspec(abstract_args["params"])
        caches_sh = shardings_for(
            shard_rules.param_specs(abstract_args["caches"], mesh,
                                    strategy=strategy, cache=True,
                                    kv_seq_axes=kv_axes,
                                    batch_axes=batch_axes), mesh)
        tok_spec = P(batch_axes if batch_axes else None, None)
        if step == "prefill_step":
            return (params_sh, NamedSharding(mesh, tok_spec), caches_sh)
        return (params_sh, caches_sh, NamedSharding(mesh, tok_spec),
                NamedSharding(mesh, P()))

    raise ValueError(step)


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def make_step_fn(model, run_cfg, step: str, xent_impl: str = "sharded",
                 grad_shardings=None):
    if step == "server_train_step":
        return steps.make_server_train_step(model, run_cfg,
                                            xent_impl=xent_impl,
                                            grad_shardings=grad_shardings)
    if step == "e2e_train_step":
        return steps.make_e2e_train_step(model, run_cfg, xent_impl=xent_impl)
    if step == "device_round_step":
        # blockwise xent: per-client local math, no resharding (params are
        # replicated in the client-parallel device phase)
        return steps.make_device_round_step(model, run_cfg, xent_impl="xla")
    if step == "prefill_step":
        return steps.make_prefill_step(model, run_cfg)
    if step == "decode_step":
        return steps.make_decode_step(model, run_cfg, scan=True)
    raise ValueError(step)


def _compile_once(model, run_cfg, shape, mesh, step: str, arch: str,
                  shape_name: str, *, cfg=None, donate=True):
    """Lower + compile one graph; returns (compiled, hlo_text, timings)."""
    cfg = cfg if cfg is not None else model.cfg
    if run_cfg.optim.master_weights and cfg.param_dtype != "bfloat16":
        cfg = replace(cfg, param_dtype="bfloat16")
        model = build_model(cfg)
    if step == "device_round_step":
        # cohort spans the full mesh (one client slot per chip)
        run_cfg = replace(run_cfg, fed=replace(
            run_cfg.fed, clients_per_round=mesh.devices.size,
            device_batch_size=1))
    abstract_args = input_specs(arch, shape_name, step, run_cfg, cfg=cfg)
    in_sh = cell_shardings(abstract_args, step, mesh, shape, run_cfg)
    grad_sh = (in_sh[0]["server"] if step == "server_train_step" else None)
    fn = make_step_fn(model, run_cfg, step, grad_shardings=grad_sh)
    args = tuple(abstract_args.values())
    seq_shard = run_cfg.sharding.sequence_sharding and shape.kind != "decode"
    rules = shard_rules.default_axis_rules(
        mesh, sequence_sharding=seq_shard,
        strategy=run_cfg.sharding.strategy)
    if step == "device_round_step":
        # client-parallel phase: everything per-client is local; no
        # logical axis binds to the mesh (the client axis owns it all)
        rules = {}
    # real host-side lower/compile timing, not sim time
    t0 = time.perf_counter()  # staticcheck: ok=wall-clock
    with axis_rules(rules, mesh), \
            analysis.grad_comm_dtype(run_cfg.optim.grad_dtype or None):
        dn = (0,) if donate and ("train" in step
                                 or step == "device_round_step") else ()
        jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=dn)
        lowered = jitted.lower(*args)
        t_lower = time.perf_counter() - t0  # staticcheck: ok=wall-clock
        compiled = lowered.compile()
        t_compile = (time.perf_counter()  # staticcheck: ok=wall-clock
                     - t0 - t_lower)
    return compiled, compiled.as_text(), (t_lower, t_compile)


def _cost_triplet(compiled, hlo):
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    coll = RL.parse_collectives(hlo)
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)),
            coll.per_device_bytes, coll.counts, coll.bytes_by_op)


def _depth_for(cfg, step: str, p: int, k: int) -> int:
    """num_layers for a k-rep analysis graph of ``step``."""
    P = cfg.pattern_period
    if step == "server_train_step":
        r0 = -(-p // P)
        return r0 * P + k * P
    return k * P


def _reps_full(cfg, step: str, p: int) -> int:
    P = cfg.pattern_period
    if step == "server_train_step":
        return cfg.num_layers // P - (-(-p // P))
    return cfg.num_layers // P


def _device_round_analysis(arch, shape_name, run_cfg, shape, chips):
    """Device-phase costs: per-device work == one client's local round
    (client-parallel mapping, params replicated), so compile the
    single-client graph on one device with unrolled scans and extrapolate
    the local-step count; the only collective is the FedAvg all-reduce,
    costed analytically."""
    cfg = registry.get_config(arch)
    model = build_model(cfg)
    p = run_cfg.split.split_point
    from repro.core import auxiliary

    vals = []
    for h in (1, 2):
        rc = replace(run_cfg, fed=replace(run_cfg.fed, clients_per_round=1,
                                          local_steps=h,
                                          device_batch_size=1))
        fn = steps.make_device_round_step(model, rc, xent_impl="xla")
        params = comm_model.abstract_params(model)
        dev, _ = jax.eval_shape(
            lambda pp: splitting.split_params(model, pp, p), params)
        aux = jax.eval_shape(
            lambda k: auxiliary.init_aux(model, k, rc.split),
            jax.random.PRNGKey(0))
        batches = {"tokens": jax.ShapeDtypeStruct((1, h, 1, shape.seq_len),
                                                  jnp.int32)}
        with analysis.unroll_scans():
            lowered = jax.jit(fn).lower(
                {"device": dev, "aux": aux}, batches,
                jax.ShapeDtypeStruct((1,), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32))
            compiled = lowered.compile()
        f, b, c, _, _ = _cost_triplet(compiled, compiled.as_text())
        vals.append((f, b))
    (f1, b1), (f2, b2) = vals
    H = run_cfg.fed.local_steps
    flops = f1 + (H - 1) * (f2 - f1)
    byts = b1 + (H - 1) * (b2 - b1)
    sizes = comm_model.split_sizes(model, run_cfg.split,
                                   seq_len=shape.seq_len)
    coll = 2.0 * (sizes.device + sizes.aux) * (chips - 1) / chips
    return flops, byts, coll, {"all-reduce": 4}, {"all-reduce": coll}


def analysis_costs(arch, shape_name, mesh, step, run_cfg, shape):
    """Exact per-device (flops, bytes, collective_bytes) via two-point
    depth extrapolation over unrolled analysis graphs.

    cost_analysis() counts while-loop bodies once, so the production
    (scanned) graph under-reports in-loop work by the trip count.  We
    compile depth-1 and depth-2 *unrolled* variants (inner scans unrolled
    via repro.analysis) and extrapolate linearly in the number of layer
    repetitions — exact for cost models that are additive per layer.
    """
    cfg = registry.get_config(arch)
    p = run_cfg.split.split_point
    rc = replace(run_cfg,
                 sharding=replace(run_cfg.sharding, scan_layers=False))
    # server steps admit a k=0 graph (partial leading period + head only),
    # halving the largest analysis graph for long-period archs (jamba P=8)
    ks = (0, 1) if step == "server_train_step" and \
        _depth_for(cfg, step, p, 0) > 0 else (1, 2)
    vals = []
    counts2, byop = {}, {}
    for k in ks:
        cfg_k = replace(cfg, num_layers=_depth_for(cfg, step, p, k))
        model_k = build_model(cfg_k)
        with analysis.unroll_scans():
            compiled, hlo, _ = _compile_once(
                model_k, rc, shape, mesh, step, arch, shape_name,
                cfg=cfg_k, donate=False)
        f, b, c, counts, bb = _cost_triplet(compiled, hlo)
        vals.append((f, b, c, bb))
        counts2 = counts
    (f1, b1, c1, bb1), (f2, b2, c2, bb2) = vals
    K = _reps_full(cfg, step, p)
    if ks[0] == 0:  # c(k) = base + k*per_rep measured at k=0,1
        extrapolate = lambda x1, x2: x1 + K * (x2 - x1)
    else:
        extrapolate = lambda x1, x2: x1 + (K - 1) * (x2 - x1)
    counts_scaled = {k: v * K for k, v in counts2.items()}  # upper-bound count
    byop = {k: extrapolate(bb1.get(k, 0.0), bb2.get(k, 0.0))
            for k in set(bb1) | set(bb2)}
    return (extrapolate(f1, f2), extrapolate(b1, b2), extrapolate(c1, c2),
            counts_scaled, byop)


def run_cell(arch: str, shape_name: str, mesh_name: str, step: str,
             *, run_cfg=None, verbose: bool = True, keep_hlo: bool = False,
             analyze: bool = True):
    """One dry-run cell: compile the PRODUCTION graph (scan-over-layers —
    this is the lowering proof + memory analysis), then derive exact
    roofline terms from depth-extrapolated analysis graphs."""
    cfg = registry.get_config(arch)
    model = build_model(cfg)
    shape = SHAPES[shape_name]
    run_cfg = run_cfg or make_run_cfg(arch, shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi_pod"))
    chips = mesh.devices.size

    compiled, hlo, (t_lower, t_compile) = _compile_once(
        model, run_cfg, shape, mesh, step, arch, shape_name)

    if analyze and step == "device_round_step":
        flops, byts, coll_bytes, coll_counts, coll_byop = \
            _device_round_analysis(arch, shape_name, run_cfg, shape, chips)
    elif analyze:
        flops, byts, coll_bytes, coll_counts, coll_byop = analysis_costs(
            arch, shape_name, mesh, step, run_cfg, shape)
    else:
        flops, byts, coll_bytes, coll_counts, coll_byop = _cost_triplet(
            compiled, hlo)

    mf = RL.model_flops_estimate(cfg, shape.kind, shape.seq_len,
                                 shape.global_batch, step)
    if step == "device_round_step":
        sizes = comm_model.split_sizes(model, run_cfg.split,
                                       seq_len=shape.seq_len)
        K, H, b = chips, run_cfg.fed.local_steps, 1  # mesh-wide cohort
        mf = 6.0 * ((sizes.device + sizes.aux) / 4) * K * H * b * shape.seq_len

    mem = compiled.memory_analysis()
    peak = (getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0))
    rl = RL.Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, step=step, chips=chips,
        flops_per_device=flops, bytes_per_device=byts,
        collective_bytes_per_device=coll_bytes,
        peak_memory_per_device=float(peak), model_flops=mf,
        collective_counts=coll_counts)
    row = rl.row()
    row["coll_mb_by_op_per_dev"] = {k: round(v / 1e6, 2)
                                    for k, v in coll_byop.items()}
    row["lower_s"] = round(t_lower, 2)
    row["compile_s"] = round(t_compile, 2)
    row["status"] = "ok"
    row["mem"] = {
        "argument_gb": getattr(mem, "argument_size_in_bytes", 0) / 1e9,
        "output_gb": getattr(mem, "output_size_in_bytes", 0) / 1e9,
        "temp_gb": getattr(mem, "temp_size_in_bytes", 0) / 1e9,
        "alias_gb": getattr(mem, "alias_size_in_bytes", 0) / 1e9,
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name} x {step}: "
              f"compile ok in {t_compile:.1f}s | "
              f"t_comp={row['t_compute_ms']:.2f}ms "
              f"t_mem={row['t_memory_ms']:.2f}ms "
              f"t_coll={row['t_collective_ms']:.2f}ms "
              f"bottleneck={row['bottleneck']} "
              f"useful={row['useful_flops_frac']:.2f} "
              f"peak_mem={row['peak_mem_gb_per_device']:.2f}GB/dev",
              flush=True)
        print(f"         memory_analysis: {row['mem']}", flush=True)
        print(f"         cost_analysis: flops/dev={row['hlo_gflops_total']/chips:.1f}G "
              f"bytes/dev={row['hbm_gb_total']/chips:.2f}GB "
              f"collectives={row['collectives']}", flush=True)
    if keep_hlo:
        row["hlo_text"] = hlo
    return row


STEP_FOR_KIND = {"train": "server_train_step", "prefill": "prefill_step",
                 "decode": "decode_step"}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single_pod", "multi_pod", "both"])
    ap.add_argument("--steps", default="auto",
                    help="comma list or 'auto' (per-shape default) or 'full' "
                         "(auto + device_round for train shapes)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-analyze", action="store_true",
                    help="compile-proof only (skip the cost-analysis "
                         "extrapolation compiles)")
    ap.add_argument("--strategy", default="",
                    choices=["", "fsdp_tp", "dp_only", "tp_only"],
                    help="override the sharding strategy (§Perf runs)")
    ap.add_argument("--master-weights", action="store_true",
                    help="bf16 params + fp32 master weights (§Perf runs)")
    args = ap.parse_args(argv)

    meshes = (["single_pod", "multi_pod"] if args.mesh == "both"
              else [args.mesh])
    cells = []
    if args.all:
        matrix = registry.cells(include_skipped=True)
    else:
        archs = [args.arch] if args.arch else list(registry.ASSIGNED_ARCHS)
        shapes = [args.shape] if args.shape else list(SHAPES)
        matrix = [(a, s, r, why) for a in archs for s in shapes
                  for (aa, ss, r, why) in registry.cells()
                  if aa == a and ss == s]

    rows = []
    failures = 0
    for arch, shape_name, runnable, why in matrix:
        if not runnable:
            rows.append({"arch": arch, "shape": shape_name, "status": "skip",
                         "reason": why})
            print(f"[dryrun] {arch} x {shape_name}: SKIP ({why})", flush=True)
            continue
        kind = SHAPES[shape_name].kind
        if args.steps == "auto":
            step_list = [STEP_FOR_KIND[kind]]
        elif args.steps == "full":
            step_list = [STEP_FOR_KIND[kind]]
            if kind == "train":
                step_list.append("device_round_step")
        else:
            step_list = args.steps.split(",")
        run_cfg = None
        if args.strategy or args.master_weights:
            run_cfg = make_run_cfg(arch, shape_name)
            if args.strategy:
                run_cfg = replace(run_cfg, sharding=replace(
                    run_cfg.sharding, strategy=args.strategy))
            if args.master_weights:
                run_cfg = replace(run_cfg, optim=replace(
                    run_cfg.optim, master_weights=True))
        for mesh_name in meshes:
            for step in step_list:
                try:
                    rows.append(run_cell(arch, shape_name, mesh_name, step,
                                         run_cfg=run_cfg,
                                         analyze=not args.no_analyze))
                except Exception as e:
                    failures += 1
                    traceback.print_exc()
                    rows.append({"arch": arch, "shape": shape_name,
                                 "mesh": mesh_name, "step": step,
                                 "status": "fail", "error": repr(e)})
                    print(f"[dryrun] {arch} x {shape_name} x {mesh_name} x "
                          f"{step}: FAIL {e}", flush=True)

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1, default=str)
        print(f"[dryrun] wrote {len(rows)} rows to {args.out}", flush=True)
    ok = sum(1 for r in rows if r.get("status") == "ok")
    print(f"[dryrun] {ok} ok / {failures} failed / "
          f"{sum(1 for r in rows if r.get('status') == 'skip')} skipped",
          flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
