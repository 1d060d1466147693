"""Prong 1: static geometry analysis of every registered Pallas kernel.

Traces each kernel entry point with abstract shapes (no FLOPs run), walks
the jaxpr for ``pallas_call`` equations, and — because TPU grids execute
*sequentially* — concretely evaluates every BlockSpec index_map over the
whole grid to recover the exact HBM window schedule each operand sees.
From that schedule it checks the four properties the interpreter cannot
exercise:

(a) **aliased-accumulator revisit distance** — the in-place accumulation
    idiom (xent dH, flash-attention dQ) is only DMA-safe because the
    aliased output window is flushed and re-fetched a known number of
    grid steps apart (nt for xent, G*nq for FA).  The analyzer
    reproduces those distances and flags any aliased operand whose
    minimum revisit distance drops below the DMA-safety threshold, or
    whose window stays resident across consecutive steps while the
    kernel still reads the aliased input (no flush/refetch happens when
    the window index does not change).
(b) **block alignment** — (sublane, lane) tile requirements per dtype:
    the sublane dim must be a multiple of 8/16/32 for 4/2/1-byte types
    (no full-dim exemption: the ``S=20 -> bq=20`` bug *was* the full
    dim), or 1 over an array dim of 1; the lane dim a multiple of 128 or
    the whole array dim.  A 1-D block must be the whole array or a
    multiple of 1024 elements.  These are the TPU compiler's rules: it
    refused a (1, bq) block over (BH, S) and a (256,) block over (2048,).
(c) **per-grid-step VMEM footprint** — double-buffered in/out windows
    plus scratch vs the ~16 MiB/core budget.
(d) **write-before-read for outputs** — output windows are undefined on
    first visit; a kernel that reads an output ref before
    unconditionally writing it consumes garbage (accumulators must
    thread the running sum through the aliased *input* ref instead).

Results are cached per (kernel sources, config, analyzer version) hash —
the CI gate re-traces only what changed.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.staticcheck.findings import ANALYZER_VERSION, Finding

SUBLANE_BY_ITEMSIZE = {4: 8, 2: 16, 1: 32}
LANE = 128
LANE_1D = 1024    # XLA tiles a 1-D array T(1024); Mosaic needs the same


@dataclasses.dataclass
class AnalyzerSettings:
    """Thresholds for the geometry checks."""

    dma_safety_threshold: int = 2   # min acceptable aliased revisit distance
    vmem_budget_bytes: int = 16 * 2 ** 20
    max_grid_steps: int = 1 << 20   # refuse to enumerate absurd grids

    def key(self) -> str:
        return (f"{self.dma_safety_threshold}/{self.vmem_budget_bytes}"
                f"/{self.max_grid_steps}")


@dataclasses.dataclass
class OperandGeometry:
    """One block-spec'd operand (input or output) of a pallas_call."""

    origin: str                 # ref name from the kernel signature
    kind: str                   # "in" | "out"
    index: int                  # position within its kind
    block_shape: Tuple[int, ...]
    array_shape: Tuple[int, ...]
    dtype: str
    n_blocks: int = 0           # distinct windows over the grid
    min_revisit: Optional[int] = None   # grid steps between revisits
    max_run_len: int = 1        # longest consecutive-step residency
    reads: bool = False
    writes: bool = False
    read_before_write: bool = False

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class PallasCallGeometry:
    """Everything the analyzer derived about one pallas_call."""

    name: str
    grid: Tuple[int, ...]
    aliases: Tuple[Tuple[int, int], ...]   # (input idx, output idx)
    operands: List[OperandGeometry]
    scratch_shapes: List[Tuple[Tuple[int, ...], str]]
    vmem_bytes: int = 0

    def operand(self, kind: str, index: int) -> OperandGeometry:
        for op in self.operands:
            if op.kind == kind and op.index == index:
                return op
        raise KeyError((kind, index))

    def to_dict(self) -> dict:
        return {"name": self.name, "grid": list(self.grid),
                "aliases": [list(a) for a in self.aliases],
                "operands": [o.to_dict() for o in self.operands],
                "scratch_shapes": [[list(s), d]
                                   for s, d in self.scratch_shapes],
                "vmem_bytes": self.vmem_bytes}


# ---------------------------------------------------------------------------
# jaxpr walking


def _find_pallas_eqns(jaxpr, out):
    from jax import core as jcore
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for sub in jcore.jaxprs_in_params(eqn.params):
            _find_pallas_eqns(sub, out)
    return out


def trace_pallas_calls(fn, args) -> List:
    """All pallas_call eqns reachable from ``fn(*args)`` (abstract trace)."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*args)
    return _find_pallas_eqns(jaxpr.jaxpr, [])


def _block_ints(block_shape) -> Tuple[int, ...]:
    # block dims arrive as ints or ``pl.Blocked(block_size)``; mapped
    # (None / pl.Squeezed) dims occupy one element of the window
    def size(d):
        if isinstance(d, (int, np.integer)):
            return int(d)
        return int(getattr(d, "block_size", 1) or 1)
    return tuple(size(d) for d in block_shape)


def _eval_index_map(bm, idx) -> Tuple[int, ...]:
    from jax import core as jcore
    closed = bm.index_map_jaxpr
    out = jcore.eval_jaxpr(closed.jaxpr, closed.consts,
                           *(np.int32(i) for i in idx))
    return tuple(int(x) for x in out)


def _visit_stats(seq: Sequence[Tuple[int, ...]]):
    """(n_blocks, min_revisit, max_run_len) for one operand's window
    schedule.  A *run* is a maximal span of consecutive grid steps with
    the same window index (the window stays resident — no flush or
    refetch inside a run); the revisit distance is the number of grid
    steps between the end of one run and the start of the next for the
    same index."""
    runs: Dict[Tuple[int, ...], List[List[int]]] = {}
    prev = None
    for step, b in enumerate(seq):
        if b == prev:
            runs[b][-1][1] = step
        else:
            runs.setdefault(b, []).append([step, step])
        prev = b
    min_revisit: Optional[int] = None
    max_run = 1
    for rlist in runs.values():
        for start, end in rlist:
            max_run = max(max_run, end - start + 1)
        for (_, e1), (s2, _) in zip(rlist, rlist[1:]):
            gap = s2 - e1
            min_revisit = gap if min_revisit is None else min(min_revisit,
                                                              gap)
    return len(runs), min_revisit, max_run


# ref-access classification ---------------------------------------------------


def _ref_accesses(kernel_jaxpr, n_operands: int):
    """Ordered (op, conditional) access lists per kernel ref operand.

    Walks the kernel jaxpr in program order, descending into ``cond``
    branches (everything inside is conditional — ``pl.when`` lowers to
    cond) and ``pjit``/``scan`` sub-jaxprs with positional ref mapping.
    """
    from jax import core as jcore
    from jax.extend.core import Var

    acc: Dict[int, List[Tuple[str, bool]]] = {i: [] for i in
                                              range(n_operands)}
    env = {v: i for i, v in enumerate(kernel_jaxpr.invars)
           if i < n_operands}

    def ref_of(var):
        return env.get(var) if isinstance(var, Var) else None

    def walk(jaxpr, local_env, conditional):
        def rid(var):
            return (local_env.get(var)
                    if isinstance(var, Var) else None)

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "get":
                i = rid(eqn.invars[0])
                if i is not None:
                    acc[i].append(("read", conditional))
            elif prim == "swap":
                i = rid(eqn.invars[0])
                if i is not None:
                    acc[i].append(("write", conditional))
            elif prim == "addupdate":
                i = rid(eqn.invars[0])
                if i is not None:
                    acc[i].append(("read", conditional))
                    acc[i].append(("write", conditional))
            elif prim == "cond":
                for branch in eqn.params["branches"]:
                    benv = {}
                    for bv, iv in zip(branch.jaxpr.invars, eqn.invars[1:]):
                        i = rid(iv)
                        if i is not None:
                            benv[bv] = i
                    walk(branch.jaxpr, benv, True)
            elif prim in ("pjit", "closed_call", "core_call",
                          "remat_call", "checkpoint"):
                inner = eqn.params.get("jaxpr") or eqn.params.get(
                    "call_jaxpr")
                if inner is not None:
                    ij = getattr(inner, "jaxpr", inner)
                    senv = {}
                    for sv, iv in zip(ij.invars, eqn.invars):
                        i = rid(iv)
                        if i is not None:
                            senv[sv] = i
                    walk(ij, senv, conditional)
            elif prim == "scan":
                ij = eqn.params["jaxpr"].jaxpr
                senv = {}
                for sv, iv in zip(ij.invars, eqn.invars):
                    i = rid(iv)
                    if i is not None:
                        senv[sv] = i
                # loop bodies re-execute: order across iterations is not
                # modeled, so treat everything inside as conditional
                walk(ij, senv, True)
            else:
                # unknown higher-order primitive consuming a ref:
                # conservatively record a conditional read
                if any(True for _ in jcore.jaxprs_in_params(eqn.params)):
                    for iv in eqn.invars:
                        i = rid(iv)
                        if i is not None:
                            acc[i].append(("read", True))

    walk(kernel_jaxpr, env, False)
    return acc


def _reads(accesses) -> bool:
    return any(op == "read" for op, _ in accesses)


def _writes(accesses) -> bool:
    return any(op == "write" for op, _ in accesses)


def _read_before_write(accesses) -> bool:
    """True when a read can observe the window before any unconditional
    write initialized it (conditional writes may not run on the first
    visit, so they don't count as initialization)."""
    for op, conditional in accesses:
        if op == "read":
            return True
        if op == "write" and not conditional:
            return False
    return False


# ---------------------------------------------------------------------------
# per-call analysis


def analyze_pallas_eqn(eqn, *, config_name: str, path: str,
                       settings: AnalyzerSettings):
    """(PallasCallGeometry, [Finding]) for one pallas_call equation."""
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    aliases = tuple((int(a), int(b))
                    for a, b in eqn.params.get("input_output_aliases", ()))
    n_idx = gm.num_index_operands
    n_in, n_out = gm.num_inputs, gm.num_outputs
    kernel_jaxpr = eqn.params["jaxpr"]
    # the kernel function's name ("_fwd_kernel at <file>:<line>")
    src = getattr(kernel_jaxpr.debug_info, "func_src_info", None) or ""
    name = eqn.params.get("name") or src.split(" ")[0] or "pallas_call"
    findings: List[Finding] = []

    # ref accesses: kernel invars are [index ops..., inputs..., outputs...,
    # scratch...]; block_mappings cover inputs+outputs only
    n_refs = len(kernel_jaxpr.invars)
    accesses = _ref_accesses(kernel_jaxpr, n_refs)

    scratch_shapes: List[Tuple[Tuple[int, ...], str]] = []
    for v in kernel_jaxpr.invars[n_idx + n_in + n_out:]:
        scratch_shapes.append((tuple(int(d) for d in v.aval.shape),
                               str(v.aval.dtype)))

    n_steps = 1
    for g in grid:
        n_steps *= g
    if n_steps > settings.max_grid_steps:
        findings.append(Finding(
            rule="grid-too-large", severity="warning", path=path, line=0,
            message=f"{name}: grid {grid} has {n_steps} steps — schedule "
                    "checks skipped (raise max_grid_steps or shrink the "
                    "representative config)",
            context=config_name, detail=name))
        geom = PallasCallGeometry(name=name, grid=grid, aliases=aliases,
                                  operands=[], scratch_shapes=scratch_shapes)
        return geom, findings

    operands: List[OperandGeometry] = []
    schedules: List[List[Tuple[int, ...]]] = []
    steps = list(np.ndindex(*grid)) if grid else [()]
    vmem = 0
    for pos, bm in enumerate(gm.block_mappings):
        kind = "in" if pos < n_in else "out"
        index = pos if pos < n_in else pos - n_in
        block = _block_ints(bm.block_shape)
        sds = bm.array_aval
        dtype = np.dtype(sds.dtype)
        ref_pos = n_idx + pos
        acc = accesses[ref_pos]
        op = OperandGeometry(
            origin=str(getattr(bm, "origin", f"{kind}{index}")),
            kind=kind, index=index, block_shape=block,
            array_shape=tuple(int(d) for d in sds.shape),
            dtype=str(sds.dtype),
            reads=_reads(acc), writes=_writes(acc),
            read_before_write=_read_before_write(acc))
        seq = [_eval_index_map(bm, idx) for idx in steps]
        op.n_blocks, op.min_revisit, op.max_run_len = _visit_stats(seq)
        operands.append(op)
        schedules.append(seq)

        # (b) block alignment vs per-dtype tile requirements
        sub_req = SUBLANE_BY_ITEMSIZE.get(dtype.itemsize, 8)
        if len(block) == 1:
            if block[0] != op.array_shape[0] and block[0] % LANE_1D:
                findings.append(Finding(
                    rule="block-misaligned", severity="error", path=path,
                    line=0,
                    message=f"{name}: {op.origin} 1-D block {block} is "
                            f"neither the whole array {op.array_shape} nor "
                            f"a multiple of XLA's {LANE_1D}-element 1-D "
                            "tile (use a 2-D layout)",
                    context=config_name,
                    detail=f"{name}/{op.origin}/1d"))
        if len(block) >= 2:
            sublane, lane = block[-2], block[-1]
            if (sublane % sub_req if sublane > 1
                    else op.array_shape[-2] > 1):
                findings.append(Finding(
                    rule="block-misaligned", severity="error", path=path,
                    line=0,
                    message=f"{name}: {op.origin} block {block} sublane "
                            f"dim {sublane} is not a multiple of the "
                            f"{sub_req}-row {sds.dtype} tile",
                    context=config_name,
                    detail=f"{name}/{op.origin}/sublane"))
            if lane % LANE and lane != op.array_shape[-1]:
                findings.append(Finding(
                    rule="block-misaligned", severity="error", path=path,
                    line=0,
                    message=f"{name}: {op.origin} block {block} lane dim "
                            f"{lane} is neither a multiple of {LANE} nor "
                            f"the full array dim {op.array_shape[-1]}",
                    context=config_name,
                    detail=f"{name}/{op.origin}/lane"))

        # windows are double-buffered (pipelined fetch/flush)
        nbytes = dtype.itemsize
        for d in block:
            nbytes *= d
        vmem += 2 * nbytes

        # (d) outputs are undefined on first visit
        if kind == "out" and op.read_before_write:
            findings.append(Finding(
                rule="output-read-before-write", severity="error",
                path=path, line=0,
                message=f"{name}: output {op.origin} is read before any "
                        "unconditional write — the window is undefined on "
                        "first visit (accumulate through an aliased input "
                        "ref or VMEM scratch instead)",
                context=config_name, detail=f"{name}/{op.origin}"))

    for shape, dt in scratch_shapes:
        nbytes = np.dtype(dt).itemsize
        for d in shape:
            nbytes *= d
        vmem += nbytes

    geom = PallasCallGeometry(name=name, grid=grid, aliases=aliases,
                              operands=operands,
                              scratch_shapes=scratch_shapes,
                              vmem_bytes=vmem)

    # (c) per-grid-step VMEM footprint
    if vmem > settings.vmem_budget_bytes:
        findings.append(Finding(
            rule="vmem-over-budget", severity="error", path=path, line=0,
            message=f"{name}: per-step VMEM estimate {vmem} bytes exceeds "
                    f"the {settings.vmem_budget_bytes}-byte budget",
            context=config_name, detail=name))

    # (a) aliased-accumulator schedule checks
    for in_idx, out_idx in aliases:
        in_op, out_op = geom.operand("in", in_idx), geom.operand("out",
                                                                 out_idx)
        tag = f"{name}/{out_op.origin}<-{in_op.origin}"
        if schedules and schedules[in_idx] != schedules[n_in + out_idx]:
            findings.append(Finding(
                rule="alias-index-mismatch", severity="error", path=path,
                line=0,
                message=f"{name}: aliased pair {in_op.origin}->"
                        f"{out_op.origin} have different index-map "
                        "schedules — the accumulation would read and "
                        "write different windows of the shared buffer",
                context=config_name, detail=tag))
            continue
        if not in_op.reads:
            # scratch-fallback shape (nt==1 / G*nq==1): the aliased input
            # is never fetched, so revisit semantics are not relied on
            continue
        if out_op.max_run_len > 1:
            findings.append(Finding(
                rule="alias-no-refetch", severity="error", path=path,
                line=0,
                message=f"{name}: aliased window {out_op.origin} stays "
                        f"resident for {out_op.max_run_len} consecutive "
                        "grid steps while the kernel reads "
                        f"{in_op.origin} — the input window is not "
                        "re-fetched when its index does not change, so "
                        "the accumulation reads stale values",
                context=config_name, detail=tag))
        if (out_op.min_revisit is not None
                and out_op.min_revisit < settings.dma_safety_threshold):
            findings.append(Finding(
                rule="alias-revisit-close", severity="error", path=path,
                line=0,
                message=f"{name}: aliased window {out_op.origin} is "
                        f"revisited {out_op.min_revisit} grid step(s) "
                        "apart — below the DMA-safety threshold "
                        f"{settings.dma_safety_threshold}; the output "
                        "flush may still be in flight when the input "
                        "fetch for the revisit issues",
                context=config_name, detail=tag))
    return geom, findings


def analyze_traceable(fn, args, *, config_name: str, path: str,
                      settings: Optional[AnalyzerSettings] = None):
    """([PallasCallGeometry], [Finding]) for every pallas_call in fn."""
    settings = settings or AnalyzerSettings()
    geoms, findings = [], []
    eqns = trace_pallas_calls(fn, args)
    if not eqns:
        findings.append(Finding(
            rule="no-pallas-call", severity="warning", path=path, line=0,
            message="no pallas_call found in the traced entry point",
            context=config_name, detail="trace"))
    for eqn in eqns:
        geom, fs = analyze_pallas_eqn(eqn, config_name=config_name,
                                      path=path, settings=settings)
        geoms.append(geom)
        findings.extend(fs)
    return geoms, findings


# ---------------------------------------------------------------------------
# config-matrix driver with source-hash caching


def _module_file(module: str) -> Optional[str]:
    spec = importlib.util.find_spec(module)
    return spec.origin if spec and spec.origin else None


def _config_cache_key(cfg, settings: AnalyzerSettings) -> str:
    h = hashlib.blake2b(digest_size=16)
    h.update(f"v{ANALYZER_VERSION}|{cfg.name}|{settings.key()}".encode())
    for module in cfg.hash_modules:
        fname = _module_file(module)
        if fname and os.path.exists(fname):
            with open(fname, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def _summarize(cfg_name: str, geoms: Sequence[PallasCallGeometry]):
    rows = []
    for g in geoms:
        revisits = [o.min_revisit for o in g.operands
                    for (i, j) in g.aliases
                    if o.kind == "out" and o.index == j
                    and o.min_revisit is not None]
        rows.append({
            "config": cfg_name, "call": g.name,
            "grid": "x".join(map(str, g.grid)) or "-",
            "aliases": ",".join(f"in{i}->out{j}" for i, j in g.aliases)
            or "-",
            "revisit": min(revisits) if revisits else "-",
            "vmem": f"{g.vmem_bytes / 2 ** 20:.2f} MiB",
        })
    return rows


def analyze_kernel_configs(configs=None, *,
                           settings: Optional[AnalyzerSettings] = None,
                           cache_path: Optional[str] = None,
                           use_cache: bool = True):
    """Run the analyzer over the registered config matrix.

    Returns ``(findings, summaries, geometries)`` where ``geometries``
    maps config name -> [PallasCallGeometry] (only for configs traced
    this run — cache hits carry findings + summary rows but not the
    full geometry objects).
    """
    from repro.staticcheck.kernel_configs import KERNEL_CONFIGS

    settings = settings or AnalyzerSettings()
    configs = list(KERNEL_CONFIGS if configs is None else configs)
    cache = {}
    if use_cache and cache_path and os.path.exists(cache_path):
        try:
            with open(cache_path) as f:
                cache = json.load(f)
        except (OSError, ValueError):
            cache = {}

    findings: List[Finding] = []
    summaries: List[dict] = []
    geometries: Dict[str, List[PallasCallGeometry]] = {}
    dirty = False
    for cfg in configs:
        key = _config_cache_key(cfg, settings)
        hit = cache.get(cfg.name)
        if use_cache and hit and hit.get("key") == key:
            findings.extend(Finding(**f) for f in hit["findings"])
            summaries.extend(hit["summary"])
            continue
        fn, args = cfg.build()
        geoms, fs = analyze_traceable(fn, args, config_name=cfg.name,
                                      path=cfg.path, settings=settings)
        rows = _summarize(cfg.name, geoms)
        findings.extend(fs)
        summaries.extend(rows)
        geometries[cfg.name] = geoms
        cache[cfg.name] = {
            "key": key,
            "findings": [dataclasses.asdict(f) for f in fs],
            "summary": rows,
        }
        dirty = True
    if use_cache and cache_path and dirty:
        tmp = cache_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, cache_path)
    return findings, summaries, geometries
