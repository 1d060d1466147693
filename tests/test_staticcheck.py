"""Static-analysis pass: Pallas kernel geometry + determinism lint.

The load-bearing assertions pin the analyzer to the kernel READMEs'
hand-derived schedules: the xent backward's aliased dH window must be
revisited exactly ``nt`` grid steps apart and flash attention's fused
dQ window exactly ``G*nq`` apart — those distances are *why* the
in-place accumulation idiom is DMA-safe, and the whole point of the
static checker is that it re-derives them from the jaxpr rather than
trusting the comment.  The rest covers the negative space: misaligned
blocks, read-before-write outputs, too-close revisits, each lint rule
firing (and staying quiet when waived), and the baseline gate contract.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.staticcheck import (AnalyzerSettings, Baseline, BaselineEntry,
                               Finding, analyze_traceable, lint_source,
                               run_staticcheck)
from repro.staticcheck.kernel_analyzer import analyze_kernel_configs
from repro.staticcheck.kernel_configs import KERNEL_CONFIGS, get_config


def _analyze(name, settings=None):
    cfg = get_config(name)
    fn, args = cfg.build()
    geoms, findings = analyze_traceable(
        fn, args, config_name=cfg.name, path=cfg.path, settings=settings)
    return cfg, geoms, findings


# ---------------------------------------------------------------------------
# aliased-accumulator revisit distances (kernel READMEs, re-derived)
# ---------------------------------------------------------------------------


def test_xent_bwd_dh_revisit_distance_is_nt():
    """README: dH's aliased window cycles through all nv vocab tiles
    before returning — revisit distance == nt == T/block_t == 4."""
    cfg, geoms, findings = _analyze("xent_bwd_alias")
    assert findings == []
    g = next(g for g in geoms if g.aliases)
    assert g.grid == cfg.expect["grid"]
    assert g.aliases == cfg.expect["aliases"]
    in_idx, out_idx = g.aliases[0]
    out_op = g.operand("out", out_idx)
    assert out_op.min_revisit == cfg.expect["dh_revisit"] == 4
    assert out_op.max_run_len == 1          # flushed every step
    assert g.operand("in", in_idx).reads    # the accumulator is consumed


def test_flash_bwd_fused_dq_revisit_distance_is_g_nq():
    """README: dQ's aliased window returns after the inner (G, nq) loops
    wrap — revisit distance == G*nq == 2*2 == 4."""
    cfg, geoms, findings = _analyze("flash_bwd_fused_alias")
    assert findings == []
    g = next(g for g in geoms if g.aliases)
    assert g.grid == cfg.expect["grid"]
    assert g.aliases == cfg.expect["aliases"]
    in_idx, out_idx = g.aliases[0]
    out_op = g.operand("out", out_idx)
    assert out_op.min_revisit == cfg.expect["dq_revisit"] == 4
    assert out_op.max_run_len == 1
    assert g.operand("in", in_idx).reads


def test_scratch_fallbacks_do_not_rely_on_revisit():
    """nt==1 / G*nq==1 degenerate shapes switch to the VMEM-scratch
    accumulator: the aliased input is never read, so revisit semantics
    must be reported as unused (and nothing may be flagged)."""
    for name in ("xent_bwd_alias_nt1", "flash_bwd_fused_alias_gnq1"):
        cfg, geoms, findings = _analyze(name)
        assert findings == [], name
        g = next(g for g in geoms if g.aliases)
        in_idx, _ = g.aliases[0]
        assert not g.operand("in", in_idx).reads, name


def test_config_matrix_is_clean_and_matches_expectations():
    findings, summaries, geometries = analyze_kernel_configs(use_cache=False)
    assert findings == []
    by_name = {c.name: c for c in KERNEL_CONFIGS}
    assert set(geometries) == set(by_name)
    for name, geoms in geometries.items():
        exp = by_name[name].expect
        if "n_calls" in exp:
            assert len(geoms) == exp["n_calls"], name
        if "grid" in exp:
            assert geoms[0].grid == exp["grid"], name
        if "aliases" in exp:
            assert geoms[0].aliases == exp["aliases"], name
    # every config produced at least one summary row for the report
    assert {r["config"] for r in summaries} == set(by_name)


# ---------------------------------------------------------------------------
# negative space: toy kernels that MUST be flagged
# ---------------------------------------------------------------------------


def _toy_call(kernel, grid, in_specs, out_spec, out_shape, args, **kw):
    from jax.experimental import pallas as pl

    def fn(*a):
        return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                              out_specs=out_spec, out_shape=out_shape,
                              interpret=True, **kw)(*a)
    return fn, args


def test_misaligned_block_is_flagged():
    """A (20, 128) fp32 block (the PR 5 regression shape) must trip the
    sublane tile rule for both the input and the output."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    fn, args = _toy_call(
        kernel, grid=(2,),
        in_specs=[pl.BlockSpec((20, 128), lambda i: (i, 0))],
        out_spec=pl.BlockSpec((20, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((40, 128), jnp.float32),
        args=[jax.ShapeDtypeStruct((40, 128), jnp.float32)])
    _, findings = analyze_traceable(fn, args, config_name="toy",
                                    path="toy.py")
    rules = [f.rule for f in findings]
    assert rules.count("block-misaligned") == 2
    assert all(f.severity == "error" for f in findings)


# geometries the TPU compiler refused in the kernels: flash attention's
# (1, bq) lse block over (BH, S), xent's 1-D (bt,) label block, and the
# SSD kernel's (1, Q) dt block over (B*nc*H, Q)
REFUSED_GEOMETRIES = {
    "flash_lse_row": ((1, 128), (32, 256), lambda i: (i, 0)),
    "xent_labels_1d": ((256,), (2048,), lambda i: (i,)),
    "ssd_dt_row": ((1, 256), (8, 256), lambda i: (i, 0)),
}


@pytest.mark.parametrize("geometry", sorted(REFUSED_GEOMETRIES))
def test_refused_geometry_is_flagged(geometry):
    from jax.experimental import pallas as pl

    block, shape, index_map = REFUSED_GEOMETRIES[geometry]

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    fn, args = _toy_call(
        kernel, grid=(shape[0] // block[0],),
        in_specs=[pl.BlockSpec(block, index_map)],
        out_spec=pl.BlockSpec(block, index_map),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        args=[jax.ShapeDtypeStruct(shape, jnp.float32)])
    _, findings = analyze_traceable(fn, args, config_name="toy",
                                    path="toy.py")
    assert [f.rule for f in findings] == ["block-misaligned"] * 2


def test_output_read_before_write_is_flagged():
    """``o_ref[...] += x`` reads the undefined output window on its
    first visit — must be flagged even though the code 'looks like' a
    normal accumulator."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] += x_ref[...]

    fn, args = _toy_call(
        kernel, grid=(2, 2),
        in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
        out_spec=pl.BlockSpec((8, 128), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
        args=[jax.ShapeDtypeStruct((16, 256), jnp.float32)])
    _, findings = analyze_traceable(fn, args, config_name="toy",
                                    path="toy.py")
    assert "output-read-before-write" in [f.rule for f in findings]


def test_close_revisit_is_flagged_under_tighter_threshold():
    """A distance-2 aliased revisit (the physical minimum) passes the
    default threshold but must be flagged when the DMA-safety threshold
    is raised to 3 — the knob hardware validation would turn."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, acc_ref, o_ref):
        o_ref[...] = acc_ref[...] + x_ref[...]

    def build():
        return _toy_call(
            kernel, grid=(2, 2),
            in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j)),
                      pl.BlockSpec((8, 128), lambda i, j: (j, 0))],
            out_spec=pl.BlockSpec((8, 128), lambda i, j: (j, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
            args=[jax.ShapeDtypeStruct((16, 256), jnp.float32),
                  jax.ShapeDtypeStruct((16, 128), jnp.float32)],
            input_output_aliases={1: 0})

    fn, args = build()
    geoms, findings = analyze_traceable(fn, args, config_name="toy",
                                        path="toy.py")
    assert findings == []                      # distance 2 is the idiom
    assert geoms[0].operand("out", 0).min_revisit == 2

    fn, args = build()
    _, findings = analyze_traceable(
        fn, args, config_name="toy", path="toy.py",
        settings=AnalyzerSettings(dma_safety_threshold=3))
    assert "alias-revisit-close" in [f.rule for f in findings]


def test_alias_resident_window_with_read_is_flagged():
    """An aliased window that stays resident across consecutive steps is
    never flushed/refetched between them; reading the aliased input then
    observes stale values."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, acc_ref, o_ref):
        o_ref[...] = acc_ref[...] + x_ref[...]

    fn, args = _toy_call(
        kernel, grid=(2, 2),
        in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j)),
                  pl.BlockSpec((8, 128), lambda i, j: (i, 0))],
        out_spec=pl.BlockSpec((8, 128), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
        args=[jax.ShapeDtypeStruct((16, 256), jnp.float32),
              jax.ShapeDtypeStruct((16, 128), jnp.float32)],
        input_output_aliases={1: 0})
    _, findings = analyze_traceable(fn, args, config_name="toy",
                                    path="toy.py")
    assert "alias-no-refetch" in [f.rule for f in findings]


def test_vmem_budget_is_flagged():
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    fn, args = _toy_call(
        kernel, grid=(2,),
        in_specs=[pl.BlockSpec((4096, 1024), lambda i: (i, 0))],
        out_spec=pl.BlockSpec((4096, 1024), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((8192, 1024), jnp.float32),
        args=[jax.ShapeDtypeStruct((8192, 1024), jnp.float32)])
    _, findings = analyze_traceable(
        fn, args, config_name="toy", path="toy.py",
        settings=AnalyzerSettings(vmem_budget_bytes=16 * 2 ** 20))
    assert "vmem-over-budget" in [f.rule for f in findings]


# ---------------------------------------------------------------------------
# determinism lint rules
# ---------------------------------------------------------------------------

SIM_PATH = "src/repro/fleet/toy.py"
PERSIST_PATH = "src/repro/runtime/toy.py"
FREE_PATH = "src/repro/observability/toy.py"


def _rules(source, path):
    return [f.rule for f in lint_source(source, path)]


def test_lint_wall_clock_in_sim_domain():
    src = "import time\nt = time.perf_counter()\n"
    assert _rules(src, SIM_PATH) == ["wall-clock"]
    # observability is out of the sim domain (real tracer timestamps)
    assert _rules(src, FREE_PATH) == []
    # the socket transport talks to real sockets
    assert _rules(src, "src/repro/transport/socket_transport.py") == []
    waived = ("import time\n"
              "t = time.perf_counter()  # staticcheck: ok=wall-clock x\n")
    assert _rules(waived, SIM_PATH) == []


def test_lint_waiver_on_preceding_line():
    src = ("import time\n"
           "# staticcheck: ok=wall-clock display only\n"
           "t = time.perf_counter()\n")
    assert _rules(src, SIM_PATH) == []


def test_lint_sleep_in_sim_domain():
    src = "import time\ntime.sleep(0.1)\n"
    assert _rules(src, SIM_PATH) == ["sleep-in-sim"]
    assert _rules(src, "src/repro/transport/socket_transport.py") == []


def test_lint_unseeded_rng():
    assert _rules("import numpy as np\nx = np.random.rand(3)\n",
                  FREE_PATH) == ["unseeded-rng"]
    assert _rules("import numpy as np\nr = np.random.default_rng()\n",
                  FREE_PATH) == ["unseeded-rng"]
    assert _rules("import numpy as np\nr = np.random.default_rng(0)\n",
                  FREE_PATH) == []
    assert _rules("import random\nx = random.random()\n",
                  FREE_PATH) == ["unseeded-rng"]
    assert _rules("import random\nr = random.Random(7)\n", FREE_PATH) == []


def test_lint_json_sort_keys_in_persist_domain():
    src = "import json\ns = json.dumps({'a': 1})\n"
    assert _rules(src, PERSIST_PATH) == ["json-unsorted-keys"]
    ok = "import json\ns = json.dumps({'a': 1}, sort_keys=True)\n"
    assert _rules(ok, PERSIST_PATH) == []
    # outside the persistence domain the rule does not apply
    assert _rules(src, "src/repro/core/toy.py") == []


def test_lint_binary_write_without_crc():
    src = ("import struct\n"
           "def save(f, x):\n"
           "    f.write(struct.pack('<I', x))\n")
    assert _rules(src, PERSIST_PATH) == ["binary-no-crc"]
    withcrc = src.replace("import struct\n",
                          "import struct\nfrom repro.transport.framing "
                          "import crc32\n")
    assert _rules(withcrc, PERSIST_PATH) == []


def test_lint_unordered_iteration():
    assert _rules("for x in {1, 2, 3}:\n    pass\n",
                  FREE_PATH) == ["unordered-iteration"]
    assert _rules("for x in sorted({1, 2, 3}):\n    pass\n",
                  FREE_PATH) == []
    assert _rules("ys = [y for y in set([3, 1])]\n",
                  FREE_PATH) == ["unordered-iteration"]


def test_lint_fingerprints_stable_under_line_moves():
    a = lint_source("import time\nt = time.time()\n", SIM_PATH)
    b = lint_source("import time\n\n\n\nt = time.time()\n", SIM_PATH)
    assert [f.fingerprint for f in a] == [f.fingerprint for f in b]
    assert a[0].line != b[0].line


# ---------------------------------------------------------------------------
# gate contract
# ---------------------------------------------------------------------------


def _finding(rule="wall-clock", detail="time.time#0"):
    return Finding(rule=rule, severity="error", path="src/repro/fleet/x.py",
                   line=3, message="m", context="f", detail=detail)


def test_gate_fails_on_new_passes_on_baselined(tmp_path):
    f = _finding()
    gate = Baseline().check([f])
    assert not gate.ok and gate.new == [f]

    bl = Baseline.from_findings([f], reason="known issue")
    p = str(tmp_path / "bl.json")
    bl.save(p)
    gate = Baseline.load(p).check([f])
    assert gate.ok and gate.accepted == [f] and not gate.stale

    # injected second finding still fails even with the first baselined
    g = _finding(detail="time.time#1")
    gate = Baseline.load(p).check([f, g])
    assert not gate.ok and gate.new == [g]


def test_gate_reports_stale_entries(tmp_path):
    bl = Baseline.from_findings([_finding()], reason="gone")
    gate = bl.check([])
    assert gate.ok and len(gate.stale) == 1


def test_shipped_tree_passes_the_gate(repo_root=None):
    """The committed baseline accepts everything the checker finds on
    the shipped tree — exactly what scripts/staticcheck.py --gate runs
    in CI (kernel prong skipped here: covered above, and the config
    matrix re-trace is the slow part)."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings, _ = run_staticcheck(root, kernels=False)
    baseline = Baseline.load(os.path.join(root,
                                          "STATICCHECK_baseline.json"))
    gate = baseline.check(findings)
    assert gate.new == [], "\n".join(f.format() for f in gate.new)


def test_baseline_file_reasons_are_filled():
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "STATICCHECK_baseline.json")) as f:
        raw = json.load(f)
    assert raw["version"] == 1
    for e in raw["accepted"]:
        assert e["reason"].strip() and "TODO" not in e["reason"], e
