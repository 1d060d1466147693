"""The per-layer metrics that read the program's own spans and compile
counter.

On hand-made spans each reader gives its known answer, and nothing
where the program lacks what it reads (an older program's spans carry
no links and no compile counts).  On a small CPU run of each phase the
program's spans exist and nest under the right parents, on their own
threads where a producer runs them, and every reader of the cell gives
a share between 0 and 100.  A CPU profile of a small streamed server
phase carries the feed and eval waits in its host plane, inside
``server.epoch``."""

import importlib.util
import json
import pathlib
import time
import types

import pytest

from repro.observability.tracer import SpanRecord

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2 ** 33 + 13
# the readers of the program's spans and compile counter (the MFUs, host
# clock times FLOPs, are not)
PROGRAM = [m for m in SPEC["per_layer"] if m["source"] == "program_span"
           or m["name"].startswith("jit_share.")]


@pytest.fixture(scope="module")
def run_mod():
    spec = importlib.util.spec_from_file_location("chipbench_run_spans",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._paths()
    return mod


def _reader(name):
    from harness.registry import metric_reader

    return metric_reader(name)


# ---------------------------------------------------------------------------
# hand-made spans
# ---------------------------------------------------------------------------


def _span(sid, name, dur, parent=None, **attrs):
    return SpanRecord(name=name, track="t", kind="span", t_wall=0.0,
                      dur_wall=dur, attrs=attrs, span_id=sid,
                      parent_id=parent)


# a 10 s server window: one streamed epoch with its merged eval; the
# producer thread's spans have no parent
SERVER = [
    _span(1, "server.phase", 10.0, jit_s=0.5),
    _span(2, "server.epoch", 9.0, 1),
    _span(3, "server.feed_wait", 1.0, 2),
    _span(4, "server.feed_wait", 2.0, 2),
    _span(5, "merged_eval", 4.0, 2),
    _span(6, "eval.wait", 0.5, 5),
    _span(7, "eval.wait", 1.0, 5, jit_s=0.25),
    _span(8, "feed.gather", 3.0, None, jit_s=0.25),
    _span(9, "server.pool_upload", 0.75, 1),
]
# a 10 s device window: one call, one round with its aux eval; an
# eval.wait under another parent is not the aux eval's
DEVICE = [
    _span(1, "device.phase", 10.0),
    _span(2, "device.pool_upload", 0.5, 1, jit_s=1.0),
    _span(3, "device.round", 9.0, 1, traces=1, jit_s=0.5),
    _span(4, "aux_eval", 6.0, 3),
    _span(5, "eval.wait", 2.0, 4),
    _span(6, "eval.wait", 3.0, 4),
    _span(7, "merged_eval", 0.5, None),
    _span(8, "eval.wait", 0.25, 7),
]

EXPECTED = {
    "feed_wait_share": ("server", 30.0),
    "pool_upload_share.server": ("server", 7.5),
    "eval_wait_share.server": ("server", 15.0),
    "jit_share.server": ("server", 10.0),
    "pool_upload_share.round": ("device", 5.0),
    "eval_wait_share.round": ("device", 50.0),
    "jit_share.round": ("device", 15.0),
}


def _ctx(driver, spans, window_s=10.0):
    return types.SimpleNamespace(driver=driver, spans=spans, trace=None,
                                 info={"window_s": window_s})


def test_every_new_reader_has_a_hand_made_answer():
    names = {m["name"] for m in PROGRAM} - {"merged_eval_share",
                                             "aux_eval_share"}
    assert names == set(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_hand_made_spans(run_mod, name):
    driver, want = EXPECTED[name]
    spans = SERVER if driver == "server" else DEVICE
    read = _reader(name).read
    assert read(_ctx(driver, spans)) == pytest.approx(want)
    other = "device" if driver == "server" else "server"
    assert read(_ctx(other, spans)) is None
    assert read(_ctx("consolidate", spans)) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_from_an_older_program(run_mod, name):
    """Spans of a program without links or compile counts (the shape of
    a span record before either existed) give no reading."""
    driver, _ = EXPECTED[name]
    old = [types.SimpleNamespace(name=s.name, dur_wall=s.dur_wall,
                                 kind="span", attrs={})
           for s in (SERVER if driver == "server" else DEVICE)
           if s.name not in ("server.phase", "device.phase",
                             "server.pool_upload", "device.pool_upload",
                             "server.feed_wait", "eval.wait")]
    assert _reader(name).read(_ctx(driver, old)) is None


# ---------------------------------------------------------------------------
# small CPU runs of each phase
# ---------------------------------------------------------------------------


def _drive(run_mod, cell_name, profile_dir=None):
    """Set-up and one window of ``cell_name`` at a small size, with the
    window under ``jax.profiler.trace(profile_dir)`` when given; returns
    the window's spans, its info and the tracer."""
    import jax

    from harness import registry
    from harness.build import build
    from harness.small import edit
    from repro.observability import Observability

    cell = registry.workload(cell_name)
    cfg = registry.config(cell["config"])
    edit(cfg, cell)
    driver = registry.driver(cell["driver"])
    obs = Observability(enabled=True, profile=profile_dir is not None)
    h = types.SimpleNamespace(cfg=cfg, cell=cell, seed=SEED,
                              ref=registry.reference(cell["config"]),
                              model_dict=cfg["model"])
    h.b = build(cfg, cell, SEED, obs, smoke=True)
    driver.setup(h)
    driver.prime(h)
    driver.size(h, 0.01)
    tracer = obs.tracer
    w0 = time.perf_counter() - tracer.t0
    if profile_dir is None:
        _, info = driver.window(h)
    else:
        with jax.profiler.trace(str(profile_dir)):
            _, info = driver.window(h)
    w1 = time.perf_counter() - tracer.t0
    return types.SimpleNamespace(
        driver=cell["driver"], info=info, tracer=tracer,
        spans=run_mod._spans_in(tracer, w0, w1))


@pytest.fixture(scope="module")
def runs(run_mod, tmp_path_factory):
    """Each cell driven once; the streamed server cell under a profile."""
    out = {}
    for cell in CELLS:
        profile = (tmp_path_factory.mktemp("profile")
                   if cell == "vit-s.server" else None)
        out[cell] = _drive(run_mod, cell, profile)
        out[cell].profile_dir = profile
    return out


# each cell's new spans: name -> the name of its parent (None: the top of
# its thread); "main" spans run on the caller's thread, the others on a
# producer thread of their own
PARENTS = {
    "vit-s.server": {"server.epoch": "server.phase",
                     "server.feed_wait": "server.epoch",
                     "server.sync": "server.epoch",
                     "merged_eval": "server.epoch",
                     "eval.wait": "merged_eval",
                     "feed.gather": None, "feed.put": None},
    "mobilenet-l.server": {"server.pool_upload": "server.phase",
                           "server.epoch": "server.phase",
                           "server.sync": "server.epoch",
                           "merged_eval": "server.epoch",
                           "eval.wait": "merged_eval"},
    "mobilenet-l.device": {"device.pool_upload": "device.phase",
                           "device.round": "device.phase",
                           "aux_eval": "device.round",
                           "eval.wait": "aux_eval"},
    "vit-s.consolidate": {"consolidate.feed_wait": "consolidate",
                          "consolidate.fetch": "consolidate",
                          "consolidate.submit": "consolidate",
                          "consolidate.finish": "consolidate",
                          "store.write": None,
                          "feed.gather": None, "feed.put": None},
}
PRODUCED = {"feed.gather", "feed.put", "store.write"}
ABSENT = {"vit-s.server": {"server.pool_upload"},
          "mobilenet-l.server": {"server.feed_wait", "feed.gather"},
          "mobilenet-l.device": set(), "vit-s.consolidate": set()}


@pytest.mark.parametrize("cell", CELLS)
def test_phase_spans_and_their_parents(runs, cell):
    r = runs[cell]
    by_id = {s.span_id: s for s in r.tracer.events}
    names = {s.name for s in r.spans}
    assert set(PARENTS[cell]) <= names, set(PARENTS[cell]) - names
    assert not names & ABSENT[cell]
    main = {s.thread for s in r.spans if s.name not in PRODUCED}
    assert len(main) == 1
    for s in r.spans:
        if s.name not in PARENTS[cell]:
            continue
        parent = by_id.get(s.parent_id)
        assert (parent.name if parent else None) == PARENTS[cell][s.name], s
        assert (s.thread in main) == (s.name not in PRODUCED), s
        if parent is not None:
            assert parent.thread == s.thread
            assert parent.t_wall <= s.t_wall
            assert s.t_wall + s.dur_wall <= parent.t_wall + parent.dur_wall
    assert r.tracer.summary()["open_spans"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_readers_of_each_cell_read_shares(runs, cell):
    r = runs[cell]
    ctx = _ctx(r.driver, r.spans, r.info["window_s"])
    got = {}
    for m in PROGRAM:
        v = _reader(m["name"]).read(ctx)
        if cell in m["workloads"]:
            assert v is not None and 0.0 <= v <= 100.0, (m["name"], v)
            got[m["name"]] = v
        else:
            assert v is None, (m["name"], v)
    if "eval_wait_share.server" in got:
        assert got["eval_wait_share.server"] <= got["merged_eval_share"]
    if "eval_wait_share.round" in got:
        assert got["eval_wait_share.round"] <= got["aux_eval_share"]


def test_the_first_call_compiles_inside_its_phase(runs):
    """The compile counter puts a cold step's trace and compile on the
    span that ran it: the first round of set-up's first call."""
    events = runs["mobilenet-l.device"].tracer.events
    first = next(s for s in events if s.name == "device.round")
    assert first.attrs["traces"] >= 1 and first.attrs["jit_s"] > 0
    assert first.attrs.get("compiles", 0) \
        + first.attrs.get("cache_loads", 0) >= 1


def test_profile_host_plane_has_waits_inside_the_epoch(runs):
    """With ``profile=True`` the spans reach the profiler's host plane
    on its own clock: every feed and eval wait lies inside a
    ``server.epoch``."""
    from jax.profiler import ProfileData

    from harness.trace import find_xplane

    r = runs["vit-s.server"]
    pd = ProfileData.from_file(find_xplane(str(r.profile_dir)))
    host = {"server.epoch": [], "server.feed_wait": [], "eval.wait": []}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in host:
                    host[e.name].append((e.start_ns,
                                         e.start_ns + e.duration_ns))
    assert host["server.epoch"] and host["server.feed_wait"]
    assert host["eval.wait"]
    for name in ("server.feed_wait", "eval.wait"):
        for s, e in host[name]:
            assert any(es <= s and e <= ee for es, ee in host["server.epoch"])
