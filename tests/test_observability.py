"""Span tracing, metrics registry, and exporters.

The load-bearing assertion is *zero perturbation*: a fault-free run
with observability enabled must produce a byte-identical history
(loss/acc/comm_bytes/sim_time, record for record) to the same seed with
observability disabled — spans and metrics are write-only and never
feed back into accounting, RNG, or control flow.  The rest covers the
tracer's nesting/attribute semantics, the Chrome trace-event exporter's
schema (what Perfetto actually needs: ph/ts/pid/tid, non-negative dur,
LIFO bracketing per row), the CRC'd span-log round trip, and the
``scripts/trace_report.py`` CLI over the committed chaos-smoke artifact.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from repro.configs.base import FedConfig, OptimConfig, RunConfig
from repro.experiments import (DataSpec, ExperimentSpec, ObservabilitySpec,
                               run_experiment)
from repro.observability.export import (read_span_log, to_chrome_trace,
                                        validate_chrome_trace,
                                        write_span_log)
from repro.observability.metrics import (MetricsRegistry, format_phase_table,
                                         metric_key, parse_metric_key)
from repro.observability.tracer import NULL_SPAN, NULL_TRACER, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = "vit-s"


# ---------------------------------------------------------------------------
# tracer semantics
# ---------------------------------------------------------------------------


def test_span_nesting_and_attribute_capture():
    t = Tracer(sim_clock=lambda: 42.0)
    with t.span("outer", track="server", epoch=3) as outer:
        with t.span("inner", track="server") as inner:
            inner.set(loss=1.5)
        outer.set(val_acc=0.9)
    t.instant("marker", track="server", round=7)

    assert [e.name for e in t.events] == ["inner", "outer", "marker"]
    inner_rec, outer_rec, marker = t.events
    assert inner_rec.depth == 1 and outer_rec.depth == 0
    assert outer_rec.attrs == {"epoch": 3, "val_acc": 0.9}
    assert inner_rec.attrs == {"loss": 1.5}
    assert marker.kind == "instant" and marker.attrs["round"] == 7
    # dual clocks: wall durations are real, sim sampled via the clock
    assert outer_rec.dur_wall >= inner_rec.dur_wall >= 0.0
    assert outer_rec.t_sim == 42.0 and outer_rec.dur_sim == 0.0
    assert t.summary()["open_spans"] == 0
    assert t.tracks() == ["server"]


def test_disabled_tracer_records_nothing_and_yields_null_span():
    t = Tracer(enabled=False)
    with t.span("x", track="a") as sp:
        assert sp is NULL_SPAN
        sp.set(anything=1)          # must be a no-op, not an error
    t.instant("y")
    t.record_span("z", t_sim=0.0, dur_sim=1.0)
    assert t.events == [] and t.summary()["events"] == 0


def test_current_span_is_the_innermost_open_on_this_thread():
    t = Tracer()
    assert t.current_span() is NULL_SPAN
    with t.span("outer", track="server") as outer:
        with t.span("inner", track="server"):
            pass
        assert t.current_span() is outer
        seen = []
        th = threading.Thread(target=lambda: seen.append(t.current_span()))
        th.start()
        th.join()
        assert seen == [NULL_SPAN]      # another thread's stack is empty
        t.current_span().set(late=1)
    assert t.events[-1].attrs == {"late": 1}
    assert Tracer(enabled=False).current_span() is NULL_SPAN


def test_event_cap_drops_and_counts_instead_of_erroring():
    t = Tracer(max_events=2)
    for i in range(5):
        t.instant(f"e{i}")
    assert len(t.events) == 2 and t.dropped == 3


def test_sim_clock_binds_once():
    t = Tracer()
    t.bind_sim_clock(lambda: 1.0)
    t.bind_sim_clock(lambda: 2.0)       # later binds must not override
    t.instant("x")
    assert t.events[0].t_sim == 1.0


def test_spans_nest_per_thread_with_parent_links():
    """A producer thread's spans nest on a stack of their own: they take
    no parent from the consumer's open spans, and the consumer's spans
    keep theirs while the producer runs concurrently."""
    from repro.data.pipeline import Prefetcher

    t = Tracer()

    def produce():
        for i in range(4):
            with t.span("pack", track="feed"):
                time.sleep(0.002)
            yield i

    with t.span("epoch", track="main") as epoch:
        feed = Prefetcher(t.iter_span(produce(), "gather", "feed"))
        for _ in t.iter_span(feed, "wait", "main"):
            with t.span("step", track="main"):
                time.sleep(0.002)
    feed.thread.join(timeout=30)
    assert not feed.thread.is_alive()

    by_id = {e.span_id: e for e in t.events}
    assert len(by_id) == len(t.events)          # ids are unique
    main = threading.current_thread().name
    for e in t.events:
        parent = by_id.get(e.parent_id)
        if e.name == "epoch":
            assert parent is None and e.depth == 0
        elif e.name in ("wait", "step"):
            assert parent is epoch and e.thread == main and e.depth == 1
        elif e.name == "gather":
            assert parent is None and e.thread != main and e.depth == 0
        else:
            assert e.name == "pack" and parent.name == "gather"
            assert e.thread == parent.thread != main and e.depth == 1
    names = [e.name for e in t.events]
    assert names.count("wait") == 5 and names.count("gather") == 5
    assert t.summary()["open_spans"] == 0


def test_iter_span_times_each_next_and_nothing_between():
    clock = [0.0]
    t = Tracer(wall_clock=lambda: clock[0])

    def produce():
        for i in range(3):
            clock[0] += 1.0             # each item takes 1 s to produce
            yield i
        clock[0] += 0.5                 # and the end 0.5 s to find

    got = []
    with t.span("loop") as loop:
        for i in t.iter_span(produce(), "next", "feed"):
            got.append(i)
            clock[0] += 10.0            # the consumer's own time
    assert got == [0, 1, 2]
    spans = [e for e in t.events if e.name == "next"]
    assert [e.dur_wall for e in spans] == [1.0, 1.0, 1.0, 0.5]
    assert all(e.parent_id == loop.span_id and e.track == "feed"
               for e in spans)
    # a disabled tracer hands back the plain iterator, recording nothing
    it = iter([1, 2])
    assert NULL_TRACER.iter_span(it, "next") is it
    assert NULL_TRACER.events == []


def test_compile_counter_charges_the_innermost_open_span():
    """A fresh jit's trace and backend compile land, counted once, on
    the innermost span open on the calling thread; compile work with no
    span open lands on the tracer's ``jit_outside``."""
    import jax
    import jax.numpy as jnp

    t = Tracer()
    x = jnp.arange(5.0)                 # made (and compiled) outside spans
    x.block_until_ready()

    @jax.jit
    def f(v):                           # traces jnp ops within its trace
        return jnp.sin(v) * 2.0 + jnp.cos(v)

    with t.span("outer") as outer:
        with t.span("inner") as inner:
            f(x).block_until_ready()
        f(x).block_until_ready()        # cached: no jit work at all
    assert inner.attrs["traces"] == 1
    assert inner.attrs.get("compiles", 0) \
        + inner.attrs.get("cache_loads", 0) == 1
    assert 0.0 < inner.attrs["jit_s"] < inner.dur_wall
    assert not {"traces", "compiles", "cache_loads",
                "jit_s"} & set(outer.attrs)

    before = dict(t.jit_outside)
    jax.jit(lambda v: v * 3.0)(x).block_until_ready()
    assert t.jit_outside["traces"] == before.get("traces", 0) + 1
    assert t.jit_outside["jit_s"] > before.get("jit_s", 0.0)
    assert t.summary()["jit_outside"] == t.jit_outside


def test_profile_annotation_is_held_by_its_span(monkeypatch):
    """In profile mode each span, on any thread, holds a profiler
    annotation of its name from entry to exit, nested as the spans."""
    import jax.profiler

    log = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name, threading.current_thread().name))

        def __exit__(self, *exc):
            log.append(("exit", self.name, threading.current_thread().name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    plain = Tracer()
    with plain.span("unprofiled"):
        pass
    assert log == []

    t = Tracer(profile=True)
    main = threading.current_thread().name
    with t.span("outer"):
        with t.span("inner"):
            assert log == [("enter", "outer", main), ("enter", "inner", main)]
        def side():
            with t.span("side"):
                pass

        worker = threading.Thread(target=side, name="side-thread")
        worker.start()
        worker.join(timeout=30)
        assert not worker.is_alive()
    assert log[2] == ("exit", "inner", main)
    assert log[3:] == [("enter", "side", "side-thread"),
                       ("exit", "side", "side-thread"),
                       ("exit", "outer", main)]


def test_span_links_survive_both_exports(tmp_path):
    t = Tracer()
    with t.span("outer", track="server") as outer:
        with t.span("inner", track="server") as inner:
            pass
    path = str(tmp_path / "spans.jsonl")
    write_span_log(t, path)
    back = {e.name: e for e in read_span_log(path, strict=True)}
    assert back["inner"].parent_id == outer.span_id == back["outer"].span_id
    assert back["inner"].span_id == inner.span_id
    assert back["outer"].parent_id is None
    assert back["inner"].thread == threading.current_thread().name
    doc = to_chrome_trace(t)
    args = {e["name"]: e["args"] for e in doc["traceEvents"]
            if e["ph"] == "X"}
    assert args["inner"]["parent_id"] == outer.span_id
    assert "parent_id" not in args["outer"]
    # a span log written before spans carried links still reads
    old = read_span_log(os.path.join(REPO, "examples", "traces",
                                     "chaos_smoke_spans.jsonl"))
    assert old and all(e.span_id is None and e.parent_id is None
                       for e in old)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metric_key_roundtrip_and_phase_table():
    k = metric_key("comm_bytes", {"phase": "device", "direction": "up"})
    assert k == "comm_bytes{direction=up,phase=device}"
    assert parse_metric_key(k) == (
        "comm_bytes", {"direction": "up", "phase": "device"})

    m = MetricsRegistry()
    m.counter("comm_bytes", 100, phase="device", direction="up")
    m.counter("comm_bytes", 40, phase="device", direction="down")
    m.counter("comm_bytes", 999, phase="transfer")      # undirected
    m.counter("steps", 2, phase="device")
    m.counter("retries", 3, phase="device")
    m.counter("excluded_devices", 1, phase="device")
    m.observe("step_wall_s", 0.5, phase="device")
    m.observe("step_sim_s", 2.0, phase="device")
    rows = {r["phase"]: r for r in m.phase_table()}
    dev = rows["device"]
    assert dev["bytes_up"] == 100 and dev["bytes_down"] == 40
    assert dev["bytes_total"] == 140        # up+down fallback
    assert dev["steps"] == 2 and dev["retries"] == 3 and dev["excluded"] == 1
    assert dev["wall_s"] == 0.5 and dev["sim_s"] == 2.0
    assert rows["transfer"]["bytes_total"] == 999
    md = format_phase_table(m.phase_table(), title="t")
    assert md.startswith("### t") and "| device |" in md


def test_histogram_summary_quantiles():
    m = MetricsRegistry()
    for v in range(1, 101):
        m.observe("staleness", float(v), phase="fedbuff")
    h = m.hist_summary("staleness{phase=fedbuff}")
    assert h["count"] == 100 and h["min"] == 1.0 and h["max"] == 100.0
    assert h["p50"] == pytest.approx(50.0, abs=1.0)
    assert h["p90"] == pytest.approx(90.0, abs=1.0)


# ---------------------------------------------------------------------------
# exporters: Chrome trace schema + CRC'd span log
# ---------------------------------------------------------------------------


def _traced_tracer():
    t = Tracer(sim_clock=lambda: 0.0)
    with t.span("round", track="device/3", round=0):
        with t.span("step", track="device/3"):
            pass
    t.instant("excluded", track="transport", device=5)
    t.record_span("round", track="scheduler", t_sim=1.0, dur_sim=2.5,
                  round=0)
    return t


def test_chrome_trace_schema_is_valid_and_perfetto_shaped():
    t = _traced_tracer()
    doc = to_chrome_trace(t)
    assert validate_chrome_trace(doc) == []
    events = doc["traceEvents"]
    # metadata names one process per track group, one thread per track
    meta = [e for e in events if e["ph"] == "M"]
    procs = {e["args"]["name"] for e in meta
             if e["name"] == "process_name"}
    assert procs == {"device", "transport", "scheduler"}
    # sim-domain span lands at simulated microseconds
    sched = [e for e in events
             if e["ph"] == "X" and e["args"].get("clock") == "sim"]
    assert sched and sched[0]["ts"] == 1.0e6 and sched[0]["dur"] == 2.5e6
    # instants carry the "i" phase
    assert any(e["ph"] == "i" and e["name"] == "excluded" for e in events)


def test_chrome_trace_validator_catches_broken_documents():
    assert validate_chrome_trace({}) == ["traceEvents missing or empty"]
    missing = {"traceEvents": [{"ph": "X", "ts": 0, "pid": 1}]}
    assert any("missing 'tid'" in p for p in validate_chrome_trace(missing))
    crossing = {"traceEvents": [
        {"ph": "X", "ts": 0, "dur": 10, "pid": 1, "tid": 1, "name": "a"},
        {"ph": "X", "ts": 5, "dur": 10, "pid": 1, "tid": 1, "name": "b"},
    ]}
    assert any("not LIFO" in p for p in validate_chrome_trace(crossing))


def test_span_log_crc_roundtrip_and_corruption_detection(tmp_path):
    t = _traced_tracer()
    path = str(tmp_path / "spans.jsonl")
    n = write_span_log(t, path)
    assert n == len(t.events)
    back = read_span_log(path, strict=True)
    assert [(e.name, e.track, e.kind) for e in back] == \
        [(e.name, e.track, e.kind) for e in t.events]
    assert back[0].attrs == t.events[0].attrs

    # flip one byte inside a record: strict load raises, salvage skips
    raw = open(path).read()
    corrupted = raw.replace('"round": 0', '"round": 1', 1)
    assert corrupted != raw
    path2 = str(tmp_path / "corrupt.jsonl")
    open(path2, "w").write(corrupted)
    with pytest.raises(ValueError, match="CRC mismatch|truncated"):
        read_span_log(path2, strict=True)
    salvaged = read_span_log(path2, strict=False)
    assert len(salvaged) < len(back)


# ---------------------------------------------------------------------------
# zero perturbation: byte-identical histories with observability on/off
# ---------------------------------------------------------------------------


def _spec(**kw):
    base = dict(
        name="obs", systems=("ampere", "fedbuff"), arch=ARCH,
        run=RunConfig(
            arch=ARCH,
            fed=FedConfig(num_clients=6, clients_per_round=3,
                          local_steps=2, device_batch_size=4,
                          server_batch_size=8, dirichlet_alpha=0.5),
            optim=OptimConfig(name="momentum", lr=0.1,
                              schedule="inverse_time", decay_gamma=0.01)),
        data=DataSpec(train_samples=144, eval_samples=48),
        max_rounds=2, max_server_epochs=1, patience=50)
    base.update(kw)
    return ExperimentSpec(**base)


def _fleet_cfg():
    from repro.fleet import FleetConfig
    return FleetConfig(n_devices=6, seed=0, min_cohort=2, max_cohort=3,
                       init_cohort=3, dropout_hazard=0.0, p_online0=1.0,
                       async_buffer_size=2, max_concurrent=3)


def test_observability_never_perturbs_faultfree_history():
    """ampere + fedbuff, fault-free: history with tracing+metrics on is
    byte-identical to the same seed with observability off (the
    ``observability`` summary block aside)."""
    fleet = _fleet_cfg()
    obs_on = run_experiment(
        _spec(fleet=fleet, observability=ObservabilitySpec(enabled=True)),
        write_results=False)
    obs_off = run_experiment(_spec(fleet=fleet), write_results=False)
    for name in ("ampere", "fedbuff"):
        h_on = dict(obs_on["results"][name]["history"])
        obs_block = h_on.pop("observability")
        assert h_on == obs_off["results"][name]["history"]
        # and the run did actually trace + meter
        assert obs_block["tracer"]["events"] > 0
        assert obs_block["tracer"]["open_spans"] == 0
        assert obs_block["metrics"]["counters"]
        phases = {r["phase"] for r in obs_on["summary"][name]["phases"]}
        assert "server" in phases and "transfer" in phases
        assert ("fedbuff" if name == "fedbuff" else "fleet") in phases
        assert "phases" not in obs_off["summary"][name]
    # fault-free analytic accounting agrees with the phase table totals
    for name in ("ampere", "fedbuff"):
        rows = obs_on["summary"][name]["phases"]
        total = sum(r["bytes_total"] for r in rows)
        assert total == obs_on["results"][name]["history"]["comm_bytes"]


def test_artifacts_written_per_system(tmp_path):
    out = run_experiment(
        _spec(systems=("ampere",), results_dir=str(tmp_path),
              observability=ObservabilitySpec(enabled=True)))
    arts = out["summary"]["ampere"]["artifacts"]
    doc = json.load(open(arts["trace_json"]))
    assert validate_chrome_trace(doc) == []
    spans = read_span_log(arts["span_log"], strict=True)
    assert spans and any(e.track == "transfer" for e in spans)


# ---------------------------------------------------------------------------
# transport delta stats (per-round reset-and-emit)
# ---------------------------------------------------------------------------


def test_delta_stats_resets_mark_but_not_cumulative():
    from repro.transport import InProcessTransport

    t = InProcessTransport()
    t.transfer("a", 100)
    d1 = t.delta_stats()
    assert d1["sends"] == 1 and d1["wire_bytes"] == 100
    assert "retries" not in d1               # zero entries omitted
    t.transfer("b", 50)
    d2 = t.delta_stats()
    assert d2["sends"] == 1 and d2["wire_bytes"] == 50
    assert t.delta_stats() == {}             # nothing since the last call
    assert t.stats["sends"] == 2 and t.stats["wire_bytes"] == 150


# ---------------------------------------------------------------------------
# MetricsLogger: injected clock + repr fallback
# ---------------------------------------------------------------------------


def test_metrics_logger_injected_clock_and_repr_fallback(tmp_path):
    from repro.runtime.metrics import MetricsLogger

    path = str(tmp_path / "m.jsonl")
    clock = [7.5]
    with MetricsLogger(path, clock=lambda: clock[0]) as log:
        log.log(loss=1.0)
        clock[0] = 9.25
        log.log(weird=object())          # not JSON-dumpable
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["t"] == 7.5 and lines[0]["loss"] == 1.0
    assert lines[1]["t"] == 9.25
    assert lines[1]["_repr"] is True
    assert lines[1]["weird"].startswith("<object object")
    # close is idempotent
    log2 = MetricsLogger(str(tmp_path / "m2.jsonl"))
    log2.close()
    log2.close()


# ---------------------------------------------------------------------------
# trace_report CLI over the committed chaos-smoke artifact
# ---------------------------------------------------------------------------


def test_trace_report_on_committed_chaos_artifact(tmp_path):
    """The committed chaos-smoke span log (examples/traces/) renders a
    round-by-round report, validates strictly, and carries the retry
    spans the CI gate requires."""
    src = os.path.join(REPO, "examples", "traces")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out_md = str(tmp_path / "report.md")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         os.path.join(src, "chaos_smoke_spans.jsonl"),
         "--validate", "--require-retries", "--out", out_md],
        capture_output=True, text=True, env=env, timeout=120)
    assert res.returncode == 0, res.stderr
    report = open(out_md).read()
    assert "### Rounds" in report and "### Transport" in report
    assert "retries:" in report
    # the committed Chrome trace next to it is Perfetto-valid too
    doc = json.load(open(os.path.join(src, "chaos_smoke_trace.json")))
    assert validate_chrome_trace(doc) == []
