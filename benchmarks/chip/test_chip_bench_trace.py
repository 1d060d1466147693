"""The reduction from a profiler trace to busy time, idle share and the
breakdown (``harness/trace.py``), on a hand-made trace whose answer is
known and on a small trace recorded on a TPU v5e."""

import gzip
import importlib.util
import json
import pathlib

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
RECORDED = BENCH / "testdata" / "trace_small.json.gz"


@pytest.fixture(scope="module")
def T():
    spec = importlib.util.spec_from_file_location(
        "chipbench_trace", BENCH / "harness" / "trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_hand_made_trace(T):
    ms = 1_000_000
    tr = {"device": {"/device:TPU:0": [
              ["%fusion.1 = f32[] fusion()", 5 * ms, 20 * ms],   # clipped
              ["%fusion.2 = f32[] fusion()", 30 * ms, 10 * ms],
              ["%copy.3 = f32[] copy()", 35 * ms, 10 * ms],      # overlaps
              ["%fusion.1 = f32[] fusion()", 70 * ms, 10 * ms],
              ["%fusion.9 = f32[] fusion()", 120 * ms, 5 * ms]]},  # after
          "host": [["bench.window", 10 * ms, 100 * ms],
                   ["server.epoch", 10 * ms, 80 * ms],
                   ["merged_eval", 50 * ms, 20 * ms]]}
    out = T.reduce(tr)
    # busy: [10,25) + [30,45) + [70,80) = 40 ms of a 100 ms window
    assert out["busy_s"] == pytest.approx(0.040)
    assert out["window_s"] == pytest.approx(0.100)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["%fusion.1 = f32[] fusion()"] == pytest.approx(0.025)
    assert "%fusion.9 = f32[] fusion()" not in ops
    gaps = dict(out["breakdown"]["idle_gaps"])
    # [25,30), [45,50) and [80,90) in server.epoch, [50,70) in the
    # merged_eval inside it, [90,110) outside any program span
    assert gaps["server.epoch"] == pytest.approx(0.020)
    assert gaps["merged_eval"] == pytest.approx(0.020)
    assert gaps[T.OUTSIDE] == pytest.approx(0.020)
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(0.100)


def test_union_merges_overlaps(T):
    assert T.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]


def _brute_busy_ns(events, w0, w1, step):
    """Busy time by sampling the window on a grid: an independent
    check of the interval union."""
    import numpy as np

    grid = np.arange(w0, w1, step)
    busy = np.zeros(grid.shape, bool)
    for _, s, d in events:
        busy |= (grid >= s) & (grid < s + d)
    return busy.sum() * step


def test_recorded_trace(T):
    tr = json.loads(gzip.decompress(RECORDED.read_bytes()))
    out = T.reduce(tr)
    w0, w1 = T.window_of(tr)
    (events,) = tr["device"].values()
    brute = _brute_busy_ns(events, w0, w1, 1000)
    assert out["busy_s"] == pytest.approx(brute / 1e9, rel=1e-3)
    assert 0 < out["busy_s"] < out["window_s"] == pytest.approx(
        (w1 - w0) / 1e9)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    assert len(out["breakdown"]["device_ops"]) == 10
    assert all(v > 0 for _, v in out["breakdown"]["device_ops"])
