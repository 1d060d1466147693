"""Reduce a profiler trace to device busy time, idle share and a breakdown.

Two steps, so that the second can be checked on a small recorded trace
without a chip (``testdata/trace_small.json``):

* :func:`compact` reads the ``.xplane.pb`` that ``jax.profiler`` writes
  and keeps only what the reduction needs: per device plane, the events
  of its op line (``[name, start_ns, dur_ns]``), and the host-side
  annotations (the program's tracer spans and the harness's window) on
  the same clock.
* :func:`reduce` works on that compact form: busy time is the union of
  the op intervals inside the window, averaged over the chips used;
  each idle gap is split at the host spans' edges and every piece is
  labelled by the innermost span that encloses it.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW_SPAN = "bench.window"
# ops on the device's op line: one event per executed HLO op (fusion)
DEVICE_OP_LINE = "XLA Ops"
OUTSIDE = "outside program spans"


def trace_options():
    """Profiler options for a traced window: device and host activity,
    no Python function tracer (it would swamp the host track)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def find_xplane(logdir):
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def compact(xplane_path, host_names):
    """The compact form of one trace; ``host_names`` selects the host
    annotations to keep (exact names)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == DEVICE_OP_LINE:
                    # an op's event is named by its whole HLO line;
                    # keep the op's name ("%fusion.12")
                    device[plane.name] = [
                        [e.name.split(" = ", 1)[0], int(e.start_ns),
                         int(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_names:
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"device": device, "host": sorted(host, key=lambda h: h[1])}


def union(intervals):
    """Merge ``[(start, end), ...]`` into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def window_of(tr):
    """(start_ns, end_ns) of the harness's window span; the last one when
    the trace holds several."""
    wins = [h for h in tr["host"] if h[0] == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    _, s, d = wins[-1]
    return s, s + d


def _label(spans, t):
    """Innermost (shortest) program span that contains instant ``t``."""
    best = None
    for name, s, d in spans:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else OUTSIDE


def _attribute(spans, g0, g1, acc):
    """Split the idle gap [g0, g1) at the program spans' edges and add
    each piece to the innermost span that encloses it."""
    inside = [sp for sp in spans if sp[1] < g1 and sp[1] + sp[2] > g0]
    cuts = sorted({g0, g1} | {t for _, s, d in inside for t in (s, s + d)
                              if g0 < t < g1})
    for a, b in zip(cuts, cuts[1:]):
        acc[_label(inside, (a + b) / 2)] += b - a


def reduce(tr, top=10):
    """Device busy seconds, window seconds and the breakdown of one
    trace in compact form.  ``busy_s`` and the op times are averaged
    over the device planes that ran anything; the idle gaps are summed
    per label over the same planes and averaged likewise."""
    w0, w1 = window_of(tr)
    planes = {k: v for k, v in tr["device"].items() if v}
    if not planes:
        raise ValueError("trace has no device op events")
    spans = [h for h in tr["host"] if h[0] != WINDOW_SPAN]
    n = len(planes)
    busy_ns = 0
    op_ns = defaultdict(int)
    gap_ns = defaultdict(int)
    for events in planes.values():
        iv = []
        for name, s, d in events:
            cs, ce = _clip(s, s + d, w0, w1)
            if ce > cs:
                iv.append((cs, ce))
                op_ns[name] += ce - cs
        merged = union(iv)
        busy_ns += sum(e - s for s, e in merged)
        cursor = w0
        for s, e in merged + [[w1, w1]]:
            if s > cursor:
                _attribute(spans, cursor, s, gap_ns)
            cursor = max(cursor, e)

    def top_list(acc):
        items = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n / 1e9] for k, v in items]

    return {"busy_s": busy_ns / n / 1e9, "window_s": (w1 - w0) / 1e9,
            "chips": n,
            "breakdown": {"device_ops": top_list(op_ns),
                          "idle_gaps": top_list(gap_ns)}}
