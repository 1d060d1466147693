"""Zero-perturbation span tracing with dual clocks.

A :class:`Tracer` records *where time and bytes go* without ever feeding
back into the run: spans never touch RNG state, accounting, or control
flow, so a fault-free run with tracing enabled is byte-identical (same
``history``) to the same seed with tracing disabled — the invariant
``tests/test_observability.py`` asserts for ampere and fedbuff.

Every span carries two clocks:

* **wall** — host ``time.perf_counter`` seconds, relative to the
  tracer's construction.  This is the timeline Perfetto renders
  (``repro.observability.export.write_chrome_trace``).
* **sim** — the run's simulated clock (the same quantity accumulated
  into ``Runner.history["sim_time"]``), sampled at span entry/exit via
  an injected ``sim_clock`` callable.  Scheduler and fleet-trace spans
  live *entirely* in the sim domain (``clock="sim"``): their start/end
  are scheduler event times, and the exporter places them on the
  timeline at those sim instants.

Tracks are plain strings (``"server"``, ``"device/3"``, ``"scheduler"``,
``"transport"``); the first ``/`` segment becomes the Perfetto process,
the full string the thread.  A disabled tracer (``Tracer(enabled=False)``
or the shared :data:`NULL_TRACER`) costs one attribute check per call
and records nothing, so it can be threaded unconditionally through hot
paths.

Spans nest per thread: each thread keeps its own stack of open spans,
so spans opened in a producer thread (a prefetcher's, a store writer's)
never cross the consumer's.  Each span records its ``span_id``, the
``parent_id`` of the span that enclosed it on the same thread (``None``
at a thread's top) and the ``thread`` that opened it, from which a
reader computes self time.

Compile work is counted where it happens: the first enabled tracer
registers one process-wide ``jax.monitoring`` listener, and each jit
trace, lowering and backend compile adds to the innermost open span of
every enabled tracer on the calling thread (attrs ``traces``,
``compiles``, ``cache_loads``, ``jit_s``), or to the tracer's
:attr:`Tracer.jit_outside` where none of its spans is open there.

This module is stdlib-only at import time (the transport layer, which is
stdlib-only by contract, hooks into it).
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
import weakref
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional


@dataclasses.dataclass
class SpanRecord:
    """One closed span (or instant event, when ``dur`` entries are 0)."""

    name: str
    track: str                      # "group" or "group/subtrack"
    kind: str                       # "span" | "instant"
    t_wall: float                   # seconds since tracer start
    dur_wall: float
    t_sim: Optional[float] = None   # simulated seconds (run clock)
    dur_sim: Optional[float] = None
    clock: str = "wall"             # timeline domain: "wall" | "sim"
    depth: int = 0                  # nesting depth on its thread at entry
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    span_id: Optional[int] = None   # unique within its tracer
    parent_id: Optional[int] = None  # enclosing span on the same thread
    thread: Optional[str] = None    # name of the thread that opened it

    def set(self, **attrs):
        """Attach attributes after entry (e.g. a loss known at exit)."""
        self.attrs.update(attrs)


class _NullSpan:
    """Shared do-nothing span for disabled tracers; supports ``set``."""

    __slots__ = ()

    def set(self, **attrs):
        pass

    def __bool__(self):
        return False


NULL_SPAN = _NullSpan()


class _SpanCM:
    """Context manager closing one live span on its thread's stack; in
    profile mode it also holds the span's profiler annotation."""

    __slots__ = ("_tracer", "_rec", "_stack", "_annotation")

    def __init__(self, tracer: "Tracer", rec: SpanRecord,
                 stack: List[SpanRecord], annotation):
        self._tracer = tracer
        self._rec = rec
        self._stack = stack
        self._annotation = annotation

    def __enter__(self) -> SpanRecord:
        return self._rec

    def __exit__(self, exc_type, exc, tb):
        rec, stack = self._rec, self._stack
        # spans close LIFO by construction (context managers unwind the
        # stack); tolerate a mismatch rather than corrupt the stack
        if stack and stack[-1] is rec:
            stack.pop()
        else:
            for i, open_rec in enumerate(stack):
                if open_rec is rec:
                    del stack[i]
                    break
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        tracer = self._tracer
        rec.dur_wall = tracer._now() - rec.t_wall
        sim1 = tracer._sim_now()
        if rec.t_sim is not None and sim1 is not None:
            rec.dur_sim = sim1 - rec.t_sim
        tracer._store(rec)
        return False


class _NullCM:
    __slots__ = ()

    def __enter__(self):
        return NULL_SPAN

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_CM = _NullCM()


class Tracer:
    """Records spans and instant events; never affects the traced run.

    ``sim_clock`` (when bound) supplies the simulated-time reading taken
    at span entry/exit; :meth:`bind_sim_clock` lets the owning
    :class:`~repro.experiments.runner.Runner` inject it after
    construction.  ``max_events`` bounds memory: past the cap new events
    are counted in :attr:`dropped` instead of stored (never an error —
    observability must not take the run down).  ``profile=True`` makes
    every span, on every thread, also hold a
    ``jax.profiler.TraceAnnotation`` of its name, so that a
    ``jax.profiler.trace`` shows it in its host planes on the device
    trace's clock.
    """

    def __init__(self, enabled: bool = True, *,
                 sim_clock: Optional[Callable[[], float]] = None,
                 wall_clock: Optional[Callable[[], float]] = None,
                 max_events: int = 250_000, profile: bool = False):
        self.enabled = bool(enabled)
        self.sim_clock = sim_clock
        self._wall = wall_clock or time.perf_counter
        self.max_events = int(max_events)
        self.profile = bool(profile)
        self.t0 = self._wall()
        self.events: List[SpanRecord] = []   # closed, in close order
        self.dropped = 0
        # compile work (traces, compiles, cache_loads, jit_s) that no
        # span of this tracer held: none was open on its thread
        self.jit_outside: Dict[str, float] = {}
        self._ids = itertools.count(1)
        self._local = threading.local()      # .stack: this thread's open spans
        self._stacks: Dict[int, List[SpanRecord]] = {}   # every thread's
        self._annotation = None
        if self.enabled:
            if self.profile:
                try:
                    from jax.profiler import TraceAnnotation
                    self._annotation = TraceAnnotation
                except ImportError:
                    pass
            _COMPILE_WATCH.add(self)

    # ------------------------------------------------------------------
    def bind_sim_clock(self, fn: Callable[[], float]):
        """Install the simulated-time reader if none is bound yet."""
        if self.sim_clock is None:
            self.sim_clock = fn

    def _now(self) -> float:
        return self._wall() - self.t0

    def _sim_now(self) -> Optional[float]:
        return None if self.sim_clock is None else float(self.sim_clock())

    def _thread_stack(self) -> List[SpanRecord]:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            self._stacks[threading.get_ident()] = stack
            return stack

    # ------------------------------------------------------------------
    def span(self, name: str, *, track: str = "main", **attrs):
        """Context manager timing one dual-clock span.

        Yields the live :class:`SpanRecord` so callers can attach exit
        attributes (``sp.set(loss=...)``); a disabled tracer yields a
        shared null span instead.
        """
        if not self.enabled:
            return _NULL_CM
        stack = self._thread_stack()
        rec = SpanRecord(name=name, track=track, kind="span",
                         t_wall=self._now(), dur_wall=0.0,
                         t_sim=self._sim_now(), clock="wall",
                         depth=len(stack), attrs=attrs,
                         span_id=next(self._ids),
                         parent_id=stack[-1].span_id if stack else None,
                         thread=threading.current_thread().name)
        stack.append(rec)
        annotation = None
        if self._annotation is not None:
            annotation = self._annotation(name)
            annotation.__enter__()
        return _SpanCM(self, rec, stack, annotation)

    def current_span(self):
        """The innermost span open on the calling thread, for attributes
        known only later (a shared null span when none is open or the
        tracer is disabled)."""
        stack = getattr(self._local, "stack", None) if self.enabled else None
        return stack[-1] if stack else NULL_SPAN

    def iter_span(self, iterable: Iterable, name: str,
                  track: str = "main") -> Iterator:
        """Iterate ``iterable`` with each ``next()`` in a span of its own
        (the last, which finds it exhausted, too): the time a consumer
        waits on its producer, or a producer takes per item."""
        if not self.enabled:
            return iter(iterable)
        return self._iter_span(iter(iterable), name, track)

    def _iter_span(self, it: Iterator, name: str, track: str) -> Iterator:
        while True:
            with self.span(name, track=track):
                try:
                    item = next(it)
                except StopIteration:
                    return
            yield item

    def instant(self, name: str, *, track: str = "main", **attrs):
        """Record a zero-duration event at the current clocks."""
        if not self.enabled:
            return
        stack = self._thread_stack()
        self._store(SpanRecord(name=name, track=track, kind="instant",
                               t_wall=self._now(), dur_wall=0.0,
                               t_sim=self._sim_now(), dur_sim=0.0,
                               clock="wall", depth=len(stack),
                               attrs=attrs, span_id=next(self._ids),
                               parent_id=(stack[-1].span_id if stack
                                          else None),
                               thread=threading.current_thread().name))

    def record_span(self, name: str, *, track: str = "main",
                    t_sim: float, dur_sim: float, kind: str = "span",
                    **attrs):
        """Record an after-the-fact span in the *sim* clock domain.

        Used for replayed artifacts whose timing is already known —
        scheduler heap events and fleet-trace rounds — where the wall
        clock of the recording moment is meaningless.
        """
        if not self.enabled:
            return
        self._store(SpanRecord(name=name, track=track, kind=kind,
                               t_wall=self._now(), dur_wall=0.0,
                               t_sim=float(t_sim), dur_sim=float(dur_sim),
                               clock="sim", depth=0, attrs=attrs,
                               span_id=next(self._ids)))

    def _store(self, rec: SpanRecord):
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        self.events.append(rec)

    def _add_jit(self, counts: Dict[str, float]):
        """Add one compile event's counts to the innermost span open on
        the calling thread, else to :attr:`jit_outside`."""
        stack = getattr(self._local, "stack", None)
        target = stack[-1].attrs if stack else self.jit_outside
        for k, v in counts.items():
            target[k] = target.get(k, 0) + v

    # ------------------------------------------------------------------
    def ingest_fleet_trace(self, trace, *, track: str = "scheduler",
                           events: bool = True):
        """Replay a :class:`~repro.fleet.FleetTrace` into scheduler-track
        sim-domain spans: one span per round, one instant per raw heap
        event (churn/dropout/straggler/heartbeat/quorum/...).

        Heartbeats dominate multi-100k-event traces; they are folded
        into a per-round count attribute instead of one instant each so
        the track stays readable (and under ``max_events``).
        """
        if not self.enabled:
            return
        for p in trace.rounds:
            attrs = {"round": p.round_idx, "cohort_size": p.cohort_size,
                     "clients": len(p.clients), "dropped": len(p.dropped)}
            if p.staleness:
                attrs["staleness_max"] = max(p.staleness)
            self.record_span("round", track=track, t_sim=p.t_start,
                             dur_sim=p.round_time, **attrs)
        if not events:
            return
        heartbeats: Dict[int, int] = {}
        for t, kind, dev, rnd in trace.events:
            if kind == "heartbeat":
                heartbeats[rnd] = heartbeats.get(rnd, 0) + 1
                continue
            self.record_span(kind, track=f"{track}/events", t_sim=t,
                             dur_sim=0.0, kind="instant", device=dev,
                             round=rnd)
        round_end = {p.round_idx: p.t_end for p in trace.rounds}
        fallback = trace.rounds[-1].t_end if trace.rounds else 0.0
        for rnd, n in sorted(heartbeats.items()):
            self.record_span("heartbeats", track=f"{track}/events",
                             t_sim=float(round_end.get(rnd, fallback)),
                             dur_sim=0.0, kind="instant", round=rnd,
                             count=n)

    # ------------------------------------------------------------------
    def tracks(self) -> List[str]:
        return sorted({e.track for e in self.events})

    def summary(self) -> dict:
        open_spans = sum(len(s) for s in list(self._stacks.values()))
        return {"events": len(self.events), "dropped": self.dropped,
                "open_spans": open_spans, "tracks": self.tracks(),
                "jit_outside": dict(self.jit_outside)}


# ---------------------------------------------------------------------------
# compile counter: one jax.monitoring listener for the process
# ---------------------------------------------------------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_JIT_EVENTS = (_TRACE_EVENT, _LOWER_EVENT, _COMPILE_EVENT)


class _CompileWatch:
    """Hands each jit trace, lowering and backend compile of the process
    to every live enabled tracer (:meth:`Tracer._add_jit`).

    JAX reports each of the three at exit with its seconds, and at entry
    as a scalar (its start time).  Traces nest (a jitted function traces
    the jitted functions it calls, ``jnp`` ops included), so an event
    counts only where no event of its kind encloses it on its thread,
    and its seconds only where no event of any kind does: ``jit_s`` is
    wall time, never counted twice.  A backend compile that found its
    executable in the persistent cache (the cache-hit event fires inside
    it) counts as a ``cache_loads``, else as a ``compiles``.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._registered = False
        self._tracers: tuple = ()      # weakrefs; replaced whole, never mutated
        self._local = threading.local()   # .open: the thread's open events

    def add(self, tracer: "Tracer"):
        with self._lock:
            if not self._registered:
                try:
                    from jax import monitoring
                except ImportError:
                    return
                monitoring.register_scalar_listener(self._enter)
                monitoring.register_event_listener(self._event)
                monitoring.register_event_duration_secs_listener(self._exit)
                self._registered = True
            self._tracers = tuple(r for r in self._tracers
                                  if r() is not None) + (weakref.ref(tracer),)

    def _open(self) -> list:
        try:
            return self._local.open
        except AttributeError:
            self._local.open = []
            self._local.hit = False
            return self._local.open

    def _enter(self, event, value, **_):
        if event in _JIT_EVENTS:
            self._open().append(event)

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self._open()
            self._local.hit = True

    def _exit(self, event, duration, **_):
        if event not in _JIT_EVENTS:
            return
        open_events = self._open()
        for i in range(len(open_events) - 1, -1, -1):
            if open_events[i] == event:
                del open_events[i]
                break
        counts: Dict[str, float] = {}
        if event not in open_events:
            if event == _TRACE_EVENT:
                counts["traces"] = 1
            elif event == _COMPILE_EVENT:
                counts["cache_loads" if self._local.hit else "compiles"] = 1
        if event == _COMPILE_EVENT:
            self._local.hit = False
        if not open_events:
            counts["jit_s"] = float(duration)
        if counts:
            for ref in self._tracers:
                tracer = ref()
                if tracer is not None:
                    tracer._add_jit(counts)


_COMPILE_WATCH = _CompileWatch()

# shared disabled tracer: thread it unconditionally, costs ~nothing
NULL_TRACER = Tracer(enabled=False)
