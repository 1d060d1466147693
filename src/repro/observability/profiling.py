"""Optional ``jax.profiler`` coupling.

``--profile`` on ``scripts/run_experiment.py`` / ``benchmarks/run.py``
wraps the run in :func:`profile_run` and builds its tracers with
``profile=True``: every tracer span, on every thread, then also holds a
``jax.profiler.TraceAnnotation`` of its name, so the profiler's host
planes show the program's spans on the device trace's clock.  The
jitted steps and the kernels' entry points carry ``jax.named_scope``
names, which the profiler records in the device ops' metadata.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def profile_run(logdir: str):
    """``jax.profiler.trace`` around a whole run.

    Yields the logdir (``tensorboard --logdir`` / Perfetto opens it).
    Missing jax profiler support runs the body untraced.
    """
    try:
        import jax
        cm = jax.profiler.trace(logdir)
    except (ImportError, AttributeError):
        cm = contextlib.nullcontext()
    with cm:
        yield logdir
