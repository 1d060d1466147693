"""What this process compiles for: Pallas mode and the compile cache.

Two decisions every entry point and kernel shares, kept in one place:

* :func:`pallas_interpret` — Pallas kernels run compiled on a TPU and
  through the interpreter on the CPU (the test backend).  Any other
  backend is an error, never a silent fallback to the interpreter.
* :func:`enable_compile_cache` — JAX's persistent compilation cache at
  ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at the fixed
  ``<repo>/.jax_cache``.  The path is part of the cache key, so it never
  depends on a temp dir, a pid or the time.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def pallas_interpret() -> bool:
    """``interpret=`` for a ``pallas_call`` on the current default backend:
    False on ``tpu``, True on ``cpu``; any other backend raises."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    no other directory is set here."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
