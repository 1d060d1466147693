"""Shared machinery of the chip benchmark (``benchmarks/chip/run.py``).

What belongs to one configuration, traffic mix (phase driver), cell or
per-layer metric lives in a file of its own under ``configs/``,
``drivers/``, ``workloads/`` and ``metrics/``; this package finds them by
the names in ``BENCHMARK.json`` and holds only what every cell shares.
"""
