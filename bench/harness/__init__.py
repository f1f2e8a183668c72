"""Shared machinery of the benchmark: cell loading, traffic generation,
the plain reference, the open-loop and bulk loops, trace reduction,
needed-work counts and the table of device peaks.

Nothing here is specific to one configuration, traffic mix or per-layer
metric: those live in their own files under ``bench/configs``,
``bench/traffic``, ``bench/cells`` and ``bench/metrics`` and are found by
the names ``BENCHMARK.json`` gives.
"""
