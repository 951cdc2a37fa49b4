"""The benchmark: one cell of ``BENCHMARK.json`` per run of ``bench/run.py``.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it (see ``harness.py``).
"""
