"""Chip benchmark for the serving main path (``python bench/run.py``).

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py``.
"""
