"""The chip benchmark: open-loop serving cells on one TPU, driven by data.

``run.py`` is the entry point; ``BENCHMARK.json`` at the repository root
names the cells. Configurations, traffic mixes and per-layer metric
readers sit in ``configs/``, ``traffic/`` and ``metrics/``, one file each,
found by the name ``BENCHMARK.json`` gives them; each configuration's
reference module (layer equations, weight layout, work counts) sits in
``references/``, found by the name the configuration gives it.
"""
