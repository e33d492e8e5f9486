"""Chip benchmark of the GaisNet system: one cell per run, driven by data.

``BENCHMARK.json`` names the cells; each configuration, traffic mix,
per-layer metric and kernel cost lives in a file of its own under this
directory and is found by its name. See ``run.py``.
"""
