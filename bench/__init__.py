"""Chip benchmark of the FL system: one harness, driven by the data files
beside it (``BENCHMARK.json`` at the checkout root names the cells)."""
