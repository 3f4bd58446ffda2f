"""Chip benchmark of the JAX path: one cell (configuration x traffic mix)
per run, named in ``BENCHMARK.json`` at the root of the checkout."""
