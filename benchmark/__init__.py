"""hvtpu's benchmark: the yardstick every PR is measured with.

``BENCHMARK.json`` at the root of the repo names the cells; everything
that belongs to one configuration, one traffic mix or one per-layer
metric sits in a file of its own under this directory, found by that
name (``configs/``, ``traffic/``, ``layer_metrics/``, ``builders/``).
``run.py`` is the one command.  ``PERF.md`` says why each piece exists.
"""
