"""One module per per-layer metric, named as in ``BENCHMARK.json``.

Each states its ``LAYER`` (a name of PERF.md section 3), its ``UNIT``,
the end-to-end metric it ``MOVES``, and ``read(obs)``: the value from a
run's ``Observations``, or None when there is nothing to read it from
(the harness then leaves the metric out of the line).
"""
