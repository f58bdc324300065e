"""One module per end-to-end metric, named as in ``BENCHMARK.json``:
its ``UNIT`` and ``read(obs)`` over the untraced window's host clock."""
