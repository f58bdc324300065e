"""95th percentile of the intervals between successive returns of the
lagged fence.  In synchronous data-parallel training the slowest step
on any worker paces all of them, so the tail is what a pod feels."""

from benchmark.quantiles import percentile

UNIT = "ms"


def read(obs):
    return 1e3 * percentile(obs.window.step_intervals(), 95.0)
