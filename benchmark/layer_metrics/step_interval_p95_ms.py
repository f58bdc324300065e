"""95th percentile of the intervals between successive returns of the
lagged fence, where it does not repeat well enough to be held to a
bound (there ``step_time_p95_ms`` is not reported): the host's jitter
over a loader that only just keeps up."""

from benchmark.end_to_end import step_time_p95_ms

LAYER, UNIT, MOVES = "train_step", "ms", "samples_per_s_per_chip"


def read(obs):
    return step_time_p95_ms.read(obs)
