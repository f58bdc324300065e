"""Share of its time the loader's prefetch thread spent making batches
(fetch and transform) and not parked on a full queue, over the batches
queued inside the untraced window (``benchmark/producer_stages.py``):
sums of ``hvtpu_data_fetch_seconds`` and ``hvtpu_data_transform_seconds``
over those two and ``hvtpu_data_backpressure_seconds``.  Near 100 the
producer is never parked and the loader paces the job.  Unlike the
consumer's wait it does not depend on which call the loop happens to
block in, and as a ratio of the producer's own times it means the same
should the loader be given more threads."""

from benchmark import producer_stages

LAYER, UNIT, MOVES = "input", "%", "samples_per_s_per_chip"


def read(obs):
    seen = producer_stages.over_window(obs)
    if seen is None:
        return None
    fetch, transform, parked = (
        seen[name]["sum"] for name in producer_stages.COUNTERS)
    return 100.0 * (fetch + transform) / (fetch + transform + parked)
