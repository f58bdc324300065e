"""What the loader's prefetch thread takes to plan and fetch one batch
from the source: the sum of ``hvtpu_data_fetch_seconds`` over its count,
for the batches queued inside the untraced window
(``benchmark/producer_stages.py``).  The same stage is the span
``hvtpu:loader.fetch`` in a profile.  With one prefetch thread the job
cannot step faster than this and the transform together."""

from benchmark import producer_stages

LAYER, UNIT, MOVES = "input", "ms", "samples_per_s_per_chip"


def read(obs):
    return producer_stages.ms_per_batch(obs, "hvtpu_data_fetch_seconds")
