"""What the loader's prefetch thread takes to hand one fetched batch to
the chips, the user transform and ``jax.device_put`` as far as the call
goes: the sum of ``hvtpu_data_transform_seconds`` over its count, for
the batches queued inside the untraced window
(``benchmark/producer_stages.py``); the span ``hvtpu:loader.transform``
in a profile.  ``device_put`` returns before the copy has landed, so
this is the host's share of the copy and not the copy."""

from benchmark import producer_stages

LAYER, UNIT, MOVES = "input", "ms", "samples_per_s_per_chip"


def read(obs):
    return producer_stages.ms_per_batch(obs, "hvtpu_data_transform_seconds")
