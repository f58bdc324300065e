"""``peak_bytes_in_use`` plus ``peak_bytes_reserved`` of the fullest
chip's ``memory_stats()`` after the window (``run.memory_peak_bytes``
says why both)."""

LAYER, UNIT, MOVES = "device", "GiB", "samples_per_s_per_chip"


def read(obs):
    if obs.memory_peak_bytes is None:
        return None
    return obs.memory_peak_bytes / 2 ** 30
