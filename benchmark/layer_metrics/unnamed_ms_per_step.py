"""Device time a step in ops without any ``op_name``: what the compiler
made by itself (copies, converts, relayouts, broadcasts), on some
scope's behalf or none (``benchmark/passes.py``, ``unnamed``).  No scope
metric and no other pass holds it."""

from benchmark import passes

LAYER, UNIT, MOVES = "device", "ms", "samples_per_s_per_chip"


def read(obs):
    return passes.pass_ms(obs, passes.UNNAMED)
