"""Device time a step in the backward pass proper: the ops whose
``op_name`` holds ``transpose(`` and not ``rematted_computation``
(``benchmark/passes.py``)."""

from benchmark import passes

LAYER, UNIT, MOVES = "train_step", "ms", "samples_per_s_per_chip"


def read(obs):
    return passes.pass_ms(obs, passes.BACKWARD)
