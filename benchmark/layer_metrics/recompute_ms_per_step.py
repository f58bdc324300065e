"""Device time a step spent making again, in the backward pass, what the
forward pass had made and a ``jax.checkpoint`` did not keep: the ops
whose ``op_name`` holds ``rematted_computation``
(``benchmark/passes.py``).  Only the cells whose models checkpoint their
layers report it."""

from benchmark import passes

LAYER, UNIT, MOVES = "train_step", "ms", "samples_per_s_per_chip"


def read(obs):
    return passes.pass_ms(obs, passes.RECOMPUTE)
