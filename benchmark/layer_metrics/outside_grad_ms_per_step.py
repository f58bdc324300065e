"""Device time a step in ops that have an ``op_name`` and belong to no
pass of the gradient (``benchmark/passes.py``, ``rest``): the exchange,
the non-finite guard, the optimizer's update, the user's
``optax.apply_updates``, and what the model computes from integers
(masks, positions, routing plans: nothing to differentiate)."""

from benchmark import passes

LAYER, UNIT, MOVES = "train_step", "ms", "samples_per_s_per_chip"


def read(obs):
    return passes.pass_ms(obs, passes.REST)
