"""Device time a step under ``hvtpu:optimizer.guard`` (the ``is_finite``
reductions over the reduced gradients and their ``reduce_and``) and
``hvtpu:optimizer.update`` (the wrapped optax transformation, in both
branches of the guard's ``lax.cond``).  ``optax.apply_updates`` is the
user's line and outside both: ``outside_grad_ms_per_step`` holds it."""

from benchmark import passes

LAYER, UNIT, MOVES = "train_step", "ms", "samples_per_s_per_chip"


def read(obs):
    return passes.framework_ms(obs, "hvtpu:optimizer.")
