"""Device time a step in the forward pass: the ops whose ``op_name``
holds ``jvp(`` and neither ``transpose(`` nor ``rematted_computation``
(``benchmark/passes.py``).  What a ``jax.checkpoint`` makes again in the
backward pass is not in it."""

from benchmark import passes

LAYER, UNIT, MOVES = "train_step", "ms", "samples_per_s_per_chip"


def read(obs):
    line = passes.account(obs.trace, obs.compiled_text)
    if line:
        print(line, flush=True)   # the run's log: run.py has no hook
    return passes.pass_ms(obs, passes.FORWARD)
