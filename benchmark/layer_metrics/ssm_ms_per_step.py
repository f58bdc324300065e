"""Device time a step under the Mamba-2 mixer's four scopes
(``hvtpu:ssm.proj``, ``.conv``, ``.scan``, ``.gate``), forward,
recomputed and backward, summed over the Mamba layers.  ``.proj`` holds
both projections with the norm before and the residual add after; the
layer's MLP (``hvtpu:mlp``) is not in it."""

from benchmark import scopes

LAYER, UNIT, MOVES = "ssm", "ms", "samples_per_s_per_chip"


def read(obs):
    line = scopes.account(obs.trace, obs.compiled_text)
    if line:
        print(line, flush=True)   # the run's log: run.py has no hook
    return scopes.scoped_ms(obs, "hvtpu:ssm.")
