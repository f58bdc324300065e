"""Device time a step under the Mamba-2 mixer's four scopes
(``hvtpu:ssm.proj``, ``.conv``, ``.scan``, ``.gate``) in the stack of
one-mixer layers, whose mixers read ``B`` and ``C`` in eight groups:
forward, recomputed and backward, summed over the Mamba layers.
``.proj`` holds both projections with the layer's norm before and the
residual add after; such a layer has nothing else.  ``ssm_ms_per_step``
is the one-group hybrid cell's."""

from benchmark import scopes

LAYER, UNIT, MOVES = "ssm", "ms", "samples_per_s_per_chip"


def read(obs):
    line = scopes.account(obs.trace, obs.compiled_text)
    if line:
        print(line, flush=True)   # the run's log: run.py has no hook
    return scopes.scoped_ms(obs, "hvtpu:ssm.")
