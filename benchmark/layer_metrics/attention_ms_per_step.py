"""Device time a step under ``hvtpu:attention``: the masked attention
in tiles (scores, online softmax, weighted values), forward, recomputed
and backward, summed over the layers.  The projections around it are
not in it."""

from benchmark import scopes

LAYER, UNIT, MOVES = "attention", "ms", "samples_per_s_per_chip"


def read(obs):
    line = scopes.account(obs.trace, obs.compiled_text)
    if line:
        print(line, flush=True)   # the run's log: run.py has no hook
    return scopes.scoped_ms(obs, "hvtpu:attention")
