"""Device time a step under ``hvtpu:attention`` in the stack of
one-mixer layers: the causal attention inside a document of its one
attention layer in nine, sixteen query heads on a key/value head
(scores, softmax, weighted values; whatever is handed to or taken from
kernels), forward, recomputed and backward.  The projections around it
(``hvtpu:attn.proj``) are not in it."""

from benchmark import scopes

LAYER, UNIT, MOVES = "attention", "ms", "samples_per_s_per_chip"

SCOPE = "hvtpu:attention"


def read(obs):
    by_scope = scopes.ms_per_step(obs.trace, obs.compiled_text)
    return by_scope and by_scope.get(SCOPE)
