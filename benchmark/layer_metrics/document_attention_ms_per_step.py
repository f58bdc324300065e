"""Device time a step under ``hvtpu:attention`` in the hybrid cell: the
causal attention inside a document of its one attention layer in ten
(scores, softmax, weighted values; whatever is handed to or taken from
kernels), forward, recomputed and backward.  The projections around it
are not in it.  ``attention_ms_per_step`` is the transformer cell's."""

from benchmark import scopes

LAYER, UNIT, MOVES = "attention", "ms", "samples_per_s_per_chip"


def read(obs):
    return scopes.scoped_ms(obs, "hvtpu:attention")
