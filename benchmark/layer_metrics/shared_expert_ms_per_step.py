"""Device time a step under ``hvtpu:moe.shared``: the shared expert
every token goes through beside its routed experts (two plain products
with a squared ReLU between), forward, recomputed and backward, summed
over the expert layers."""

from benchmark import scopes

LAYER, UNIT, MOVES = "moe", "ms", "samples_per_s_per_chip"


def read(obs):
    return scopes.scoped_ms(obs, "hvtpu:moe.shared")
