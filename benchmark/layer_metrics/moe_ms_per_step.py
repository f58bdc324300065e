"""Device time a step under the expert layer's four scopes
(``hvtpu:moe.route``, ``.dispatch``, ``.experts``, ``.combine``),
forward, recomputed and backward, summed over the layers."""

from benchmark import scopes

LAYER, UNIT, MOVES = "moe", "ms", "samples_per_s_per_chip"


def read(obs):
    return scopes.scoped_ms(obs, "hvtpu:moe.")
