"""Share of the expert layer's device time that is not the expert
products: routing, the sort and the row gathers of the dispatch, the
weighted scatter-add of the combine."""

from benchmark import scopes

LAYER, UNIT, MOVES = "moe", "%", "samples_per_s_per_chip"


def read(obs):
    whole = scopes.scoped_ms(obs, "hvtpu:moe.")
    products = scopes.scoped_ms(obs, "hvtpu:moe.experts")
    if not whole or products is None:
        return None
    return 100.0 * (1.0 - products / whole)
