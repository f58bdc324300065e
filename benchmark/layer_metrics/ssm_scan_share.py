"""Share of the Mamba-2 mixer's device time that is not its two
projections: the convolution, the chunked scan and the gated norm, what
the mechanism costs beyond its matrix products."""

from benchmark import scopes

LAYER, UNIT, MOVES = "ssm", "%", "samples_per_s_per_chip"


def read(obs):
    whole = scopes.scoped_ms(obs, "hvtpu:ssm.")
    products = scopes.scoped_ms(obs, "hvtpu:ssm.proj")
    if not whole or products is None:
        return None
    return 100.0 * (1.0 - products / whole)
