"""Device time a step under the gated short convolution's two scopes
(``hvtpu:shortconv.proj``, ``.conv``): forward, recomputed and backward,
summed over the convolution layers.  ``.proj`` holds the input
projection to ``b, c, x`` and the output projection, with the layer's
norm before and the residual add after; ``.conv`` the gates ``b x`` and
``c z`` and the taps of the convolution cut at document starts."""

from benchmark import scopes

LAYER, UNIT, MOVES = "conv", "ms", "samples_per_s_per_chip"


def read(obs):
    return scopes.scoped_ms(obs, "hvtpu:shortconv.")
