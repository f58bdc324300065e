"""Device time a step under the delta-rule mixer's four scopes
(``hvtpu:kda.proj``, ``.conv``, ``.gate``, ``.delta``): forward,
recomputed and backward, summed over the KDA layers.  ``.proj`` holds
the q, k, v and output projections with the layer's norm before and the
residual add after; ``.conv`` the convolution, SiLU and the keys' and
queries' unit length; ``.gate`` the two low-rank pairs, softplus,
``beta``, the output gate and the head norm; ``.delta`` everything from
``q, k, v, g, beta`` to ``o``."""

from benchmark import scopes

LAYER, UNIT, MOVES = "kda", "ms", "samples_per_s_per_chip"


def read(obs):
    return scopes.scoped_ms(obs, "hvtpu:kda.")
