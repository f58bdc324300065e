"""Device time a step under the exchange's scopes: ``hvtpu:exchange.pack``
(reshape, cast, concatenate into a flat bucket), ``hvtpu:exchange.reduce``
(scaling, the compressor's casts, the ``psum``, the division by the
number of workers) and ``hvtpu:exchange.unpack`` (slices, reshapes, casts
back).  On one chip it is what the fusion path costs with nobody to
talk to.  ``collective_ms_per_step`` times the collectives alone, from
outside."""

from benchmark import passes

LAYER, UNIT, MOVES = "exchange", "ms", "samples_per_s_per_chip"


def read(obs):
    return passes.framework_ms(obs, "hvtpu:exchange.")
