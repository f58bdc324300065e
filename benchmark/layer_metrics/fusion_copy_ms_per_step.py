"""Device time a step under ``hvtpu:exchange.pack`` and
``hvtpu:exchange.unpack``: the copies into and out of the flat buckets,
the part of ``exchange_ms_per_step`` that moves nothing between chips."""

from benchmark import passes

LAYER, UNIT, MOVES = "exchange", "ms", "samples_per_s_per_chip"
SCOPES = ("hvtpu:exchange.pack", "hvtpu:exchange.unpack")


def read(obs):
    # both or neither: None is for a program without the framework's scopes
    parts = [passes.framework_ms(obs, scope) for scope in SCOPES]
    return None if None in parts else sum(parts)
