"""Programs set-up took from the persistent compilation cache."""

LAYER, UNIT, MOVES = "entry", "programs", "setup_s"


def read(obs):
    return obs.cache_hits
