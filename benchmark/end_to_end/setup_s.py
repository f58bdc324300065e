"""Process start -> first measured dispatch: imports, hvt.init, the
host pool, initialisation, compilation or the cache's loads, the
reference check and its warm-up steps."""

UNIT = "s"


def read(obs):
    return obs.setup_s
