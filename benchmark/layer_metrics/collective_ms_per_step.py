"""Device time of the ops that move data between chips, per step:
union of the collective ops' intervals in the traced steady window over
its steps, median over the chips."""

LAYER, UNIT, MOVES = "exchange", "ms", "samples_per_s_per_chip"


def read(obs):
    return obs.trace.collective_ms_per_step if obs.trace else None
