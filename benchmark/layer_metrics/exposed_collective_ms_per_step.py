"""The part of collective_ms_per_step during which no other op ran on
that chip: what the exchange adds to the step."""

LAYER, UNIT, MOVES = "exchange", "ms", "samples_per_s_per_chip"


def read(obs):
    return obs.trace.exposed_collective_ms_per_step if obs.trace else None
