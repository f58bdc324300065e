"""Collective ops in the compiled step program (optimised HLO text): a
count, which repeats exactly.  An asynchronous pair counts once."""

from benchmark.hlo import collective_calls

LAYER, UNIT, MOVES = "exchange", "calls", "samples_per_s_per_chip"


def read(obs):
    if obs.compiled_text is None:
        return None
    return collective_calls(obs.compiled_text)
