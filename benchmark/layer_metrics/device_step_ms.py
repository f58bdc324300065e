"""Median device duration of the step program, over the steps and
chips of the traced steady window ("XLA Modules" line)."""

LAYER, UNIT, MOVES = "train_step", "ms", "samples_per_s_per_chip"


def read(obs):
    return obs.trace.device_step_ms if obs.trace else None
