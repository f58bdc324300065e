"""Seconds the backend spent compiling during set-up (jax.monitoring):
tens of seconds cold, what the persistent cache leaves when warm."""

LAYER, UNIT, MOVES = "entry", "s", "setup_s"


def read(obs):
    return obs.compile_s
