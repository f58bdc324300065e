"""Bytes of one gradient tree — what every chip hands to the exchange
each step — from the parameters' shapes."""

LAYER, UNIT, MOVES = "exchange", "MiB", "samples_per_s_per_chip"


def read(obs):
    return obs.gradient_bytes / 2 ** 20
