"""Device time a step under what the looped decoder does after each
pass: ``hvtpu:loop.exit`` (the final norm, the exit gate, the exit
distribution, its entropy and the expected loss) and ``hvtpu:lm_head``
(the logits and cross-entropy of every exit, made and made again in the
backward pass, and the embedding's gather and its gradient)."""

from benchmark import scopes

LAYER, UNIT, MOVES = "loop", "ms", "samples_per_s_per_chip"


def read(obs):
    gate = scopes.scoped_ms(obs, "hvtpu:loop.exit")
    if gate is None:
        return None
    return gate + (scopes.scoped_ms(obs, "hvtpu:lm_head") or 0.0)
