"""Device time a step in the latent-attention layer's mixer:
``hvtpu:attention`` (scores over keys of 128 + 64, softmax, values of
128 weighted; whatever is handed to or taken from kernels) and
``hvtpu:mla.proj`` (the latent and its norm, the up-projections, the
queries', the shared key part laid beside every head's own, the output
projection, the layer's norm before and the residual add after),
forward, recomputed and backward."""

from benchmark import scopes

LAYER, UNIT, MOVES = "attention", "ms", "samples_per_s_per_chip"

SCOPES = ("hvtpu:attention", "hvtpu:mla.proj")


def read(obs):
    by_scope = scopes.ms_per_step(obs.trace, obs.compiled_text)
    if by_scope is None:
        return None
    found = [by_scope[scope] for scope in SCOPES if scope in by_scope]
    return sum(found) if found else None
