"""Device time a step under the looped stack's scopes: ``hvtpu:loop.proj``
(the four projections with RoPE, the norm before and after and the
residual add), ``hvtpu:attention`` (the causal attention inside a
document) and ``hvtpu:loop.mlp`` (the SwiGLU MLP between its two norms,
the residual add), forward, recomputed and backward, summed over every
use of every layer held (layers x passes)."""

from benchmark import scopes

LAYER, UNIT, MOVES = "loop", "ms", "samples_per_s_per_chip"
SCOPES = ("hvtpu:loop.proj", "hvtpu:loop.mlp", "hvtpu:attention")


def stack_ms(obs):
    if scopes.scoped_ms(obs, "hvtpu:loop.") is None:
        return None               # another program's attention, or none
    return sum(scopes.scoped_ms(obs, scope) or 0.0 for scope in SCOPES)


def read(obs):
    line = scopes.account(obs.trace, obs.compiled_text)
    if line:
        print(line, flush=True)   # the run's log: run.py has no hook
    return stack_ms(obs)
