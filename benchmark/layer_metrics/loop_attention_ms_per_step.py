"""Device time a step under ``hvtpu:attention`` in the looped cell: the
causal attention inside a document of every use of every layer held
(the kernels or tiles, ``delta``'s reduction, the relayouts of ``lse``,
``delta`` and the ids, the flags and the held pairs of the walk),
forward, recomputed and backward.  It is a part of
``loop_stack_ms_per_step``; ``document_attention_ms_per_step`` is the
hybrid cell's and ``attention_ms_per_step`` the transformer cell's."""

from benchmark import scopes

LAYER, UNIT, MOVES = "attention", "ms", "samples_per_s_per_chip"


def read(obs):
    return scopes.scoped_ms(obs, "hvtpu:attention")
