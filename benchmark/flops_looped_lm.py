"""Operations a looped language model's configuration requires of a
training step, from its shapes alone (``flops.py``'s rule: what the
forward and backward passes *require*, whatever implements them;
recomputed, padded and masked-out operations add nothing).

A layer held is *used* ``total_ut_steps`` times a forward pass, and
every use is required work: the count goes by uses, not by parameters
(``6 N D`` would understate this model ``total_ut_steps``-fold).  The
head is applied after every pass, so it is counted once an exit.

Counted, a token a layer use forward, in multiply-accumulates: the four
projections (``hidden x head_dim x (2 heads + 2 key/value heads)``); a
score and a weighted value for every (query, key) pair that *causal and
same document* allows (the pairs are data: the caller counts them,
``flops_hybrid_ssm_lm.visible_pairs``); the SwiGLU MLP (``3 x hidden x
width``).  An exit: the head (``hidden x vocabulary``) and the gate
(``hidden``).  Norms, RoPE, softmax, SiLU, the exit distribution, the
embedding's gather and the loss are elementwise, gathers or reductions
and are left out.
"""

from __future__ import annotations

from typing import Dict

TRAIN_PASSES = 3      # forward, weight gradient, input gradient


def forward_macs_per_row(config: dict,
                         pairs_per_row: float) -> Dict[str, float]:
    """Multiply-accumulates of one forward pass over one row of
    ``sequence_length`` tokens, by part, summed over the layer uses and
    the exits; ``pairs_per_row`` as ``visible_pairs`` counts them, a
    head."""
    t, d = config["sequence_length"], config["hidden_size"]
    heads, hd = config["num_attention_heads"], config["head_dim"]
    exits = config["total_ut_steps"]
    uses = exits * config["num_hidden_layers"]
    return {
        "projections": uses * t * d * hd * 2 * (
            heads + config["num_key_value_heads"]),
        "attention": uses * heads * 2 * hd * pairs_per_row,
        "mlp": uses * t * 3 * d * config["intermediate_size"],
        "head": exits * t * d * config["vocab_size"],
        "gate": exits * t * d,
    }


STACK = ("projections", "attention", "mlp")    # hvtpu:loop.proj|mlp, attention
EXITS = ("head",)                              # hvtpu:lm_head


def train_flops_per_sample(config: dict, pairs_per_row: float) -> int:
    """FLOPs (2 a multiply-accumulate) one token requires of a training
    step: forward, weight gradient and input gradient of every part."""
    macs = sum(forward_macs_per_row(config, pairs_per_row).values())
    return round(2 * TRAIN_PASSES * macs / config["sequence_length"])


def train_flops_per_step(config: dict, pairs_per_row: float, rows: int,
                         parts) -> float:
    """What the named parts of ``forward_macs_per_row`` require of one
    step over ``rows`` rows."""
    macs = forward_macs_per_row(config, pairs_per_row)
    return 2 * TRAIN_PASSES * rows * sum(macs[part] for part in parts)
