"""Operations a block-diffusion language model's configuration requires
of a training step, from its shapes alone (``flops.py``'s rule: what
the forward and backward passes *require*, whatever implements them;
recomputed, padded and masked-out operations add nothing).

Counted: the attention projections, the attention itself over the key
positions the mask lets a query see, the router, the expert products
for the share of every position's ``top_k`` choices that falls on the
experts held here (``top_k * held / total`` a position: the router's
choice is taken as even, which is what random weights give), and the
head over the noised half.  Norms, rotary embedding, softmax, SiLU, the
embedding's gather and the loss are elementwise, gathers or reductions
and are left out.

The model runs on ``xt ++ x``: two positions for every token of the
sequence; a *sample* is a token of the sequence, so a sample costs two
positions through every layer and one through the head.
"""

from __future__ import annotations

from typing import Dict


def visible_pairs(seq_len: int, block_length: int) -> int:
    """(query, key) pairs the block-diffusion mask allows in one
    sequence of ``seq_len`` tokens, for one head: a noised query sees
    its own block's noised keys (``block_length``) and the clean keys
    of earlier blocks; a clean query the clean keys of its own and
    earlier blocks.  ``seq_len**2 + seq_len * block_length`` when the
    blocks are whole."""
    pairs = 0
    for first in range(0, seq_len, block_length):
        size = min(block_length, seq_len - first)
        pairs += size * (size + first)            # the block's noised queries
        pairs += size * (first + size)            # ... and its clean ones
    return pairs


def forward_macs_per_sequence(config: dict) -> Dict[str, int]:
    """Multiply-accumulates of one forward pass over one sequence, by
    part, summed over the layers."""
    seq_len, d = config["sequence_length"], config["hidden_size"]
    hd, layers = config["head_dim"], config["num_hidden_layers"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    positions = 2 * seq_len
    total_experts = config["published"]["num_experts"]
    # rows the held experts get: exact in expectation, and an integer
    # for every configuration whose share divides its router
    expert_rows, rest = divmod(
        positions * config["num_experts_per_tok"] * config["num_experts"],
        total_experts)
    if rest:
        raise ValueError("the experts held do not get a whole number of "
                         "rows a sequence; count them by hand")
    return {
        "projections": layers * positions * d * hd * 2 * (heads + kv_heads),
        "attention": layers * heads * 2 * hd * visible_pairs(
            seq_len, config["block_length"]),
        "router": layers * positions * d * total_experts,
        "experts": layers * expert_rows * 3 * d
        * config["moe_intermediate_size"],
        "head": seq_len * d * config["vocab_size"],
    }


def attention_train_flops_per_step(config: dict, sequences: int) -> int:
    """What ``hvtpu:attention``'s tiles are required to do in one step:
    scores and weighted values, forward and both gradients."""
    macs = forward_macs_per_sequence(config)["attention"]
    return 2 * 3 * macs * sequences


def expert_train_flops_per_step(config: dict, sequences: int) -> int:
    """What ``hvtpu:moe.experts`` is required to do in one step."""
    macs = forward_macs_per_sequence(config)["experts"]
    return 2 * 3 * macs * sequences


def train_flops_per_sample(config: dict) -> int:
    """FLOPs (2 a multiply-accumulate) one token of the sequence
    requires of a training step: forward, weight gradient and input
    gradient of every part (the first layer's input gradient goes on to
    the embedding)."""
    macs = sum(forward_macs_per_sequence(config).values())
    flops, rest = divmod(2 * 3 * macs, config["sequence_length"])
    if rest:
        raise ValueError("not a whole number of FLOPs a token")
    return flops
