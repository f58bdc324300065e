"""Operations and bytes a stack of LFM2's layers (a gated short
convolution or a grouped-query attention, then a dense SwiGLU MLP or
sigmoid-routed SwiGLU experts: the ``lfm2_moe`` family) requires of a
training step, from its configuration's shapes alone (``flops.py``'s
rule: what the forward and backward passes *require*, whatever
implements them; recomputed, padded and masked-out operations add
nothing).

Counted, a token a layer forward, in multiply-accumulates: a
convolution layer's two projections (``hidden x 3 hidden`` in,
``hidden x hidden`` out); an attention layer's four projections
(queries and output ``hidden x heads x head_dim``, keys and values
``hidden x kv_heads x head_dim``) and, for every (query, key) pair that
*causal and same document* allows, a score and a weighted value over a
head's width, a query head; the dense MLP's three products; an expert
layer's router over all the published experts and the routed experts'
three products for the share of a token's ``num_experts_per_tok``
choices that falls on the experts held here (the router's choice taken
as even); the untied head over the rows of the vocabulary held.  The
convolution's taps and gates, norms, rotations, softmax, sigmoid, the
embedding's gather and the loss are elementwise, gathers or reductions
and are left out of the step's count.  The pairs are data: the caller
counts them on the document boundaries it has
(``flops_hybrid_ssm_lm.visible_pairs``).

The gated convolution alone (``short_conv_train_flops_per_step``,
``short_conv_train_bytes_per_step``): what ``hvtpu:shortconv.conv`` is
required to do, its taps and its two gates, and the bytes an
implementation that makes one pass over its operands must still move.
"""

from __future__ import annotations

from typing import Dict

TRAIN_PASSES = 3      # forward, weight gradient, input gradient


def conv_layers(config: dict) -> int:
    return config["layer_types"].count("conv")


def attention_layers(config: dict) -> int:
    return config["layer_types"].count("full_attention")


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def head_dim(config: dict) -> int:
    """The family's: ``hidden_size / num_attention_heads``."""
    return config["hidden_size"] // config["num_attention_heads"]


def held_expert_rows_per_token(config: dict) -> float:
    """Rows the experts held here get for a token at an even routing."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["published"]["num_experts"])


def forward_macs_per_row(config: dict,
                         pairs_per_row: float) -> Dict[str, float]:
    """Multiply-accumulates of one forward pass over one row of
    ``sequence_length`` tokens, by part, summed over the layers;
    ``pairs_per_row`` as ``visible_pairs`` counts them, a head."""
    t, d = config["sequence_length"], config["hidden_size"]
    hd = head_dim(config)
    heads, groups = (config["num_attention_heads"],
                     config["num_key_value_heads"])
    experts = expert_layers(config)
    return {
        "conv_projections": conv_layers(config) * t * 4 * d * d,
        "attention_projections": attention_layers(config) * t * 2 * d * hd
        * (heads + groups),
        "attention": attention_layers(config) * heads * pairs_per_row * 2
        * hd,
        "dense_mlp": config["num_dense_layers"] * t * 3 * d
        * config["intermediate_size"],
        "router": experts * t * d * config["published"]["num_experts"],
        "routed_experts": experts * t * held_expert_rows_per_token(config)
        * 3 * d * config["moe_intermediate_size"],
        "head": t * d * config["vocab_size"],
    }


def train_flops_per_sample(config: dict, pairs_per_row: float) -> int:
    """FLOPs (2 a multiply-accumulate) one token requires of a training
    step: forward, weight gradient and input gradient of every part."""
    macs = sum(forward_macs_per_row(config, pairs_per_row).values())
    return round(2 * TRAIN_PASSES * macs / config["sequence_length"])


def short_conv_train_flops_per_step(config: dict, tokens: int) -> int:
    """What ``hvtpu:shortconv.conv`` is required to do in one step, a
    channel a token: the ``K`` taps, a multiply-add each, in the forward
    pass, the weight gradient and the input gradient (``2 x 3 K``), and
    the two gates, a product each forward (``b x``, ``c z``) and two each
    backward (the gradients of both of their factors)."""
    taps = config["conv_L_cache"]
    per_channel = 2 * TRAIN_PASSES * taps + 2 + 2 * 2
    return per_channel * config["hidden_size"] * tokens * conv_layers(config)


def short_conv_train_bytes_per_step(config: dict, tokens: int,
                                    compute_bytes: int = 2) -> int:
    """What ``hvtpu:shortconv.conv`` has to move through HBM in one
    step: the forward pass reads ``b``, ``c`` and ``x`` and writes ``y``;
    the backward pass reads ``b``, ``c``, ``x`` and ``dy`` and writes
    ``db``, ``dc`` and ``dx``; all in the compute type.  The product
    ``b x``, the convolution's sum and the taps (``K x hidden``) can stay
    on the chip and are not counted, nor is a recomputed forward
    pass."""
    arrays = (3 + 1) + (4 + 3)
    return (arrays * compute_bytes * config["hidden_size"] * tokens
            * conv_layers(config))
