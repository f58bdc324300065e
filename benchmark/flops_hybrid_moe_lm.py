"""Operations and bytes a stack of one-mixer layers (Mamba-2 with B/C
groups, attention, sparse experts with a shared expert: the
``nemotron_h`` family) requires of a training step, from its
configuration's shapes alone (``flops.py``'s rule: what the forward and
backward passes *require*, whatever implements them; recomputed, padded
and masked-out operations add nothing).

Counted, a token a layer forward, in multiply-accumulates: a Mamba
layer's two projections (``hidden x (2 inner + 2 groups x state +
heads)`` and ``inner x hidden``); its recurrence in the chunked form at
the published chunk size ``Q`` (``C B^T`` inside the chunk, ``Q x
state`` a group; the masked product with ``delta x``, ``Q x inner``; the
chunk's state and ``C H`` from the state carried in, ``state x inner``
each); an attention layer's four projections and a score and a weighted
value for every (query, key) pair that *causal and same document*
allows; an expert layer's router over all the published experts, its
shared expert (two products, ``hidden x shared width`` each) and the
routed experts' two products for the share of a token's ``top_k``
choices that falls on the experts held here (``top_k x held / total``:
the router's choice is taken as even, which is what random weights
give); the untied head over the rows of the vocabulary held.
Convolution (4 taps), norms, gates, relu squared, softplus, softmax,
sigmoid, the embedding's gather and the loss are elementwise, gathers or
reductions and are left out.  The pairs are data: the caller counts them
on the document boundaries it has (``flops_hybrid_ssm_lm
.visible_pairs``).

Bytes, for the recurrence alone (``scan_train_bytes_per_step``): what a
scan that keeps its decays and its state on the chip must still read
and write in HBM, ``B`` and ``C`` every group wide.
"""

from __future__ import annotations

from typing import Dict

TRAIN_PASSES = 3      # forward, weight gradient, input gradient


def _inner(config: dict) -> int:
    return config["mamba_num_heads"] * config["mamba_head_dim"]


def _layers(config: dict, letter: str) -> int:
    return config["hybrid_override_pattern"].count(letter)


def scan_macs_per_token(config: dict) -> int:
    """The recurrence of one Mamba layer, forward, in the chunked form."""
    inner, state = _inner(config), config["ssm_state_size"]
    chunk = config["chunk_size"]
    return (chunk * state * config["n_groups"]         # C B^T, every group
            + chunk * inner                            # (L o C B^T)(delta x)
            + 2 * state * inner)                       # the state; C H


def held_expert_rows_per_token(config: dict) -> float:
    """Rows the experts held here get for a token at an even routing."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["published"]["n_routed_experts"])


def forward_macs_per_row(config: dict,
                         pairs_per_row: float) -> Dict[str, float]:
    """Multiply-accumulates of one forward pass over one row of
    ``sequence_length`` tokens, by part, summed over the layers;
    ``pairs_per_row`` as ``visible_pairs`` counts them, a head."""
    t, d = config["sequence_length"], config["hidden_size"]
    inner, state = _inner(config), config["ssm_state_size"]
    mamba, attention, experts = (_layers(config, letter)
                                 for letter in "M*E")
    heads, hd = config["num_attention_heads"], config["head_dim"]
    return {
        "ssm_projections": mamba * t * (
            d * (2 * inner + 2 * state * config["n_groups"]
                 + config["mamba_num_heads"]) + inner * d),
        "ssm_scan": mamba * t * scan_macs_per_token(config),
        "attention_projections": attention * t * 2 * d * hd * (
            heads + config["num_key_value_heads"]),
        "attention": attention * heads * 2 * hd * pairs_per_row,
        "router": experts * t * d * config["published"]["n_routed_experts"],
        "shared_experts": experts * t * config["n_shared_experts"] * 2 * d
        * config["moe_shared_expert_intermediate_size"],
        "routed_experts": experts * t * held_expert_rows_per_token(config)
        * 2 * d * config["moe_intermediate_size"],
        "head": t * d * config["vocab_size"],
    }


def train_flops_per_sample(config: dict, pairs_per_row: float) -> int:
    """FLOPs (2 a multiply-accumulate) one token requires of a training
    step: forward, weight gradient and input gradient of every part."""
    macs = sum(forward_macs_per_row(config, pairs_per_row).values())
    return round(2 * TRAIN_PASSES * macs / config["sequence_length"])


def _train_flops_per_step(config, pairs_per_row, rows, part) -> float:
    return (2 * TRAIN_PASSES * rows
            * forward_macs_per_row(config, pairs_per_row)[part])


def scan_train_flops_per_step(config: dict, tokens: int) -> int:
    """What ``hvtpu:ssm.scan`` is required to do in one step."""
    return (2 * TRAIN_PASSES * scan_macs_per_token(config) * tokens
            * _layers(config, "M"))


def scan_train_bytes_per_step(config: dict, tokens: int,
                              compute_bytes: int = 2) -> int:
    """What ``hvtpu:ssm.scan`` has to move through HBM in one step: the
    forward pass reads ``x``, ``B``, ``C`` (compute type, every group of
    the two) and ``delta`` (f32) and writes ``y``; the backward pass
    reads those four and ``dy`` and writes the four gradients.  Decays,
    ``C B^T`` and the states carried from chunk to chunk can stay on the
    chip and are not counted, nor is a recomputed forward pass."""
    inner = _inner(config)
    operands = (compute_bytes * (inner + 2 * config["ssm_state_size"]
                                 * config["n_groups"])
                + 4 * config["mamba_num_heads"])
    result = compute_bytes * inner
    a_token = (operands + result) + (operands + result + operands)
    return a_token * tokens * _layers(config, "M")


def expert_train_flops_per_step(config: dict, rows: int) -> float:
    """What ``hvtpu:moe.experts`` is required to do in one step of
    ``rows`` rows: the six products of a pass (two forward, four of
    gradients) over the rows the held experts get at an even routing."""
    return _train_flops_per_step(config, 0.0, rows, "routed_experts")


def attention_train_flops_per_step(config: dict, pairs_per_row: float,
                                   rows: int) -> float:
    """What ``hvtpu:attention`` is required to do in one step: scores and
    weighted values of the visible pairs, forward and both gradients."""
    return _train_flops_per_step(config, pairs_per_row, rows, "attention")
