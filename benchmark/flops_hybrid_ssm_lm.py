"""Operations and bytes a hybrid state-space language model's
configuration requires of a training step, from its shapes alone
(``flops.py``'s rule: what the forward and backward passes *require*,
whatever implements them; recomputed, padded and masked-out operations
add nothing).

Counted, a token a layer forward, in multiply-accumulates: a Mamba
layer's two projections (``hidden x (2 inner + 2 state + heads)`` and
``inner x hidden``); its recurrence in the chunked form at the
published chunk size ``Q`` (the state-space-duality form: ``C B^T``
inside the chunk, ``Q x state``; the masked product with ``delta x``,
``Q x inner``; the chunk's state and ``C H`` from the state carried in,
``state x inner`` each); an attention layer's four projections and a
score and a weighted value for every (query, key) pair that *causal and
same document* allows; the shared MLP of every layer (``3 x hidden x
width``); the tied head over the rows of the vocabulary held.
Convolution (4 taps), norms, gates, softplus, softmax, the embedding's
gather and the loss are elementwise, gathers or reductions and are left
out.  The pairs are data: the caller counts them on the document
boundaries it has (``visible_pairs``).

Bytes, for the recurrence alone (``scan_train_bytes_per_step``): what a
scan that keeps its decays and its state on the chip must still read
and write in HBM.
"""

from __future__ import annotations

from typing import Dict

TRAIN_PASSES = 3      # forward, weight gradient, input gradient


def visible_pairs(segment) -> int:
    """(query, key) pairs *causal and same document* allows in rows of
    ``segment`` (int ``[rows, T]``, the document's index at every
    position, documents contiguous), for one head: a document of ``n``
    tokens holds ``n (n + 1) / 2``."""
    import numpy as np

    pairs = 0
    for row in np.asarray(segment):
        starts = np.flatnonzero(np.diff(row, prepend=row[0] - 1))
        lengths = np.diff(np.append(starts, len(row))).astype(np.int64)
        pairs += int(np.sum(lengths * (lengths + 1) // 2))
    return pairs


def _inner(config: dict) -> int:
    return config["mamba_n_heads"] * config["mamba_d_head"]


def scan_macs_per_token(config: dict) -> int:
    """The recurrence of one Mamba layer, forward, in the chunked form."""
    inner, state = _inner(config), config["mamba_d_state"]
    chunk = config["mamba_chunk_size"]
    return (chunk * state * config["mamba_n_groups"]   # C B^T
            + chunk * inner                            # (L o C B^T)(delta x)
            + 2 * state * inner)                       # the state; C H


def forward_macs_per_row(config: dict,
                         pairs_per_row: float) -> Dict[str, float]:
    """Multiply-accumulates of one forward pass over one row of
    ``sequence_length`` tokens, by part, summed over the layers;
    ``pairs_per_row`` as ``visible_pairs`` counts them, a head."""
    t, d = config["sequence_length"], config["hidden_size"]
    inner, state = _inner(config), config["mamba_d_state"]
    mamba = config["layer_types"].count("mamba")
    attention = config["layer_types"].count("attention")
    heads = config["num_attention_heads"]
    hd = d // heads
    return {
        "ssm_projections": mamba * t * (
            d * (2 * inner + 2 * state * config["mamba_n_groups"]
                 + config["mamba_n_heads"]) + inner * d),
        "ssm_scan": mamba * t * scan_macs_per_token(config),
        "attention_projections": attention * t * 2 * d * hd * (
            heads + config["num_key_value_heads"]),
        "attention": attention * heads * 2 * hd * pairs_per_row,
        "mlp": (mamba + attention) * t * 3 * d
        * config["shared_intermediate_size"],
        "head": t * d * config["vocab_size"],
    }


def train_flops_per_sample(config: dict, pairs_per_row: float) -> int:
    """FLOPs (2 a multiply-accumulate) one token requires of a training
    step: forward, weight gradient and input gradient of every part."""
    macs = sum(forward_macs_per_row(config, pairs_per_row).values())
    return round(2 * TRAIN_PASSES * macs / config["sequence_length"])


def scan_train_flops_per_step(config: dict, tokens: int) -> int:
    """What ``hvtpu:ssm.scan`` is required to do in one step."""
    return (2 * TRAIN_PASSES * scan_macs_per_token(config) * tokens
            * config["layer_types"].count("mamba"))


def scan_train_bytes_per_step(config: dict, tokens: int,
                              compute_bytes: int = 2) -> int:
    """What ``hvtpu:ssm.scan`` has to move through HBM in one step: the
    forward pass reads ``x``, ``B``, ``C`` (compute type) and ``delta``
    (f32) and writes ``y``; the backward pass reads those four and
    ``dy`` and writes the four gradients.  Decays, ``C B^T`` and the
    states carried from chunk to chunk can stay on the chip and are not
    counted, nor is a recomputed forward pass."""
    inner = _inner(config)
    operands = (compute_bytes * (inner + 2 * config["mamba_d_state"]
                                 * config["mamba_n_groups"])
                + 4 * config["mamba_n_heads"])
    result = compute_bytes * inner
    a_token = (operands + result) + (operands + result + operands)
    return a_token * tokens * config["layer_types"].count("mamba")
