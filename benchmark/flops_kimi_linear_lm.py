"""Operations and bytes a stack of Kimi Linear's layers (a delta-rule
linear attention with a decay a channel or a latent attention, then a
dense SwiGLU MLP or sparse experts beside a shared expert: the
``kimi_linear`` family) requires of a training step, from its
configuration's shapes alone (``flops.py``'s rule: what the forward and
backward passes *require*, whatever implements them; recomputed, padded
and masked-out operations add nothing).

Counted, a token a layer forward, in multiply-accumulates: a KDA
layer's projections (q, k, v and o, ``hidden x inner`` each; the two
low-rank pairs ``hidden x d + d x inner``; ``hidden x heads`` for
``beta``); its delta rule as the fewest products of the chunked form at
the configuration's chunk ``C``, a head: ``K K^T`` and ``Q K^T`` inside
the chunk (``C x d_k`` each), the solved system applied to the keys and
the values (``C x (d_k + d_v)``), the pairs applied to the pseudo-values
(``C x d_v``), and three products with the state (``W S``, ``Q S`` and
the state's update, ``d_k x d_v`` each); an MLA layer's four projections
(queries ``hidden x heads x (own + shared)``, the latent and the shared
key part, the up-projection to every head's own key part and values,
the output) and, for every (query, key) pair that *causal and same
document* allows, a score over the whole key width and a weighted value
over the value width, a head; the dense MLP's three products; an expert
layer's router over all the published experts, its shared expert and
the routed experts' three products for the share of a token's
``num_experts_per_token`` choices that falls on the experts held here
(the router's choice taken as even); the untied head over the rows of
the vocabulary held.  Convolution (4 taps), norms, gates, softplus,
softmax, sigmoid, the decays' exponentials, the embedding's gather and
the loss are elementwise, gathers or reductions and are left out.  The
pairs are data: the caller counts them on the document boundaries it
has (``flops_hybrid_ssm_lm.visible_pairs``).

Bytes, for the delta rule alone (``delta_train_bytes_per_step``): what
an implementation that keeps its decays, its systems and its state on
the chip must still read and write in HBM.
"""

from __future__ import annotations

from typing import Dict

TRAIN_PASSES = 3      # forward, weight gradient, input gradient


def _kda(config: dict):
    """(heads, a head's width) of the delta-rule layers."""
    linear = config["linear_attn_config"]
    return linear["num_heads"], linear["head_dim"]


def kda_layers(config: dict) -> int:
    return len(config["linear_attn_config"]["kda_layers"])


def mla_layers(config: dict) -> int:
    return len(config["linear_attn_config"]["full_attn_layers"])


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def key_width(config: dict) -> int:
    return config["qk_nope_head_dim"] + config["qk_rope_head_dim"]


def delta_macs_per_token(config: dict) -> int:
    """The delta rule of one KDA layer, forward, in the chunked form."""
    heads, d = _kda(config)
    chunk = config["chunk_size"]
    return heads * (2 * chunk * d           # K K^T, Q K^T
                    + chunk * 2 * d         # (I + A)^-1 on K and on V
                    + chunk * d             # the pairs on the pseudo-values
                    + 3 * d * d)            # W S, Q S, the state's update


def held_expert_rows_per_token(config: dict) -> float:
    """Rows the experts held here get for a token at an even routing."""
    return (config["num_experts_per_token"] * config["num_experts"]
            / config["published"]["num_experts"])


def forward_macs_per_row(config: dict,
                         pairs_per_row: float) -> Dict[str, float]:
    """Multiply-accumulates of one forward pass over one row of
    ``sequence_length`` tokens, by part, summed over the layers;
    ``pairs_per_row`` as ``visible_pairs`` counts them, a head."""
    t, d = config["sequence_length"], config["hidden_size"]
    heads, hd = _kda(config)
    inner = heads * hd
    mla_heads = config["num_attention_heads"]
    own_and_values = config["qk_nope_head_dim"] + config["v_head_dim"]
    experts = expert_layers(config)
    f = config["moe_intermediate_size"]
    return {
        "kda_projections": kda_layers(config) * t * (
            4 * d * inner + 2 * (d * hd + hd * inner) + d * heads),
        "kda_delta": kda_layers(config) * t * delta_macs_per_token(config),
        "mla_projections": mla_layers(config) * t * (
            d * mla_heads * key_width(config)
            + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            + config["kv_lora_rank"] * mla_heads * own_and_values
            + mla_heads * config["v_head_dim"] * d),
        "attention": mla_layers(config) * mla_heads * pairs_per_row * (
            key_width(config) + config["v_head_dim"]),
        "dense_mlp": config["first_k_dense_replace"] * t * 3 * d
        * config["intermediate_size"],
        "router": experts * t * d * config["published"]["num_experts"],
        "shared_experts": experts * t * config["num_shared_experts"] * 3
        * d * f,
        "routed_experts": experts * t * held_expert_rows_per_token(config)
        * 3 * d * f,
        "head": t * d * config["vocab_size"],
    }


def train_flops_per_sample(config: dict, pairs_per_row: float) -> int:
    """FLOPs (2 a multiply-accumulate) one token requires of a training
    step: forward, weight gradient and input gradient of every part."""
    macs = sum(forward_macs_per_row(config, pairs_per_row).values())
    return round(2 * TRAIN_PASSES * macs / config["sequence_length"])


def delta_train_flops_per_step(config: dict, tokens: int) -> int:
    """What ``hvtpu:kda.delta`` is required to do in one step."""
    return (2 * TRAIN_PASSES * delta_macs_per_token(config) * tokens
            * kda_layers(config))


def delta_train_bytes_per_step(config: dict, tokens: int,
                               compute_bytes: int = 2) -> int:
    """What ``hvtpu:kda.delta`` has to move through HBM in one step: the
    forward pass reads ``q``, ``k``, ``v`` (compute type), the log-decays
    ``g`` (f32, a channel) and ``beta`` (f32, a head) and writes ``o``;
    the backward pass reads those five and ``do`` and writes the five
    gradients.  Cumulative sums, decays, the chunks' systems and the
    states carried from chunk to chunk can stay on the chip and are not
    counted, nor is a recomputed forward pass."""
    heads, hd = _kda(config)
    inner = heads * hd
    operands = 3 * compute_bytes * inner + 4 * inner + 4 * heads
    result = compute_bytes * inner
    a_token = (operands + result) + (operands + result + operands)
    return a_token * tokens * kda_layers(config)


def attention_train_flops_per_step(config: dict, pairs_per_row: float,
                                   rows: int) -> float:
    """What ``hvtpu:attention`` is required to do in one step: scores
    over the key width and weighted values over the value width of the
    visible pairs, forward and both gradients."""
    return (2 * TRAIN_PASSES * rows
            * forward_macs_per_row(config, pairs_per_row)["attention"])
