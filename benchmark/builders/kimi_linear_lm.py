"""A decoder of ``horovod_tpu.models.kimi_linear`` (a mixer and a
feed-forward part a layer, chosen apart: delta-rule linear attention
with a decay a channel or latent attention without positions; a dense
SwiGLU MLP or sparse experts beside a shared expert) under the causal
next-token loss on packed documents: what a configuration's file has to
say to get one built.  The file is the model's published ``config.json``
with the keys of ``reduced`` counting what this chip holds, the
published counts under ``published`` and the chip's place under
``deployment``.

Returns the same ``Workload`` as every builder.  Rows are packed as the
hybrid state-space builder packs them (``hybrid_ssm_lm.make_pool``:
``x``, ``segment``, ``w`` from the configuration's document-length
law); nothing here knows a cell or a traffic mix.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark import flops_kimi_linear_lm as flops
from benchmark.builders import hybrid_ssm_lm as packed
from benchmark.builders.image_classifier import Workload


def layer_kinds(config: Dict[str, Any]):
    """``(mixers, ffns)``, a name a layer, from the two lists of layer
    numbers (from 1) and the number of leading dense layers."""
    linear, depth = config["linear_attn_config"], config["num_hidden_layers"]
    kda, mla = set(linear["kda_layers"]), set(linear["full_attn_layers"])
    if kda & mla or kda | mla != set(range(1, depth + 1)):
        raise ValueError(
            f"{config['name']}: kda_layers and full_attn_layers do not "
            f"name each of the {depth} layers once")
    return (tuple("kda" if i in kda else "mla"
                  for i in range(1, depth + 1)),
            tuple("dense" if i < config["first_k_dense_replace"]
                  else "experts" for i in range(depth)))


def model_config(config: Dict[str, Any]):
    from horovod_tpu.models.kimi_linear import KimiLinearConfig

    stated = {key: config[key] for key in (
        "hidden_act", "tie_word_embeddings", "mla_use_nope", "q_lora_rank",
        "moe_router_activation_func", "moe_layer_freq", "num_expert_group",
        "topk_group", "num_shared_experts", "num_nextn_predict_layers")}
    built = {"hidden_act": "silu", "tie_word_embeddings": False,
             "mla_use_nope": True, "q_lora_rank": None,
             "moe_router_activation_func": "sigmoid", "moe_layer_freq": 1,
             # no group-limited routing: every expert is in the one group
             "num_expert_group": 1, "topk_group": 1,
             "num_shared_experts": 1, "num_nextn_predict_layers": 0}
    if stated != built:
        raise ValueError(
            f"models.kimi_linear builds {built}; {config['name']} states "
            f"{stated}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention makes a key/value head a "
                         "query head")
    place = config["deployment"]
    if place["first_expert"] != place["expert_shard"] * config["num_experts"]:
        raise ValueError("first_expert is not the expert shard's first")
    mixers, ffns = layer_kinds(config)
    linear = config["linear_attn_config"]
    return KimiLinearConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        mixers=mixers, ffns=ffns,
        kda_heads=linear["num_heads"],
        kda_head_dim=linear["head_dim"],
        conv_width=linear["short_conv_kernel_size"],
        chunk_size=config["chunk_size"],
        mla_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        shared_width=(config["moe_intermediate_size"]
                      * config["num_shared_experts"]),
        num_experts=config["published"]["num_experts"],
        experts_held=config["num_experts"],
        first_expert=place["first_expert"],
        top_k=config["num_experts_per_token"],
        renormalise=config["moe_renormalize"],
        routed_scaling_factor=config["routed_scaling_factor"],
        rms_norm_eps=config["rms_norm_eps"],
        compute_dtype=config["compute_dtype"])


def parameters(config: Dict[str, Any]) -> int:
    """The parameters the chip holds, reckoned from the file: what
    ``parameters`` in it has to state."""
    d = config["hidden_size"]
    linear = config["linear_attn_config"]
    heads, hd = linear["num_heads"], linear["head_dim"]
    inner = heads * hd
    mla_heads = config["num_attention_heads"]
    key = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    rank = config["kv_lora_rank"]
    router = config["published"]["num_experts"]
    f = config["moe_intermediate_size"]
    a_part = {
        "kda": (4 * d * inner                        # q, k, v, o
                + 2 * (d * hd + hd * inner)          # the two low-rank pairs
                + d * heads                          # beta
                + 3 * inner * linear["short_conv_kernel_size"]
                + heads + inner + hd),               # A_log, dt_bias, norm
        "mla": (d * mla_heads * key
                + d * (rank + config["qk_rope_head_dim"]) + rank
                + rank * mla_heads * (config["qk_nope_head_dim"]
                                      + config["v_head_dim"])
                + mla_heads * config["v_head_dim"] * d),
        "dense": 3 * d * config["intermediate_size"],
        "experts": (d * router + router
                    + (config["num_experts"]
                       + config["num_shared_experts"]) * 3 * d * f),
    }
    mixers, ffns = layer_kinds(config)
    return (sum(a_part[mixer] + a_part[ffn] + 2 * d
                for mixer, ffn in zip(mixers, ffns))
            + 2 * config["vocab_size"] * d + d)


def build(config: Dict[str, Any]) -> Workload:
    import jax.numpy as jnp

    from horovod_tpu.models import kimi_linear

    cfg = model_config(config)
    if parameters(config) != config["parameters"]:
        raise ValueError(
            f"{config['name']} states {config['parameters']} parameters; "
            f"its sizes make {parameters(config)}")
    expert_layers = cfg.ffns.count("experts")

    def init(key, rows):
        del rows
        return kimi_linear.init_params(key, cfg), {
            "moe_rows_per_expert": jnp.zeros(
                (expert_layers, cfg.experts_held), jnp.int32)}

    def loss_fn(params, model_state, batch):
        del model_state   # the routing's counts of the step before
        return kimi_linear.next_token_loss(params, batch, cfg)

    return Workload(
        init=init, loss_fn=loss_fn,
        make_pool=lambda rng, rows, dtype: packed.make_pool(
            config, rng, rows, dtype),
        sample_unit=config["sample_unit"],
        samples_per_row=config["sequence_length"],
        train_flops_per_sample=flops.train_flops_per_sample(
            config, packed.expected_pairs_per_row(config)),
        # the head's logits start small (hidden of unit scale against
        # columns of 0.02): the softmax is close to uniform over the rows
        # of the vocabulary held
        expected_first_loss=math.log(config["vocab_size"]))
