"""A decoder of ``horovod_tpu.models.gated_conv`` (a mixer and a
feed-forward part a layer, chosen apart: a gated short convolution or a
grouped-query attention with normed queries and keys; a dense SwiGLU MLP
or sigmoid-routed SwiGLU experts) under the causal next-token loss on
packed documents: what a configuration's file has to say to get one
built.  The file is the model's published ``config.json`` (``model_type``
``lfm2_moe``) with the keys of ``reduced`` counting what this chip holds,
the published counts under ``published`` and the chip's place under
``deployment``.

Returns the same ``Workload`` as every builder.  Rows are packed as the
hybrid state-space builder packs them (``hybrid_ssm_lm.make_pool``:
``x``, ``segment``, ``w`` from the configuration's document-length
law); nothing here knows a cell or a traffic mix.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark import flops_lfm2_moe_lm as flops
from benchmark.builders import hybrid_ssm_lm as packed
from benchmark.builders.image_classifier import Workload

# the family's ``route_tokens_to_experts`` divides the kept weights by
# their sum plus this (HF ``Lfm2MoeSparseMoeBlock``); config.json has no
# key for it
RENORMALISE_EPS = 1e-6


def layer_kinds(config: Dict[str, Any]):
    """``(mixers, ffns)``, a name a layer, from ``layer_types`` and the
    number of leading dense layers."""
    kinds = {"conv": "conv", "full_attention": "attention"}
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"] or not set(types) <= set(
            kinds):
        raise ValueError(
            f"{config['name']}: layer_types names {len(types)} layers of "
            f"{sorted(set(types))}, not {config['num_hidden_layers']} of "
            f"{sorted(kinds)}")
    return (tuple(kinds[kind] for kind in types),
            tuple("dense" if i < config["num_dense_layers"] else "experts"
                  for i in range(len(types))))


def model_config(config: Dict[str, Any]):
    from horovod_tpu.models.gated_conv import GatedConvConfig

    stated = {key: config[key] for key in (
        "model_type", "conv_bias", "use_expert_bias", "norm_topk_prob",
        "tie_word_embeddings")}
    built = {"model_type": "lfm2_moe", "conv_bias": False,
             "use_expert_bias": True, "norm_topk_prob": True,
             "tie_word_embeddings": False}
    if stated != built:
        raise ValueError(
            f"models.gated_conv builds {built}; {config['name']} states "
            f"{stated}")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("the query heads are no whole groups of the "
                         "key/value heads")
    place = config["deployment"]
    if place["first_expert"] != place["expert_shard"] * config["num_experts"]:
        raise ValueError("first_expert is not the expert shard's first")
    mixers, ffns = layer_kinds(config)
    return GatedConvConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        mixers=mixers, ffns=ffns,
        conv_width=config["conv_L_cache"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=flops.head_dim(config),
        rope_theta=float(config["rope_theta"]),
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        num_experts=config["published"]["num_experts"],
        experts_held=config["num_experts"],
        first_expert=place["first_expert"],
        top_k=config["num_experts_per_tok"],
        renormalise=config["norm_topk_prob"],
        renormalise_eps=RENORMALISE_EPS,
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=config["norm_eps"],
        compute_dtype=config["compute_dtype"])


def parameters(config: Dict[str, Any]) -> int:
    """The parameters the chip holds, reckoned from the file: what
    ``parameters`` in it has to state."""
    d, hd = config["hidden_size"], flops.head_dim(config)
    heads, groups = (config["num_attention_heads"],
                     config["num_key_value_heads"])
    router = config["published"]["num_experts"]
    a_part = {
        "conv": 4 * d * d + config["conv_L_cache"] * d,   # in, out, taps
        "attention": (2 * d * heads * hd + 2 * d * groups * hd
                      + 2 * hd),                          # q, o, k, v, norms
        "dense": 3 * d * config["intermediate_size"],
        "experts": (d * router + router + config["num_experts"] * 3 * d
                    * config["moe_intermediate_size"]),
    }
    mixers, ffns = layer_kinds(config)
    return (sum(a_part[mixer] + a_part[ffn] + 2 * d
                for mixer, ffn in zip(mixers, ffns))
            + 2 * config["vocab_size"] * d + d)


def build(config: Dict[str, Any]) -> Workload:
    import jax.numpy as jnp

    from horovod_tpu.models import gated_conv

    cfg = model_config(config)
    if parameters(config) != config["parameters"]:
        raise ValueError(
            f"{config['name']} states {config['parameters']} parameters; "
            f"its sizes make {parameters(config)}")
    expert_layers = cfg.ffns.count("experts")

    def init(key, rows):
        del rows
        return gated_conv.init_params(key, cfg), {
            "moe_rows_per_expert": jnp.zeros(
                (expert_layers, cfg.experts_held), jnp.int32)}

    def loss_fn(params, model_state, batch):
        del model_state   # the routing's counts of the step before
        return gated_conv.next_token_loss(params, batch, cfg)

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
