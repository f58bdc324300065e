"""A decoder of ``horovod_tpu.models.hybrid_moe`` (one mixer a layer by
a pattern of ``M``, ``*`` and ``E``: Mamba-2 with B/C groups, attention
without positions, sparse experts with a shared expert) under the causal
next-token loss on packed documents: what a configuration's file has to
say to get one built.  The file is the model's published ``config.json``
with the keys of ``reduced`` counting what this chip holds, the published
counts under ``published`` and the chip's place under ``deployment``.

Returns the same ``Workload`` as every builder.  Rows are packed as the
hybrid state-space builder packs them (``hybrid_ssm_lm.make_pool``:
``x``, ``segment``, ``w`` from the configuration's document-length
law); nothing here knows a cell or a traffic mix.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark import flops_hybrid_moe_lm as flops
from benchmark.builders import hybrid_ssm_lm as packed
from benchmark.builders.image_classifier import Workload


def model_config(config: Dict[str, Any]):
    from horovod_tpu.models.hybrid_moe import HybridMoEConfig

    stated = {key: config[key] for key in (
        "mamba_hidden_act", "mlp_hidden_act", "tie_word_embeddings",
        "attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias",
        "use_conv_bias", "n_shared_experts", "n_group", "topk_group")}
    built = {"mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
             "tie_word_embeddings": False, "attention_bias": False,
             "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
             "use_conv_bias": True, "n_shared_experts": 1,
             # no group-limited routing: every expert is in the one group
             "n_group": 1, "topk_group": 1}
    if stated != built:
        raise ValueError(
            f"models.hybrid_moe builds {built}; {config['name']} states "
            f"{stated}")
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"]:
        raise ValueError("models.hybrid_moe takes a letter a layer")
    place = config["deployment"]
    if place["first_expert"] != (
            place["expert_shard"] * config["n_routed_experts"]):
        raise ValueError("first_expert is not the expert shard's first")
    return HybridMoEConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        pattern=pattern,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_state=config["ssm_state_size"],
        ssm_groups=config["n_groups"],
        conv_width=config["conv_kernel"],
        chunk_size=config["chunk_size"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["moe_shared_expert_intermediate_size"],
        num_experts=config["published"]["n_routed_experts"],
        experts_held=config["n_routed_experts"],
        first_expert=place["first_expert"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=config["routed_scaling_factor"],
        rms_norm_eps=config["layer_norm_epsilon"],
        time_step_min=config["time_step_min"],
        time_step_max=config["time_step_max"],
        time_step_floor=config["time_step_floor"],
        rescale_depth=(config["published"]["num_hidden_layers"]
                       if config["rescale_prenorm_residual"] else 0),
        compute_dtype=config["compute_dtype"])


def parameters(config: Dict[str, Any]) -> int:
    """The parameters the chip holds, reckoned from the file: what
    ``parameters`` in it has to state."""
    d = config["hidden_size"]
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    conv = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    heads = config["mamba_num_heads"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    router = config["published"]["n_routed_experts"]
    a_layer = {
        "M": (d * (inner + conv + heads) + inner * d
              + conv * config["conv_kernel"] + conv + 3 * heads + inner + d),
        "*": d * q + 2 * d * kv + q * d + d,
        "E": (2 * d * config["moe_shared_expert_intermediate_size"]
              + d * router + router + d
              + config["n_routed_experts"] * 2 * d
              * config["moe_intermediate_size"]),
    }
    return (sum(a_layer[letter]
                for letter in config["hybrid_override_pattern"])
            + 2 * config["vocab_size"] * d + d)


def build(config: Dict[str, Any]) -> Workload:
    import jax.numpy as jnp

    from horovod_tpu.models import hybrid_moe

    cfg = model_config(config)
    if parameters(config) != config["parameters"]:
        raise ValueError(
            f"{config['name']} states {config['parameters']} parameters; "
            f"its sizes make {parameters(config)}")
    expert_layers = cfg.pattern.count("E")

    def init(key, rows):
        del rows
        return hybrid_moe.init_params(key, cfg), {
            "moe_rows_per_expert": jnp.zeros(
                (expert_layers, cfg.experts_held), jnp.int32)}

    def loss_fn(params, model_state, batch):
        del model_state   # the routing's counts of the step before
        return hybrid_moe.next_token_loss(params, batch, cfg)

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
