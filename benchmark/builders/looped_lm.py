"""A looped decoder of ``horovod_tpu.models.looped`` (one stack of
layers walked ``total_ut_steps`` times, an exit after every pass) under
the expected loss over its exits on packed documents: what a
configuration's file has to say to get one built.  The file is the
model's published ``config.json`` with the keys of ``reduced`` counting
what this chip holds, the published counts under ``published`` and the
chip's place under ``deployment``.

Returns the same ``Workload`` as every builder.  Rows are packed as the
hybrid state-space builder packs them (``hybrid_ssm_lm.make_pool``:
``x``, ``segment``, ``w`` from the configuration's document-length
law); nothing here knows a cell or a traffic mix.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark import flops_looped_lm as flops
from benchmark.builders import hybrid_ssm_lm as packed
from benchmark.builders.image_classifier import Workload


def model_config(config: Dict[str, Any]):
    from horovod_tpu.models.looped import LoopedConfig

    stated = {key: config[key] for key in (
        "hidden_act", "tie_word_embeddings", "use_sliding_window",
        "rope_scaling")}
    built = {"hidden_act": "silu", "tie_word_embeddings": False,
             "use_sliding_window": False, "rope_scaling": None}
    if stated != built:
        raise ValueError(
            f"models.looped builds {built}; {config['name']} states "
            f"{stated}")
    if config["layer_types"] != (
            ["full_attention"] * config["num_hidden_layers"]):
        raise ValueError("models.looped takes full attention in every layer")
    return LoopedConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        mlp_width=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        total_ut_steps=config["total_ut_steps"],
        entropy_weight=config["exit_entropy_weight"],
        compute_dtype=config["compute_dtype"])


def expected_first_loss(config: Dict[str, Any]) -> float:
    """The head's logits start small, so every exit's cross-entropy is
    near ``ln vocabulary``; the gate's start near zero, so ``lambda`` is
    a half and the exits get 1/2, 1/4, … with the last two equal."""
    exits = config["total_ut_steps"]
    p = [2.0 ** -t for t in range(1, exits)] + [2.0 ** -(exits - 1)]
    entropy = -sum(q * math.log(q) for q in p)
    return math.log(config["vocab_size"]) - (
        config["exit_entropy_weight"] * entropy)


def build(config: Dict[str, Any]) -> Workload:
    import jax.numpy as jnp

    from horovod_tpu.models import looped

    cfg = model_config(config)

    def init(key, rows):
        del rows
        nothing_yet = jnp.zeros((cfg.total_ut_steps,), jnp.float32)
        return looped.init_params(key, cfg), {
            "loop_exit_mass": nothing_yet, "loop_exit_loss": nothing_yet}

    def loss_fn(params, model_state, batch):
        del model_state   # the exits' means of the step before
        return looped.expected_exit_loss(params, batch, cfg)

    return Workload(
        init=init, loss_fn=loss_fn,
        make_pool=lambda rng, rows, dtype: packed.make_pool(
            config, rng, rows, dtype),
        sample_unit=config["sample_unit"],
        samples_per_row=config["sequence_length"],
        train_flops_per_sample=flops.train_flops_per_sample(
            config, packed.expected_pairs_per_row(config)),
        expected_first_loss=expected_first_loss(config))
