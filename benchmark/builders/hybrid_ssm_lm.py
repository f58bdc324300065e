"""A decoder of ``horovod_tpu.models.hybrid_ssm`` (Mamba-2 mixers and
attention layers by a ``layer_types`` list) under the causal next-token
loss on packed documents: what a configuration's file has to say to get
one built.  The file is the model's published ``config.json`` with the
keys of ``reduced`` counting what this chip holds, the published counts
under ``published`` and the chip's place under ``deployment``.

Returns the same ``Workload`` as every builder; nothing here knows a
cell or a traffic mix, so the sequence length and the law of the
documents' lengths are the configuration's.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark import flops_hybrid_ssm_lm as flops
from benchmark.builders.image_classifier import Workload

# rows of a pool drawn from a seed of its own, over which the pairs an
# attention head is required to score are averaged: the operations a
# token requires are then the configuration's, not a run's
_ROWS_FOR_THE_PAIRS = 512


def model_config(config: Dict[str, Any]):
    from horovod_tpu.models.hybrid_ssm import HybridSSMConfig

    stated = {key: config[key] for key in (
        "hidden_act", "tie_word_embeddings", "position_embedding_type",
        "normalization_function", "num_local_experts", "mamba_n_groups",
        "attention_bias", "mamba_proj_bias", "mamba_conv_bias")}
    built = {"hidden_act": "silu", "tie_word_embeddings": True,
             "position_embedding_type": "nope",
             "normalization_function": "rmsnorm", "num_local_experts": 0,
             "mamba_n_groups": 1, "attention_bias": False,
             "mamba_proj_bias": False, "mamba_conv_bias": True}
    if stated != built:
        raise ValueError(
            f"models.hybrid_ssm builds {built}; {config['name']} states "
            f"{stated}")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("models.hybrid_ssm takes a layer type a layer")
    if config["mamba_expand"] * config["hidden_size"] != (
            config["mamba_n_heads"] * config["mamba_d_head"]):
        raise ValueError("mamba_expand x hidden_size is not heads x d_head")
    return HybridSSMConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        mlp_width=config["shared_intermediate_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        attention_multiplier=config["attention_multiplier"],
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"],
        conv_width=config["mamba_d_conv"],
        chunk_size=config["mamba_chunk_size"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"],
        rms_norm_eps=config["rms_norm_eps"],
        compute_dtype=config["compute_dtype"])


def pack_rows(config: Dict[str, Any], rng, rows: int):
    """``segment`` int32 ``[rows, T]``: every row filled without padding
    by documents whose lengths follow ``config["document_length"]``
    (log-normal by its median and sigma, clipped), the last one cut at
    the row's end."""
    import numpy as np

    law, t = config["document_length"], config["sequence_length"]
    if law["law"] != "lognormal":
        raise ValueError(f"unknown document-length law {law['law']!r}")
    segment = np.empty((rows, t), np.int32)
    for row in segment:
        at = doc = 0
        while at < t:
            length = int(np.clip(
                rng.lognormal(math.log(law["median"]), law["sigma"]),
                law["min"], law["max"]))
            row[at:at + length] = doc
            at, doc = at + length, doc + 1
    return segment


def make_pool(config: Dict[str, Any], rng, rows: int, dtype: str):
    """``rows`` packed rows, collated on the host: ``segment`` from
    ``pack_rows``, ids ``x`` uniform over the slice of the vocabulary
    held, and ``w``, 1 where position ``t``'s next token is of the same
    document and inside the row, else 0, in the type the traffic mix
    feeds."""
    import jax.numpy as jnp
    import numpy as np

    segment = pack_rows(config, rng, rows)
    x = rng.integers(0, config["vocab_size"], segment.shape, dtype=np.int32)
    w = np.zeros(segment.shape, np.float32)
    w[:, :-1] = segment[:, 1:] == segment[:, :-1]
    return {"x": x, "segment": segment, "w": w.astype(jnp.dtype(dtype))}


def expected_pairs_per_row(config: Dict[str, Any]) -> float:
    import numpy as np

    segment = pack_rows(config, np.random.default_rng(0), _ROWS_FOR_THE_PAIRS)
    return flops.visible_pairs(segment) / _ROWS_FOR_THE_PAIRS


def build(config: Dict[str, Any]) -> Workload:
    from horovod_tpu.models import hybrid_ssm

    cfg = model_config(config)

    def init(key, rows):
        del rows
        return hybrid_ssm.init_params(key, cfg), {}

    def loss_fn(params, model_state, batch):
        return hybrid_ssm.next_token_loss(params, batch, cfg), model_state

    return Workload(
        init=init, loss_fn=loss_fn,
        make_pool=lambda rng, rows, dtype: make_pool(
            config, rng, rows, dtype),
        sample_unit=config["sample_unit"],
        samples_per_row=config["sequence_length"],
        train_flops_per_sample=flops.train_flops_per_sample(
            config, expected_pairs_per_row(config)),
        # the tied logits start small (hidden of unit scale against
        # rows of 0.02, over logits_scaling): the softmax is close to
        # uniform over the rows of the vocabulary held
        expected_first_loss=math.log(config["vocab_size"]))
