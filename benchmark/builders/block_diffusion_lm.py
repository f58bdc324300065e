"""A sparse decoder of ``horovod_tpu.models.block_diffusion`` under the
block-diffusion objective: what a configuration's file has to say to
get one built.  The file is the model's published ``config.json`` with
the keys of ``reduced`` counting what this chip holds, the published
counts under ``published`` and the chip's place under ``deployment``.

Returns the same ``Workload`` as every builder; nothing here knows a
cell or a traffic mix, so the sequence length is the configuration's.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark import flops_block_diffusion_lm as flops
from benchmark.builders.image_classifier import Workload


def model_config(config: Dict[str, Any]):
    from horovod_tpu.models.block_diffusion import BlockDiffusionConfig

    if config["hidden_act"] != "silu" or config["tie_word_embeddings"]:
        raise ValueError(
            "models.block_diffusion builds SiLU-gated experts and an "
            f"untied head; {config['name']} states "
            f"{config['hidden_act']!r}, tied={config['tie_word_embeddings']}")
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise ValueError("models.block_diffusion makes every layer sparse")
    return BlockDiffusionConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        expert_width=config["moe_intermediate_size"],
        num_experts=config["published"]["num_experts"],
        experts_held=config["num_experts"],
        first_expert=config["deployment"]["first_expert"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        block_length=config["block_length"],
        compute_dtype=config["compute_dtype"])


def make_pool(config: Dict[str, Any], rng, rows: int, dtype: str):
    """``rows`` sequences and their noise, collated on the host: ids
    ``x`` from the slice of the vocabulary held (never [MASK], its last
    id), one ``t ~ U[mask_t_min, 1]`` a block, ``mask`` where a token is
    replaced (probability ``t``), and the loss's weight ``w = 1/t`` at
    the masked positions, in the type the traffic mix feeds."""
    import jax.numpy as jnp
    import numpy as np

    seq_len, block = config["sequence_length"], config["block_length"]
    x = rng.integers(0, config["vocab_size"] - 1, (rows, seq_len),
                     dtype=np.int32)
    t = rng.uniform(config["mask_t_min"], 1.0, (rows, -(-seq_len // block)))
    t = np.repeat(t, block, axis=1)[:, :seq_len]
    mask = rng.uniform(size=(rows, seq_len)) < t
    w = np.where(mask, 1.0 / t, 0.0).astype(np.float32)
    return {"x": x, "mask": mask.astype(np.int8),
            "w": w.astype(jnp.dtype(dtype))}


def build(config: Dict[str, Any]) -> Workload:
    import jax.numpy as jnp

    from horovod_tpu.models import block_diffusion

    cfg = model_config(config)

    def init(key, rows):
        del rows
        return block_diffusion.init_params(key, cfg), {
            "moe_rows_per_expert": jnp.zeros(
                (cfg.num_layers, cfg.experts_held), jnp.int32)}

    def loss_fn(params, model_state, batch):
        del model_state   # the routing's counts of the step before
        return block_diffusion.block_diffusion_loss(params, batch, cfg)

    return Workload(
        init=init, loss_fn=loss_fn,
        make_pool=lambda rng, rows, dtype: make_pool(
            config, rng, rows, dtype),
        sample_unit=config["sample_unit"],
        samples_per_row=config["sequence_length"],
        train_flops_per_sample=flops.train_flops_per_sample(config),
        # logits start small: the softmax is close to uniform over the
        # rows of the vocabulary held, and E[mask * 1/t] = 1 a position
        expected_first_loss=math.log(config["vocab_size"]))
