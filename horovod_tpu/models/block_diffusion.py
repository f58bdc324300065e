"""A sparse decoder trained by diffusion over blocks, as one chip's
share of a layer that several chips hold together.

The block is the ``qwen3_moe`` lineage's: RMSNorm, grouped key/value
heads with a learned RMSNorm over every query and key head, rotary
positions (rotate-half), no bias, and in place of the MLP a top-k
mixture of SiLU-gated experts with no shared expert; an untied head.
Pure functions over a parameter tree, the layers stacked under one
``lax.scan`` and recomputed one at a time in the backward pass.

*The share.*  ``BlockDiffusionConfig`` counts what is held here: the
heads (``num_heads`` query heads over ``num_kv_heads`` key/value
heads), the experts (``experts_held`` from ``first_expert`` on, of
``num_experts`` the router scores) and the rows of the vocabulary.
What the absent heads and experts would have added to a layer's output
is left out and the partial result goes on to the next layer; chips
that hold disjoint shares and see the same tokens add up to the whole
layer (``docs/design.md``, "A chip's share of a layer").

*The objective* (block diffusion, as SDAR adapts an autoregressive
checkpoint with): a sequence ``x`` of ``T`` tokens is cut into blocks
of ``block_length``; the batch brings, for every token, whether it is
masked (``mask``) and the weight ``w`` of its loss (``1/t`` of its
block where masked, else 0).  The model runs once on ``xt ++ x``, 2T
positions, both halves at rotary positions ``0..T-1``, under the mask
of ``allowed``: a noised query sees the noised keys of its own block
and the clean keys of earlier blocks; a clean query sees the clean keys
of its own and earlier blocks.  The loss is ``1/T sum_i w_i CE(logits_i,
x_i)`` over the noised half, labels not shifted, mean over sequences.

Attention never forms ``[2T, 2T]``: the tile pairs in which no query
may see any key are left out when the program is traced
(``attention.tiled_attention``, the mask ``attention.allowed``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.moe import dropless_topk_moe
from .attention import tiled_attention
from .layers import rms_norm, rope

Params = Dict[str, Any]

# queries of a tile where the attention runs in XLA: 512 x up to 8,704
# f32 scores a query head (the cell's 8,192 tokens, block length 4)
_ATTENTION_TILE = 512


@dataclasses.dataclass(frozen=True)
class BlockDiffusionConfig:
    vocab_size: int          # rows of the vocabulary held; the last is [MASK]
    hidden_size: int
    num_layers: int
    num_heads: int           # query heads held
    num_kv_heads: int        # key/value heads held
    head_dim: int
    expert_width: int
    num_experts: int         # the router's outputs
    experts_held: int
    first_expert: int
    top_k: int
    norm_topk_prob: bool
    rope_theta: float
    rms_norm_eps: float
    block_length: int
    compute_dtype: str = "bfloat16"

    @property
    def mask_token_id(self) -> int:
        return self.vocab_size - 1


def init_params(key, cfg: BlockDiffusionConfig) -> Params:
    """Float32 parameters: normal(0.02) matrices, unit norm scales.

    The router starts *balanced over the chips that share the layer*:
    its columns are ``num_experts / experts_held`` copies of one random
    ``[D, experts_held]`` matrix, each with a jitter of a hundredth of
    its scale, so that a token's ``top_k`` begin as the copies of its
    best few columns, one in every chip's range.  A checkpoint trained
    with a balancing loss sends each chip about ``top_k * held / total``
    rows a token; independent random columns do not: the half of the
    noised tokens that are [MASK] share one embedding, take the same
    experts, and the rows a chip's experts get swing between nothing
    and three times the mean from one seed to the next (PERF.md,
    findings of PR 27).
    """
    d, hd, f = cfg.hidden_size, cfg.head_dim, cfg.expert_width
    n_q, n_kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    layers, held = cfg.num_layers, cfg.experts_held
    copies, rest = divmod(cfg.num_experts, held)
    if rest:
        raise ValueError(
            f"{cfg.num_experts} experts do not divide into shares of {held}")
    shapes = {
        "wq": (layers, d, n_q), "wk": (layers, d, n_kv),
        "wv": (layers, d, n_kv), "wo": (layers, n_q, d),
        "router": (layers, d, held),
        "w_gate": (layers, held, d, f), "w_up": (layers, held, d, f),
        "w_down": (layers, held, f, d),
    }
    keys = jax.random.split(key, len(shapes) + 3)

    def normal(k, shape):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)

    stack = {name: normal(k, shape)
             for k, (name, shape) in zip(keys[3:], shapes.items())}
    stack["router"] = jnp.tile(stack["router"], (1, 1, copies)) + (
        0.01 * normal(keys[2], (layers, d, cfg.num_experts)))
    for name, width in (("attn_norm", d), ("moe_norm", d),
                        ("q_norm", hd), ("k_norm", hd)):
        stack[name] = jnp.ones((layers, width), jnp.float32)
    return {"embed": normal(keys[0], (cfg.vocab_size, d)),
            "head": normal(keys[1], (d, cfg.vocab_size)),
            "final_norm": jnp.ones((d,), jnp.float32),
            "layers": stack}


# ---------------------------------------------------------------------------
# the decoder and its loss
# ---------------------------------------------------------------------------

def attention_part(cfg: BlockDiffusionConfig, p: Params, x):
    """What the heads held here add to ``x`` ``[B, 2T, D]``."""
    b, two_t, _ = x.shape
    dtype = x.dtype
    u = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)

    def heads(w, count):
        return (u @ w.astype(dtype)).reshape(b, two_t, count, cfg.head_dim)

    positions = jnp.tile(jnp.arange(two_t // 2), 2)
    q = rope(rms_norm(heads(p["wq"], cfg.num_heads), p["q_norm"],
                      cfg.rms_norm_eps), positions, cfg.rope_theta)
    k = rope(rms_norm(heads(p["wk"], cfg.num_kv_heads), p["k_norm"],
                      cfg.rms_norm_eps), positions, cfg.rope_theta)
    v = heads(p["wv"], cfg.num_kv_heads)
    o = tiled_attention(q, k, v, block_length=cfg.block_length,
                        tile=_ATTENTION_TILE)
    return o.reshape(b, two_t, -1) @ p["wo"].astype(dtype)


def expert_part(cfg: BlockDiffusionConfig, p: Params, x):
    """What the experts held here add to ``x``, and the routing."""
    b, two_t, d = x.shape
    u = rms_norm(x, p["moe_norm"], cfg.rms_norm_eps)
    y, routing = dropless_topk_moe(
        u.reshape(b * two_t, d), p["router"],
        {name: p[name] for name in ("w_gate", "w_up", "w_down")},
        top_k=cfg.top_k, num_experts=cfg.num_experts,
        first_expert=cfg.first_expert, renormalise=cfg.norm_topk_prob)
    return y.reshape(b, two_t, d), routing


def hidden_states(params: Params, ids, cfg: BlockDiffusionConfig):
    """``ids`` ``[B, 2T]`` (``xt ++ x``) -> the last layer's output and
    every layer's routing, stacked: ``rows_per_expert`` ``[layers,
    experts_held]`` and ``experts`` ``[layers, B * 2T, top_k]``."""
    x = jnp.take(params["embed"], ids, axis=0).astype(
        jnp.dtype(cfg.compute_dtype))

    @jax.checkpoint
    def layer(x, p):
        x = x + attention_part(cfg, p, x)
        y, routing = expert_part(cfg, p, x)
        return x + y, routing

    return lax.scan(layer, x, params["layers"])


def logits_of(params: Params, hidden, cfg: BlockDiffusionConfig):
    """f32 logits over the rows of the vocabulary held here."""
    with jax.named_scope("hvtpu:lm_head"):
        u = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
        return jnp.dot(u, params["head"].astype(u.dtype),
                       preferred_element_type=jnp.float32)


def block_diffusion_loss(params: Params, batch, cfg: BlockDiffusionConfig):
    """``batch``: ``x`` int ``[B, T]``, ``mask`` (non-zero where the
    token is replaced by [MASK]) and ``w`` (the loss's weight of the
    position) of the same shape.  Returns the loss and the routing's
    counts ``{"moe_rows_per_expert": int32 [layers, experts_held]}``."""
    x = batch["x"]
    seq_len = x.shape[1]
    noised = jnp.where(batch["mask"] != 0, cfg.mask_token_id, x)
    hidden, routing = hidden_states(
        params, jnp.concatenate([noised, x], axis=1), cfg)
    logits = logits_of(params, hidden[:, :seq_len], cfg)
    with jax.named_scope("hvtpu:lm_head"):
        ce = (jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, x[..., None], axis=-1)[..., 0])
        loss = jnp.mean(
            jnp.sum(batch["w"].astype(jnp.float32) * ce, axis=1) / seq_len)
    return loss, {"moe_rows_per_expert": routing["rows_per_expert"]}
