"""A decoder whose layers are a mixer and a feed-forward part chosen
apart, the mixer a gated short convolution or a grouped-query attention
with normed queries and keys, the feed-forward part a dense SwiGLU MLP
or sigmoid-routed SwiGLU experts, trained on packed documents: the
``lfm2_moe`` family (LFM2-8B-A1B).

Pure functions over a parameter tree.  ``GatedConvConfig.mixers`` and
``.ffns`` name each layer's two halves; neighbours that agree in both
are stacked and run under one ``lax.scan`` (``layer_groups``), each half
of a layer but an attention mixer recomputed in the backward pass by
itself (``layer``).  What the other
models have is called, not copied: ``layers.causal_conv`` (cut at
document starts), ``layers.rope``, ``attention.causal_document_attention``
(the Pallas kernels of ``ops/flash_attention.py`` where they run, two
key/value heads of 64 to a 128-lane block), ``parallel.moe.
dropless_topk_moe``, ``layers.swiglu`` and ``layers.weighted_next_token_
cross_entropy``.  This module is the stack and the gated convolution.

*The model* (pre-norm, RMSNorm): ``h = E[ids]``; a layer is ``h = h +
mixer(RMSNorm(h))`` and then ``h = h + ffn(RMSNorm(h))``; ``logits =
RMSNorm(h) W_head`` (the family's ``embedding_norm`` after the last
layer; the head untied).

* ``conv``: ``[b; c; x] = W_in u`` (``hidden -> 3 x hidden``, no bias);
  ``y = W_out (c * conv(b * x))``, ``conv`` a causal depthwise
  convolution of ``conv_width`` taps with no bias and no activation
  that reads nothing before its document's start.  ``b * x`` and ``c *
  .`` in the compute type; the taps summed in f32 and rounded once.
* ``attention``: ``q`` (``num_heads``), ``k`` and ``v`` (``num_kv_heads``,
  a query head reading the key/value head of its group) are projections
  of ``head_dim`` a head without bias; ``q`` and ``k`` through an RMSNorm
  over a head's channels (one scale vector for all heads), then rotated
  (rotate-half, ``rope_theta``) by their positions in the row; softmax
  of ``q . k / sqrt(head_dim)`` over the keys at or before the query in
  its own document; ``y = W_o concat_h(p v)``.  Inside one document a
  row's positions and positions that restart at the document's start
  differ by a constant, so the scores, which see only differences of
  positions, are the same up to rounding.
* ``dense``: ``W_down (silu(W_gate u) * W_up u)``.
* ``experts``: ``s = sigmoid(u W_r)`` in f32 over all ``num_experts``;
  the ``top_k`` largest of ``s + b`` are chosen, weighted by ``s``
  without ``b``, divided by their sum plus ``renormalise_eps``, times
  ``routed_scaling_factor``; an expert is SwiGLU; there is no shared
  expert.  ``b`` (``router_bias``, the family's ``expert_bias``) is a
  buffer in the parameter tree whose gradient is exact zeros.  The chip
  computes the terms of the experts it holds (``experts_held`` from
  ``first_expert``); the shares of chips that hold disjoint ranges add
  up to the whole layer.

*Documents* and the loss are ``hybrid_ssm``'s: ``segment`` gives the
document's index at every position; convolution and attention stop at
a document's start, each by comparing ids.

*Departures from the published model*, each the configuration's:
``vocab_size`` may count the rows of the vocabulary held here (ids and
loss over the slice); ``experts_held`` of ``num_experts``; positions
count along the packed row, not from each document's start (the same
scores, above).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import metrics
from ..parallel.moe import dropless_topk_moe
from .attention import causal_document_attention
from .layers import (causal_conv, rms_norm, rope, swiglu,
                     weighted_next_token_cross_entropy)

Params = Dict[str, Any]

MIXERS = ("conv", "attention")
FFNS = ("dense", "experts")


@dataclasses.dataclass(frozen=True)
class GatedConvConfig:
    vocab_size: int              # rows of the vocabulary held
    hidden_size: int
    mixers: Tuple[str, ...]      # "conv" | "attention", a layer each
    ffns: Tuple[str, ...]        # "dense" | "experts", a layer each
    conv_width: int              # taps of the short convolution
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    dense_width: int
    expert_width: int
    num_experts: int             # the router's width
    experts_held: int
    first_expert: int
    top_k: int
    renormalise: bool
    renormalise_eps: float
    routed_scaling_factor: float
    rms_norm_eps: float
    compute_dtype: str = "bfloat16"


def layer_groups(mixers, ffns) -> List[Tuple[str, str, int]]:
    """Runs of neighbours with one mixer and one feed-forward part,
    ``[(mixer, ffn, layers), ...]``: the parameter tree's ``layers``
    holds one stacked entry a run."""
    if len(mixers) != len(ffns):
        raise ValueError("a layer names a mixer and a feed-forward part")
    for mixer, ffn in zip(mixers, ffns):
        if mixer not in MIXERS or ffn not in FFNS:
            raise ValueError(
                f"a layer is one of {MIXERS} and one of {FFNS}, not "
                f"{mixer!r} and {ffn!r}")
    return [(*kind, len(list(run)))
            for kind, run in itertools.groupby(zip(mixers, ffns))]


def init_params(key, cfg: GatedConvConfig) -> Params:
    """Float32 parameters: normal(0.02) matrices, unit norm scales, a
    zero ``router_bias``, and convolution taps ``U[-1/sqrt(K),
    1/sqrt(K)]`` (``hybrid_ssm.init_params`` says why not
    normal(0.02))."""
    d, hd = cfg.hidden_size, cfg.head_dim

    def normal(k, shape):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)

    def conv(ks, n):
        return dict(
            in_proj=normal(ks[0], (n, d, 3 * d)),
            conv_w=jax.random.uniform(
                ks[1], (n, cfg.conv_width, d), jnp.float32,
                -cfg.conv_width ** -0.5, cfg.conv_width ** -0.5),
            out_proj=normal(ks[2], (n, d, d)))

    def attention(ks, n):
        return dict(
            wq=normal(ks[0], (n, d, cfg.num_heads * hd)),
            wk=normal(ks[1], (n, d, cfg.num_kv_heads * hd)),
            wv=normal(ks[2], (n, d, cfg.num_kv_heads * hd)),
            q_norm=jnp.ones((n, hd), jnp.float32),
            k_norm=jnp.ones((n, hd), jnp.float32),
            wo=normal(ks[3], (n, cfg.num_heads * hd, d)))

    def dense(ks, n):
        f = cfg.dense_width
        return dict(mlp_gate=normal(ks[0], (n, d, f)),
                    mlp_up=normal(ks[1], (n, d, f)),
                    mlp_down=normal(ks[2], (n, f, d)))

    def experts(ks, n):
        held, f = cfg.experts_held, cfg.expert_width
        return dict(
            router=normal(ks[0], (n, d, cfg.num_experts)),
            router_bias=jnp.zeros((n, cfg.num_experts), jnp.float32),
            w_gate=normal(ks[1], (n, held, d, f)),
            w_up=normal(ks[2], (n, held, d, f)),
            w_down=normal(ks[3], (n, held, f, d)))

    def group(k, mixer, ffn, n):
        k_mixer, k_ffn = jax.random.split(k)
        p = {"norm1": jnp.ones((n, d), jnp.float32),
             "norm2": jnp.ones((n, d), jnp.float32)}
        p.update((conv if mixer == "conv" else attention)(
            jax.random.split(k_mixer, 4), n))
        p.update((dense if ffn == "dense" else experts)(
            jax.random.split(k_ffn, 4), n))
        return p

    groups = layer_groups(cfg.mixers, cfg.ffns)
    keys = jax.random.split(key, len(groups) + 2)
    return {"embed": normal(keys[0], (cfg.vocab_size, d)),
            "head": normal(keys[1], (d, cfg.vocab_size)),
            "final_norm": jnp.ones((d,), jnp.float32),
            "layers": [group(k, *kind)
                       for k, kind in zip(keys[2:], groups)]}


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------

def short_conv(c, bx, conv_w, segment):
    """``c * conv(bx)``: the taps ``conv_w`` ``[K, C]`` over ``bx`` ``[B,
    T, C]`` summed in f32 and cut at document starts, rounded to ``bx``'s
    type, then gated by ``c``."""
    metrics.note_short_conv("xla", conv_w.shape[0])
    return c * causal_conv(bx, conv_w, 0.0, segment).astype(bx.dtype)


def conv_mixer(cfg: GatedConvConfig, p: Params, u, segment):
    """The mixer on ``u`` ``[B, T, D]`` (already normed)."""
    dtype = u.dtype
    with jax.named_scope("hvtpu:shortconv.proj"):
        b, c, x = jnp.split(u @ p["in_proj"].astype(dtype), 3, axis=-1)
    with jax.named_scope("hvtpu:shortconv.conv"):
        y = short_conv(c, b * x, p["conv_w"], segment)
    with jax.named_scope("hvtpu:shortconv.proj"):
        return y @ p["out_proj"].astype(dtype)


def attention_operands(cfg: GatedConvConfig, p: Params, u, positions):
    """``q`` ``[B, T, H, hd]``, ``k`` and ``v`` ``[B, T, G, hd]`` from
    ``u`` ``[B, T, D]`` (already normed), ``q`` and ``k`` normed a head
    and rotated by ``positions`` ``[T]``."""
    b, t, _ = u.shape
    dtype = u.dtype

    def heads(w, count):
        return (u @ w.astype(dtype)).reshape(b, t, count, cfg.head_dim)

    def normed_and_rotated(w, scale, count):
        return rope(rms_norm(heads(w, count), scale, cfg.rms_norm_eps),
                    positions, cfg.rope_theta)

    with jax.named_scope("hvtpu:attn.proj"):
        return (normed_and_rotated(p["wq"], p["q_norm"], cfg.num_heads),
                normed_and_rotated(p["wk"], p["k_norm"], cfg.num_kv_heads),
                heads(p["wv"], cfg.num_kv_heads))


def attention(cfg: GatedConvConfig, q, k, v, segment):
    return causal_document_attention(q, k, v, segment,
                                     scale=cfg.head_dim ** -0.5)


def attention_mixer(cfg: GatedConvConfig, p: Params, u, segment):
    """The mixer on ``u`` ``[B, T, D]`` (already normed)."""
    b, t, _ = u.shape
    o = attention(cfg, *attention_operands(cfg, p, u, jnp.arange(t)),
                  segment)
    with jax.named_scope("hvtpu:attn.proj"):
        return o.reshape(b, t, -1) @ p["wo"].astype(u.dtype)


_MIXER_SCOPE = {"conv": "hvtpu:shortconv.proj",
                "attention": "hvtpu:attn.proj"}
_MIXER = {"conv": conv_mixer, "attention": attention_mixer}


# ---------------------------------------------------------------------------
# the two feed-forward parts
# ---------------------------------------------------------------------------

def dense_ffn(cfg: GatedConvConfig, p: Params, x):
    with jax.named_scope("hvtpu:mlp"):
        u = rms_norm(x, p["norm2"], cfg.rms_norm_eps)
        return x + swiglu(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"])


def expert_ffn(cfg: GatedConvConfig, p: Params, x):
    """``x`` plus what the experts held here add, and the routing as
    ``dropless_topk_moe`` returns it."""
    b, t, d = x.shape
    with jax.named_scope("hvtpu:moe.route"):
        u = rms_norm(x, p["norm2"], cfg.rms_norm_eps).reshape(b * t, d)
    y, routing = dropless_topk_moe(
        u, p["router"], {name: p[name] for name in (
            "w_gate", "w_up", "w_down")},
        top_k=cfg.top_k, num_experts=cfg.num_experts,
        first_expert=cfg.first_expert, renormalise=cfg.renormalise,
        selection_bias=p["router_bias"], scale=cfg.routed_scaling_factor,
        renormalise_eps=cfg.renormalise_eps)
    with jax.named_scope("hvtpu:moe.combine"):
        return x + y.reshape(b, t, d), routing


def mixer_half(cfg: GatedConvConfig, mixer: str, p: Params, x, segment):
    # the norm before and the residual after are counted with the
    # projections they feed and follow
    with jax.named_scope(_MIXER_SCOPE[mixer]):
        u = rms_norm(x, p["norm1"], cfg.rms_norm_eps)
    y = _MIXER[mixer](cfg, p, u, segment)
    with jax.named_scope(_MIXER_SCOPE[mixer]):
        return x + y


def ffn_half(cfg: GatedConvConfig, ffn: str, p: Params, x):
    """``(x, the rows each held expert got)``, the rows int32
    ``[experts_held]``, or None of a dense part."""
    if ffn == "dense":
        return dense_ffn(cfg, p, x), None
    x, routing = expert_ffn(cfg, p, x)
    return x, routing["rows_per_expert"]


def layer(cfg: GatedConvConfig, mixer: str, ffn: str, p: Params, x,
          segment):
    """One layer, each half recomputed in the backward pass by itself
    (what a half keeps for its gradient is made when the other's is
    gone: a dense MLP's 7,168-wide rows, an expert layer's row buffers,
    a convolution's three projections), but an attention mixer, which
    keeps its few hundred MB through the backward pass rather than run
    its kernels a third time."""
    def mix(x, p):
        return mixer_half(cfg, mixer, p, x, segment)

    x = (mix if mixer == "attention" else jax.checkpoint(mix))(x, p)
    return jax.checkpoint(lambda x, p: ffn_half(cfg, ffn, p, x))(x, p)


def hidden_states(params: Params, ids, cfg: GatedConvConfig,
                  segment: Optional[jax.Array] = None):
    """``ids`` ``[B, T]`` -> the last layer's output ``[B, T, D]`` and
    the rows every held expert got in every expert layer, int32
    ``[expert layers, experts_held]``.  Without ``segment`` a row is one
    document."""
    if segment is None:
        segment = jnp.zeros(ids.shape, jnp.int32)
    x = jnp.take(params["embed"], ids, axis=0).astype(
        jnp.dtype(cfg.compute_dtype))
    rows = []
    for (mixer, ffn, _), stacked in zip(layer_groups(cfg.mixers, cfg.ffns),
                                        params["layers"]):
        x, got = lax.scan(
            lambda x, p, mixer=mixer, ffn=ffn: layer(
                cfg, mixer, ffn, p, x, segment), x, stacked)
        if got is not None:
            rows.append(got)
    return x, (jnp.concatenate(rows) if rows else jnp.zeros(
        (0, cfg.experts_held), jnp.int32))


def logits_of(params: Params, hidden, cfg: GatedConvConfig):
    """f32 logits over the rows of the vocabulary held here (untied)."""
    with jax.named_scope("hvtpu:lm_head"):
        u = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
        return jnp.dot(u, params["head"].astype(u.dtype),
                       preferred_element_type=jnp.float32)


def next_token_loss(params: Params, batch, cfg: GatedConvConfig):
    """``batch`` as ``hybrid_ssm.next_token_loss`` takes it (``x``,
    ``segment``, ``w``).  Returns the loss and the routing's counts
    ``{"moe_rows_per_expert": int32 [expert layers, experts_held]}``."""
    hidden, rows = hidden_states(params, batch["x"], cfg, batch["segment"])
    return (weighted_next_token_cross_entropy(
        logits_of(params, hidden, cfg), batch),
        {"moe_rows_per_expert": rows})
