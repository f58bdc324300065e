"""A decoder whose every layer is one mixer of three kinds, a Mamba-2
mixer with several B/C groups, a grouped-query attention without
positions or a sparse expert layer, trained on packed documents: the
``nemotron_h`` family (Nemotron 3 Nano 30B-A3B).

Pure functions over a parameter tree.  ``HybridMoEConfig.pattern`` has a
letter a layer, ``M``, ``*`` or ``E`` as the family's
``hybrid_override_pattern`` writes them; neighbours of one kind are
stacked and run under one ``lax.scan`` (``layer_groups``), every layer
recomputed in the backward pass.  The mechanisms are the other models':
``hybrid_ssm.mamba_mixer`` (the chunked scan, the convolution cut at
document starts, the group-wise gated norm),
``hybrid_ssm.causal_document_attention`` (the Pallas kernels of
``ops/flash_attention.py`` where they run) and
``parallel.moe.dropless_topk_moe``; this module is the stack, the
shared expert, the plain embedding and the untied head.

*The model* (``config.json`` of ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-
BF16``, ``model_type`` ``nemotron_h``): ``h = E[ids]``, not scaled; a
layer is ``h = h + mixer(RMSNorm(h))``, one norm, one mixer and the
residual add, nothing else; ``logits = RMSNorm(h) W_head`` (untied).

* ``M``: ``hybrid_ssm.mamba_mixer`` with ``ssm_groups`` B/C groups (head
  ``h`` reads group ``h // (heads / groups)``), the gated norm over each
  group's ``inner / groups`` channels, ``inner = heads x head width``
  (which need not be ``expand x hidden``).
* ``*``: q, k, v, o without bias, **no positions** (the family's
  attention layers apply no rotary embedding: order is the Mamba
  layers'), scores times ``head_dim ** -0.5``, causal inside a document.
* ``E``: ``s = sigmoid(u W_r)`` in f32 over all ``num_experts``; the
  ``top_k`` largest of ``s + b`` are chosen, weighted by ``s`` without
  ``b``, divided by their sum, times ``routed_scaling_factor``; expert
  ``i`` is ``W_down,i relu(W_up,i u) ** 2``; beside them every token goes
  through a shared expert of the same form, ``shared_width`` wide:
  ``out = sum_i w_i E_i(u) + S(u)``.  ``b`` (``router_bias``, the
  family's ``e_score_correction_bias``) is a buffer that lies in the
  parameter tree and gets a gradient of exact zeros: nothing here
  balances the load with it.  The chip computes the terms of the experts
  it holds (``experts_held`` from ``first_expert``) and ``S(u)``; what
  the experts held elsewhere would add is left out, and the shares of
  chips that hold disjoint ranges add up to the whole layer with ``S``
  counted once.

*Documents* and the loss are ``hybrid_ssm``'s: ``segment`` gives the
document's index at every position; state, convolution and attention
stop at a document's start; the loss is the weighted next-token
cross-entropy.

*Departures from the published model*, each the configuration's:
``vocab_size`` may count the rows of the vocabulary held here (ids and
loss over the slice); ``experts_held`` of ``num_experts``; no group-limited
routing (``n_group = topk_group = 1``); ``delta`` is not clamped.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.moe import dropless_topk_moe
from . import hybrid_ssm
from .block_diffusion import rms_norm

Params = Dict[str, Any]

KINDS = "M*E"      # a Mamba-2 mixer, an attention layer, an expert layer


@dataclasses.dataclass(frozen=True)
class HybridMoEConfig:
    vocab_size: int              # rows of the vocabulary held
    hidden_size: int
    pattern: str                 # "M" | "*" | "E", a layer each
    num_heads: int               # attention: query heads
    num_kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    conv_width: int
    chunk_size: int
    expert_width: int
    shared_width: int
    num_experts: int             # the router's width
    experts_held: int
    first_expert: int
    top_k: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rms_norm_eps: float
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    # the depth a residual branch's last matrix is made smaller by at the
    # start (1 / sqrt of it: ``rescale_prenorm_residual``); 0 for none
    rescale_depth: int = 0
    compute_dtype: str = "bfloat16"

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:      # x, and B and C of every group
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state


def layer_groups(pattern: str) -> List[Tuple[str, int]]:
    """Runs of neighbours of one kind, ``[(letter, layers), ...]``: the
    parameter tree's ``layers`` holds one stacked entry a run."""
    for letter in pattern:
        if letter not in KINDS:
            raise ValueError(
                f"a layer is one of {list(KINDS)}, not {letter!r}")
    return [(letter, len(list(run)))
            for letter, run in itertools.groupby(pattern)]


def init_params(key, cfg: HybridMoEConfig) -> Params:
    """Float32 parameters: normal(0.02) matrices, a residual branch's
    last matrix (``out_proj``, ``wo``, the experts' and the shared
    expert's ``w_down``) divided by ``sqrt(rescale_depth)``, unit norm
    scales, a zero ``router_bias``, and Mamba-2's own start for the
    mixer: ``A_log = log U[1, 16]``, ``dt_bias`` the inverse softplus of
    ``delta ~ logU[time_step_min, time_step_max]`` held above
    ``time_step_floor``, ``D = 1``, convolution taps ``U[-1/sqrt(K),
    1/sqrt(K)]`` with a zero bias (``hybrid_ssm.init_params`` says why
    not normal(0.02))."""
    d = cfg.hidden_size
    n_q, n_kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    out_scale = cfg.rescale_depth ** -0.5 if cfg.rescale_depth else 1.0

    def normal(k, shape, scale=1.0):
        return 0.02 * scale * jax.random.normal(k, shape, jnp.float32)

    def group(k, letter, n):
        ks = jax.random.split(k, 6)
        p = {"norm": jnp.ones((n, d), jnp.float32)}
        if letter == "*":
            p.update(wq=normal(ks[0], (n, d, n_q)),
                     wk=normal(ks[1], (n, d, n_kv)),
                     wv=normal(ks[2], (n, d, n_kv)),
                     wo=normal(ks[3], (n, n_q, d), out_scale))
        elif letter == "E":
            p.update(
                router=normal(ks[0], (n, d, cfg.num_experts)),
                router_bias=jnp.zeros((n, cfg.num_experts), jnp.float32),
                w_up=normal(ks[1], (n, cfg.experts_held, d,
                                    cfg.expert_width)),
                w_down=normal(ks[2], (n, cfg.experts_held, cfg.expert_width,
                                      d), out_scale),
                shared_up=normal(ks[3], (n, d, cfg.shared_width)),
                shared_down=normal(ks[4], (n, cfg.shared_width, d),
                                   out_scale))
        else:
            p.update(hybrid_ssm.mixer_start(
                cfg, n, in_proj=ks[1], conv_w=ks[2], out_proj=ks[4],
                delta=ks[0], a_log=ks[3], out_scale=out_scale,
                steps=(cfg.time_step_min, cfg.time_step_max,
                       cfg.time_step_floor)))
        return p

    groups = layer_groups(cfg.pattern)
    keys = jax.random.split(key, len(groups) + 2)
    return {"embed": normal(keys[0], (cfg.vocab_size, d)),
            "head": normal(keys[1], (d, cfg.vocab_size)),
            "final_norm": jnp.ones((d,), jnp.float32),
            "layers": [group(k, letter, n)
                       for k, (letter, n) in zip(keys[2:], groups)]}


# ---------------------------------------------------------------------------
# the three kinds of layer
# ---------------------------------------------------------------------------

def mamba_layer(cfg: HybridMoEConfig, p: Params, x, segment):
    # the norm before and the residual after are counted with the
    # projections they feed and follow, as in ``hybrid_ssm.mamba_layer``
    with jax.named_scope("hvtpu:ssm.proj"):
        u = rms_norm(x, p["norm"], cfg.rms_norm_eps)
    y = hybrid_ssm.mamba_mixer(cfg, p, u, segment)
    with jax.named_scope("hvtpu:ssm.proj"):
        return x + y


def attention_layer(cfg: HybridMoEConfig, p: Params, x, segment):
    b, t, _ = x.shape
    dtype = x.dtype
    with jax.named_scope("hvtpu:attn.proj"):
        u = rms_norm(x, p["norm"], cfg.rms_norm_eps)

        def heads(w, count):
            return (u @ w.astype(dtype)).reshape(b, t, count, cfg.head_dim)

        q, k, v = (heads(p["wq"], cfg.num_heads),
                   heads(p["wk"], cfg.num_kv_heads),
                   heads(p["wv"], cfg.num_kv_heads))
    o = hybrid_ssm.causal_document_attention(
        q, k, v, segment, scale=cfg.head_dim ** -0.5,
        tile=hybrid_ssm._ATTENTION_TILE)
    with jax.named_scope("hvtpu:attn.proj"):
        return x + o.reshape(b, t, -1) @ p["wo"].astype(dtype)


def relu2_expert(u, w_up, w_down):
    """``W_down relu(W_up u) ** 2``: the family's ungated expert."""
    dtype = u.dtype
    r = jax.nn.relu(jnp.dot(u, w_up.astype(dtype),
                            preferred_element_type=jnp.float32))
    return jnp.dot((r * r).astype(dtype), w_down.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)


def expert_layer(cfg: HybridMoEConfig, p: Params, x):
    """``x`` plus what the experts held here and the shared expert add,
    and the routing as ``dropless_topk_moe`` returns it (the rows each
    held expert got, every token's choice)."""
    b, t, d = x.shape
    with jax.named_scope("hvtpu:moe.route"):
        u = rms_norm(x, p["norm"], cfg.rms_norm_eps).reshape(b * t, d)
    y, routing = dropless_topk_moe(
        u, p["router"], {"w_up": p["w_up"], "w_down": p["w_down"]},
        top_k=cfg.top_k, num_experts=cfg.num_experts,
        first_expert=cfg.first_expert, renormalise=cfg.norm_topk_prob,
        selection_bias=p["router_bias"], scale=cfg.routed_scaling_factor)
    with jax.named_scope("hvtpu:moe.shared"):
        shared = relu2_expert(u, p["shared_up"], p["shared_down"])
    with jax.named_scope("hvtpu:moe.combine"):
        return x + (y + shared).reshape(b, t, d), routing


def hidden_states(params: Params, ids, cfg: HybridMoEConfig,
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
    for (letter, _), stacked in zip(layer_groups(cfg.pattern),
                                    params["layers"]):
        if letter == "E":
            x, routing = lax.scan(jax.checkpoint(
                lambda x, p: expert_layer(cfg, p, x)), x, stacked)
            rows.append(routing["rows_per_expert"])
            continue
        layer = mamba_layer if letter == "M" else attention_layer
        x, _ = lax.scan(jax.checkpoint(
            lambda x, p, layer=layer: (layer(cfg, p, x, segment), None)),
            x, stacked)
    return x, (jnp.concatenate(rows) if rows else jnp.zeros(
        (0, cfg.experts_held), jnp.int32))


def logits_of(params: Params, hidden, cfg: HybridMoEConfig):
    """f32 logits over the rows of the vocabulary held here (untied)."""
    with jax.named_scope("hvtpu:lm_head"):
        u = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
        return jnp.dot(u, params["head"].astype(u.dtype),
                       preferred_element_type=jnp.float32)


def next_token_loss(params: Params, batch, cfg: HybridMoEConfig):
    """``batch`` as ``hybrid_ssm.next_token_loss`` takes it (``x``,
    ``segment``, ``w``).  Returns the loss and the routing's counts
    ``{"moe_rows_per_expert": int32 [expert layers, experts_held]}``."""
    hidden, rows = hidden_states(params, batch["x"], cfg, batch["segment"])
    return (hybrid_ssm.weighted_next_token_cross_entropy(
        logits_of(params, hidden, cfg), batch),
        {"moe_rows_per_expert": rows})
