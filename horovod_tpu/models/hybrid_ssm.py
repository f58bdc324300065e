"""A decoder whose layers are of two kinds, Mamba-2 mixers and grouped-
query attention without positions, trained on packed documents: the
``granitemoehybrid`` family without its experts (Granite 4.0-H Micro).

Pure functions over a parameter tree.  The layers follow
``HybridSSMConfig.layer_types``; neighbours of one kind are stacked and
run under one ``lax.scan`` (``layer_groups``), every layer recomputed
in the backward pass.

*The model* (``config.json`` of ``ibm-granite/granite-4.0-h-micro`` and
the ``granitemoehybrid`` modelling code it names): ``h = embedding_
multiplier * E[ids]``; a layer is ``h = h + residual_multiplier *
mixer(RMSNorm(h))`` and then ``h = h + residual_multiplier *
mlp(RMSNorm(h))`` with ``mlp(u) = W_out (silu(g) * v)``, ``[g, v] =
W_in u``; ``logits = RMSNorm(h) E^T / logits_scaling`` (tied).

*The Mamba-2 mixer*: ``[z, xBC, dt] = W_in u``; ``xBC = silu(conv(xBC)
+ b)``, a causal depthwise convolution over time; ``[x, B, C] = xBC``
(``x`` in heads, ``B`` and ``C`` of the state's size in ``ssm_groups``
groups, head ``h`` reading group ``h // (heads / groups)``: one group
shared by all heads in Granite 4.0-H, eight in the ``nemotron_h`` stack
of ``models/hybrid_moe.py``, which calls this mixer); ``delta =
softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; ``H_t =
exp(delta_t A) H_{t-1} + delta_t x_t (x) B_t``, ``y_t = H_t C_t + D
x_t``; ``y = RMSNorm(y * silu(z))`` over each group's inner channels
(gate first, then norm; with one group over all of them); ``out = W_out
y``.  The recurrence
is computed in chunks (``ssd_scan``, the state-space-duality form):
inside a chunk the masked product ``(L o C B^T)(delta x)`` with ``L_ij
= exp(sum_{j<k<=i} delta_k A)``, the chunk's final state, and ``C H``
from the state carried in; the chunks are walked in time by a
``lax.scan`` that carries the state and is differentiated through.

*The attention layer*: q, k, v, o without bias, no positional encoding,
scores times ``attention_multiplier`` (not ``1/sqrt(head_dim)``),
causal inside a document (``attention.causal_document_attention``: on
a TPU the Pallas kernels of ``ops/flash_attention.py``, handed the
document ids; elsewhere XLA, a tile of queries at a time).

*Documents.*  A row is several documents back to back; ``segment``
gives the document's index at every position.  At a document's first
token the state starts from zero, the convolution's taps that reach
before the document's start read zeros, and a query sees only keys of
its own document.  Every such rule compares segment ids and multiplies
by 0 or 1: no decay of ``exp(-inf)``, no infinity is subtracted.
The loss is next-token cross-entropy with the batch's weight ``w`` (0
where the next token belongs to another document or lies past the
row's end), mean over the weighted positions.

*Departures from the published model*, each the configuration's:
``vocab_size`` may count the rows of the vocabulary held here (ids and
loss over the slice); ``time_step_limit`` is (0, inf), so ``delta`` is
not clamped; no experts (``num_local_experts`` 0: the shared MLP is the
only feed-forward part); no dropout, no bias but the convolution's.

*Heads are not divided* among chips here, unlike
``models.block_diffusion``: with one group the gated norm runs over all
inner channels and ``B`` and ``C`` serve every head, so a chip's share
of the heads is not a part of a sum that can be left out.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import metrics
from .attention import causal_document_attention
from .layers import causal_conv, rms_norm, weighted_next_token_cross_entropy

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig:
    vocab_size: int              # rows of the vocabulary held
    hidden_size: int
    layer_types: Tuple[str, ...]   # "mamba" | "attention", a layer each
    mlp_width: int
    num_heads: int               # attention: query heads
    num_kv_heads: int
    head_dim: int
    attention_multiplier: float
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    conv_width: int
    chunk_size: int
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    rms_norm_eps: float
    compute_dtype: str = "bfloat16"
    ssm_groups: int = 1          # B/C groups: head h reads group h // (H / G)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:      # x, and B and C of every group
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state


def layer_groups(layer_types) -> List[Tuple[str, int]]:
    """Runs of neighbours of one kind, ``[(kind, layers), ...]``: the
    parameter tree's ``layers`` holds one stacked entry a run."""
    for kind in layer_types:
        if kind not in ("mamba", "attention"):
            raise ValueError(f"a layer is 'mamba' or 'attention', not "
                             f"{kind!r}")
    return [(kind, len(list(run)))
            for kind, run in itertools.groupby(layer_types)]


def mixer_start(cfg, n: int, *, in_proj, conv_w, out_proj, delta, a_log,
                out_scale: float = 1.0, steps=(1e-3, 1e-1, 0.0)) -> Params:
    """``n`` stacked Mamba-2 mixers' float32 parameters from a key each
    (the keywords): normal(0.02) projections, the one back to the hidden
    size times ``out_scale``; and Mamba-2's own start for what a
    ``config.json`` has no key for: convolution taps ``U[-1/sqrt(K),
    1/sqrt(K)]`` and a zero bias, ``A_log = log U[1, 16]``, ``dt_bias``
    the inverse softplus of ``delta ~ logU[steps[0], steps[1]]`` held
    above ``steps[2]``, ``D = 1``, a unit gate norm.  ``cfg`` brings the
    mixer's sizes (``HybridSSMConfig``, or ``hybrid_moe``'s)."""
    d, inner, h = cfg.hidden_size, cfg.ssm_inner, cfg.ssm_heads
    least, most, floor = steps

    def normal(k, shape, scale=1.0):
        return 0.02 * scale * jax.random.normal(k, shape, jnp.float32)

    step = jnp.maximum(floor, jnp.exp(jax.random.uniform(
        delta, (n, h), jnp.float32, jnp.log(least), jnp.log(most))))
    return dict(
        in_proj=normal(in_proj, (n, d, inner + cfg.conv_channels + h)),
        conv_w=jax.random.uniform(
            conv_w, (n, cfg.conv_width, cfg.conv_channels), jnp.float32,
            -cfg.conv_width ** -0.5, cfg.conv_width ** -0.5),
        conv_b=jnp.zeros((n, cfg.conv_channels), jnp.float32),
        dt_bias=step + jnp.log(-jnp.expm1(-step)),
        A_log=jnp.log(jax.random.uniform(
            a_log, (n, h), jnp.float32, 1.0, 16.0)),
        D=jnp.ones((n, h), jnp.float32),
        gate_norm=jnp.ones((n, inner), jnp.float32),
        out_proj=normal(out_proj, (n, inner, d), out_scale))


def init_params(key, cfg: HybridSSMConfig) -> Params:
    """Float32 parameters: normal(0.02) matrices, unit norm scales, and
    Mamba-2's own start for what ``config.json`` has no key for:
    convolution taps ``U[-1/sqrt(K), 1/sqrt(K)]`` and a zero bias,
    ``A_log = log U[1, 16]``, ``dt_bias`` the inverse softplus of
    ``delta ~ logU[1e-3, 1e-1]``, ``D = 1``.  (Taps of normal(0.02)
    would leave ``x``, ``B`` and ``C`` so small that the recurrence
    adds a thousandth of what ``D x`` does, and nothing downstream
    could tell a wrong scan from a right one.)"""
    d, f = cfg.hidden_size, cfg.mlp_width
    n_q, n_kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim

    def normal(k, shape):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)

    def group(k, kind, n):
        ks = jax.random.split(k, 8)
        p = {"norm1": jnp.ones((n, d), jnp.float32),
             "norm2": jnp.ones((n, d), jnp.float32),
             "mlp_in": normal(ks[0], (n, d, 2 * f)),
             "mlp_out": normal(ks[1], (n, f, d))}
        if kind == "attention":
            p.update(wq=normal(ks[2], (n, d, n_q)),
                     wk=normal(ks[3], (n, d, n_kv)),
                     wv=normal(ks[4], (n, d, n_kv)),
                     wo=normal(ks[5], (n, n_q, d)))
            return p
        p.update(mixer_start(cfg, n, in_proj=ks[2], conv_w=ks[3],
                             out_proj=ks[4], delta=ks[6], a_log=ks[7]))
        return p

    groups = layer_groups(cfg.layer_types)
    keys = jax.random.split(key, len(groups) + 1)
    return {"embed": normal(keys[0], (cfg.vocab_size, d)),
            "final_norm": jnp.ones((d,), jnp.float32),
            "layers": [group(k, kind, n)
                       for k, (kind, n) in zip(keys[1:], groups)]}


# ---------------------------------------------------------------------------
# the Mamba-2 mixer
# ---------------------------------------------------------------------------

def _chunk(carry, inputs, a_head, d_skip, causal):
    """One chunk of ``ssd_scan`` for every row: ``carry`` is the state
    at the end of the chunk before, f32 ``[B, H, P, N]``, and the
    document its last position belonged to; ``causal`` bool ``[Q, Q]``,
    true where ``j <= i``.  ``b_in`` and ``c_out`` come in groups, ``[B,
    Q, G, N]``, and the heads are taken as ``[G, H / G]`` (``gr``)
    wherever they meet one: a reshape that moves nothing.  With one
    group they come as ``[B, Q, N]`` and there is no such axis at all:
    the products lose their ``g`` and the program is, to the last
    instruction, that of a scan that knows no groups."""
    state, seg_before = carry
    x, dt, b_in, c_out, seg = inputs       # [B, Q, ...]
    dtype = x.dtype
    groups = 1 if b_in.ndim == 3 else b_in.shape[2]

    def by_group(a, axis):      # [..., H, ...] -> [..., G, H / G, ...]
        return a if groups == 1 else a.reshape(
            *a.shape[:axis], groups, -1, *a.shape[axis + 1:])

    def product(spec, *operands):
        return jnp.einsum(spec.replace("g", "") if groups == 1 else spec,
                          *operands, preferred_element_type=jnp.float32)

    # cs_i: the log of the decay from the chunk's start through i
    cs = jnp.cumsum(dt * a_head, axis=1)                     # [B, Q, H]
    cs_h = cs.transpose(0, 2, 1)                             # [B, H, Q]
    seen = ((seg[:, :, None] == seg[:, None, :]) & causal)[:, None]
    log_l = jnp.where(seen, cs_h[..., :, None] - cs_h[..., None, :], 0.0)
    decay = jnp.where(seen, jnp.exp(log_l), 0.0)             # L [B, H, i, j]
    cb = product("bign,bjgn->bgij", c_out, b_in)
    dtx = x.astype(jnp.float32) * dt[..., None]              # delta x
    y = product(
        "bgrij,bjgrp->bigrp",
        (by_group(decay, 1) * jnp.expand_dims(cb, -3)).astype(dtype),
        by_group(dtx.astype(dtype), 2)).reshape(x.shape)
    # what the state carried in adds, unless a document started since
    into = jnp.exp(cs) * (seg == seg_before[:, None])[..., None]
    y = y + into[..., None] * product(
        "bign,bgrpn->bigrp", c_out, by_group(state.astype(dtype), 1)
    ).reshape(x.shape)
    # the chunk's own final state, and what is left of the one carried
    last, seg_last = cs[:, -1:], seg[:, -1]
    to_end = jnp.exp(last - cs) * (seg == seg_last[:, None])[..., None]
    own = product(
        "bjgrp,bjgn->bgrpn",
        by_group((dtx * to_end[..., None]).astype(dtype), 2), b_in
    ).reshape(state.shape)
    keep = jnp.exp(last[:, 0]) * (seg_last == seg_before)[:, None]
    state = state * keep[..., None, None] + own
    y = y + d_skip[:, None] * x.astype(jnp.float32)
    return (state, seg_last), y.astype(dtype)


def ssd_scan(x, dt, a_head, b_in, c_out, d_skip, segment, chunk: int):
    """``y_t = C_t . H_t + D x_t`` of ``H_t = exp(dt_t A) H_{t-1} + dt_t
    x_t (x) B_t``, the state zero at every document's first position.

    ``x`` ``[B, T, H, P]``, ``dt`` f32 ``[B, T, H]`` (positive), ``a_head``
    f32 ``[H]`` (negative), ``b_in`` and ``c_out`` ``[B, T, G, N]`` in
    ``G`` groups, head ``h`` reading group ``h // (H / G)`` (or ``[B, T,
    N]``: one group), ``d_skip`` f32 ``[H]``, ``segment`` int ``[B, T]``.
    Products take
    ``x``'s type and add up in f32; decays, cumulative sums and the
    carried state are f32.  The chunks of a row are walked in time, all
    rows at once, each chunk recomputed in the backward pass, so that
    the ``[H, chunk, chunk]`` decays exist for one chunk a row at a
    time."""
    b, t = x.shape[:2]
    if b_in.ndim == 4 and b_in.shape[2] == 1:    # one group: no axis for it
        b_in, c_out = b_in[:, :, 0], c_out[:, :, 0]
    groups = 1 if b_in.ndim == 3 else b_in.shape[2]
    if x.shape[2] % groups:
        raise ValueError(f"{x.shape[2]} heads are no whole number a group "
                         f"of {groups}")
    metrics.note_ssm_groups(groups)
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:
        # a tail of its own document with dt 0: adds nothing, decays nothing
        x, dt, b_in, c_out = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, b_in, c_out))
        segment = jnp.pad(segment, ((0, 0), (0, pad)), constant_values=-1)
    n = (t + pad) // chunk
    metrics.note_ssm_chunks(b * n)

    def chunks(a):      # [B, n * Q, ...] -> [n, B, Q, ...]
        return jnp.moveaxis(a.reshape(b, n, chunk, *a.shape[2:]), 1, 0)

    start = (jnp.zeros((b, *x.shape[2:], b_in.shape[-1]), jnp.float32),
             jnp.full((b,), -2, segment.dtype))
    # made here and not in the chunk: what a loop's body computes from
    # constants alone is moved out of the loop, and loses its scope
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    _, y = lax.scan(
        jax.checkpoint(lambda carry, inputs: _chunk(
            carry, inputs, a_head, d_skip, causal)),
        start, tuple(chunks(a) for a in (x, dt, b_in, c_out, segment)))
    return jnp.moveaxis(y, 0, 1).reshape(b, t + pad, *x.shape[2:])[:, :t]


def mamba_mixer(cfg: HybridSSMConfig, p: Params, u, segment):
    """The mixer on ``u`` ``[B, T, D]`` (already normed)."""
    b, t, _ = u.shape
    dtype, inner, groups = u.dtype, cfg.ssm_inner, cfg.ssm_groups
    n = groups * cfg.ssm_state
    with jax.named_scope("hvtpu:ssm.proj"):
        wide = inner + cfg.conv_channels
        z_xbc = u @ p["in_proj"][:, :wide].astype(dtype)
        z, xbc = z_xbc[..., :inner], z_xbc[..., inner:]
        dt = jnp.dot(u, p["in_proj"][:, wide:].astype(dtype),
                     preferred_element_type=jnp.float32)
    with jax.named_scope("hvtpu:ssm.conv"):
        xbc = jax.nn.silu(causal_conv(
            xbc, p["conv_w"], p["conv_b"], segment)).astype(dtype)
    with jax.named_scope("hvtpu:ssm.scan"):
        x = xbc[..., :inner].reshape(b, t, cfg.ssm_heads, cfg.ssm_head_dim)
        y = ssd_scan(
            x, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
            xbc[..., inner:inner + n].reshape(b, t, groups, -1),
            xbc[..., inner + n:].reshape(b, t, groups, -1), p["D"],
            segment, cfg.chunk_size).reshape(b, t, inner)
        # the gate's gradient comes back in the scan's type: without the
        # barrier the compiler regrouped it into heads in f32 first, a
        # relayout of 64-wide rows at twice the bytes (PERF.md, PR 32)
        y = lax.optimization_barrier(y)
    with jax.named_scope("hvtpu:ssm.gate"):
        # every group's inner / G channels by their own mean square
        y = rms_norm(
            (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
             ).reshape(b, t, groups, -1),
            p["gate_norm"].reshape(groups, -1), cfg.rms_norm_eps
        ).reshape(b, t, inner).astype(dtype)
    with jax.named_scope("hvtpu:ssm.proj"):
        return y @ p["out_proj"].astype(dtype)


# ---------------------------------------------------------------------------
# the attention layer
# ---------------------------------------------------------------------------

def attention_mixer(cfg: HybridSSMConfig, p: Params, u, segment):
    b, t, _ = u.shape
    dtype = u.dtype

    def heads(w, count):
        return (u @ w.astype(dtype)).reshape(b, t, count, cfg.head_dim)

    o = causal_document_attention(
        heads(p["wq"], cfg.num_heads), heads(p["wk"], cfg.num_kv_heads),
        heads(p["wv"], cfg.num_kv_heads), segment,
        scale=cfg.attention_multiplier)
    return o.reshape(b, t, -1) @ p["wo"].astype(dtype)


# ---------------------------------------------------------------------------
# the decoder and its loss
# ---------------------------------------------------------------------------

def mlp(cfg: HybridSSMConfig, p: Params, x):
    """``x + residual_multiplier * W_out (silu(g) * v)`` of the normed
    ``x``."""
    with jax.named_scope("hvtpu:mlp"):
        dtype = x.dtype
        u = rms_norm(x, p["norm2"], cfg.rms_norm_eps)
        g, v = jnp.split(u @ p["mlp_in"].astype(dtype), 2, axis=-1)
        return x + cfg.residual_multiplier * (
            (jax.nn.silu(g) * v) @ p["mlp_out"].astype(dtype))


def mamba_layer(cfg: HybridSSMConfig, p: Params, x, segment):
    # the norm before and the residual after are counted with the
    # projections they feed and follow
    with jax.named_scope("hvtpu:ssm.proj"):
        u = rms_norm(x, p["norm1"], cfg.rms_norm_eps)
    y = mamba_mixer(cfg, p, u, segment)
    with jax.named_scope("hvtpu:ssm.proj"):
        x = x + cfg.residual_multiplier * y
    return mlp(cfg, p, x)


def attention_layer(cfg: HybridSSMConfig, p: Params, x, segment):
    u = rms_norm(x, p["norm1"], cfg.rms_norm_eps)
    x = x + cfg.residual_multiplier * attention_mixer(cfg, p, u, segment)
    return mlp(cfg, p, x)


_LAYER = {"mamba": mamba_layer, "attention": attention_layer}


def hidden_states(params: Params, ids, cfg: HybridSSMConfig,
                  segment: Optional[jax.Array] = None):
    """``ids`` ``[B, T]`` -> the last layer's output ``[B, T, D]``.
    Without ``segment`` a row is one document."""
    if segment is None:
        segment = jnp.zeros(ids.shape, jnp.int32)
    x = (cfg.embedding_multiplier * jnp.take(params["embed"], ids, axis=0)
         ).astype(jnp.dtype(cfg.compute_dtype))
    for (kind, _), stacked in zip(layer_groups(cfg.layer_types),
                                  params["layers"]):
        layer = jax.checkpoint(
            lambda x, p, kind=kind: (_LAYER[kind](cfg, p, x, segment), None))
        x, _ = lax.scan(layer, x, stacked)
    return x


def logits_of(params: Params, hidden, cfg: HybridSSMConfig):
    """f32 logits over the rows of the vocabulary held here (tied)."""
    with jax.named_scope("hvtpu:lm_head"):
        u = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
        return lax.dot_general(
            u, params["embed"].astype(u.dtype), (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / cfg.logits_scaling


def next_token_loss(params: Params, batch, cfg: HybridSSMConfig):
    """``batch``: ``x`` int ``[B, T]``, ``segment`` (the document's
    index at every position) and ``w``, the weight of position ``t``'s
    prediction of ``x[t + 1]``: 0 where that token belongs to another
    document or lies past the row's end.  The mean of the weighted
    cross-entropies over the batch's weighted positions."""
    hidden = hidden_states(params, batch["x"], cfg, batch["segment"])
    return weighted_next_token_cross_entropy(
        logits_of(params, hidden, cfg), batch)
