"""A decoder whose layers are a mixer and a feed-forward part chosen
apart, the mixer a delta-rule linear attention with a decay a channel
(Kimi Delta Attention, KDA) or a latent attention without positions
(MLA), the feed-forward part a dense SwiGLU MLP or sparse experts beside
a shared expert, trained on packed documents: the ``kimi_linear`` family
(Kimi-Linear-48B-A3B; arXiv:2510.26692).

Pure functions over a parameter tree.  ``KimiLinearConfig.mixers`` and
``.ffns`` name each layer's two halves; neighbours that agree in both
are stacked and run under one ``lax.scan`` (``layer_groups``), each
half of a layer recomputed in the backward pass by itself.  What the
other models have is called, not copied: ``layers.causal_conv`` (cut at
document starts), ``attention.causal_document_attention`` (the Pallas
kernels of ``ops/flash_attention.py`` where they run, a key width of
their own), ``parallel.moe.dropless_topk_moe`` and
``layers.weighted_next_token_cross_entropy``.  This module is the
stack, the two mixers, the chunked delta rule and the dense and shared
SwiGLU parts.

*The model* (pre-norm, RMSNorm): ``h = E[ids]``; a layer is ``h = h +
mixer(RMSNorm(h))`` and then ``h = h + ffn(RMSNorm(h))``; ``logits =
RMSNorm(h) W_head`` (untied).

* ``kda``, a head ``h`` of ``kda_heads`` with keys and values of
  ``kda_head_dim``: ``[q~, k~, v~] = silu(conv([W_q, W_k, W_v] x))``,
  a causal depthwise convolution that stops at a document's start; ``q =
  q~ / |q~| / sqrt(d_k)``, ``k = k~ / |k~|``; a log-decay a *channel*
  ``g_t = -exp(A_log_h) softplus(W_f_up W_f_down x_t + dt_bias)``; ``beta_t
  = sigmoid(w_beta,h . x_t)``; the state ``S`` (``d_k x d_v`` a head) is
  decayed a channel at a time and then corrected along the key, ``S_t =
  (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t
  = S_t^T q_t``, ``S = 0`` before a document's first token; ``y = W_o
  [RMSNorm_head(o_t) * sigmoid(W_g_up W_g_down x_t)]``.
* ``mla`` (no rotation anywhere: ``mla_use_nope``): ``[c, k_pe] = W_kva
  x`` (``kv_lora_rank`` + ``qk_rope_head_dim``); ``c = RMSNorm(c)``;
  ``[k_nope,h ; v_h] = W_kvb,h c``; ``k_h = [k_nope,h ; k_pe]`` with
  ``k_pe`` the same for every head; ``q_h = W_q,h x``; softmax of ``q_h .
  k_h / sqrt(key width)`` over the keys at or before the query in its own
  document; ``y = W_o concat_h(p v_h)``.  A head's keys (128 + 64) are
  wider than its values (128).
* ``dense``: ``W_down (silu(W_gate u) * W_up u)``.
* ``experts``: ``s = sigmoid(u W_r)`` in f32 over all ``num_experts``;
  the ``top_k`` largest of ``s + b`` are chosen, weighted by ``s``
  without ``b``, divided by their sum, times ``routed_scaling_factor``;
  the experts and the shared expert beside them are SwiGLU.  ``b``
  (``router_bias``) is a buffer in the parameter tree whose gradient is
  exact zeros.  The chip computes the terms of the experts it holds
  (``experts_held`` from ``first_expert``) and the shared expert; the
  shares of chips that hold disjoint ranges add up to the whole layer
  with the shared expert counted once.

*The delta rule in chunks* (``chunked_delta_rule``).  Inside a chunk of
``C`` positions, with ``G_i`` the cumulative log-decay from the chunk's
start through ``i``, the pseudo-values ``u`` (what position ``i`` writes
after the correction) solve a unit lower triangular system: ``(I + A) U
= beta (V - (exp(G) K) S_0)`` with ``A_ij = beta_i sum_c k_ic k_jc
exp(G_ic - G_jc)``, ``j < i``; then ``O = (exp(G) Q) S_0 + (P o lower)
U`` with ``P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)`` and ``S_C =
Diag(exp G_C) S_0 + (exp(G_C - G) K)^T U``.  **Every decay enters as a
difference** ``exp(G_i - G_j)``, ``i >= j``, never as ``1 / exp(G_j)``:
with ``exp(A_log)`` up to 16 a chunk's ``exp(-G)`` overflows f32.  The
pairs of a chunk are cut into sub-chunks of 16 positions: inside one the
``16 x 16 x d_k`` differences are made a channel at a time and summed;
across two, the decays are split at the later sub-chunk's first position
(``exp(G_i - G_first) exp(G_first - G_j)``, both at most 1) and the sums
are matrix products.  The system is inverted in blocks: the diagonal
blocks of 16 positions by forward substitution, elementwise, then by
doubling, the inverse of ``[[P, 0], [R, Q]]`` being ``[[P^-1, 0], [-Q^-1
R P^-1, Q^-1]]``, up to the chunk: forward substitution in blocks, as
stable, and all products from 16 up.  Cumulative sums, decays, the
system, its inverse and the state carried from chunk to chunk are f32
(products of f32 operands at ``HIGHEST``); the products with the state
take the compute type and add up in f32.  Everything up to ``U_v = (I +
A)^-1 beta V`` and ``W = (I + A)^-1 beta exp(G) K`` is made for all
chunks at once; the chunks are then walked in time by a ``lax.scan`` that
carries the state and is differentiated through, each step recomputed in
the backward pass.  That is the XLA form, which runs on the CPU, with
``HVTPU_PALLAS=0`` and for shapes the kernels do not take; on a TPU the
same arithmetic runs in the two Pallas kernels of ``ops/delta_rule.py``,
a chunk's products, system, inverse and state in VMEM
(``hvtpu_kda_calls_total{path=}`` says which was built in).

*Documents* and the loss are ``hybrid_ssm``'s: ``segment`` gives the
document's index at every position; state, convolution and attention
stop at a document's start, each by comparing ids and multiplying by 0
or 1.

*Departures from the published model*, each the configuration's:
``vocab_size`` may count the rows of the vocabulary held here (ids and
loss over the slice); ``experts_held`` of ``num_experts``; no
group-limited routing (one group).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import metrics
from ..ops import backend, delta_rule
from ..parallel.moe import dropless_topk_moe
from .attention import causal_document_attention
from .layers import (causal_conv, rms_norm, swiglu,
                     weighted_next_token_cross_entropy)

Params = Dict[str, Any]

MIXERS = ("kda", "mla")
FFNS = ("dense", "experts")

# positions of a sub-chunk: the pairs inside one are summed a channel at
# a time (16 x 16 x d_k differences), those across two are products
_SUB_CHUNK = 16
_HIGHEST = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int              # rows of the vocabulary held
    hidden_size: int
    mixers: Tuple[str, ...]      # "kda" | "mla", a layer each
    ffns: Tuple[str, ...]        # "dense" | "experts", a layer each
    kda_heads: int
    kda_head_dim: int            # of a head's keys and of its values
    conv_width: int
    chunk_size: int
    mla_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int        # a head's own part of its keys
    qk_rope_head_dim: int        # the part every head shares (not rotated)
    v_head_dim: int
    dense_width: int
    expert_width: int
    shared_width: int
    num_experts: int             # the router's width
    experts_held: int
    first_expert: int
    top_k: int
    renormalise: bool
    routed_scaling_factor: float
    rms_norm_eps: float
    compute_dtype: str = "bfloat16"

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def key_width(self) -> int:      # of a latent-attention head
        return self.qk_nope_head_dim + self.qk_rope_head_dim


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


def init_params(key, cfg: KimiLinearConfig) -> Params:
    """Float32 parameters: normal(0.02) matrices, unit norm scales, a
    zero ``router_bias``, and for what a ``config.json`` has no key for
    the start of the family's own code: ``A_log = log U[1, 16]`` a head,
    ``dt_bias`` the inverse softplus of ``delta ~ logU[1e-3, 1e-1]`` a
    channel, convolution taps ``U[-1/sqrt(K), 1/sqrt(K)]`` without a bias
    (``hybrid_ssm.init_params`` says why not normal(0.02))."""
    d, inner, hd = cfg.hidden_size, cfg.kda_inner, cfg.kda_head_dim

    def normal(k, shape):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)

    def kda(ks, n):
        step = jnp.exp(jax.random.uniform(
            ks[7], (n, inner), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return dict(
            w_qkv=normal(ks[0], (n, d, 3 * inner)),
            conv_w=jax.random.uniform(
                ks[1], (n, cfg.conv_width, 3 * inner), jnp.float32,
                -cfg.conv_width ** -0.5, cfg.conv_width ** -0.5),
            f_down=normal(ks[2], (n, d, hd)),
            f_up=normal(ks[3], (n, hd, inner)),
            dt_bias=step + jnp.log(-jnp.expm1(-step)),
            A_log=jnp.log(jax.random.uniform(
                ks[8], (n, cfg.kda_heads), jnp.float32, 1.0, 16.0)),
            b_proj=normal(ks[4], (n, d, cfg.kda_heads)),
            g_down=normal(ks[5], (n, d, hd)),
            g_up=normal(ks[6], (n, hd, inner)),
            head_norm=jnp.ones((n, hd), jnp.float32),
            wo=normal(ks[9], (n, inner, d)))

    def mla(ks, n):
        heads = cfg.mla_heads
        return dict(
            wq=normal(ks[0], (n, d, heads * cfg.key_width)),
            w_kva=normal(ks[1], (n, d, cfg.kv_lora_rank
                                 + cfg.qk_rope_head_dim)),
            kv_norm=jnp.ones((n, cfg.kv_lora_rank), jnp.float32),
            w_kvb=normal(ks[2], (n, cfg.kv_lora_rank, heads * (
                cfg.qk_nope_head_dim + cfg.v_head_dim))),
            wo=normal(ks[3], (n, heads * cfg.v_head_dim, d)))

    def dense(ks, n):
        f = cfg.dense_width
        return dict(mlp_gate=normal(ks[0], (n, d, f)),
                    mlp_up=normal(ks[1], (n, d, f)),
                    mlp_down=normal(ks[2], (n, f, d)))

    def experts(ks, n):
        held, f, s = cfg.experts_held, cfg.expert_width, cfg.shared_width
        return dict(
            router=normal(ks[0], (n, d, cfg.num_experts)),
            router_bias=jnp.zeros((n, cfg.num_experts), jnp.float32),
            w_gate=normal(ks[1], (n, held, d, f)),
            w_up=normal(ks[2], (n, held, d, f)),
            w_down=normal(ks[3], (n, held, f, d)),
            shared_gate=normal(ks[4], (n, d, s)),
            shared_up=normal(ks[5], (n, d, s)),
            shared_down=normal(ks[6], (n, s, d)))

    def group(k, mixer, ffn, n):
        k_mixer, k_ffn = jax.random.split(k)
        p = {"norm1": jnp.ones((n, d), jnp.float32),
             "norm2": jnp.ones((n, d), jnp.float32)}
        p.update((kda if mixer == "kda" else mla)(
            jax.random.split(k_mixer, 10), n))
        p.update((dense if ffn == "dense" else experts)(
            jax.random.split(k_ffn, 7), n))
        return p

    groups = layer_groups(cfg.mixers, cfg.ffns)
    keys = jax.random.split(key, len(groups) + 2)
    return {"embed": normal(keys[0], (cfg.vocab_size, d)),
            "head": normal(keys[1], (d, cfg.vocab_size)),
            "final_norm": jnp.ones((d,), jnp.float32),
            "layers": [group(k, *kind)
                       for k, kind in zip(keys[2:], groups)]}


# ---------------------------------------------------------------------------
# the delta rule, in chunks
# ---------------------------------------------------------------------------

def _product(spec, a, b):
    """A product of f32 operands, in f32."""
    return jnp.einsum(spec, a, b, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def _decayed_products(q, k, cs, sub: int):
    """``sum_c q_ic k_jc exp(cs_ic - cs_jc)`` and the same of ``k_ic``,
    for the pairs ``j <= i`` of every chunk (zeros above the diagonal):
    two f32 ``[..., C, C]`` from f32 ``q``, ``k``, ``cs`` ``[..., C, K]``,
    ``cs`` falling along a chunk, ``C`` and ``sub`` powers of two.  No
    decay is made but as a difference of two positions' sums, the later
    minus the earlier: inside a sub-chunk of ``sub`` positions the
    differences themselves, a channel at a time; then, doubling, the
    pairs between the two halves of a block with the decays split at
    the later half's first position, both factors at most 1, as a
    product."""
    c = cs.shape[-2]
    m = min(sub, c)

    def cut(a, *block):      # [..., C, K] -> [..., n, *block, K]
        return a.reshape(*a.shape[:-2], -1, *block, a.shape[-1])

    q_s, k_s, cs_s = cut(q, m), cut(k, m), cut(cs, m)
    at = jnp.arange(m)
    lower = (at[:, None] >= at[None, :])[..., None]           # [i, j, 1]
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, cs_s[..., :, None, :] - cs_s[..., None, :, :], 0.0)), 0.0)
    to_keys = k_s[..., None, :, :] * decay                    # [.., i, j, K]
    blocks = [jnp.sum(a[..., :, None, :] * to_keys, axis=-1)
              for a in (q_s, k_s)]                            # [.., n, m, m]
    while m < c:
        q_h, k_h, cs_h = cut(q, 2, m), cut(k, 2, m), cut(cs, 2, m)
        first = cs_h[..., 1, :1, :]
        earlier = k_h[..., 0, :, :] * jnp.exp(first - cs_h[..., 0, :, :])
        later = jnp.exp(cs_h[..., 1, :, :] - first)
        grown = []
        for left, diagonal in zip((q_h, k_h), blocks):
            between = _product("...ik,...jk->...ij",
                               left[..., 1, :, :] * later, earlier)
            halves = diagonal.reshape(*between.shape[:-2], 2, m, m)
            grown.append(jnp.concatenate([
                jnp.concatenate([halves[..., 0, :, :],
                                 jnp.zeros_like(between)], axis=-1),
                jnp.concatenate([between, halves[..., 1, :, :]], axis=-1)],
                axis=-2))
        blocks, m = grown, 2 * m
    return tuple(block[..., 0, :, :] for block in blocks)


def _inverse_by_substitution(a, m: int):
    """The inverses of the diagonal blocks of ``m`` positions of ``I +
    a``, laid back on the diagonal of a ``[..., C, C]`` of zeros: forward
    substitution a row at a time, each row a sum over the rows above it,
    elementwise in f32 (``m`` small products of a handful of numbers
    are no work for the MXU: six levels of doubling from blocks of one
    position took 21 ms a layer's forward pass on the chip, a third of
    the whole rule; PERF.md, findings of PR 40).  The blocks are walked
    with all of them side by side along the last axis, ``[m, m,
    blocks]``: a row of sixteen numbers a block is laid out in tiles of
    8 x 128 on the chip and takes many times its size."""
    c = a.shape[-1]
    n = c // m
    cut = a.reshape(-1, n, m, n, m)
    blocks = jnp.stack([cut[:, i, :, i, :] for i in range(n)], axis=1)
    blocks = jnp.moveaxis(blocks.reshape(-1, m, m), 0, -1)   # [i, j, blocks]
    unit = jnp.eye(m, dtype=a.dtype)[:, :, None]
    rows = [jnp.broadcast_to(unit[0], blocks.shape[1:])]
    for i in range(1, m):
        rows.append(unit[i] - jnp.sum(
            blocks[i, :i, None, :] * jnp.stack(rows), axis=0))
    solved = jnp.moveaxis(jnp.stack(rows), -1, 0).reshape(
        *a.shape[:-2], n, m, m)
    return jnp.concatenate([
        jnp.pad(solved[..., i, :, :],
                [(0, 0)] * (a.ndim - 1) + [(i * m, c - (i + 1) * m)])
        for i in range(n)], axis=-2)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular f32 ``a`` ``[..., C,
    C]``, ``C`` a power of two: the diagonal blocks of ``_SUB_CHUNK``
    positions by forward substitution, then by doubling, the inverse
    over the diagonal blocks of ``m`` positions giving that over blocks
    of ``2 m`` through ``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R
    P^-1, Q^-1]]``.  Block forward substitution: nothing grows that the
    inverse itself does not hold.  Its gradient is the inverse's own,
    ``-X^T dX X^T`` from the result ``X`` alone: the levels keep nothing
    for the backward pass."""
    c = a.shape[-1]
    if c & (c - 1):
        raise ValueError(f"a chunk of {c} positions is no power of two")
    at = jnp.arange(c)
    m = min(_SUB_CHUNK, c)
    inverse = _inverse_by_substitution(a, m)
    while m < c:
        block, half = at // (2 * m), at // m
        below = ((block[:, None] == block[None, :])
                 & (half[:, None] > half[None, :]))      # the R of each pair
        inverse = inverse - _product(
            "...ij,...jk->...ik",
            _product("...ij,...jk->...ik", inverse,
                     jnp.where(below, a, 0.0)), inverse)
        m *= 2
    return inverse


def _unit_lower_inverse_fwd(a):
    inverse = unit_lower_inverse(a)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, d_inverse):
    return (-_product("...ji,...jk->...ik", inverse,
                      _product("...ij,...kj->...ik", d_inverse, inverse)),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _walk(state, chunk, dtype):
    """One chunk of every row and head: ``state`` f32 ``[B, H, K, V]``
    is the state at the end of the chunk before."""
    w, u_own, q_in, k_end, pairs, keep = chunk

    def product(spec, a, b):     # f32 operands at HIGHEST, like ``_product``
        return jnp.einsum(
            spec, a.astype(dtype), b.astype(dtype),
            precision=_HIGHEST if dtype == jnp.float32 else None,
            preferred_element_type=jnp.float32)

    u = u_own - product("bhck,bhkv->bhcv", w, state)
    out = (product("bhck,bhkv->bhcv", q_in, state)
           + product("bhij,bhjv->bhiv", pairs, u))
    state = keep[..., None] * state + product("bhck,bhcv->bhkv", k_end, u)
    return state, out.astype(dtype)


def chunked_delta_rule(q, k, v, g, beta, segment, chunk: int):
    """``o_t = S_t^T q_t`` of ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t)
    S_{t-1} + beta_t k_t v_t^T``, the state zero before every document's
    first position.

    ``q``, ``k`` ``[B, T, H, K]`` (``k`` of unit length), ``v`` ``[B, T,
    H, V]``, ``g`` f32 ``[B, T, H, K]`` (a log-decay a channel, at most
    0), ``beta`` f32 ``[B, T, H]``, ``segment`` int ``[B, T]``; ``chunk``
    a power of two.  The result is ``[B, T, H, V]`` in ``q``'s type.  The
    module's docstring has the form and what is kept in f32.

    Which implementation runs is observed, not set, as in
    ``attention.masked_attention``: the Pallas kernels of
    ``ops/delta_rule.py`` where ``ops.backend`` compiles kernels and
    ``delta_rule.supports`` the widths, the chunk and the type; the XLA
    form below everywhere else."""
    b, t, h, dk = q.shape
    dtype, f32 = q.dtype, jnp.float32
    chunk = min(chunk, 1 << (t - 1).bit_length())
    pad = -t % chunk
    if pad:
        # a tail of its own document with beta 0: writes and decays nothing
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
        segment = jnp.pad(segment, ((0, 0), (0, pad)), constant_values=-1)
    n = (t + pad) // chunk
    metrics.note_kda_chunks(b * n, chunk)
    use, interpret = backend.pallas_mode()
    if use and q.dtype == k.dtype == v.dtype and delta_rule.supports(
            dk, v.shape[-1], chunk, q.dtype):
        metrics.note_kda_path("pallas")
        return delta_rule.chunked_delta_rule(
            q, k, v, g, beta, segment, chunk, interpret=interpret)[:, :t]
    metrics.note_kda_path("xla")

    def chunks(a):      # [B, n * C, H, ...] -> [B, H, n, C, ...]
        return jnp.moveaxis(
            a.reshape(b, n, chunk, h, *a.shape[3:]), 3, 1)

    # heads-major in the type they come in: the barrier keeps the
    # compiler from turning three bfloat16 relayouts into f32 ones
    q, k, v = (a.astype(f32) for a in lax.optimization_barrier(
        tuple(chunks(a) for a in (q, k, v))))
    g, beta = chunks(g).astype(f32), chunks(beta).astype(f32)
    seg = segment.reshape(b, 1, n, chunk)
    last = seg[..., -1:]
    before = jnp.concatenate(
        [jnp.full((b, 1, 1, 1), -2, seg.dtype), last[:, :, :-1]], axis=2)
    same = seg[..., :, None] == seg[..., None, :]
    at = jnp.arange(chunk)
    cs = jnp.cumsum(g, axis=3)                               # [B, H, n, C, K]
    to_queries, to_keys = _decayed_products(q, k, cs, _SUB_CHUNK)
    system = jnp.where(same & (at[:, None] > at[None, :]),
                       beta[..., None] * to_keys, 0.0)
    solved = unit_lower_inverse(system)                      # (I + A)^-1
    since_start = jnp.exp(cs) * (seg == before)[..., None]
    from_end = jnp.exp(cs[..., -1:, :] - cs) * (seg == last)[..., None]
    # what the walk multiplies with the state is kept in the type its
    # products take it in
    walk = (
        _product("...ij,...jk->...ik", solved,
                 beta[..., None] * since_start * k).astype(dtype),    # W
        _product("...ij,...jv->...iv", solved, beta[..., None] * v),
        (since_start * q).astype(dtype),
        (from_end * k).astype(dtype),
        jnp.where(same & (at[:, None] >= at[None, :]), to_queries,
                  0.0).astype(dtype),
        jnp.exp(cs[..., -1, :]) * (last == before))          # [B, H, n, K]
    start = jnp.zeros((b, h, dk, v.shape[-1]), f32)
    _, out = lax.scan(
        jax.checkpoint(lambda state, chunk: _walk(state, chunk, dtype)),
        start, tuple(jnp.moveaxis(a, 2, 0) for a in walk))
    # [n, B, H, C, V] -> [B, T, H, V]
    return jnp.moveaxis(out, (0, 2), (1, 3)).reshape(
        b, t + pad, h, -1)[:, :t]


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------

def _unit_length(x):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _low_rank(u, down, up):
    return jnp.dot(u @ down.astype(u.dtype), up.astype(u.dtype),
                   preferred_element_type=jnp.float32)


def kda_operands(cfg: KimiLinearConfig, p: Params, u, segment):
    """What the delta rule takes, from ``u`` ``[B, T, D]`` (already
    normed): ``q``, ``k``, ``v`` ``[B, T, H, d]`` in ``u``'s type (``q``
    and ``k`` of unit length, ``q`` times ``d ** -0.5``), the log-decays
    ``g`` f32 ``[B, T, H, d]`` and ``beta`` f32 ``[B, T, H]``."""
    b, t, _ = u.shape
    dtype, f32 = u.dtype, jnp.float32
    heads, hd = cfg.kda_heads, cfg.kda_head_dim
    with jax.named_scope("hvtpu:kda.proj"):
        qkv = u @ p["w_qkv"].astype(dtype)
    with jax.named_scope("hvtpu:kda.conv"):
        # rounded once, as the Mamba mixer's convolution is: the f32 sum
        # over the taps is 0.8 GB that nothing has to keep
        q, k, v = jnp.split(
            jax.nn.silu(causal_conv(
                qkv, p["conv_w"], 0.0, segment)).astype(dtype).reshape(
                    b, t, 3 * heads, hd), 3, axis=2)
        q = (_unit_length(q) * hd ** -0.5).astype(dtype)
        k = _unit_length(k).astype(dtype)
    with jax.named_scope("hvtpu:kda.gate"):
        g = (-jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            _low_rank(u, p["f_down"], p["f_up"]) + p["dt_bias"]
        ).reshape(b, t, heads, hd))
        beta = jax.nn.sigmoid(jnp.dot(
            u, p["b_proj"].astype(dtype), preferred_element_type=f32))
    return q, k, v, g, beta


def kda_mixer(cfg: KimiLinearConfig, p: Params, u, segment):
    """The mixer on ``u`` ``[B, T, D]`` (already normed)."""
    b, t, _ = u.shape
    dtype, f32 = u.dtype, jnp.float32
    operands = kda_operands(cfg, p, u, segment)
    with jax.named_scope("hvtpu:kda.delta"):
        o = chunked_delta_rule(*operands, segment, cfg.chunk_size)
        # the gate's gradient comes back in the rule's type, as the
        # Mamba mixer's does (``hybrid_ssm.mamba_mixer``)
        o = lax.optimization_barrier(o)
    with jax.named_scope("hvtpu:kda.gate"):
        gate = jax.nn.sigmoid(_low_rank(u, p["g_down"], p["g_up"]))
        y = (rms_norm(o.astype(f32), p["head_norm"], cfg.rms_norm_eps)
             * gate.reshape(o.shape)).reshape(b, t, -1).astype(dtype)
    with jax.named_scope("hvtpu:kda.proj"):
        return y @ p["wo"].astype(dtype)


def mla_operands(cfg: KimiLinearConfig, p: Params, u):
    """``q``, ``k`` ``[B, T, H, own + shared]`` and ``v`` ``[B, T, H,
    V]`` from ``u`` ``[B, T, D]`` (already normed): the shared key part
    is made once a token and laid beside every head's own."""
    b, t, _ = u.shape
    dtype, heads = u.dtype, cfg.mla_heads
    with jax.named_scope("hvtpu:mla.proj"):
        q = (u @ p["wq"].astype(dtype)).reshape(b, t, heads, cfg.key_width)
        latent, shared = jnp.split(
            u @ p["w_kva"].astype(dtype), [cfg.kv_lora_rank], axis=-1)
        latent = rms_norm(latent, p["kv_norm"], cfg.rms_norm_eps)
        own, v = jnp.split(
            (latent @ p["w_kvb"].astype(dtype)).reshape(b, t, heads, -1),
            [cfg.qk_nope_head_dim], axis=-1)
        k = jnp.concatenate([own, jnp.broadcast_to(
            shared[:, :, None], (b, t, heads, shared.shape[-1]))], axis=-1)
    return q, k, v


def mla_attention(cfg: KimiLinearConfig, q, k, v, segment):
    return causal_document_attention(q, k, v, segment,
                                     scale=cfg.key_width ** -0.5)


def mla_mixer(cfg: KimiLinearConfig, p: Params, u, segment):
    """The mixer on ``u`` ``[B, T, D]`` (already normed)."""
    b, t, _ = u.shape
    o = mla_attention(cfg, *mla_operands(cfg, p, u), segment)
    with jax.named_scope("hvtpu:mla.proj"):
        return o.reshape(b, t, -1) @ p["wo"].astype(u.dtype)


_MIXER_SCOPE = {"kda": "hvtpu:kda.proj", "mla": "hvtpu:mla.proj"}
_MIXER = {"kda": kda_mixer, "mla": mla_mixer}


# ---------------------------------------------------------------------------
# the two feed-forward parts
# ---------------------------------------------------------------------------

def dense_ffn(cfg: KimiLinearConfig, p: Params, x):
    with jax.named_scope("hvtpu:mlp"):
        u = rms_norm(x, p["norm2"], cfg.rms_norm_eps)
        return x + swiglu(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"])


def expert_ffn(cfg: KimiLinearConfig, p: Params, x):
    """``x`` plus what the experts held here and the shared expert add,
    and the routing as ``dropless_topk_moe`` returns it."""
    b, t, d = x.shape
    with jax.named_scope("hvtpu:moe.route"):
        u = rms_norm(x, p["norm2"], cfg.rms_norm_eps).reshape(b * t, d)
    y, routing = dropless_topk_moe(
        u, p["router"], {name: p[name] for name in (
            "w_gate", "w_up", "w_down")},
        top_k=cfg.top_k, num_experts=cfg.num_experts,
        first_expert=cfg.first_expert, renormalise=cfg.renormalise,
        selection_bias=p["router_bias"], scale=cfg.routed_scaling_factor)
    with jax.named_scope("hvtpu:moe.shared"):
        shared = swiglu(u, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
    with jax.named_scope("hvtpu:moe.combine"):
        return x + (y + shared).reshape(b, t, d), routing


def mixer_half(cfg: KimiLinearConfig, mixer: str, p: Params, x, segment):
    # the norm before and the residual after are counted with the
    # projections they feed and follow
    with jax.named_scope(_MIXER_SCOPE[mixer]):
        u = rms_norm(x, p["norm1"], cfg.rms_norm_eps)
    y = _MIXER[mixer](cfg, p, u, segment)
    with jax.named_scope(_MIXER_SCOPE[mixer]):
        return x + y


def ffn_half(cfg: KimiLinearConfig, ffn: str, p: Params, x):
    """``(x, the rows each held expert got)``, the rows int32
    ``[experts_held]``, or None of a dense part."""
    if ffn == "dense":
        return dense_ffn(cfg, p, x), None
    x, routing = expert_ffn(cfg, p, x)
    return x, routing["rows_per_expert"]


def layer(cfg: KimiLinearConfig, mixer: str, ffn: str, p: Params, x,
          segment):
    """One layer, each half recomputed in the backward pass by itself:
    what the mixer keeps for its gradient (a delta rule's chunks, 2 GB at
    the published widths) is made when the feed-forward part's (an
    expert layer's row buffers, 3 GB) is gone."""
    x = jax.checkpoint(
        lambda x, p: mixer_half(cfg, mixer, p, x, segment))(x, p)
    return jax.checkpoint(lambda x, p: ffn_half(cfg, ffn, p, x))(x, p)


def hidden_states(params: Params, ids, cfg: KimiLinearConfig,
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


def logits_of(params: Params, hidden, cfg: KimiLinearConfig):
    """f32 logits over the rows of the vocabulary held here (untied)."""
    with jax.named_scope("hvtpu:lm_head"):
        u = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
        return jnp.dot(u, params["head"].astype(u.dtype),
                       preferred_element_type=jnp.float32)


def next_token_loss(params: Params, batch, cfg: KimiLinearConfig):
    """``batch`` as ``hybrid_ssm.next_token_loss`` takes it (``x``,
    ``segment``, ``w``).  Returns the loss and the routing's counts
    ``{"moe_rows_per_expert": int32 [expert layers, experts_held]}``."""
    hidden, rows = hidden_states(params, batch["x"], cfg, batch["segment"])
    return (weighted_next_token_cross_entropy(
        logits_of(params, hidden, cfg), batch),
        {"moe_rows_per_expert": rows})
