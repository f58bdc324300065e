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

Attention never forms ``[2T, 2T]``: queries and keys are cut into
tiles, the tile pairs in which no query may see any key are left out
when the program is traced, and the rest go through an online softmax,
forward and backward (``tiled_attention``).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import metrics
from ..ops import flash_attention, pallas_ops
from ..parallel.moe import dropless_topk_moe

logger = logging.getLogger("horovod_tpu")

Params = Dict[str, Any]

# what a masked score is set to: finite, so that a row of a tile pair
# in which it sees no key gives exp(0) and no NaN; the first real key
# of the row wipes that out again (exp(_MASKED - m) == 0)
_MASKED = -1e30

# queries and keys of a tile: 2,048 x 512 f32 scores a key/value head
_ATTENTION_TILE = 512

# queries and keys of a block of the Pallas kernels
# (``ops/flash_attention.py``), chosen on the chip: PERF.md, findings
# of PR 28, has the sizes tried and what each measured
_FLASH_BLOCK_Q = 512
_FLASH_BLOCK_KV = 512


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


def rms_norm(x, scale, eps):
    """In f32 over the last axis, back in ``x``'s type."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rope(x, positions, theta):
    """Rotate-half rotary embedding of ``[..., P, heads, head_dim]`` at
    ``positions`` ``[P]``, computed in f32."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention in tiles under the block-diffusion mask
# ---------------------------------------------------------------------------

def allowed(q_pos, k_pos, seq_len: int, block_length: int):
    """May the query at ``q_pos`` see the key at ``k_pos``?  Positions
    count over ``xt ++ x``: below ``seq_len`` the noised half.  Works on
    numpy and on traced integers alike."""
    q_clean, k_clean = q_pos >= seq_len, k_pos >= seq_len
    q_blk = (q_pos - q_clean * seq_len) // block_length
    k_blk = (k_pos - k_clean * seq_len) // block_length
    return ((~q_clean & ~k_clean & (k_blk == q_blk))
            | (~q_clean & k_clean & (k_blk < q_blk))
            | (q_clean & k_clean & (k_blk <= q_blk)))


def tile_work(seq_len: int, block_length: int, tile_q: int, tile_k: int):
    """What each (query tile, key tile) pair holds under ``allowed``,
    int8 ``[2 nq, 2 nk]``: 0 nothing, 1 some pairs (the mask has to be
    applied), 2 every query of the tile sees every key.  Each half is
    cut into ``ceil(seq_len / tile)`` tiles of its own (the noised
    half's first), so no tile straddles the halves.  In closed form
    from the first and last block a tile touches: ``allowed`` compares
    blocks, so a tile stands for the range of its blocks."""
    def blocks(tile):
        first = np.arange(-(-seq_len // tile)) * tile
        last = np.minimum(first + tile, seq_len) - 1
        return (first // block_length, last // block_length,
                first + tile <= seq_len)

    (q_lo, q_hi, q_whole), (lo, hi, k_whole) = blocks(tile_q), blocks(tile_k)
    q_lo, q_hi, nq, nk = q_lo[:, None], q_hi[:, None], len(q_lo), len(lo)
    some = np.zeros((2 * nq, 2 * nk), bool)
    every = np.zeros((2 * nq, 2 * nk), bool)
    some[:nq, :nk] = (lo <= q_hi) & (q_lo <= hi)   # some block in common
    every[:nq, :nk] = (q_lo == q_hi) & (lo == hi) & (q_lo == lo)
    some[:nq, nk:] = lo < q_hi                     # an earlier clean block
    every[:nq, nk:] = hi < q_lo
    some[nq:, nk:] = lo <= q_hi
    every[nq:, nk:] = hi <= q_lo
    # a tile with padding is never seen whole
    every &= np.tile(q_whole, 2)[:, None] & np.tile(k_whole, 2)
    return some.astype(np.int8) + (some & every)


def tile_runs(seq_len: int, block_length: int, tile: int):
    """The tile pairs that hold work, as runs ``(first query tile, one
    past the last, first key tile)`` of pairs that lie on one diagonal
    (tiles ``0..n-1`` noised, ``n..2n-1`` clean)."""
    n = -(-seq_len // tile)
    work = tile_work(seq_len, block_length, tile, tile) > 0
    runs = []
    for off in range(-(2 * n - 1), 2 * n):       # key tile = query tile - off
        start = None
        for qi in range(2 * n + 1):
            on = qi < 2 * n and 0 <= qi - off < 2 * n and work[qi, qi - off]
            if on and start is None:
                start = qi
            elif not on and start is not None:
                runs.append((start, qi, start - off))
                start = None
    return tuple(runs)


def _positions(first_tile, count, n, tile, seq_len):
    """Positions over ``xt ++ x`` of ``count`` tiles from ``first_tile``
    on, ``[count, tile]``; a padded row of a half lies beyond both."""
    t = first_tile + lax.broadcasted_iota(jnp.int32, (count, tile), 0)
    clean = t >= n
    within = (t - clean * n) * tile + lax.broadcasted_iota(
        jnp.int32, (count, tile), 1)
    return jnp.where(within < seq_len, within + clean * seq_len,
                     4 * seq_len + 4 * tile)


def _run_scores(q, k, run, spec):
    """Scaled, masked f32 scores of one run: ``[B, G, R, tiles, tq, tk]``."""
    seq_len, block_length, tile, n, scale = spec
    q_lo, q_hi, k_lo = run
    count = q_hi - q_lo
    s = jnp.einsum("bgrnqd,bgnkd->bgrnqk", q[:, :, :, q_lo:q_hi],
                   k[:, :, k_lo:k_lo + count],
                   preferred_element_type=jnp.float32) * scale
    q_pos = _positions(q_lo, count, n, tile, seq_len)[:, :, None]
    k_pos = _positions(k_lo, count, n, tile, seq_len)[:, None, :]
    # a padded key lies beyond every block; a padded query sees nothing
    seen = allowed(q_pos, k_pos, seq_len, block_length) & (
        k_pos < 2 * seq_len)
    return jnp.where(seen, s, _MASKED)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _tiled(q, k, v, runs, spec):
    return _tiled_fwd(q, k, v, runs, spec)[0]


@jax.named_scope("hvtpu:attention")
def _tiled_fwd(q, k, v, runs, spec):
    b, g, r, tiles, tile, hd = q.shape
    m = jnp.full((b, g, r, tiles, tile), _MASKED, jnp.float32)
    l = jnp.zeros((b, g, r, tiles, tile), jnp.float32)
    acc = jnp.zeros((b, g, r, tiles, tile, hd), jnp.float32)
    for run in runs:
        q_lo, q_hi, k_lo = run
        s = _run_scores(q, k, run, spec)
        m_old = m[:, :, :, q_lo:q_hi]
        m_new = jnp.maximum(m_old, s.max(axis=-1))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new[..., None])
        pv = jnp.einsum("bgrnqk,bgnkd->bgrnqd", p.astype(v.dtype),
                        v[:, :, k_lo:k_lo + q_hi - q_lo],
                        preferred_element_type=jnp.float32)
        m = m.at[:, :, :, q_lo:q_hi].set(m_new)
        l = l.at[:, :, :, q_lo:q_hi].set(
            alpha * l[:, :, :, q_lo:q_hi] + p.sum(axis=-1))
        acc = acc.at[:, :, :, q_lo:q_hi].set(
            alpha[..., None] * acc[:, :, :, q_lo:q_hi] + pv)
    out = (acc / l[..., None]).astype(q.dtype)
    return out, (q, k, v, out, m + jnp.log(l))


@jax.named_scope("hvtpu:attention")
def _tiled_bwd(runs, spec, res, d_out):
    q, k, v, out, lse = res
    scale = spec[4]
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    dq = jnp.zeros(q.shape, jnp.float32)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    for run in runs:
        q_lo, q_hi, k_lo = run
        k_hi = k_lo + q_hi - q_lo
        p = jnp.exp(_run_scores(q, k, run, spec)
                    - lse[:, :, :, q_lo:q_hi, :, None])
        d_run = d_out[:, :, :, q_lo:q_hi]
        dv = dv.at[:, :, k_lo:k_hi].add(jnp.einsum(
            "bgrnqk,bgrnqd->bgnkd", p.astype(v.dtype), d_run,
            preferred_element_type=jnp.float32))
        dp = jnp.einsum("bgrnqd,bgnkd->bgrnqk", d_run, v[:, :, k_lo:k_hi],
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, :, :, q_lo:q_hi, :, None]) * scale
              ).astype(q.dtype)
        dq = dq.at[:, :, :, q_lo:q_hi].add(jnp.einsum(
            "bgrnqk,bgnkd->bgrnqd", ds, k[:, :, k_lo:k_hi],
            preferred_element_type=jnp.float32))
        dk = dk.at[:, :, k_lo:k_hi].add(jnp.einsum(
            "bgrnqk,bgrnqd->bgnkd", ds, q[:, :, :, q_lo:q_hi],
            preferred_element_type=jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_tiled.defvjp(_tiled_fwd, _tiled_bwd)


@functools.lru_cache(maxsize=None)
def _flash_schedule(seq_len: int, block_length: int, block_q: int,
                    block_kv: int) -> flash_attention.PairSchedule:
    """The kernels' walk over ``xt ++ x``: a half is a whole number of
    blocks, so tile ``i`` is positions ``i * block`` on."""
    work = tile_work(seq_len, block_length, block_q, block_kv)
    logger.info(
        "block-diffusion attention runs in the Pallas kernels: blocks of "
        "%d queries x %d keys over 2 x %d positions, block length %d; %d "
        "of %d block pairs hold work, %d partial and %d full, at most %d "
        "key blocks a query block", block_q, block_kv, seq_len,
        block_length, np.count_nonzero(work), work.size,
        np.count_nonzero(work == flash_attention.PARTIAL),
        np.count_nonzero(work == flash_attention.FULL),
        np.count_nonzero(work, axis=1).max())
    return flash_attention.pair_schedule(work, block_q, block_kv)


@functools.lru_cache(maxsize=None)
def _note_xla_path(seq_len, block_length, tile, why):
    logger.info(
        "block-diffusion attention runs in XLA tiles of %d over 2 x %d "
        "positions, block length %d (%s)", tile, seq_len, block_length, why)


def _in_kernels(kernels, spec, *operands):
    """``flash_attention.forward`` or ``.backward`` on ``operands``
    (``q`` first) with this mask's schedule and ``allowed`` itself."""
    seq_len, block_length, block_q, block_kv, interpret = spec
    return kernels(
        *operands, _flash_schedule(seq_len, block_length, block_q, block_kv),
        functools.partial(allowed, seq_len=seq_len,
                          block_length=block_length),
        scale=1.0 / math.sqrt(operands[0].shape[-1]), mask_value=_MASKED,
        interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, spec):
    return _flash_fwd(q, k, v, spec)[0]


@jax.named_scope("hvtpu:attention")
def _flash_fwd(q, k, v, spec):
    out, lse, exact = _in_kernels(flash_attention.forward, spec, q, k, v)
    return out, (q, k, v, exact, lse)


@jax.named_scope("hvtpu:attention")
def _flash_bwd(spec, res, d_out):
    return _in_kernels(flash_attention.backward, spec, *res, d_out)


_flash.defvjp(_flash_fwd, _flash_bwd)


def tiled_attention(q, k, v, *, block_length: int, tile: int):
    """Attention of ``q`` ``[B, 2T, H, hd]`` over ``k``, ``v`` ``[B, 2T,
    G, hd]`` under the block-diffusion mask (``allowed``), every query
    head reading the key/value head of its group.  Scores and softmax in
    f32; the products take ``q``'s type.  Everything in it runs under
    the scope ``hvtpu:attention``.

    One algorithm, an online softmax over the tile pairs that hold
    work, in two implementations; which one runs is observed, not set.
    Where ``ops.pallas_ops`` compiles kernels (a TPU backend, or the
    interpreter in tests) and the shapes are ones they take, the pairs
    run in the Pallas kernels of ``ops/flash_attention.py`` in blocks of
    ``_FLASH_BLOCK_Q`` x ``_FLASH_BLOCK_KV`` and a pair's scores stay in
    VMEM; elsewhere in XLA over diagonal runs of ``tile`` x ``tile``.
    ``hvtpu_attention_calls_total{path=}`` counts, when a program is
    traced, which it was."""
    b, two_t, heads, hd = q.shape
    seq_len, groups = two_t // 2, k.shape[2]
    tile = min(tile, seq_len)
    use, interpret = pallas_ops._pallas_mode()
    blocks = min(_FLASH_BLOCK_Q, seq_len), min(_FLASH_BLOCK_KV, seq_len)
    if not use:
        why = "no Pallas on this backend"
    elif not (q.dtype == k.dtype == v.dtype and flash_attention.supports(
            hd, q.dtype, seq_len, *blocks, groups)):
        why = (f"the kernels take heads of 128 lanes, or pairs of heads of "
               f"64, and halves of whole blocks, not {groups} heads of "
               f"{hd}, {q.dtype}, blocks of {blocks}")
    else:
        metrics.note_attention_path("pallas")
        return _flash(q, k, v, (seq_len, block_length, *blocks, interpret))
    metrics.note_attention_path("xla")
    _note_xla_path(seq_len, block_length, tile, why)
    n = -(-seq_len // tile)
    pad = n * tile - seq_len

    def tiles(x):   # [B, 2T, h, hd] -> [B, h, 2n, tile, hd]
        x = x.reshape(b, 2, seq_len, x.shape[2], hd)
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        return x.reshape(b, 2 * n, tile, -1, hd).transpose(0, 3, 1, 2, 4)

    spec = (seq_len, block_length, tile, n, 1.0 / math.sqrt(hd))
    with jax.named_scope("hvtpu:attention"):
        out = _tiled(
            tiles(q).reshape(b, groups, heads // groups, 2 * n, tile, hd),
            tiles(k), tiles(v), tile_runs(seq_len, block_length, tile),
            spec)
        out = out.reshape(b, heads, 2, n * tile, hd)[:, :, :, :seq_len]
        return out.transpose(0, 2, 3, 1, 4).reshape(b, two_t, heads, hd)


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
