"""The one attention of ``models/``: ``masked_attention`` computes,
forward and backward, only the tile pairs a mask leaves work in.

A mask is a small hashable value that says which tile pairs hold work at
a tile size (``work``), whether a query sees a key (``seen``), and
whether the batch's data takes part (``ids``: a document's index at
every position).  ``BlockDiffusionMask`` and ``DocumentMask`` are the
two; ``tiled_attention`` and ``causal_document_attention`` are the
models' calls under each.

Which implementation runs is observed, not set: where ``ops.backend``
compiles kernels and ``flash_attention.supports`` the shapes, the Pallas
kernels of ``ops/flash_attention.py`` behind one ``custom_vjp``
(``_flash``); elsewhere XLA, a tile of queries at a time against the key
tiles its mask lists (``key_ranges``).  ``hvtpu_attention_calls_total
{path=}`` counts which, one log line a mask and shape says why, and
everything runs under the scope ``hvtpu:attention``.  Scores and
softmax in f32; the products take ``q``'s type.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import metrics
from ..ops import backend, flash_attention
from .layers import _MASKED

logger = logging.getLogger("horovod_tpu")

# queries and keys of a block of the Pallas kernels
# (``ops/flash_attention.py``), chosen on the chip: PERF.md's findings
# on the flash kernels have the sizes tried and what each measured
_FLASH_BLOCK_Q = 512
_FLASH_BLOCK_KV = 512

# queries of a tile of the document attention where it runs in XLA: the
# f32 scores of a tile against every earlier key, 2 rows x 32 heads x
# 256 x 8,192, are 0.5 GB
_DOCUMENT_TILE = 256


# ---------------------------------------------------------------------------
# the two masks
# ---------------------------------------------------------------------------

def allowed(q_pos, k_pos, seq_len: int, block_length: int):
    """May the query at ``q_pos`` see the key at ``k_pos``?  Positions
    count over ``xt ++ x``: below ``seq_len`` the noised half.  A noised
    query sees the noised keys of its own block and the clean keys of
    earlier blocks; a clean query sees the clean keys of its own and
    earlier blocks.  Works on numpy and on traced integers alike."""
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


def _seen(q_pos, k_pos, q_doc, k_doc):
    """The document mask: a key at or before the query, in its
    document."""
    return (k_pos <= q_pos) & (q_doc == k_doc)


def live_pairs(segment, block_q: int, block_kv: int):
    """int32 ``[B, query blocks, key blocks]``: 0 where no query of the
    one block and no key of the other can be of one document, because
    the ranges of their ids do not meet.  ``segment`` numpy or jax."""
    b = segment.shape[0]

    def ranges(block):
        ids = segment.reshape(b, -1, block)
        return ids.min(axis=-1), ids.max(axis=-1)

    (q_lo, q_hi), (k_lo, k_hi) = ranges(block_q), ranges(block_kv)
    return ((k_lo[:, None, :] <= q_hi[:, :, None])
            & (q_lo[:, :, None] <= k_hi[:, None, :])).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMask:
    """``allowed`` over ``xt ++ x``: two spans of ``seq_len`` positions,
    the noised half first, each cut into tiles of its own; its table is
    ``tile_work``."""
    seq_len: int
    block_length: int
    ids = False

    @property
    def spans(self) -> Tuple[int, int]:
        """How many spans of how many positions, each of whole tiles."""
        return 2, self.seq_len

    def seen(self, q_pos, k_pos):
        return allowed(q_pos, k_pos, self.seq_len, self.block_length)

    def work(self, tile_q: int, tile_k: int):
        return tile_work(self.seq_len, self.block_length, tile_q, tile_k)


@dataclasses.dataclass(frozen=True)
class DocumentMask:
    """``_seen`` over one span of ``positions``.  The documents are data
    (``ids``), so the table is the causal triangle of tile pairs, made
    from positions alone, every pair ``PARTIAL``: which of them hold a
    pair of one document is ``live_pairs``."""
    positions: int
    ids = True

    @property
    def spans(self) -> Tuple[int, int]:
        return 1, self.positions

    seen = staticmethod(_seen)

    def work(self, tile_q: int, tile_k: int):
        first_key = np.arange(0, self.positions, tile_k)
        last_query = np.arange(0, self.positions, tile_q) + tile_q - 1
        return flash_attention.PARTIAL * (
            first_key[None, :] <= last_query[:, None])


# ---------------------------------------------------------------------------
# in the Pallas kernels
# ---------------------------------------------------------------------------

def _flash_blocks(positions: int) -> Tuple[int, int]:
    return min(_FLASH_BLOCK_Q, positions), min(_FLASH_BLOCK_KV, positions)


@functools.lru_cache(maxsize=None)
def _flash_schedule(mask, block_q: int,
                    block_kv: int) -> flash_attention.PairSchedule:
    """The kernels' walk: a span is a whole number of blocks, so tile
    ``i`` is positions ``i * block`` on."""
    return flash_attention.pair_schedule(
        mask.work(block_q, block_kv), block_q, block_kv)


def note_attention_pairs(segment) -> None:
    """Count, from the host's loop like ``metrics.note_packed_batch``,
    the block pairs of the causal triangle the kernels run and skip on a
    batch of this ``segment`` (numpy ``[B, T]``, whole blocks)."""
    blocks = _flash_blocks(segment.shape[1])
    query_block, key_block = _flash_schedule(
        DocumentMask(segment.shape[1]), *blocks).by_query[:2]
    run = int(live_pairs(np.asarray(segment), *blocks)[
        :, query_block, key_block].sum())
    metrics.note_attention_pairs(
        run, segment.shape[0] * len(query_block) - run)


def _in_kernels(kernels, static, ids, *operands):
    """``flash_attention.forward`` or ``.backward`` on ``operands``
    (``q`` first) under the mask, its ids the mask's data where it has
    any."""
    mask, scale, block_q, block_kv, interpret = static
    data = (dict(ids=ids, live=live_pairs(ids, block_q, block_kv))
            if mask.ids else {})
    return kernels(
        *operands, _flash_schedule(mask, block_q, block_kv), mask.seen,
        scale=scale, mask_value=_MASKED, interpret=interpret, **data)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash(q, k, v, ids, static):
    return _flash_fwd(q, k, v, ids, static)[0]


@jax.named_scope("hvtpu:attention")
def _flash_fwd(q, k, v, ids, static):
    out, lse, exact = _in_kernels(flash_attention.forward, static, ids,
                                  q, k, v)
    return out, (q, k, v, exact, lse, ids)


@jax.named_scope("hvtpu:attention")
def _flash_bwd(static, res, d_out):
    *res, ids = res
    return (*_in_kernels(flash_attention.backward, static, ids, *res,
                         d_out), None)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# in XLA
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def key_ranges(mask, tile: int):
    """The XLA path's walk: for every tile of ``tile`` queries (each span
    cut into tiles of its own, the last one shorter where ``tile`` does
    not divide it), ``((first, end), ranges)``: its positions, and the
    ranges ``(first, end)`` of the keys it reads, the key tiles that
    ``mask.work(tile, tile)`` lists for it with neighbours joined."""
    count, span = mask.spans
    first = np.concatenate([s * span + np.arange(0, span, tile)
                            for s in range(count)])
    end = np.minimum(first + tile, (first // span + 1) * span)
    walk = []
    for q_first, q_end, row in zip(first, end, mask.work(tile, tile) > 0):
        ranges = []
        for lo, hi in zip(first[row], end[row]):
            if ranges and ranges[-1][1] == lo:
                ranges[-1][1] = int(hi)
            else:
                ranges.append([int(lo), int(hi)])
        walk.append(((int(q_first), int(q_end)),
                     tuple(tuple(r) for r in ranges)))
    return tuple(walk)


def _in_rows(q, k, v, ids, mask, scale, tile):
    """A tile of queries at a time against the keys ``key_ranges`` lists
    for it, the tiles beyond never computed; ``seen`` on absolute
    positions (and ids), a plain softmax, each tile recomputed in the
    backward pass."""
    b, positions, heads, hd = q.shape
    groups, hdv = k.shape[2], v.shape[3]

    def cut(x, ranges):
        parts = [x[:, lo:hi] for lo, hi in ranges]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 1)

    @jax.checkpoint
    def rows(q_rows, keys, values, q_pos, k_pos, *doc):
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q_rows, keys,
                       preferred_element_type=jnp.float32) * scale
        if mask.ids:
            doc = doc[0][:, :, None], doc[1][:, None, :]
        seen = mask.seen(q_pos[:, None], k_pos[None, :], *doc)
        s = jnp.where(seen[:, None, None] if mask.ids else seen, s, _MASKED)
        # the row's maximum is made once and kept: left to itself inside
        # a whole step, the compiler recomputed it for every score as a
        # window of 16,383 keys, 47 ms a tile (PERF.md, the hybrid cell)
        top = lax.optimization_barrier(
            lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
        p = jnp.exp(s - top)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(values.dtype), values,
                          preferred_element_type=jnp.float32
                          ).astype(q_rows.dtype)

    q = q.reshape(b, positions, groups, heads // groups, hd)
    out = []
    for (first, end), ranges in key_ranges(mask, tile):
        doc = (ids[:, first:end], cut(ids, ranges)) if mask.ids else ()
        out.append(rows(
            q[:, first:end], cut(k, ranges), cut(v, ranges),
            np.arange(first, end),
            np.concatenate([np.arange(lo, hi) for lo, hi in ranges]), *doc))
    return jnp.concatenate(out, axis=1).reshape(b, positions, heads, hdv)


# ---------------------------------------------------------------------------
# the entry
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _note_path(mask, how: str) -> None:
    logger.info("attention under %s runs %s", mask, how)


def masked_attention(q, k, v, mask, *, scale: float, tile: int, ids=None):
    """Attention of ``q`` ``[B, P, H, hd]`` over ``k`` ``[B, P, G, hd]``
    and ``v`` ``[B, P, G, hdv]`` under ``mask``, every query head reading
    the key/value head of its group, scores times ``scale``; the result
    is ``[B, P, H, hdv]``.  A head has two widths, ``hd`` of its queries
    and keys and ``hdv`` of its values and results, which need not be
    equal (latent attention: keys of 128 + 64 against values of 128).
    ``ids`` int ``[B, P]`` where the mask has data; ``tile`` the queries
    of a tile of the XLA path."""
    hd, groups, hdv = q.shape[3], k.shape[2], v.shape[3]
    span = mask.spans[1]
    use, interpret = backend.pallas_mode()
    blocks = _flash_blocks(span)
    if not use:
        why = "no Pallas on this backend"
    elif not (q.dtype == k.dtype == v.dtype and flash_attention.supports(
            hd, q.dtype, span, *blocks, groups, hdv)):
        why = (f"the kernels take heads of whole 128-lane vectors, or of "
               f"halves of one in pairs, and spans of whole blocks, not "
               f"{groups} heads of {hd} / {hdv}, {q.dtype}, blocks of "
               f"{blocks} over {span} positions")
    else:
        metrics.note_attention_path("pallas")
        _note_path(mask, f"in the Pallas kernels: blocks of {blocks}, "
                         f"{_flash_schedule(mask, *blocks).pairs} pairs")
        return _flash(q, k, v, ids.astype(jnp.int32) if mask.ids else None,
                      (mask, scale, *blocks, interpret))
    metrics.note_attention_path("xla")
    tile = min(tile, span)
    _note_path(mask, f"in XLA tiles of {tile} queries ({why})")
    with jax.named_scope("hvtpu:attention"):
        return _in_rows(q, k, v, ids, mask, scale, tile)


def tiled_attention(q, k, v, *, block_length: int, tile: int):
    """``masked_attention`` of ``q`` ``[B, 2T, H, hd]`` over ``k``, ``v``
    ``[B, 2T, G, hd]`` (``xt ++ x``) under the block-diffusion mask,
    scores times ``1 / sqrt(hd)``."""
    return masked_attention(
        q, k, v, BlockDiffusionMask(q.shape[1] // 2, block_length),
        scale=1.0 / math.sqrt(q.shape[-1]), tile=tile)


def causal_document_attention(q, k, v, segment, *, scale: float,
                              tile: int = _DOCUMENT_TILE):
    """``masked_attention`` of ``q`` ``[B, T, H, hd]`` over ``k`` ``[B,
    T, G, hd]`` and ``v`` ``[B, T, G, hdv]``: a query sees the keys at or
    before it in its own document, ``segment`` ``[B, T]`` the document's
    index at every position."""
    return masked_attention(q, k, v, DocumentMask(q.shape[1]), scale=scale,
                            tile=tile, ids=segment)
