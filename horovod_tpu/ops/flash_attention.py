"""Flash attention over the tile pairs a mask leaves work in: three
Pallas TPU kernels (forward, ``dq``, ``dk``/``dv``) that keep a tile
pair's scores in VMEM between the two products.

What a caller brings: ``q`` ``[B, P, H, hd]``, ``k`` ``[B, P, G, hd]``
and ``v`` ``[B, P, G, hdv]`` as the projections leave them (a head has
two widths, of its queries and keys and of its values and results, which
need not be equal: latent attention's 192 against 128; head-minor, the
positions not split into tiles: the kernels' blocks are cut by their
index maps, so nothing is transposed on the way in or out), a ``PairSchedule`` that
lists the (query tile, key tile) pairs that hold work and says of each
whether every query sees every key, and ``seen(q_pos, k_pos)``, the
mask itself, which a kernel evaluates on iotas in the pairs that are
not full.  Nothing here knows which mask it is
(``models/block_diffusion.py`` brings ``allowed``).  A mask may also
be *data*: with ``ids`` ``[B, P]`` (a document's index at every
position, say) ``seen(q_pos, k_pos, q_id, k_id)`` gets the ids of the
tile's queries and keys too, cut into blocks by the same index maps as
``q`` and ``k``, and ``live`` ``[B, query tiles, key tiles]``, computed
in the step from the same data, says which pairs of the schedule a row
need not run at all (``models/hybrid_ssm.py`` brings both).  Without
them the kernels are built as if neither existed.
``jax.experimental.pallas.ops.tpu.splash_attention`` is the model for
the schedule and the oracle in the tests, and does not ship: it hands
``pallas_call`` a ``metadata=``, which XLA prints over three lines of
the compiled text, and ``benchmark/scopes.py`` reads an instruction as
one line (PERF.md, section 7).

*The walk.*  A kernel's grid is ``(B, blocks of heads, pairs)``: the
pairs in the order of the tile that accumulates (the query tile for the
forward and ``dq`` kernels, the key tile for ``dk``/``dv``), handed
over by scalar prefetch, so that a block's index map reads both tiles'
indices from SMEM and no step is spent on a pair without work.  The
accumulators live in VMEM scratch, are cleared at a tile's first pair
and written out at its last.

*What a block holds* is chosen from the shapes alone (``block_heads``).
The query heads of a group are walked inside a step against the one
key/value tile the step fetched, so a partial pair's mask is computed
once for all of them.  Heads of 64 go two key/value heads to a block (a
block's last axis is whole 128-lane vectors) with the query heads of
both, and a head is a 64-lane slice of its block: half of the MXU idles
either way, but nothing is zero-filled or laid out anew around the
kernels (PERF.md, findings of PR 33).  Where a group holds few query
heads, more key/value heads go side by side in a block, up to eight
query heads a step: with one query head a group a step would carry one
head's 512 x 512 pair, 0.7 us of products at a v5e's peak under a
microsecond of a grid step's bookkeeping and a fetch it cannot hide,
and eight to a block took the three kernels from 9.97 to 5.67 ms a
layer use at 16 x 1 x 128 (PERF.md, findings of PR 35).  A head's
result does not depend on which heads share its block, bit for bit.

*A pair a row skips* (``live``) takes its grid step, since the schedule
is static, but runs no body and fetches nothing: beside ``live`` the
step prefetches, a row, the pair whose blocks a step holds
(``held_pairs``: its own where it is live, else the last live pair the
walk passed), and the index maps of the operands that change along the
walk read their tile through it, so the step names the blocks the
pipeline already has and Pallas issues no copy.  The accumulating
tile's blocks and the results read the table itself.  What a skipped
step still costs is its bookkeeping, about a microsecond for the eight
blocks a step names.

*The arithmetic* is ``models.block_diffusion._tiled``'s: f32 scores
scaled after the product, f32 softmax, the products in the operands'
type with f32 accumulation, a masked score ``mask_value``.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import metrics

_LANES = 128
# the most query heads a grid step carries where a block could hold fewer:
# what the hybrid cell's blocks of two key/value heads of 64 carry.  At
# 16 x 1 x 128 the three kernels took 9.97 ms a layer use with 1 a step,
# 7.14 with 2, 6.21 with 4 and 5.67 with 8 (PERF.md, findings of PR 35)
_BLOCK_QUERY_HEADS = 8
# a pair's kind in the tables
PARTIAL, FULL = 1, 2

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
# blocks of 512 x 512 fit the 16 MiB of VMEM a v5e kernel gets unasked;
# 1,024 x 1,024 and 512 x 2,048 do not (Mosaic: out of memory in vmem).
# Asked for always, so that the sizes tried on the chip all compiled
# and a larger block is a constant's change
_VMEM_LIMIT = 64 * 1024 * 1024


class PairSchedule(NamedTuple):
    """The tile pairs with work, twice: in query-tile order and in
    key-tile order.  Each table is int32 ``[3, pairs]``: the query
    tile, the key tile, the pair's kind."""
    block_q: int
    block_kv: int
    by_query: np.ndarray
    by_key: np.ndarray

    @property
    def pairs(self) -> int:
        return self.by_query.shape[1]


def pair_schedule(work: np.ndarray, block_q: int, block_kv: int):
    """``work`` ``[query tiles, key tiles]``: 0 where no query of the
    one sees a key of the other, else ``PARTIAL`` or ``FULL``."""
    qi, kj = np.nonzero(work)                    # sorted by query tile
    by_query = np.stack([qi, kj, work[qi, kj]]).astype(np.int32)
    return PairSchedule(
        block_q, block_kv, by_query,
        by_query[:, np.lexsort((qi, kj))])       # ... and by key tile


def supports(head_dim: int, dtype, positions: int, block_q: int,
             block_kv: int, kv_heads: int,
             value_dim: Optional[int] = None) -> bool:
    """Shapes the kernels take: a head's two widths, ``head_dim`` of its
    queries and keys and ``value_dim`` of its values and results (the
    same without one), each of whole 128-lane vectors, or of whole
    halves of one (64, or 192: latent attention's keys of 128 + 64
    against values of 128) where the key/value heads pair up into whole
    vectors; blocks of whole vector tiles that divide the positions;
    operands the MXU takes."""
    half = _LANES // 2
    widths = {head_dim, head_dim if value_dim is None else value_dim}
    return (all(width % _LANES == 0
                or width % half == 0 and kv_heads % 2 == 0
                for width in widths)
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
            and block_q % _LANES == 0 and block_kv % _LANES == 0
            and positions % block_q == 0 and positions % block_kv == 0)


def _lanes(x, width):
    """``[rows, 128]`` of equal lanes -> ``[rows, width]``."""
    if width <= _LANES:
        return x[:, :width]
    return jnp.tile(x, (1, width // _LANES))


def _optional(refs, live: bool, ids: bool):
    """A kernel's references after the table: ``live``'s (and ``held``'s
    behind it, which only the index maps read) and the two of the ids
    where the call has them, then the rest."""
    refs = list(refs)
    live_ref = refs.pop(0) if live else None
    if live:
        refs.pop(0)
    id_refs = (refs.pop(0), refs.pop(0)) if ids else ()
    return live_ref, id_refs, refs


def _walk(table_ref, live_ref, own: int):
    """This step's pair, and whether it is the first and the last of
    the tile that accumulates (row ``own`` of the table).  A pair this
    row need not run is of no kind."""
    p, n = pl.program_id(2), pl.num_programs(2)
    tile = table_ref[own, p]
    first = (p == 0) | (table_ref[own, jnp.maximum(p - 1, 0)] != tile)
    last = (p == n - 1) | (table_ref[own, jnp.minimum(p + 1, n - 1)] != tile)
    kind = table_ref[2, p]
    if live_ref is not None:
        kind = jnp.where(live_ref[pl.program_id(0), p] != 0, kind, 0)
    return table_ref[0, p], table_ref[1, p], kind, first, last


def _by_kind(kind, pair):
    """``pair(masked)``: a full pair applies no mask."""
    pl.when(kind == FULL)(functools.partial(pair, False))
    pl.when(kind == PARTIAL)(functools.partial(pair, True))


def _seen_tile(seen, first_query, first_key, shape, query_axis: int,
               id_refs):
    """The mask of a tile, queries along ``query_axis``; the ids, where
    there are any, a column of the queries' against a row of the keys'
    or the other way round."""
    return seen(
        first_query + lax.broadcasted_iota(jnp.int32, shape, query_axis),
        first_key + lax.broadcasted_iota(jnp.int32, shape, 1 - query_axis),
        *(ref[...] for ref in id_refs))


class _Columns(NamedTuple):
    """A query head's columns in the blocks of a step: ``q`` in the
    queries' (and ``dq``'s), ``out`` in the results' (and ``d_out``'s),
    ``k`` and ``v`` its key/value head's in the keys' and the values'.
    With one width a head (``hd == hdv``) ``q`` and ``out`` are one
    slice, and ``k`` and ``v``."""
    q: slice
    out: slice
    k: slice
    v: slice


def _columns(r, group, hd, hdv):
    def at(i, width):
        return slice(i * width, (i + 1) * width)
    return _Columns(at(r, hd), at(r, hdv), at(r // group, hd),
                    at(r // group, hdv))


def _heads(k_ref, v_ref, heads, group, hd, hdv):
    """For every query head of a block: its index, its columns
    (``_Columns``), and its key/value head's keys and values, loaded
    once a pair."""
    for r in range(heads):
        cols = _columns(r, group, hd, hdv)
        if r % group == 0:
            k, v = k_ref[:, cols.k], v_ref[:, cols.v]
        yield r, cols, k, v


def _scores(a, b, scale, keep, mask_value):
    s = lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    s = s * scale
    return s if keep is None else jnp.where(keep, s, mask_value)


def _fwd_kernel(table_ref, *refs, heads, group, hd, hdv, scale, seen,
                mask_value, live, ids):
    live_ref, id_refs, (q_ref, k_ref, v_ref, out_ref, lse_ref, *rest) = (
        _optional(refs, live, ids))
    # an operand narrower than f32 brings one more result: ``out``
    # before it is rounded
    *exact_ref, m_scr, l_scr, acc_scr = rest
    bq, bkv = q_ref.shape[0], k_ref.shape[0]
    qi, kj, kind, first, last = _walk(table_ref, live_ref, 0)

    @pl.when(first)
    def _():
        m_scr[...] = jnp.full_like(m_scr, mask_value)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def pair(masked):
        keep = _seen_tile(seen, qi * bq, kj * bkv, (bq, bkv), 0, id_refs) \
            if masked else None
        for r, cols, k, v in _heads(k_ref, v_ref, heads, group, hd, hdv):
            s = _scores(q_ref[:, cols.q], k, scale, keep, mask_value)
            m_old = m_scr[r]
            m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            p = jnp.exp(s - _lanes(m_new, bkv))
            m_scr[r] = m_new
            l_scr[r] = alpha * l_scr[r] + p.sum(axis=-1, keepdims=True)
            acc_scr[:, cols.out] = (
                _lanes(alpha, hdv) * acc_scr[:, cols.out] + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32))

    _by_kind(kind, pair)

    @pl.when(last)
    def _():
        for r in range(heads):
            cols = _columns(r, group, hd, hdv).out
            l = l_scr[r]
            out = acc_scr[:, cols] / _lanes(l, hdv)
            out_ref[:, cols] = out.astype(out_ref.dtype)
            for ref in exact_ref:
                ref[:, cols] = out
            lse_ref[:, r:r + 1] = (m_scr[r] + jnp.log(l))[:, :1]


def _dq_kernel(table_ref, *refs, heads, group, hd, hdv, scale, seen,
               mask_value, live, ids):
    live_ref, id_refs, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_scr) = _optional(refs, live, ids)
    bq, bkv = q_ref.shape[0], k_ref.shape[0]
    qi, kj, kind, first, last = _walk(table_ref, live_ref, 0)

    @pl.when(first)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def pair(masked):
        keep = _seen_tile(seen, qi * bq, kj * bkv, (bq, bkv), 0, id_refs) \
            if masked else None
        for r, cols, k, v in _heads(k_ref, v_ref, heads, group, hd, hdv):
            q = q_ref[:, cols.q]
            p = jnp.exp(_scores(q, k, scale, keep, mask_value)
                        - lse_ref[:, r:r + 1])
            dp = lax.dot_general(do_ref[:, cols.out], v, _NT,
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[:, r:r + 1]) * scale).astype(q.dtype)
            dq_scr[:, cols.q] += jnp.dot(
                ds, k, preferred_element_type=jnp.float32)

    _by_kind(kind, pair)

    @pl.when(last)
    def _():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(table_ref, *refs, heads, group, hd, hdv, scale, seen,
                mask_value, live, ids):
    """Scores transposed, ``[keys, queries]``: every product is then a
    plain or a last-axes one, and ``lse``, ``delta`` and the queries'
    ids lie along the lanes."""
    live_ref, id_refs, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dk_scr, dv_scr) = _optional(
                            refs, live, ids)
    bq, bkv = q_ref.shape[0], k_ref.shape[0]
    qi, kj, kind, first, last = _walk(table_ref, live_ref, 1)

    @pl.when(first)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def pair(masked):
        keep = _seen_tile(seen, qi * bq, kj * bkv, (bkv, bq), 1, id_refs) \
            if masked else None
        for r, cols, k, v in _heads(k_ref, v_ref, heads, group, hd, hdv):
            q, do = q_ref[:, cols.q], do_ref[:, cols.out]
            p = jnp.exp(_scores(k, q, scale, keep, mask_value)
                        - lse_ref[r:r + 1, :])
            dv_scr[:, cols.v] += jnp.dot(p.astype(do.dtype), do,
                                         preferred_element_type=jnp.float32)
            dp = lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[r:r + 1, :]) * scale).astype(q.dtype)
            dk_scr[:, cols.k] += jnp.dot(
                ds, q, preferred_element_type=jnp.float32)

    _by_kind(kind, pair)

    @pl.when(last)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def block_heads(heads: int, kv_heads: int, hd: int,
                hdv: Optional[int] = None):
    """``(query heads, key/value heads)`` of a block, from the shapes
    alone: the fewest key/value heads that fill whole 128-lane vectors
    at both of a head's widths (``hd`` of its keys, ``hdv`` of its
    values: two of 64, and two of 192 against 128), and more of them,
    as many as divide ``kv_heads`` evenly, while their query heads stay
    at or under ``_BLOCK_QUERY_HEADS``.  A group is never divided: the fewest key/value heads bring all their query
    heads, so a group wider than ``_BLOCK_QUERY_HEADS`` (16 query heads
    on one key/value head of 128: the one-mixer stack of
    ``models/hybrid_moe.py``) makes a step of more query heads than
    that, all walked against the one key/value block the step fetched,
    ``dk`` and ``dv`` summed over them in the step (512 x 512 blocks of
    16 x 128 lanes fit the VMEM the kernels ask for: in the forward
    kernel 17 MiB of operands and results in flight, two buffers each,
    and 12 MiB of f32 sums)."""
    group = heads // kv_heads
    fewest = max(_LANES // math.gcd(width, _LANES)
                 for width in (hd, hd if hdv is None else hdv))
    most = max(fewest, _BLOCK_QUERY_HEADS // group)
    kv = max(n for n in range(fewest, most + 1, fewest) if kv_heads % n == 0)
    return kv * group, kv


def held_pairs(live):
    """``live`` ``[B, pairs]`` in a walk's order -> int32 ``[B, pairs]``,
    the pair whose blocks a step holds: its own where it is live, else
    the last live pair the walk passed (the first live one before any
    was), whose blocks the pipeline has already."""
    at = lax.broadcasted_iota(jnp.int32, live.shape, 1)
    last = lax.cummax(jnp.where(live != 0, at, -1), axis=1)
    first = jnp.argmax(live != 0, axis=1).astype(jnp.int32)
    return jnp.where(last < 0, first[:, None], last)


class _Kernels(NamedTuple):
    """What the three kernels of one attention share."""
    batch: int
    positions: int
    groups: int     # blocks of heads (``block_heads``) the heads make
    heads: int      # query heads of a block
    group: int      # query heads of a key/value head
    hd: int         # a head's width in q and k
    hdv: int        # ... and in v and the result
    schedule: PairSchedule
    static: dict    # scale, mask_value, seen: the kernel bodies' keywords
    interpret: bool
    ids: Optional[jax.Array]
    live: Optional[jax.Array]

    @classmethod
    def of(cls, q, k, v, schedule, seen, scale, mask_value, interpret, ids,
           live):
        batch, positions, all_heads, hd = q.shape
        hdv = v.shape[3]
        heads, kv_heads = block_heads(all_heads, k.shape[2], hd, hdv)
        return cls(batch, positions, k.shape[2] // kv_heads, heads,
                   all_heads // k.shape[2], hd, hdv, schedule,
                   dict(scale=scale, mask_value=mask_value, seen=seen),
                   interpret, ids, live)

    @property
    def kv_heads(self) -> int:
        """The key/value heads of a block."""
        return self.heads // self.group

    def operand(self, kind: str, own: int = 0):
        """An operand's shape and how its blocks are cut, by its kind:
        ``wide`` (a block's query heads of a query tile, as wide as ``q``;
        ``wide_v`` as wide as the result), ``narrow`` (its key/value
        heads of a key tile, as wide as ``k``; ``narrow_v`` as ``v``),
        ``column`` and ``row`` (a
        query tile's f32 statistics down the sublanes or along the
        lanes), ``query_ids`` and ``key_ids`` down the sublanes or, with
        ``_row``, along the lanes.  An index map reads the pair's tiles
        from the table, the first of what is prefetched: the tile that
        accumulates (row ``own``) of the step's own pair, the other, with
        ``live``, of the pair the step holds (``held_pairs``, the last
        prefetched), so that a pair a row skips names the blocks the
        pipeline has and nothing is copied for it."""
        bq, bkv = self.schedule.block_q, self.schedule.block_kv
        b, p, g, h = self[:4]
        kv = self.kv_heads

        def tile(row):
            through_held = self.live is not None and row != own

            def of(b, i, table, *flags):
                return table[row, flags[-1][b, i] if through_held else i]
            return of

        tq, tk = tile(0), tile(1)

        def wide(hd):
            return ((b, p, g * h * hd), pl.BlockSpec(
                (None, bq, h * hd), lambda b, g, i, *t: (b, tq(b, i, *t), g)))

        def narrow(hd):
            return ((b, p, g * kv * hd), pl.BlockSpec(
                (None, bkv, kv * hd),
                lambda b, g, i, *t: (b, tk(b, i, *t), g)))

        return {
            "wide": wide(self.hd), "wide_v": wide(self.hdv),
            "narrow": narrow(self.hd), "narrow_v": narrow(self.hdv),
            "column": ((b, g, p, h), pl.BlockSpec(
                (None, None, bq, h),
                lambda b, g, i, *t: (b, g, tq(b, i, *t), 0))),
            "row": ((b, g, h, p), pl.BlockSpec(
                (None, None, h, bq),
                lambda b, g, i, *t: (b, g, 0, tq(b, i, *t)))),
            "query_ids": ((b, p, 1), pl.BlockSpec(
                (None, bq, 1), lambda b, g, i, *t: (b, tq(b, i, *t), 0))),
            "key_ids": ((b, p, 1), pl.BlockSpec(
                (None, bkv, 1), lambda b, g, i, *t: (b, tk(b, i, *t), 0))),
            "query_ids_row": ((b, 1, p), pl.BlockSpec(
                (None, 1, bq), lambda b, g, i, *t: (b, 0, tq(b, i, *t)))),
            "key_ids_row": ((b, 1, p), pl.BlockSpec(
                (None, 1, bkv), lambda b, g, i, *t: (b, 0, tk(b, i, *t)))),
        }[kind]

    def call(self, body, name, own, ins, outs, scratch, *operands):
        """Run ``body`` over the pairs in the order of the tile that
        accumulates (``own``: 0 the query tile, 1 the key tile); ``ins``
        the kinds of ``operands``, ``outs`` the results' ``(kind,
        dtype)``.  ``live``, where there is one, is prefetched beside the
        table in the table's order, and ``held_pairs`` of it; the ids go
        in before ``operands``, the accumulating tile's down the
        sublanes."""
        table = (self.schedule.by_query, self.schedule.by_key)[own]
        prefetch = [jnp.asarray(table)]
        if self.live is not None:
            live = self.live.reshape(self.batch, -1)[
                :, table[0] * self.live.shape[2] + table[1]]
            prefetch += [live, held_pairs(live)]
        if self.ids is not None:
            id_kinds = (("query_ids", "key_ids_row"),
                        ("query_ids_row", "key_ids"))[own]
            ins = id_kinds + ins
            operands = tuple(self.ids.reshape(self.operand(kind)[0])
                             for kind in id_kinds) + operands
        # no ``metadata=``: it would break the instruction over three
        # lines of the compiled text, where the benchmark's scopes cannot
        # follow
        return pl.pallas_call(
            functools.partial(body, heads=self.heads, group=self.group,
                              hd=self.hd, hdv=self.hdv,
                              live=self.live is not None,
                              ids=self.ids is not None, **self.static),
            name=name,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(self.batch, self.groups, self.schedule.pairs),
                in_specs=[self.operand(kind, own)[1] for kind in ins],
                out_specs=[self.operand(kind, own)[1] for kind, _ in outs],
                scratch_shapes=scratch),
            out_shape=[jax.ShapeDtypeStruct(self.operand(kind)[0], dtype)
                       for kind, dtype in outs],
            interpret=self.interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
        )(*prefetch, *operands)


def _flat(x):
    return x.reshape(*x.shape[:2], -1)


def forward(q, k, v, schedule: PairSchedule, seen: Callable, *,
            scale: float, mask_value: float, interpret: bool = False,
            ids: Optional[jax.Array] = None,
            live: Optional[jax.Array] = None):
    """``(out, lse, exact)``: ``out`` like ``q`` with ``v``'s last axis
    (``k`` is as wide as ``q``; ``v`` may be of another width); every row's
    log-sum-exp, f32 ``[B, blocks of heads, P, query heads of a block]``
    (``block_heads``), which only ``backward`` reads; and ``out``
    in f32 as it was before it was rounded to ``q``'s type (``out``
    itself where that is f32), which is what ``backward`` wants.

    ``ids`` int32 ``[B, P]``: ``seen`` then takes the queries' and the
    keys' ids after their positions.  ``live`` int32 ``[B, query tiles,
    key tiles]``: 0 where row ``b`` need not run a pair of the schedule
    because ``seen`` is false all over it; every query tile keeps a pair
    (one with none would divide by a sum of nothing)."""
    ks = _Kernels.of(q, k, v, schedule, seen, scale, mask_value, interpret,
                     ids, live)
    metrics.note_attention_block(ks.heads, ks.kv_heads)
    metrics.note_attention_head_width(ks.hd, ks.hdv)
    f32, bq = jnp.float32, schedule.block_q
    outs = (("wide_v", q.dtype), ("column", f32)) + (
        () if q.dtype == f32 else (("wide_v", f32),))
    out, lse, *exact = ks.call(
        _fwd_kernel, "hvtpu_flash_attention_fwd", 0,
        ("wide", "narrow", "narrow_v"), outs,
        [pltpu.VMEM((ks.heads, bq, _LANES), f32),
         pltpu.VMEM((ks.heads, bq, _LANES), f32),
         pltpu.VMEM((bq, ks.heads * ks.hdv), f32)],
        _flat(q), _flat(k), _flat(v))
    shape = (*q.shape[:3], ks.hdv)
    out = out.reshape(shape)
    return out, lse, exact[0].reshape(shape) if exact else out


def backward(q, k, v, out, lse, d_out, schedule: PairSchedule,
             seen: Callable, *, scale: float, mask_value: float,
             interpret: bool = False, ids: Optional[jax.Array] = None,
             live: Optional[jax.Array] = None):
    """``dq``, ``dk``, ``dv`` in the operands' types, from ``forward``'s
    ``exact`` (as ``out``) and ``lse``, under the same ``ids`` and
    ``live``.  ``delta``, a row's ``sum(d_out * out)``, is an XLA
    reduction: one pass over two arrays.  It takes
    ``out`` before its rounding because ``dp - delta`` cancels: in a
    row with one dominant key nearly all of ``dp`` goes, and what
    ``out``'s rounding to bf16 adds to ``delta`` stays (15 % further
    from an f32 reference in ``dq`` and ``dk`` on the chip, PERF.md,
    findings of PR 28).  XLA gives its own tiles the same: it drops a
    rounding between two of its own fusions (excess precision), which
    it cannot do across a kernel's boundary."""
    ks = _Kernels.of(q, k, v, schedule, seen, scale, mask_value, interpret,
                     ids, live)
    f32 = jnp.float32
    delta = jnp.sum(d_out.astype(f32) * out.astype(f32), axis=-1).reshape(
        ks.batch, ks.positions, ks.groups, ks.heads).transpose(0, 2, 1, 3)
    operands = (_flat(q), _flat(k), _flat(v), _flat(d_out))
    ins = ("wide", "narrow", "narrow_v", "wide_v")
    dq, = ks.call(
        _dq_kernel, "hvtpu_flash_attention_dq", 0,
        ins + ("column", "column"), (("wide", q.dtype),),
        [pltpu.VMEM((schedule.block_q, ks.heads * ks.hd), f32)],
        *operands, lse, delta)
    dk, dv = ks.call(
        _dkv_kernel, "hvtpu_flash_attention_dkv", 1,
        ins + ("row", "row"), (("narrow", k.dtype), ("narrow_v", v.dtype)),
        [pltpu.VMEM((schedule.block_kv, ks.kv_heads * width), f32)
         for width in (ks.hd, ks.hdv)],
        *operands, lse.swapaxes(2, 3), delta.swapaxes(2, 3))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)
