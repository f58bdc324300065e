"""The chunked delta rule as two Pallas TPU kernels, forward and
backward, that keep everything a chunk makes on the chip: its
cumulative log-decays, its decayed products, its unit lower triangular
system and the system's inverse, ``W``, ``U`` and the ``d_k x d_v``
state carried from chunk to chunk.

What a caller brings (``models/kimi_linear.py`` does): ``q``, ``k``
``[B, T, H, K]``, ``v`` ``[B, T, H, V]`` in one type (bfloat16 or
float32), ``g`` f32 ``[B, T, H, K]``, ``beta`` f32 ``[B, T, H]`` and
``segment`` int ``[B, T]``, ``T`` whole chunks (the caller pads a tail
of its own document with ``beta`` 0).  The arithmetic is
``models.kimi_linear.chunked_delta_rule``'s, whose docstring has it;
nothing is rounded there that is not rounded here: cumulative sums,
decays, the system, its inverse and the carried state are f32, products
of f32 operands run at ``HIGHEST``, the products with the state take the
compute type and add up in f32, and every decay is ``exp`` of a
difference of two cumulative sums, the later minus the earlier.

*The walk.*  A kernel's grid is ``(B, blocks of heads, chunks)``, the
chunk axis last and ``"arbitrary"``: the state of every head of a block
lives in VMEM scratch from a row's first chunk to its last (the
backward kernel walks the chunks from the last and carries the state's
gradient the same way).  The operands are read where the projections
leave them, ``[B, T, H * K]``, a block of heads' lanes a step, so
nothing is laid out anew around the kernels; ``beta`` comes as ``[B,
H / heads, T, heads]`` (a 2 MB array) and the document starts as an
int ``[B, T, 1]``.  A step walks its heads one at a time.

*A chunk* (``_chunk``), for one head, ``C`` positions: the cumulative
log-decays ``G`` by doubling along the rows; the decayed products ``P_ij
= sum_c q_ic k_jc exp(G_ic - G_jc)`` and the same of ``k_i``: inside a
sub-chunk of ``_SUB`` positions a column ``j`` at a time, the
``_SUB x K`` differences made and summed; across, split at the later
sub-chunk's first position ``f`` (``exp(G_i - G_f) exp(G_f - G_j)``,
both at most 1) as one product a sub-chunk.  The system ``I + A``,
``A_ij = beta_i P^k_ij`` below the diagonal inside a document, is
inverted as ``unit_lower_inverse`` does: the diagonal blocks by
substitution, then doubling.  ``[W | U] = (I + A)^-1 [beta e^G K |
beta V]`` is one product.  The state ``S`` is cut at a document's
start by masks made from the starts inside the chunk.

*The backward kernel* recomputes the chunk from its operands and reads
the state the chunk started from: the forward kernel, built for the
gradient, writes it out (f32 ``[B, H, chunks, K, V]``, 537 MB a layer at
the Kimi Linear cell's shape, live from the layer's recomputed forward
pass to its backward pass).  Walking the states again from the first
chunk would cost a second forward walk in the backward pass for every
chunk; a state a chunk read back costs 64 KB of HBM traffic.  On the
chip a layer's rule takes 16.7 ms forward and 43.2 forward and backward
here, 50.1 and 114.2 in the XLA form (PERF.md, findings of PR 41).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# positions of a sub-chunk: the pairs inside one are summed a channel at
# a time, those across two are products
_SUB = 16
# heads a grid step carries at most: at the Kimi Linear cell's shape one
# head a step took 17.9 ms a layer's forward pass, eight 16.7, sixteen
# 16.6 (PERF.md, findings of PR 41)
_MAX_BLOCK_HEADS = 8
# rows of an f32 vector register: the pairs inside a sub-chunk are made a
# tile of rows at a time, and a tile wholly before a column is skipped
_TILE = 8
_HIGHEST = lax.Precision.HIGHEST
_VMEM_LIMIT = 64 * 1024 * 1024
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def supports(key_dim: int, value_dim: int, chunk: int, dtype) -> bool:
    """Shapes the kernels take: a head's keys and values of whole
    128-lane vectors, a chunk of 16 to 128 positions, a power of two,
    and operands the MXU takes."""
    return (key_dim % _LANES == 0 and value_dim % _LANES == 0
            and chunk in (16, 32, 64, 128)
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32))


def block_heads(heads: int) -> int:
    """Heads a grid step carries: the most, up to ``_MAX_BLOCK_HEADS``,
    that divide ``heads``."""
    return max(d for d in range(1, min(heads, _MAX_BLOCK_HEADS) + 1)
               if heads % d == 0)


# ---------------------------------------------------------------------------
# a chunk of one head, on values in VMEM
# ---------------------------------------------------------------------------

def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _f32(a, b, dims=None):
    """A product of f32 operands, in f32 at ``HIGHEST``."""
    return lax.dot_general(a, b, dims or (((1,), (0,)), ((), ())),
                           precision=_HIGHEST, preferred_element_type=_F32)


def _dot(a, b, dtype, dims=None):
    """A product in the compute type, added up in f32 (at ``HIGHEST``
    where the compute type is f32)."""
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype), dims or (((1,), (0,)), ((), ())),
        precision=_HIGHEST if dtype == _F32 else None,
        preferred_element_type=_F32)


def _cumulative(x, reverse: bool = False):
    """Sums along the rows, each row's and those before it (after it,
    ``reverse``), by doubling."""
    c = x.shape[0]
    row = _iota(x.shape, 0)
    d = 1
    while d < c:
        if reverse:
            x = x + jnp.where(row < c - d, pltpu.roll(x, c - d, 0), 0.0)
        else:
            x = x + jnp.where(row >= d, pltpu.roll(x, d, 0), 0.0)
        d *= 2
    return x


def _as_column(row):
    """``[1, N]`` -> ``[N, 1]``."""
    n = row.shape[1]
    return jnp.sum(jnp.where(_iota((n, n), 0) == _iota((n, n), 1), row, 0.0),
                   axis=1, keepdims=True)


def _as_row(column):
    """``[N, 1]`` -> ``[1, N]``."""
    n = column.shape[0]
    return jnp.sum(jnp.where(_iota((n, n), 0) == _iota((n, n), 1), column,
                             0.0), axis=0, keepdims=True)


def _documents(first):
    """From ``first`` f32 ``[C, 1]`` (1 where a document starts): ``same``
    ``[C, C]`` (positions of one document), ``since_start`` ``[C, 1]``
    (of the document the chunk before ended in), ``to_end`` ``[C, 1]``
    (of the document the chunk ends in) and ``keep`` ``[1, 1]`` (no
    document starts inside the chunk)."""
    c = first.shape[0]
    ri, ci = _iota((c, c), 0), _iota((c, c), 1)
    starts = _as_row(first)
    count = jnp.sum(jnp.where(ci <= ri, starts, 0.0), axis=1, keepdims=True)
    count_row = jnp.sum(jnp.where(ri <= ci, first, 0.0), axis=0,
                        keepdims=True)
    last = count_row[:, c - 1:]
    return (count == count_row, count == 0.0, count == last, last == 0.0)


def _inside(lo: int, sub: int):
    """The pairs ``j <= i`` inside the sub-chunk that starts at ``lo``,
    by tiles of rows ``i`` an f32 vector register high: ``(first row of
    the tile, j, whether some rows of the tile come before j)``."""
    for r in range(lo, lo + sub, _TILE):
        for j in range(lo, min(r + _TILE, lo + sub)):
            yield r, j, j > r


def _decay(cs, r: int, j: int, cut: bool):
    """``exp(cs_i - cs_j)`` for the rows ``i`` of the tile at ``r``,
    zero where ``i < j`` (``cut``)."""
    d = cs[r:r + _TILE] - cs[j:j + 1]
    if not cut:
        return jnp.exp(d)
    lower = _iota((_TILE, 1), 0) >= j - r
    return jnp.where(lower, jnp.exp(jnp.where(lower, d, 0.0)), 0.0)


def _split(q, k, cs, lo: int, sub: int):
    """The pairs of the sub-chunk at ``lo`` with the positions before it,
    the decays split at ``lo``: ``exp(cs_i - cs_lo)`` of its rows, their
    ``q`` and ``k`` times it side by side, and ``exp(cs_lo - cs_j)`` of
    the earlier positions (zero from ``lo`` on)."""
    first = cs[lo:lo + 1]
    earlier = _iota((k.shape[0], 1), 0) < lo
    decay = jnp.where(earlier, jnp.exp(jnp.where(earlier, first - cs, 0.0)),
                      0.0)
    later = jnp.exp(cs[lo:lo + sub] - first)
    return later, jnp.concatenate([q[lo:lo + sub] * later,
                                   k[lo:lo + sub] * later]), decay


def _decayed_products(q, k, cs, sub: int):
    """``sum_c q_ic k_jc exp(cs_ic - cs_jc)`` and the same of ``k_i``,
    f32 ``[C, C]`` for the pairs ``j <= i`` (zeros above the diagonal)."""
    c = q.shape[0]
    col = _iota((_TILE, c), 1)
    to_queries, to_keys = {}, {}
    for lo in range(0, c, sub):
        if lo:
            _, rows, decay = _split(q, k, cs, lo, sub)
            both = _f32(rows, k * decay, _NT)
        else:
            both = jnp.zeros((2 * sub, c), _F32)
        for r in range(lo, lo + sub, _TILE):
            to_queries[r] = both[r - lo:r - lo + _TILE]
            to_keys[r] = both[sub + r - lo:sub + r - lo + _TILE]
        for r, j, cut in _inside(lo, sub):
            to_j = k[j:j + 1] * _decay(cs, r, j, cut)
            at = col == j
            to_queries[r] = jnp.where(at, jnp.sum(
                q[r:r + _TILE] * to_j, axis=1, keepdims=True), to_queries[r])
            to_keys[r] = jnp.where(at, jnp.sum(
                k[r:r + _TILE] * to_j, axis=1, keepdims=True), to_keys[r])
    return (jnp.concatenate([to_queries[r] for r in sorted(to_queries)]),
            jnp.concatenate([to_keys[r] for r in sorted(to_keys)]))


def _decayed_products_vjp(q, k, cs, d_queries, d_keys, sub: int):
    """The gradient of ``_decayed_products`` by ``q``, by ``k`` where it
    stands as ``i`` and as ``j``: ``sum_j dP_ij k_j e_ij``, the same of
    the keys' products, and ``sum_i (dP_ij q_i + dP^k_ij k_i) e_ij``."""
    c, width = k.shape
    dq, dk_row, dk_col = {}, {}, {}
    dk_across = jnp.zeros((c, width), _F32)
    for lo in range(0, c, sub):
        if lo:
            later, rows, decay = _split(q, k, cs, lo, sub)
            d_both = jnp.concatenate([d_queries[lo:lo + sub],
                                      d_keys[lo:lo + sub]])
            to_rows = _f32(d_both, k * decay) * jnp.concatenate(
                [later, later])
            dk_across = dk_across + decay * _f32(d_both, rows, _TN)
        else:
            to_rows = jnp.zeros((2 * sub, width), _F32)
        for r in range(lo, lo + sub, _TILE):
            dq[r] = to_rows[r - lo:r - lo + _TILE]
            dk_row[r] = to_rows[sub + r - lo:sub + r - lo + _TILE]
        for r, j, cut in _inside(lo, sub):
            decay = _decay(cs, r, j, cut)
            a = d_queries[r:r + _TILE, j:j + 1]
            b = d_keys[r:r + _TILE, j:j + 1]
            to_j = k[j:j + 1] * decay
            dq[r] = dq[r] + a * to_j
            dk_row[r] = dk_row[r] + b * to_j
            part = jnp.sum((a * q[r:r + _TILE] + b * k[r:r + _TILE]) * decay,
                           axis=0, keepdims=True)
            dk_col[j] = part if j not in dk_col else dk_col[j] + part
    row_t = _iota((_TILE, 1), 0)
    columns = []
    for r in range(0, c, _TILE):
        tile = jnp.zeros((_TILE, width), _F32)
        for j in range(r, r + _TILE):
            tile = jnp.where(row_t == j - r, dk_col[j], tile)
        columns.append(tile)
    return (jnp.concatenate([dq[r] for r in sorted(dq)]),
            jnp.concatenate([dk_row[r] for r in sorted(dk_row)]),
            jnp.concatenate(columns) + dk_across)


def _unit_lower_inverse(a, sub: int):
    """``(I + a)^-1`` of strictly lower triangular f32 ``a`` ``[C, C]``:
    the diagonal blocks of ``sub`` positions by substitution, a column of
    every block at a time, then doubling: the inverse over blocks of
    ``m`` gives that over blocks of ``2 m`` through ``[[P, 0], [R,
    Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``."""
    c = a.shape[0]
    ri, ci = _iota((c, c), 0), _iota((c, c), 1)
    shift = sub.bit_length() - 1
    blocks = (ri >> shift) == (ci >> shift)
    own = ci & (sub - 1)
    x = (ri == ci).astype(_F32)
    for j in range(sub - 1):
        column = jnp.sum(jnp.where(blocks & (own == j), a, 0.0), axis=1,
                         keepdims=True)
        pivots = sum(x[lo + j:lo + j + 1] for lo in range(0, c, sub))
        x = x - column * jnp.where(blocks, pivots, 0.0)
    while (1 << shift) < c:
        below = (((ri >> (shift + 1)) == (ci >> (shift + 1)))
                 & ((ri >> shift) > (ci >> shift)))
        x = x - _f32(_f32(x, jnp.where(below, a, 0.0)), x)
        shift += 1
    return x


def _chunk(q, k, v, g, beta, first, sub: int):
    """What a chunk of one head makes before it meets the state, all
    f32: ``W``, ``U``, ``e^G q`` and ``e^{G_C - G} k`` cut at the
    documents, the masked products of the queries, the decay of the
    state a channel and what the backward pass needs besides."""
    c, width = k.shape
    ri, ci = _iota((c, c), 0), _iota((c, c), 1)
    same, since_start, to_end, keep = _documents(first)
    cs = _cumulative(g)
    to_queries, to_keys = _decayed_products(q, k, cs, sub)
    system = jnp.where(same & (ri > ci), beta * to_keys, 0.0)
    inverse = _unit_lower_inverse(system, sub)
    from_start = jnp.where(since_start, jnp.exp(cs), 0.0)
    last = cs[c - 1:]
    from_end = jnp.where(to_end, jnp.exp(last - cs), 0.0)
    pre = jnp.concatenate([beta * from_start * k, beta * v], axis=1)
    wu = _f32(inverse, pre)
    return dict(
        cs=cs, same=same, to_keys=to_keys, inverse=inverse, pre=pre, wu=wu,
        w=wu[:, :width], u=wu[:, width:], from_start=from_start,
        from_end=from_end, q_in=from_start * q, k_end=from_end * k,
        pairs=jnp.where(same & (ri >= ci), to_queries, 0.0),
        keep=jnp.where(keep, jnp.exp(last), 0.0))              # [1, K]


def _pseudo_values(parts, state, dtype):
    """What the chunk's positions write after the correction, ``U - W
    S``, from ``state`` f32 ``[K, V]``, the state at the end of the chunk
    before."""
    return parts["u"] - _dot(parts["w"], state, dtype)


def _walk(parts, state, dtype):
    """A chunk against ``state``: ``(o, the state at its end)``."""
    u = _pseudo_values(parts, state, dtype)
    o = (_dot(parts["q_in"], state, dtype)
         + _dot(parts["pairs"], u, dtype))
    return o, (_as_column(parts["keep"]) * state
               + _dot(parts["k_end"].T, u, dtype))


def _chunk_vjp(q, k, v, beta, parts, u, state, d_state, d_o, dtype,
               sub: int):
    """The gradients of one chunk of one head: of ``q, k, v, g, beta``
    and of the state the chunk started from, given those of its result
    ``d_o`` and of the state at its end ``d_state``."""
    c, width = k.shape
    ri, ci = _iota((c, c), 0), _iota((c, c), 1)
    du = (_dot(parts["pairs"].T, d_o, dtype)
          + _dot(parts["k_end"], d_state, dtype))
    d_start = (_as_column(parts["keep"]) * d_state
               + _dot(parts["q_in"].T, d_o, dtype)
               - _dot(parts["w"].T, du, dtype))
    d_q_in = _dot(d_o, state, dtype, _NT)
    d_pairs = _dot(d_o, u, dtype, _NT)
    d_k_end = _dot(u, d_state, dtype, _NT)
    d_w = -_dot(du, state, dtype, _NT)
    d_keep = _as_row(jnp.sum(d_state * state, axis=1, keepdims=True))
    # through e^G q and e^{G_C - G} k
    dq = d_q_in * parts["from_start"]
    dk = d_k_end * parts["from_end"]
    d_cs = d_q_in * parts["q_in"] - d_k_end * parts["k_end"]
    d_last = (jnp.sum(d_k_end * parts["k_end"], axis=0, keepdims=True)
              + d_keep * parts["keep"])
    # through [W | U] = (I + A)^-1 [beta e^G K | beta V]
    d_pre = _f32(parts["inverse"], jnp.concatenate([d_w, du], axis=1), _TN)
    d_system = jnp.where(parts["same"] & (ri > ci),
                         -_f32(d_pre, parts["wu"], _NT), 0.0)
    d_wpre, d_upre = d_pre[:, :width], d_pre[:, width:]
    dv = beta * d_upre
    d_beta = (jnp.sum(d_upre * v, axis=1, keepdims=True)
              + jnp.sum(d_wpre * parts["from_start"] * k, axis=1,
                        keepdims=True)
              + jnp.sum(d_system * parts["to_keys"], axis=1, keepdims=True))
    dk = dk + d_wpre * beta * parts["from_start"]
    d_cs = d_cs + d_wpre * parts["pre"][:, :width]
    # through the decayed products
    dq_p, dk_row, dk_col = _decayed_products_vjp(
        q, k, parts["cs"], jnp.where(parts["same"] & (ri >= ci), d_pairs,
                                     0.0), beta * d_system, sub)
    dq = dq + dq_p
    dk = dk + dk_row + dk_col
    d_cs = (d_cs + q * dq_p + k * (dk_row - dk_col)
            + jnp.where(_iota((c, 1), 0) == c - 1, d_last, 0.0))
    return dq, dk, dv, _cumulative(d_cs, reverse=True), d_beta, d_start


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _head_operands(refs, h, width_k, width_v):
    lanes_k = pl.ds(pl.multiple_of(h * width_k, _LANES), width_k)
    lanes_v = pl.ds(pl.multiple_of(h * width_v, _LANES), width_v)
    q_ref, k_ref, v_ref, g_ref, beta_ref, first_ref = refs
    beta = jnp.sum(jnp.where(_iota(beta_ref.shape, 1) == h, beta_ref[...],
                             0.0), axis=1, keepdims=True)
    return (lanes_k, lanes_v,
            q_ref[:, lanes_k].astype(_F32), k_ref[:, lanes_k].astype(_F32),
            v_ref[:, lanes_v].astype(_F32), g_ref[:, lanes_k], beta,
            first_ref[...].astype(_F32))


def _fwd_kernel(*refs, heads, width_k, width_v, sub, dtype, keep_states):
    ins, (o_ref, *rest) = refs[:6], refs[6:]
    states_ref, state_ref = rest if keep_states else (None, *rest)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, _F32)

    def head(h, carry):
        lanes_k, lanes_v, q, k, v, g, beta, first = _head_operands(
            ins, h, width_k, width_v)
        state = state_ref[h]
        if keep_states:
            states_ref[h] = state
        o, state_ref[h] = _walk(_chunk(q, k, v, g, beta, first, sub),
                                state, dtype)
        o_ref[:, lanes_v] = o.astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, heads, head, 0)


def _bwd_kernel(*refs, heads, width_k, width_v, sub, dtype):
    ins, (states_ref, d_o_ref), outs = refs[:6], refs[6:8], refs[8:13]
    dq_ref, dk_ref, dv_ref, dg_ref, d_beta_ref = outs
    d_state_ref = refs[13]

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state_ref[...] = jnp.zeros(d_state_ref.shape, _F32)

    d_beta_ref[...] = jnp.zeros(d_beta_ref.shape, _F32)

    def head(h, carry):
        lanes_k, lanes_v, q, k, v, g, beta, first = _head_operands(
            ins, h, width_k, width_v)
        parts = _chunk(q, k, v, g, beta, first, sub)
        state = states_ref[h]
        dq, dk, dv, dg, d_beta, d_state_ref[h] = _chunk_vjp(
            q, k, v, beta, parts, _pseudo_values(parts, state, dtype),
            state, d_state_ref[h],
            d_o_ref[:, lanes_v].astype(_F32), dtype, sub)
        dq_ref[:, lanes_k] = dq.astype(dq_ref.dtype)
        dk_ref[:, lanes_k] = dk.astype(dk_ref.dtype)
        dv_ref[:, lanes_v] = dv.astype(dv_ref.dtype)
        dg_ref[:, lanes_k] = dg
        d_beta_ref[...] = jnp.where(_iota(d_beta_ref.shape, 1) == h, d_beta,
                                    d_beta_ref[...])
        return carry

    lax.fori_loop(0, heads, head, 0)


class _Shape:
    """The sizes of one call and the blocks its kernels read."""

    def __init__(self, q, v, chunk: int, interpret: bool):
        self.b, self.t, self.h, self.kd = q.shape
        self.vd = v.shape[-1]
        self.chunk, self.n = chunk, self.t // chunk
        self.heads = block_heads(self.h)
        self.dtype = q.dtype
        self.interpret = interpret

    def spec(self, kind, reverse=False):
        c, hb, n = self.chunk, self.heads, self.n

        def at(i):
            return n - 1 - i if reverse else i

        return {
            "k": pl.BlockSpec((None, c, hb * self.kd),
                              lambda b, j, i: (b, at(i), j)),
            "v": pl.BlockSpec((None, c, hb * self.vd),
                              lambda b, j, i: (b, at(i), j)),
            "beta": pl.BlockSpec((None, None, c, hb),
                                 lambda b, j, i: (b, j, at(i), 0)),
            "first": pl.BlockSpec((None, c, 1),
                                  lambda b, j, i: (b, at(i), 0)),
            "state": pl.BlockSpec((None, hb, None, self.kd, self.vd),
                                  lambda b, j, i: (b, j, at(i), 0, 0)),
        }[kind]

    def call(self, body, name, ins, outs, reverse, *operands, **static):
        return pl.pallas_call(
            functools.partial(body, heads=self.heads, width_k=self.kd,
                              width_v=self.vd, sub=min(_SUB, self.chunk),
                              dtype=self.dtype, **static),
            name=name,
            grid=(self.b, self.h // self.heads, self.n),
            in_specs=[self.spec(kind, reverse) for kind in ins],
            out_specs=[self.spec(kind, reverse) for kind, _ in outs],
            out_shape=[jax.ShapeDtypeStruct(shape, dtype)
                       for (_, (shape, dtype)) in outs],
            scratch_shapes=[pltpu.VMEM((self.heads, self.kd, self.vd), _F32)],
            interpret=self.interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
        )(*operands)


_INS = ("k", "k", "v", "k", "beta", "first")


def _operands(q, k, v, g, beta, segment, shape: _Shape):
    b, t, h = shape.b, shape.t, shape.h
    hb = shape.heads
    before = jnp.concatenate(
        [jnp.full((b, 1), -1, segment.dtype), segment[:, :-1]], axis=1)
    first = ((segment != before)
             | (jnp.arange(t) == 0)[None]).astype(jnp.int32)[..., None]
    return (q.reshape(b, t, -1), k.reshape(b, t, -1), v.reshape(b, t, -1),
            g.astype(_F32).reshape(b, t, -1),
            jnp.moveaxis(beta.astype(_F32).reshape(b, t, h // hb, hb), 2, 1),
            first)


def _forward(operands, shape: _Shape, keep_states: bool):
    b, t = shape.b, shape.t
    outs = [("v", ((b, t, shape.h * shape.vd), shape.dtype))]
    if keep_states:
        outs.append(("state", ((b, shape.h, shape.n, shape.kd, shape.vd),
                               _F32)))
    return shape.call(_fwd_kernel, "hvtpu_delta_rule_fwd", _INS, outs, False,
                      *operands, keep_states=keep_states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
@jax.named_scope("hvtpu:kda.delta")
def _rule(q, k, v, g, beta, segment, chunk, interpret):
    shape = _Shape(q, v, chunk, interpret)
    o, = _forward(_operands(q, k, v, g, beta, segment, shape), shape,
                  keep_states=False)
    return o.reshape(v.shape)


@jax.named_scope("hvtpu:kda.delta")
def _rule_fwd(q, k, v, g, beta, segment, chunk, interpret):
    shape = _Shape(q, v, chunk, interpret)
    operands = _operands(q, k, v, g, beta, segment, shape)
    o, states = _forward(operands, shape, keep_states=True)
    return (o.reshape(v.shape),
            (q, k, v, g, beta, segment, states))


@jax.named_scope("hvtpu:kda.delta")
def _rule_bwd(chunk, interpret, res, d_o):
    q, k, v, g, beta, segment, states = res
    shape = _Shape(q, v, chunk, interpret)
    b, t, h = shape.b, shape.t, shape.h
    operands = _operands(q, k, v, g, beta, segment, shape)
    dq, dk, dv, dg, d_beta = shape.call(
        _bwd_kernel, "hvtpu_delta_rule_bwd", _INS + ("state", "v"),
        [("k", ((b, t, h * shape.kd), q.dtype)),
         ("k", ((b, t, h * shape.kd), k.dtype)),
         ("v", ((b, t, h * shape.vd), v.dtype)),
         ("k", ((b, t, h * shape.kd), _F32)),
         ("beta", ((b, h // shape.heads, t, shape.heads), _F32))],
        True, *operands, states, d_o.reshape(b, t, -1))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape).astype(g.dtype),
            jnp.moveaxis(d_beta, 1, 2).reshape(beta.shape).astype(beta.dtype),
            None)


_rule.defvjp(_rule_fwd, _rule_bwd)


def chunked_delta_rule(q, k, v, g, beta, segment, chunk: int, *,
                       interpret: bool = False):
    """``models.kimi_linear.chunked_delta_rule`` on ``T`` whole chunks
    (``supports`` holds): ``[B, T, H, V]`` in ``q``'s type."""
    return _rule(q, k, v, g, beta, segment.astype(jnp.int32), chunk,
                 interpret)
