"""An expert layer's products over rows sorted by expert: two Pallas
TPU kernels, one a pass, that run every group's rows against the
group's weights with no loop around them, for experts of two forms:
SiLU-gated, three weights, and ungated with a squared ReLU, two.

What a caller brings (``parallel/moe.py`` does): ``xs`` ``[rows, D]``,
the rows of every group lying contiguous, group after group, with the
rows each group has (``counts`` int32 ``[G]``) beside them; the
buffer holds the worst case, and the rows past the last group's are
nobody's and **may hold anything, NaN too**.  ``wt`` ``[rows, lanes]``
float32 weights a row's result and holds a row's weight in every lane
(a ``[rows, 1]`` array takes the room of 128 lanes on the chip all the
same, in a layout XLA's own loops do not write, so that every pass
would copy it; PERF.md, findings of PR 37).  The weights are
``w_gate``, ``w_up`` ``[G, D, F]`` and ``w_down`` ``[G, F, D]``, or
``w_up`` and ``w_down`` alone.

*The walk.*  The buffer is cut into tiles of ``tile_rows`` rows whatever
the groups are, and a kernel's grid is the list of *visits*: a (group,
tile) pair for every tile that holds rows of the group, group after
group, handed over by scalar prefetch (``visits``).  The grid's length
is the number of visits the routing made, a value of the run: the
worst-case buffer costs no grid step beyond the live ones.  A tile in
which one group ends and the next begins is visited twice in a row, its
blocks stay in VMEM between the two, and each visit writes the rows of
its own group alone.  A group's weights are fetched when the group
changes and not again for its further tiles.
``jax.experimental.pallas.ops.tpu.megablox`` is the model for the walk
and does not ship here: a product a call, it leaves ``a``, ``b`` and
``dh`` in HBM in f32 for XLA to pass over whole, worst case and all
(2.9 ms a pass over ``[262144, 768]`` on a v5e, where all eleven
products take 10), and its eleven calls took 11.2 ms where these two
take 9.9 (PERF.md, findings of PR 37).

*The kernels.*  ``forward``: ``y = (silu(xs Wg) * (xs Wu)) Wd * wt``,
three products a visit; ``a``, ``b`` and ``h`` never leave VMEM, and
``y`` is written where ``xs`` was.  ``backward``: ``a``, ``b`` again,
``dh = gs Wd^T``, ``dwt``, ``da``, ``db``, ``dx = da Wg^T + db Wu^T``
(written where ``xs`` was) and the three weight gradients ``dWg = xs^T
da``, ``dWu = xs^T db``, ``dWd = h^T (gs * wt)``, eight products a
visit: a group's three f32 ``[., .]`` sums stay in VMEM across the
group's tiles and are written once (a group with no row is visited
once, to write zeros).  Those sums run over rows, so in a tile that is
not all one group's the other rows of ``xs``, ``gs`` and ``wt`` are set
to 0 *before* any product (zeros in give zeros in every operand of the
three sums: a 0 on one side alone does not make a NaN on the other
harmless); a row's own results depend on that row alone, and a visit
stores its own rows only.  This backward kernel takes the group's whole
inner width at once, its sums as double-buffered output blocks, and
with them three weights twice over: 2 x 3 x D x F x (2 + 4) bytes in
bfloat16, 54 MiB at 2048 x 768, where ``_VMEM_FOR_A_GROUP`` leaves the
tiles room.  Wider gated experts take the blocked kernel below (2304 x
1024 would want 81 MiB here and 54 there, 2048 x 1792 126 here and 84
there).

*The ungated kernels* (``W_down relu(W_up u) ** 2``) walk the same
visits.  ``forward``: ``a = xs Wu``, ``h = relu(a) ** 2``, ``y = (h Wd)
* wt``, two products a visit, the inner width in blocks of
``inner_block(F)`` columns; ``backward``: ``a`` again, ``dh = gs
Wd^T``, ``dwt``, ``da = dh * wt * 2 relu(a)``, ``dx = da Wu^T``, ``dWu
= xs^T da``, ``dWd = h^T (gs * wt)``, five, always blocked.  Such
experts are wide (2688 x 1856 in the cell that has them: a weight 10 MB
in bfloat16, an f32 sum 20), so a group's weights *and* sums, each
twice over, do not fit the 128 MiB of VMEM.

*The blocked backward kernel*, of either form (the form is the number
of weights it is handed).  Nothing of an expert couples two columns of
the inner width, so it walks it in blocks of ``inner_block(F)``
columns (``h[:, blk]`` needs ``Wg[:, blk]`` and ``Wu[:, blk]`` alone,
``dWg[:, blk]``, ``dWu[:, blk]`` and ``dWd[blk, :]`` that block alone;
``dx`` and ``dwt`` add up over the blocks in f32 values of one visit):
the temporaries are a block's, the weights come whole, twice over,
through their block specs, and the f32 sums are **once** in VMEM, in
the kernel's own scratch: a group's last visit sends each block of them
off to HBM by a copy of its own as soon as the block is summed, and the
next group's first visit waits for a block's copy just before it
clears the block, so the copies run beside the products of both
visits.  That is weights x D x F x (2 x 2 + 4) bytes in bfloat16, 78.8
MiB at 2688 x 1920 with two weights and 54 MiB at 2304 x 1024 with
three, under ``_VMEM_FOR_BLOCKS``.  Every visit clears the rows that
are not its group's and stores its own rows only, a whole tile like a
shared one: one body, not the whole-width gated kernels' two (the
selects are a hundredth of a visit; with a second path for whole tiles
the ungated backward kernel took 3.33 ms for 2.33 and was twice the
code), and the walk over the blocks is a loop the compiler keeps
(``lax.fori_loop``, the blocks cut at lane offsets that are multiples
of 128): unrolled, a kernel is three to five times the code for a
tenth less time, and a step whose expert layers are programs of their
own holds a copy of each kernel a layer, which its every start loads.
The ungated ``F`` is a whole number of 128-lane vectors: a caller fills
an odd width with zero columns of ``w_up`` and zero rows of ``w_down``
(``padded_width``), which is exact.  Timed alone on a v5e at the
ungated cell's shape (8 groups, 6,144 uneven live rows of 98,304;
PERF.md, section 6): forward 1.08 ms, backward 2.55 (0.62 and
1.56 at the peak; 0.92 and 2.33 unrolled), where the backward pass as
two kernels (rows with ``da`` and ``h`` to HBM, then the sums through
double-buffered output blocks) took 4.10 and ``lax.ragged_dot`` over
the whole buffers 11.9 and 27.5.

*The arithmetic* is the tile loop's that these replace: operands in
the rows' type, f32 accumulation, ``a`` and ``b`` f32 until ``silu(a) *
b`` (``relu(a) ** 2``) is rounded once, ``dwt`` and the weighting in
f32, the weight gradients summed in f32.

No ``metadata=`` on the ``pallas_call``s: XLA prints it over three
lines of the compiled text, where ``benchmark/scopes.py`` cannot follow
(PERF.md, section 7).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import metrics

_LANES = 128
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
# the gated backward kernel with a group's whole inner width holds the
# group's three f32 [D, F] sums twice over (a block and the one being
# written back) and its three weights twice (a block and the next
# group's on its way): 54 MiB of bfloat16 at 2048 x 768, of the 128 a
# v5e core has, before a tile or a temporary
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_FOR_A_GROUP = 64 * 1024 * 1024
# the blocked one holds the weights twice and their sums once, and its
# temporaries are a block's of the inner width: 78.8 MiB of bfloat16 at
# 2688 x 1920 with two weights, where visits of 128 rows and blocks of
# 384 columns fit the 100 MiB and 256 rows or 640 columns do not (they
# want 120: PERF.md, section 6); 54 MiB at 2304 x 1024 with three, and
# 84 at 2048 x 1792 (the LFM2 cell's), which fit with visits of 128 rows
# and blocks of 256 columns where 94.5 at 2304 x 1792 do not.  The
# tiles' room grows with D: 84 MiB at 4096 x 896 want 100.3 in all
_VMEM_FOR_BLOCKS = 84 * 1024 * 1024
_F_BLOCK = 512


def supports(dtype, d: int, f: int, rows: int, tile_rows: int,
             weights: int = 3) -> bool:
    """Shapes the kernels take: operands the MXU takes, widths of whole
    128-lane vectors, a buffer of whole tiles, tiles of whole vector
    tiles (16 sublanes of bfloat16), and a group's weights and sums
    that leave the tiles room in VMEM, in one backward kernel or the
    other (``_blocked``).  Of the ungated form (``weights`` 2) ``f`` may
    be any: its caller fills it to ``padded_width(f)``."""
    if weights == 2:
        f = padded_width(f)
    return (jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
            and d % _LANES == 0 and f % _LANES == 0
            and tile_rows % 16 == 0 and rows >= tile_rows
            and rows % tile_rows == 0
            and _blocked(dtype, d, f, weights) is not None)


def _blocked(dtype, d: int, f: int, weights: int):
    """Whether the backward kernel walks the inner width in blocks: not
    where a gated group's three weights and three f32 sums fit VMEM
    twice over whole (``_backward_kernel``), else where all of a
    group's weights twice and their sums once do
    (``_blocked_backward_kernel``); ``None`` where neither fits."""
    size = jnp.dtype(dtype).itemsize
    if weights == 3 and 2 * 3 * d * f * (size + 4) <= _VMEM_FOR_A_GROUP:
        return False
    if weights * d * f * (2 * size + 4) <= _VMEM_FOR_BLOCKS:
        return True
    return None


def padded_width(f: int) -> int:
    """The inner width the ungated kernels want of a caller's ``f``:
    whole 128-lane vectors, the further columns of ``w_up`` and rows of
    ``w_down`` zeros (``relu(0) ** 2`` meets a zero row: exact)."""
    return -(-f // _LANES) * _LANES


def inner_block(f: int) -> int:
    """The columns of an expert's inner width a kernel that walks it in
    blocks takes at once: the most whole vectors, up to ``_F_BLOCK``
    lanes, that divide ``f``."""
    return max(b for b in range(_LANES, _F_BLOCK + 1, _LANES) if f % b == 0)


class Visits(NamedTuple):
    """The (group, tile) pairs of a walk, for as many as the worst
    routing makes; ``count`` says how many this one made."""
    offsets: jax.Array      # [G + 1]: group g's rows are offsets[g:g + 2]
    group: jax.Array        # a visit's group
    tile: jax.Array         # ... and its tile of the buffer
    count: jax.Array        # visits in all


def visits(counts, rows: int, tile_rows: int, empty_groups: bool) -> Visits:
    """Every group's tiles in turn.  With ``empty_groups`` a group
    without rows is visited once all the same (its weight gradient has
    to be written: zeros)."""
    groups, tiles = counts.shape[0], rows // tile_rows
    ends = jnp.cumsum(counts)
    starts = ends - counts
    # a group without rows names the tile the walk is at already
    first = jnp.where(counts > 0, starts, jnp.maximum(starts - 1, 0)
                      ) // tile_rows
    per = jnp.where(counts > 0, (ends - 1) // tile_rows - first + 1,
                    int(empty_groups))
    upto = jnp.cumsum(per)
    # a tile is visited once, and once more for every group that starts
    # inside it or has no row.  Which group's a visit is, by comparing
    # with every group's last visit: 16 compares a visit, where a
    # binary search is a loop of gathers
    v = jnp.arange(tiles + groups - 1, dtype=jnp.int32)[:, None]
    g = jnp.minimum(jnp.sum(v >= upto, axis=1, dtype=jnp.int32), groups - 1)
    tile = jnp.sum(jnp.where(g[:, None] == jnp.arange(groups),
                             first + v - (upto - per), 0), axis=1)
    return Visits(jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]), g,
                  jnp.clip(tile, 0, tiles - 1), upto[-1])


def _mine(offsets, group, tile, tile_rows):
    """Of this visit's tile: which rows are the group's ``[tile_rows,
    1]``, whether all are, whether any is."""
    v = pl.program_id(0)
    start, end = offsets[group[v]], offsets[group[v] + 1]
    row = tile[v] * tile_rows + lax.broadcasted_iota(
        jnp.int32, (tile_rows, 1), 0)
    whole = (start <= tile[v] * tile_rows) & (
        (tile[v] + 1) * tile_rows <= end)
    return (row >= start) & (row < end), whole, end > start


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _store_rows(ref, value, mine):
    """``ref[...] = value`` for this visit's own rows: the others are
    the neighbouring group's, already there or yet to come."""
    # f32: a v5e selects no 16-bit vectors
    ref[...] = jnp.where(mine, value.astype(jnp.float32),
                         ref[...].astype(jnp.float32)).astype(ref.dtype)


def _forward_kernel(offsets, group, tile, x_ref, wt_ref, wg_ref, wu_ref,
                    wd_ref, y_ref, *, tile_rows):
    mine, whole, _ = _mine(offsets, group, tile, tile_rows)
    x = x_ref[...]
    a = _dot(x, wg_ref[...])
    b = _dot(x, wu_ref[...])
    h = (jax.nn.silu(a) * b).astype(x.dtype)
    y = _dot(h, wd_ref[...]) * wt_ref[:, :1]

    @pl.when(whole)
    def _():
        y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(jnp.logical_not(whole))
    def _():
        _store_rows(y_ref, y, mine)


def _backward_kernel(offsets, group, tile, x_ref, g_ref, wt_ref, wg_ref,
                     wu_ref, wd_ref, dx_ref, dwt_ref, dwg_ref, dwu_ref,
                     dwd_ref, *, tile_rows):
    v = pl.program_id(0)
    mine, whole, some = _mine(offsets, group, tile, tile_rows)

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != group[v]))
    def _():
        for ref in (dwg_ref, dwu_ref, dwd_ref):
            ref[...] = jnp.zeros_like(ref)

    def visit(own):
        x, g, wt = own(x_ref[...]), own(g_ref[...]), own(wt_ref[:, :1])
        wg, wu = wg_ref[...], wu_ref[...]
        a = _dot(x, wg)
        b = _dot(x, wu)
        sig = jax.nn.sigmoid(a)
        s = a * sig
        h = s * b
        dh = _dot(g, wd_ref[...], _NT)              # before the weighting
        dwt = jnp.sum(dh * h, axis=-1, keepdims=True)
        dh = dh * wt
        da = (dh * b * sig * (1.0 + a * (1.0 - sig))).astype(x.dtype)
        db = (dh * s).astype(x.dtype)
        dy = (g.astype(jnp.float32) * wt).astype(x.dtype)
        dwg_ref[...] += _dot(x, da, _TN)
        dwu_ref[...] += _dot(x, db, _TN)
        dwd_ref[...] += _dot(h.astype(x.dtype), dy, _TN)
        return _dot(da, wg, _NT) + _dot(db, wu, _NT), dwt

    @pl.when(whole)
    def _():
        dx, dwt = visit(lambda rows: rows)
        dx_ref[...] = dx.astype(dx_ref.dtype)
        dwt_ref[...] = jnp.broadcast_to(dwt, dwt_ref.shape)

    @pl.when(some & jnp.logical_not(whole))
    def _():
        # the other rows as zeros give zeros: da, db, dy, dx and dwt
        dx, dwt = visit(lambda rows: jnp.where(
            mine, rows.astype(jnp.float32), 0.0).astype(rows.dtype))
        _store_rows(dx_ref, dx, mine)
        _store_rows(dwt_ref, dwt, mine)

def _relu2_forward_kernel(offsets, group, tile, x_ref, wt_ref, wu_ref, wd_ref,
                          y_ref, *, tile_rows):
    mine, _, _ = _mine(offsets, group, tile, tile_rows)
    f_block = inner_block(wu_ref.shape[1])
    x = x_ref[...]

    def block(j, y):
        at = pl.multiple_of(j * f_block, _LANES)
        r = jax.nn.relu(_dot(x, wu_ref[:, pl.ds(at, f_block)]))
        return y + _dot((r * r).astype(x.dtype),
                        wd_ref[pl.ds(at, f_block), :])

    y = lax.fori_loop(0, wu_ref.shape[1] // f_block, block,
                      jnp.zeros(y_ref.shape, jnp.float32))
    _store_rows(y_ref, y * wt_ref[:, :1], mine)


def _hidden(pre):
    """An expert's hidden row from its first products ``pre`` (f32),
    and what the row's weighted cotangent makes of each of them:
    ``silu(a) * b`` of two (the gated form), ``relu(a) ** 2`` of one."""
    if len(pre) == 2:
        a, b = pre
        sig = jax.nn.sigmoid(a)
        s = a * sig
        return s * b, lambda dh: (dh * b * sig * (1.0 + a * (1.0 - sig)),
                                  dh * s)
    r = jax.nn.relu(pre[0])
    return r * r, lambda dh: (dh * (2.0 * r),)


def _blocked_backward_kernel(offsets, group, tile, x_ref, g_ref, wt_ref,
                             *refs, tile_rows):
    """The backward kernel with the inner width walked in blocks, of
    either form: ``refs`` are the weights (three or two, the last the
    one back to ``D``), ``dx``, ``dwt``, the weights' gradients where
    they lie in HBM, their sums in VMEM, a weight each, and the copies'
    semaphores."""
    n = (len(refs) - 3) // 3
    *w_in, wd_ref = refs[:n]
    dx_ref, dwt_ref = refs[n:n + 2]
    grads, sums, sems = refs[n + 2:2 * n + 2], refs[2 * n + 2:-1], refs[-1]
    v = pl.program_id(0)
    mine, _, _ = _mine(offsets, group, tile, tile_rows)
    start, end = offsets[group[v]], offsets[group[v] + 1]
    # the tile holds the group's first row, its last row (a group
    # without rows is visited once: both)
    first = start >= tile[v] * tile_rows
    last = end <= (tile[v] + 1) * tile_rows
    blocks, _, f_block = sums[0].shape

    def leaving(j):
        """Block ``j`` of the group's sums on its way out."""
        at = pl.multiple_of(j * f_block, _LANES)
        cols, rows = (slice(None), pl.ds(at, f_block)), (pl.ds(at, f_block),)
        return [pltpu.make_async_copy(
                    total.at[j], grad.at[(group[v],) + where], sems.at[k, j])
                for k, (total, grad, where) in enumerate(zip(
                    sums, grads, [cols] * (n - 1) + [rows]))]

    def clear(j):
        """A group's first visit clears block ``j`` of the sums, once
        the group before has let go of it."""
        @pl.when(first)
        def _():
            @pl.when(v > 0)
            def _():
                for copy in leaving(j):
                    copy.wait()
            for total in sums:
                total[j] = jnp.zeros_like(total[j])

    def send(j):
        @pl.when(last)
        def _():
            for copy in leaving(j):
                copy.start()

    def own(rows):
        # the other rows as zeros give zeros: da, db, dy, dx, dwt, the sums
        return jnp.where(mine, rows.astype(jnp.float32), 0.0).astype(
            rows.dtype)

    x, g, wt = own(x_ref[...]), own(g_ref[...]), own(wt_ref[:, :1])
    dy = (g.astype(jnp.float32) * wt).astype(x.dtype)

    def block(j, carry):
        dx, dwt = carry
        at = pl.multiple_of(j * f_block, _LANES)
        w_blk = [w[:, pl.ds(at, f_block)] for w in w_in]
        wd = wd_ref[pl.ds(at, f_block), :]
        h, slopes = _hidden([_dot(x, w) for w in w_blk])
        dh = _dot(g, wd, _NT)                       # before the weighting
        dwt += jnp.sum(dh * h, axis=-1, keepdims=True)
        d_pre = [d.astype(x.dtype) for d in slopes(dh * wt)]
        for d, w in zip(d_pre, w_blk):
            dx += _dot(d, w, _NT)
        clear(j)
        for total, d in zip(sums, d_pre):
            total[j] += _dot(x, d, _TN)
        sums[-1][j] += _dot(h.astype(x.dtype), dy, _TN)
        send(j)
        return dx, dwt

    # a loop the compiler keeps: unrolled, a kernel is five times the
    # code, and a step holds a copy of it for every expert layer
    dx, dwt = lax.fori_loop(
        0, blocks, block, (jnp.zeros(dx_ref.shape, jnp.float32),
                           jnp.zeros((tile_rows, 1), jnp.float32)))
    _store_rows(dx_ref, dx, mine)
    _store_rows(dwt_ref, dwt, mine)

    @pl.when(last & (group[v] == grads[0].shape[0] - 1))
    def _():
        for j in range(blocks):
            for copy in leaving(j):
                copy.wait()


def _call(kernel, name, walk, tile_rows, ins, outs, interpret, scratch=()):
    """``ins`` / ``outs``: (array or its shape and type, ``"rows"``,
    ``"group"`` or ``"hbm"``): cut into this visit's tile of rows, or
    this visit's group's whole matrix, or left where it is for the
    kernel's own copies.  The first result is written where the first
    operand was, tile for tile: a visit has read its tile of the one
    before it writes the other, and a tile visited twice in a row is
    neither fetched nor written in between."""
    def spec(shape, kind):
        if kind == "rows":
            return pl.BlockSpec(
                (tile_rows, shape[1]),
                lambda v, offsets, group, tile: (tile[v], 0))
        if kind == "hbm":
            return pl.BlockSpec(memory_space=pl.ANY)
        return pl.BlockSpec(
            (None,) + tuple(shape[1:]),
            lambda v, offsets, group, tile: (group[v], 0, 0))

    return pl.pallas_call(
        functools.partial(kernel, tile_rows=tile_rows), name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(walk.count,),
            in_specs=[spec(a.shape, kind) for a, kind in ins],
            out_specs=[spec(a.shape, kind) for a, kind in outs],
            scratch_shapes=list(scratch)),
        out_shape=[a for a, _ in outs], interpret=interpret,
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(walk.offsets, walk.group, walk.tile, *(a for a, _ in ins))


def forward(xs, wt, counts, *weights, tile_rows: int,
            interpret: bool = False):
    """``y`` ``[rows, D]`` in ``xs``'s type **in ``xs``'s room**; the
    rows past the last group's are not written.  ``weights``: ``w_gate,
    w_up, w_down``, or ``w_up, w_down`` of the ungated form."""
    walk = visits(counts, xs.shape[0], tile_rows, False)
    return _call(
        _forward_kernel if len(weights) == 3 else _relu2_forward_kernel,
        "hvtpu_grouped_ffn_fwd", walk, tile_rows,
        [(xs, "rows"), (wt, "rows")] + [(w, "group") for w in weights],
        [(jax.ShapeDtypeStruct(xs.shape, xs.dtype), "rows")], interpret)[0]


def backward(xs, gs, wt, counts, *weights, tile_rows: int,
             interpret: bool = False):
    """``gs`` is ``y``'s cotangent in the rows' order.  Returns ``dx``
    ``[rows, D]`` in ``xs``'s type **in ``xs``'s room**, ``dwt`` ``[rows,
    lanes]`` float32, a row's value in every lane (of both, the rows
    past the last group's are not written), and the weights' gradients,
    float32 ``[G, ., .]``."""
    walk = visits(counts, xs.shape[0], tile_rows, True)
    ins = [(xs, "rows"), (gs, "rows"), (wt, "rows")] + [
        (w, "group") for w in weights]
    rows = [(jax.ShapeDtypeStruct(xs.shape, xs.dtype), "rows"),
            (jax.ShapeDtypeStruct(wt.shape, jnp.float32), "rows")]
    sums = [jax.ShapeDtypeStruct(w.shape, jnp.float32) for w in weights]
    _, d, f = weights[0].shape
    blocked = _blocked(xs.dtype, d, f, len(weights))
    metrics.note_moe_backward_kernel("blocked" if blocked else "whole")
    if not blocked:
        return _call(_backward_kernel, "hvtpu_grouped_ffn_bwd", walk,
                     tile_rows, ins, rows + [(a, "group") for a in sums],
                     interpret)
    # the sums in the kernel's own VMEM, a block of the inner width
    # after another, and a semaphore for each block's copy out
    f_block = inner_block(f)
    blocks = f // f_block
    return _call(
        _blocked_backward_kernel, "hvtpu_grouped_ffn_bwd", walk, tile_rows,
        ins, rows + [(a, "hbm") for a in sums], interpret,
        scratch=[pltpu.VMEM((blocks, d, f_block), jnp.float32)] * (
            len(weights) - 1)
        + [pltpu.VMEM((blocks, f_block, d), jnp.float32),
           pltpu.SemaphoreType.DMA((len(weights), blocks))])
