"""Expert parallelism: Switch-style top-1 MoE with all_to_all dispatch.

Out of the reference's scope (SURVEY.md §2.7: EP absent; its
``hvd.alltoall`` is the primitive EP is built from).  TPU-first
formulation per GShard/Switch: routing is dense einsum algebra over
one-hot dispatch/combine tensors (MXU-friendly, static shapes,
capacity-bounded), and the only communication is a pair of
``lax.all_to_all``s over the ``ep`` axis — tokens travel to their
expert's device and back in two ICI hops.

Capacity discipline: each expert accepts at most
``C = ceil(tokens_per_device * capacity_factor / E)`` tokens from each
ep peer; overflow tokens fall through the residual connection (standard
Switch behaviour — keeps every shape static for XLA).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from horovod_tpu.obs import metrics
from horovod_tpu.ops import backend, grouped_ffn, pallas_ops


def switch_route(
    x: jax.Array,
    gate_w: jax.Array,
    num_experts: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-1 routing: returns (dispatch [N,E,C] bool-ish one-hot,
    combine [N,E,C] weights, aux load-balancing loss scalar)."""
    n = x.shape[0]
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), gate_w)
    probs = jax.nn.softmax(logits, axis=-1)  # [N, E]
    expert_idx = jnp.argmax(probs, axis=-1)  # [N]
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(expert_idx, num_experts, dtype=jnp.float32)
    # Position of each token within its expert's queue.
    pos = (jnp.cumsum(onehot, axis=0) - onehot) * onehot  # [N, E]
    pos_in_expert = pos.sum(axis=-1).astype(jnp.int32)  # [N]
    keep = pos_in_expert < capacity
    dispatch = (
        onehot
        * keep[:, None].astype(jnp.float32)
    )[..., None] * jax.nn.one_hot(
        pos_in_expert, capacity, dtype=jnp.float32
    )[:, None, :]  # [N, E, C]
    combine = dispatch * gate[:, None, None]
    # Switch aux loss: E * Σ_e fraction_tokens_e · mean_prob_e.
    frac = onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = num_experts * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def expert_parallel_moe(
    x: jax.Array,
    gate_w: jax.Array,
    expert_params: Any,
    expert_fn: Callable[[Any, jax.Array], jax.Array],
    axis_name: str,
    *,
    num_experts: int,
    capacity_factor: float = 1.25,
) -> Tuple[jax.Array, jax.Array]:
    """Switch-MoE layer over the ``ep`` mesh axis (inside shard_map).

    Args:
      x: local tokens ``[N, D]`` (flatten batch×seq before calling).
      gate_w: router weights ``[D, E]`` (replicated).
      expert_params: pytree stacked ``[E_local, ...]`` — this device's
        ``E_local = E/ep`` experts' params.
      expert_fn: ``(params_one_expert, tokens [C', D]) -> [C', D]``.
      axis_name: the ep mesh axis.
      num_experts: E, total experts across the ep group.

    Returns:
      (output ``[N, D]``, aux load-balancing loss scalar).
    """
    ep = lax.axis_size(axis_name)
    if num_experts % ep != 0:
        raise ValueError(f"E={num_experts} not divisible by ep={ep}")
    e_local = num_experts // ep
    n, d = x.shape
    capacity = max(1, math.ceil(n * capacity_factor / num_experts))

    dispatch, combine, aux = switch_route(x, gate_w, num_experts, capacity)
    # Gather each expert's token queue: [E, C, D].
    sent = jnp.einsum("nec,nd->ecd", dispatch, x.astype(jnp.float32))
    # ep-th of the E dim goes to each peer; received queues stack along
    # capacity: [E, C, D] -> [E_local, ep*C, D].
    recv = lax.all_to_all(
        sent, axis_name, split_axis=0, concat_axis=1, tiled=True
    )
    recv = recv.astype(x.dtype)
    # Run this device's experts over their queues.
    out = jax.vmap(expert_fn)(expert_params, recv)  # [E_local, ep*C, D]
    # Return trip + weighted combine back into token order.
    back = lax.all_to_all(
        out.astype(jnp.float32), axis_name, split_axis=1, concat_axis=0,
        tiled=True,
    )  # [E, C, D]
    y = jnp.einsum("nec,ecd->nd", combine, back)
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Top-k, dropless, over the experts this chip holds
# ---------------------------------------------------------------------------

# Rows of one tile: what a turn of the gathers moves, what a visit of
# the grouped kernels multiplies and what the way back adds into a block
# of tokens.  The experts' rows lie contiguous in the buffers, expert
# after expert with nothing between them: no row is padding, and a tile
# in which one expert's rows end and the next one's begin is multiplied
# once for each.
_TILE_ROWS = 512
# ... and of those, the rows a visit of the grouped kernels multiplies:
# at [2048, 768] and 45,047 rows on a v5e the two kernels took 9.89 ms
# in visits of 128 rows, 13.53 in visits of 256 and 12.73 in visits of
# 512, where the backward kernel's temporaries crowd its three f32
# sums in VMEM (PERF.md, findings of PR 37)
_PRODUCT_ROWS = 128
# lanes of the routing weights' buffer in expert order: a row's weight in
# every lane of one vector, the room and the layout a [rows, 1] array
# gets on the chip anyway
_WEIGHT_LANES = 128


def _row_buffer(rows, width, dtype, near):
    """Room for ``rows`` rows, sized for the routing's worst case and
    **not cleared**: no pass over 1.1 GB of which an even routing uses
    an eighth (PERF.md, findings of PR 31).  What nobody wrote may hold
    anything, NaN too, so every reader leaves the rows that are nobody's
    out *before* any product: a 0 in a 0/1 matrix does not make a NaN
    harmless.

    On a TPU the room is the output of a kernel that does nothing, and
    ``near`` (an array the layer made, left where it is and not read)
    is its operand: ``lax.empty`` there is an ``AllocateBuffer`` with
    no operand, which XLA moves out of a ``scan`` over layers as loop
    invariant, and then copies, whole, in every layer, because the
    layer's loops write into it.  The room exists from when ``near``
    does: the way back hands in the rows it will read, so that its
    output is not held while the experts' products run (135 MB of the
    step's peak in the benchmark's cell)."""
    use, interpret = backend.pallas_mode()
    if not use or interpret or not pallas_ops._mosaic_dtype(dtype):
        return lax.empty((rows, width), dtype)
    # no ``metadata=``: XLA prints it over three lines of the compiled
    # text, where benchmark/scopes.py cannot follow (PERF.md, section 7)
    return pl.pallas_call(
        lambda near_ref, room_ref: None, name="hvtpu_moe_row_buffer",
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((rows, width), dtype))(near)


def buffer_rows(tokens: int, top_k: int, experts_held: int) -> int:
    """Rows of the buffers ``dropless_topk_moe`` keeps in expert order
    for ``tokens`` tokens: the worst case, ``min(top_k, experts_held)``
    rows a token, in whole tiles."""
    tile_rows = min(_TILE_ROWS, tokens)
    return -(-tokens * min(top_k, experts_held) // tile_rows) * tile_rows


class _Plan(NamedTuple):
    # row r of a buffer in expert order is assignment by_expert[r], as
    # e * N + n; the entries past the last assignment are no token's
    by_expert: jax.Array
    counts: jax.Array           # the rows of each expert
    # by block of consecutive tokens, [blocks, a whole number of tiles]:
    block_tokens: jax.Array     # an assignment's token, counted in the block
    block_rows: jax.Array       # ... and its row of the buffer
    block_counts: jax.Array     # the assignments of each block


def _by_block(hit, dest, tile_rows, rows_of_buffer):
    """The assignments of every block of tokens, gathered to the front
    of the block's list in no particular order: ``hit`` and ``dest``
    (an assignment's row of the buffer) ``[blocks, tokens a block,
    E_held]`` -> each assignment's token in its block and its row,
    ``[blocks, a whole number of tiles]``.  One sort a block, all at
    once; where a token and a row fit one int32 together (up to 2**21
    rows of the buffer at 512 tokens a block) they are sorted as one
    key, which costs 0.20 ms where a key with a payload costs 0.53
    (524,288 entries on a v5e: PERF.md, findings of PR 31)."""
    blocks, block, e_held = hit.shape
    width = -(-block * e_held // tile_rows) * tile_rows
    token = jnp.broadcast_to(
        jnp.arange(block, dtype=jnp.int32)[:, None], hit.shape)
    row_bits = int(rows_of_buffer).bit_length()
    packs = int(block).bit_length() + row_bits <= 31

    def lists(a, fill):
        a = jnp.where(hit, a, fill).reshape(blocks, -1)
        return jnp.pad(a, ((0, 0), (0, width - a.shape[1])),
                       constant_values=fill)

    if packs:
        key = lax.sort(lists(token << row_bits | dest, 2 ** 31 - 1),
                       dimension=1, is_stable=False)
        return key >> row_bits, key & (2 ** row_bits - 1)
    return lax.sort((lists(token, block), lists(dest, 0)), dimension=1,
                    num_keys=1, is_stable=False)


def _plan(hit, top_k):
    """Where every (token, held expert) assignment of ``hit`` ``[N,
    E_held]`` goes, both ways.

    *By expert*: the assignments sorted by expert, tokens ascending;
    that order is the buffers' own, so an expert's first row is the sum
    of the counts before it, and the products run a group of rows
    against one expert's weights with the counts as the groups' sizes.
    *By block*: the tokens cut into blocks of ``3/4 tile_rows`` (at an
    even routing a block then has 3/4 of a tile's rows, and one that
    fills a second tile is rare), and of each block the list of its
    assignments, each with its row of that buffer, so that a tile of
    the list adds into one block of consecutive tokens: the way back
    needs a gather and a product with a 0/1 matrix, and no scatter of
    rows (which costs microseconds a row on the chip: PERF.md, findings
    of PR 27).

    Returns the plan's arrays and its static sizes ``(tile_rows, tokens
    a block, rows of the buffer)``: the buffer holds the worst case,
    ``min(top_k, E_held)`` rows a token."""
    n, e_held = hit.shape
    tile_rows = min(_TILE_ROWS, n)
    block = max(1, 3 * tile_rows // 4)
    blocks = -(-n // block)
    rows = buffer_rows(n, top_k, e_held)
    counts = hit.sum(axis=0, dtype=jnp.int32)
    size = n * e_held
    idx = jnp.arange(size, dtype=jnp.int32)
    # the hits' e * N + n ascending, then the others (as index + size):
    # keys that are all different, so one sort of one array; a tile that
    # starts among the last hits reads ``tile_rows`` past them
    by_expert = jnp.concatenate([
        lax.sort(jnp.where(hit.T.reshape(-1), idx, idx + size),
                 is_stable=False), jnp.full((tile_rows,), size, jnp.int32)])
    rank = jnp.cumsum(hit, axis=0, dtype=jnp.int32) - hit   # among e's rows
    dest = (jnp.cumsum(counts) - counts)[None, :] + rank

    def in_blocks(a):
        return jnp.pad(a, ((0, blocks * block - n), (0, 0))).reshape(
            blocks, block, e_held)

    hit = in_blocks(hit)
    block_tokens, block_rows = _by_block(hit, in_blocks(dest), tile_rows,
                                         rows)
    return (_Plan(by_expert, counts, block_tokens, block_rows,
                  hit.sum(axis=(1, 2), dtype=jnp.int32)),
            (tile_rows, block, rows))


def _live_tiles(plan, tile_rows):
    return (plan.counts.sum() + tile_rows - 1) // tile_rows


def _to_experts(arrays, weight, plan, sizes):
    """The way out: the rows of each of ``arrays`` ``[N, D]`` and the
    router's weights ``[N, E_held]``, gathered into buffers in expert
    order, one turn a tile and the tiles that hold assignments only.
    The last tile's further rows get some token's row: nobody's, like
    the rows of the tiles not gathered at all."""
    tile_rows, _, rows = sizes
    n = weight.shape[0]
    by_expert_weight = weight.T.reshape(-1)         # at e * N + n

    def tile(t, bufs):
        at = t * tile_rows
        idx = lax.dynamic_slice(plan.by_expert, (at,), (tile_rows,))
        token = idx % n
        wt = jnp.take(by_expert_weight, idx, mode="clip")
        gathered = [jnp.take(a, token, axis=0, mode="clip") for a in arrays]
        gathered.append(jnp.broadcast_to(wt[:, None],
                                         (tile_rows, _WEIGHT_LANES)))
        return tuple(lax.dynamic_update_slice(buf, tile_of, (at, 0))
                     for buf, tile_of in zip(bufs, gathered))

    # two rooms of one shape made from one operand are one room to XLA,
    # which then copies it, whole: each gets an operand of its own
    return lax.fori_loop(
        0, _live_tiles(plan, tile_rows), tile,
        tuple(_row_buffer(rows, a.shape[1], a.dtype, near)
              for a, near in zip(arrays, (plan.block_counts, plan.counts)))
        + (_row_buffer(rows, _WEIGHT_LANES, weight.dtype,
                       plan.block_counts),))


def _to_assignments(dwt, plan, sizes, n, e_held):
    """``dwt`` ``[rows, lanes]``, a scalar a row of the buffer (in
    every lane), to its (token, expert) ``[N, E_held]``: a scatter of
    scalars costs nanoseconds each, unlike one of rows.  Zero where no
    assignment is."""
    tile_rows = sizes[0]
    live = plan.counts.sum()

    def tile(t, out):
        at = t * tile_rows
        idx = lax.dynamic_slice(plan.by_expert, (at,), (tile_rows,))
        mine = at + jnp.arange(tile_rows, dtype=jnp.int32) < live
        return out.at[jnp.where(mine, idx, n * e_held)].set(
            # the tile as it lies: for a slice of one lane XLA lays the
            # whole buffer out lane by lane first, a copy a layer
            lax.dynamic_slice(dwt, (at, 0), (tile_rows, dwt.shape[1]))[:, 0],
            mode="drop", unique_indices=True, indices_are_sorted=True)

    return lax.fori_loop(
        0, _live_tiles(plan, tile_rows), tile,
        jnp.zeros((n * e_held,), dwt.dtype)).reshape(e_held, n).T


def _to_tokens(rows_buf, plan, sizes, n):
    """The way back: ``out[n] = sum_e rows_buf[row of (n, e)]`` over the
    assignments, ``[N, D]`` in the rows' type, summed in f32.  One turn
    a block of tokens, which gathers the block's rows, adds them by
    token (a product with a 0/1 matrix) and writes the block once; a
    block with more rows than a tile takes further tiles before it is
    written.  Rows of the buffer that no assignment owns are never
    used, whatever they hold."""
    tile_rows, block, _ = sizes
    blocks = plan.block_counts.shape[0]
    d = rows_buf.shape[1]
    in_block = jnp.arange(block, dtype=jnp.int32)[:, None]

    def tile(b, j):
        at = (b, j * tile_rows)
        token = lax.dynamic_slice(plan.block_tokens, at, (1, tile_rows))[0]
        row = lax.dynamic_slice(plan.block_rows, at, (1, tile_rows))[0]
        live = (jnp.arange(tile_rows, dtype=jnp.int32)
                < plan.block_counts[b] - j * tile_rows)
        z = jnp.where(live[:, None],
                      jnp.take(rows_buf, row, axis=0, mode="clip"),
                      jnp.zeros((), rows_buf.dtype))
        # 0/1: row r of the tile belongs to token i of the block
        mine = (token == in_block) & live
        return _dot(mine.astype(z.dtype), z, ((1,), (0,)))

    def one_block(b, out):
        tiles = (plan.block_counts[b] + tile_rows - 1) // tile_rows
        acc = lax.fori_loop(1, tiles, lambda j, acc: acc + tile(b, j),
                            tile(b, 0))
        return lax.dynamic_update_index_in_dim(
            out, acc.astype(out.dtype), b, axis=0)

    out = lax.fori_loop(
        0, blocks, one_block,
        _row_buffer(blocks * block, d, rows_buf.dtype,
                    rows_buf).reshape(blocks, block, d))
    return out.reshape(blocks * block, d)[:n]


def _dot(a, b, dims, precision=None):
    return lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


def products_path(dtype, d: int, f: int, tokens: int, top_k: int,
                  experts_held: int, form: str = "gated") -> str:
    """How the experts' products of a layer of these shapes run, from
    what the code can see: ``"grouped"``, in the Pallas kernels of
    ``ops/grouped_ffn.py``, where ``ops.backend`` compiles kernels (a
    TPU backend, or the interpreter in tests) and the shapes are ones
    the kernels of the experts' ``form`` take (``"gated"``, three
    weights: ``d`` and ``f`` whole 128-lane vectors; ``"relu2"``, two:
    ``d`` alone, the layer fills ``f``; either way rows the MXU takes,
    whole tiles, and a group's weights and sums in VMEM); elsewhere
    ``"ragged_dot"``, the same products over the same buffers as
    ``lax.ragged_dot``."""
    use, _ = backend.pallas_mode()
    return ("grouped" if use and grouped_ffn.supports(
        dtype, d, f, buffer_rows(tokens, top_k, experts_held),
        _product_rows(min(_TILE_ROWS, tokens)),
        weights=3 if form == "gated" else 2) else "ragged_dot")


def _in_whole_vectors(w_up, w_down):
    """An ungated expert's two matrices as its grouped kernels want
    them: the inner width filled to whole 128-lane vectors with zero
    columns of ``w_up`` and zero rows of ``w_down``.  Exact (``relu(0)
    ** 2`` meets a zero row), made of the copies in the rows' type the
    layer makes every step anyway, and the gradient of a fill is a
    slice: the parameters and their gradients keep their shapes."""
    more = grouped_ffn.padded_width(w_up.shape[2]) - w_up.shape[2]
    return (jnp.pad(w_up, ((0, 0), (0, 0), (0, more))),
            jnp.pad(w_down, ((0, 0), (0, more), (0, 0))))


def _product_rows(tile_rows):
    return min(_PRODUCT_ROWS, tile_rows)


def _nobodys_cleared(counts, *bufs):
    """For ``lax.ragged_dot``, which promises nothing about the rows
    past the last group's: those rows of ``bufs`` as zeros."""
    mine = (jnp.arange(bufs[0].shape[0], dtype=jnp.int32)
            < counts.sum())[:, None]
    return [jnp.where(mine, buf, jnp.zeros((), buf.dtype)) for buf in bufs]


def _ragged(lhs, rhs, counts):
    return lax.ragged_dot(lhs, rhs, counts,
                          preferred_element_type=jnp.float32)


def _hidden(pre):
    """What an expert's first products ``pre`` (f32) make of a row, and
    its slope by each of them: ``silu(a) * b`` of two (the gated form),
    ``relu(a) ** 2`` of one."""
    if len(pre) == 2:
        a, b = pre
        sig = jax.nn.sigmoid(a)
        s = a * sig
        return s * b, (b * sig * (1.0 + a * (1.0 - sig)), s)
    r = jax.nn.relu(pre[0])
    return r * r, (2.0 * r,)


def _ragged_forward(xs, wt, counts, *weights):
    *w_in, w_down = weights
    xs, wt = _nobodys_cleared(counts, xs, wt[:, :1])
    h = _hidden([_ragged(xs, w, counts) for w in w_in])[0].astype(xs.dtype)
    return (_ragged(h, w_down, counts) * wt).astype(xs.dtype)


def _ragged_backward(xs, gs, wt, counts, *weights):
    def by_group(lhs, rhs):     # lhs[group's rows].T @ rhs[group's rows]
        return lax.ragged_dot_general(
            lhs, rhs, counts, lax.RaggedDotDimensionNumbers(
                (((0,), (0,)), ((), ())), [0], []),
            preferred_element_type=jnp.float32)

    *w_in, w_down = weights
    lanes = wt.shape[1]
    xs, gs, wt = _nobodys_cleared(counts, xs, gs, wt[:, :1])
    h, slopes = _hidden([_ragged(xs, w, counts) for w in w_in])
    dh = _ragged(gs, w_down.swapaxes(1, 2), counts)  # before the weighting
    dwt = jnp.sum(dh * h, axis=-1, keepdims=True)
    dh = dh * wt
    d_pre = [(dh * slope).astype(xs.dtype) for slope in slopes]
    dy = (gs.astype(jnp.float32) * wt).astype(xs.dtype)
    dx = sum(_ragged(d, w.swapaxes(1, 2), counts)
             for d, w in zip(d_pre, w_in)).astype(xs.dtype)
    return (dx, jnp.broadcast_to(dwt, (dwt.shape[0], lanes)),
            *(by_group(xs, d) for d in d_pre),
            by_group(h.astype(xs.dtype), dy))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped_ffn(sizes, x, weight, plan, weights):
    """``y[n] = sum_e weight[n, e] * FFN_e(x[n])`` over the assignments
    of ``plan``: the rows gathered once into expert order, every product
    one grouped product over all of them with the experts' counts as
    the groups' sizes, so no row is dropped and none is computed that
    no expert got.  ``weights`` are the experts' stacked matrices, the
    last the one back to ``D``: three of the gated form, two of the
    ungated (``_hidden``).  ``sizes`` is the plan's static sizes and how
    the products run (``products_path``).  A row is weighted where it is
    made, so that it travels once each way and the way back only adds.
    """
    return _grouped_ffn_fwd(sizes, x, weight, plan, weights)[0]


def _kernels(path, tile_rows):
    if path != "grouped":
        return _ragged_forward, _ragged_backward
    how = dict(tile_rows=_product_rows(tile_rows),
               interpret=backend.pallas_mode()[1])
    return (functools.partial(grouped_ffn.forward, **how),
            functools.partial(grouped_ffn.backward, **how))


def _grouped_ffn_fwd(sizes, x, weight, plan, weights):
    *sizes, path = sizes
    with jax.named_scope("hvtpu:moe.dispatch"):
        xs, wt = _to_experts((x,), weight, plan, sizes)
    with jax.named_scope("hvtpu:moe.experts"):
        forward, _ = _kernels(path, sizes[0])
        y = forward(xs, wt, plan.counts, *weights)
    with jax.named_scope("hvtpu:moe.combine"):
        out = _to_tokens(y, plan, sizes, x.shape[0])
    return out, (x, weight, plan, weights)


def _grouped_ffn_bwd(sizes, res, g):
    x, weight, plan, weights = res
    *sizes, path = sizes
    with jax.named_scope("hvtpu:moe.dispatch"):
        xs, gs, wt = _to_experts((x, g), weight, plan, sizes)
    with jax.named_scope("hvtpu:moe.experts"):
        _, backward = _kernels(path, sizes[0])
        dx, dwt, *d_weights = backward(xs, gs, wt, plan.counts, *weights)
    with jax.named_scope("hvtpu:moe.combine"):
        dweight = _to_assignments(dwt, plan, sizes, *weight.shape)
        dx = _to_tokens(dx, plan, sizes, x.shape[0])
    return (dx, dweight.astype(weight.dtype), None,
            tuple(dw.astype(w.dtype) for dw, w in zip(d_weights, weights)))


_grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(probs, k):
    """``lax.top_k`` along the last axis of ``[N, E]``, with a gradient
    that selects: ``lax.top_k``'s own scatters N * k scalars into zeros
    (2.3 ms a layer on the v5e, under no scope of the trace), where a
    sum over a 0/1 selection fuses into the softmax's backward pass."""
    return _top_k_fwd(probs, k)[0]


def _top_k_fwd(probs, k):
    values, indices = lax.top_k(probs, k)
    return (values, indices), (indices, probs.shape[-1])


def _top_k_bwd(k, res, cotangents):
    indices, width = res
    chosen = indices[:, :, None] == jnp.arange(width)       # [N, k, E]
    return (jnp.sum(jnp.where(chosen, cotangents[0][:, :, None], 0.0),
                    axis=1),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


def dropless_topk_moe(
    x: jax.Array,
    gate_w: jax.Array,
    expert_params: Any,
    *,
    top_k: int,
    num_experts: int,
    first_expert: int,
    renormalise: bool,
    selection_bias: Optional[jax.Array] = None,
    scale: float = 1.0,
    renormalise_eps: float = 0.0,
) -> Tuple[jax.Array, dict]:
    """The part of a top-k expert layer that the experts held here give.

    The layer is told which experts it holds: ``expert_params`` are the
    stacked weights of experts ``first_expert`` to ``first_expert +
    E_held`` of ``num_experts``, in one of two forms, told apart by
    their names: *gated*, ``{"w_gate", "w_up": [E_held, D, F], "w_down":
    [E_held, F, D]}``, an expert ``W_down (silu(W_gate u) * W_up u)``;
    or *ungated*, ``{"w_up", "w_down"}`` alone, an expert ``W_down
    relu(W_up u) ** 2``.

    Every token is routed over all ``num_experts`` in f32 (``gate_w`` is
    ``[D, num_experts]``) by one of two rules.  Without
    ``selection_bias``: the softmax of the router's logits, a token
    keeping its ``top_k`` largest probabilities.  With it (f32
    ``[num_experts]``): the scores are sigmoids of the logits, the
    ``top_k`` experts are *chosen* by score plus bias and *weighted* by
    the score without it; the bias is a buffer that steers the load and
    gets no gradient.  Either way the kept weights are divided by their
    sum if ``renormalise`` (by their sum plus ``renormalise_eps`` where
    that is not 0: the LFM2 family's ``sum + 1e-6``) and multiplied by
    ``scale``.

    The assignments whose expert is held here are
    sorted by expert, gathered, multiplied a group at a time (in the
    kernels of ``ops/grouped_ffn.py`` where they run, either form:
    ``products_path``;
    ``hvtpu_moe_products_total{path=}`` counts, when a program is traced,
    which it was, ``hvtpu_moe_router_total{rule=}`` and
    ``hvtpu_moe_experts_form_total{form=}`` the rule and the form) and
    added back weighted (``_plan`` says how, without a
    scatter).  What the experts held elsewhere would add is left
    out: the shares of chips that hold disjoint ranges of experts and
    see the same tokens add up to the whole layer.  Nothing is dropped
    and there is no capacity: a routing that sends every token here
    costs ``top_k`` rows a token.  One chip's share runs without an
    exchange.

    Args:
      x: tokens ``[N, D]`` (flatten batch and sequence first).
    Returns:
      (``[N, D]`` in ``x``'s type, the routing: ``rows_per_expert``
      int32 ``[E_held]``, the rows each held expert got, and
      ``experts`` int32 ``[N, top_k]``, every token's choice among all
      ``num_experts``).
    """
    names = (("w_gate", "w_up", "w_down") if "w_gate" in expert_params
             else ("w_up", "w_down"))
    form = "gated" if len(names) == 3 else "relu2"
    e_held = expert_params["w_up"].shape[0]
    if not 0 <= first_expert <= num_experts - e_held:
        raise ValueError(
            f"experts {first_expert} to {first_expert + e_held} are not "
            f"among {num_experts}")
    with jax.named_scope("hvtpu:moe.route"):
        logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        if selection_bias is None:
            top_p, top_i = _top_k(jax.nn.softmax(logits, axis=-1), top_k)
        else:
            scores = jax.nn.sigmoid(logits)
            top_i = lax.top_k(
                scores + lax.stop_gradient(selection_bias), top_k)[1]
            # the chosen scores by a 0/1 selection, as ``_top_k``'s
            # gradient is: a gather's gradient is a scatter of scalars
            top_p = jnp.sum(jnp.where(
                top_i[:, :, None] == jnp.arange(num_experts),
                scores[:, None, :], 0.0), axis=-1)
        if renormalise:
            total = top_p.sum(axis=-1, keepdims=True)
            top_p = top_p / (total + renormalise_eps if renormalise_eps
                             else total)
        if scale != 1.0:
            top_p = scale * top_p
        held = first_expert + jnp.arange(e_held)
        chosen = top_i[:, :, None] == held                   # [N, k, E_held]
        weight = jnp.sum(jnp.where(chosen, top_p[:, :, None], 0.0), axis=1)
        hit = chosen.any(axis=1)
    with jax.named_scope("hvtpu:moe.dispatch"):
        plan, sizes = _plan(hit, top_k)
    path = products_path(x.dtype, x.shape[1],
                         expert_params["w_up"].shape[2], x.shape[0],
                         top_k, e_held, form)
    metrics.note_moe_products(path)
    metrics.note_moe_layer(
        "softmax" if selection_bias is None else "sigmoid_bias", form)
    weights = tuple(expert_params[k].astype(x.dtype) for k in names)
    if path == "grouped" and form == "relu2":
        weights = _in_whole_vectors(*weights)
    y = _grouped_ffn((*sizes, path), x, weight, plan, weights)
    return y, {"rows_per_expert": hit.sum(axis=0, dtype=jnp.int32),
               "experts": top_i}
