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
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax


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

# Rows of one grouped product.  Every expert's rows are padded up to a
# whole number of tiles so that a tile multiplies by one expert's
# weights; half a tile an expert is wasted on average.
_TILE_ROWS = 512


def _sorted_hits(flat):
    """Indices of the True entries of ``flat``, ascending, then the
    others; a tile that starts among the last reads past them."""
    order = jnp.argsort(~flat, stable=True).astype(jnp.int32)
    return jnp.concatenate([order, jnp.zeros((_TILE_ROWS,), jnp.int32)])


class _Groups(NamedTuple):
    """Rows of a sorted list, group by group, cut into tiles that never
    span two groups."""
    counts: jax.Array       # rows of each group
    starts: jax.Array       # where a group's rows begin in the list
    tiles: jax.Array        # tiles of each group
    tile_ends: jax.Array    # ... and their running count


def _groups(counts, tile_rows) -> _Groups:
    tiles = (counts + tile_rows - 1) // tile_rows
    return _Groups(counts, jnp.cumsum(counts) - counts, tiles,
                   jnp.cumsum(tiles))


class _Plan(NamedTuple):
    by_expert: jax.Array        # the assignments' e * N + n, by expert
    expert_groups: _Groups
    by_token: jax.Array         # their n * E_held + e, by token
    token_groups: _Groups       # a group is a block of consecutive tokens
    dest: jax.Array             # [N * E_held]: an assignment's buffer row


def _tile(groups, t, tile_rows):
    """Tile ``t``: its group, where its rows begin in the sorted list,
    and which of its rows are the group's (the rest belong to the next
    group or to nobody, and are masked)."""
    g = jnp.sum(groups.tile_ends <= t).astype(jnp.int32)
    first = (t - (groups.tile_ends[g] - groups.tiles[g])) * tile_rows
    rows = jnp.arange(tile_rows, dtype=jnp.int32)
    return g, groups.starts[g] + first, first + rows < groups.counts[g]


def _plan(hit, top_k, tile_rows):
    """Where every (token, held expert) assignment of ``hit`` ``[N,
    E_held]`` goes, both ways.

    *By expert*: the assignments sorted by expert, tokens ascending,
    each expert's rows cut into tiles; tile ``t`` is multiplied by one
    expert's weights and its result lies at rows ``t * tile_rows``
    onwards of a buffer in that order (``dest`` says where an
    assignment's row is).  *By token*: the same assignments sorted by
    token, the tokens cut into blocks of ``tile_rows // 2`` and each
    block's rows into tiles, so that a tile's rows add into one block
    of consecutive tokens: the way back needs a gather and a product
    with a 0/1 matrix, and no scatter (which costs microseconds a row
    on the chip: PERF.md, findings of PR 27).

    Returns the plan's arrays and its static sizes ``(tile_rows, tokens
    a block, rows of the buffer)``: the buffer holds the worst case,
    ``min(top_k, E_held)`` rows a token."""
    n, e_held = hit.shape
    block = max(1, tile_rows // 2)
    blocks = -(-n // block)
    by_expert = _sorted_hits(hit.T.reshape(-1))             # e * N + n
    padded = jnp.pad(hit, ((0, blocks * block - n), (0, 0)))
    by_token = _sorted_hits(padded.reshape(-1))             # n * E_held + e
    expert_groups = _groups(hit.sum(axis=0, dtype=jnp.int32), tile_rows)
    token_groups = _groups(
        padded.reshape(blocks, -1).sum(axis=1, dtype=jnp.int32), tile_rows)
    rank = jnp.cumsum(hit, axis=0, dtype=jnp.int32) - hit   # among e's rows
    first_tile = expert_groups.tile_ends - expert_groups.tiles
    dest = (first_tile * tile_rows)[None, :] + rank
    buffer_rows = (n * min(top_k, e_held) // tile_rows + e_held + 1
                   ) * tile_rows
    return (_Plan(by_expert, expert_groups, by_token, token_groups,
                  dest.reshape(-1)), (tile_rows, block, buffer_rows))


def _expert(w, e):
    return lax.dynamic_index_in_dim(w, e, keepdims=False)


def _expert_tile(plan, t, n, tile_rows):
    """Tile ``t`` by expert: the expert, its rows' tokens (clamped where
    the row is not the expert's) and which rows are real."""
    e, start, valid = _tile(plan.expert_groups, t, tile_rows)
    idx = lax.dynamic_slice(plan.by_expert, (start,), (tile_rows,))
    return e, jnp.clip(idx - e * n, 0, n - 1), valid


def _gather_rows(x, tok, valid):
    rows = jnp.take(x, tok, axis=0, mode="clip")
    return jnp.where(valid[:, None], rows, jnp.zeros((), x.dtype))


def _dot(a, b, dims, precision=None):
    return lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


def _to_tokens(rows_buf, scalars_buf, scale, plan, sizes, n, e_held):
    """The way back: ``out[n] = sum_e scale[n, e] * rows_buf[dest[n,
    e]]`` over the assignments (``scale`` None: 1), f32 ``[N, D]``; and,
    where ``scalars_buf`` is given, ``[N, E_held]`` with ``scalars_buf[
    dest[n, e]]`` at the assignments and 0 elsewhere."""
    by_token, token_groups, dest = (plan.by_token, plan.token_groups,
                                    plan.dest)
    tile_rows, block, _ = sizes
    blocks = token_groups.counts.shape[0]
    d = rows_buf.shape[1]
    in_block = jnp.arange(block, dtype=jnp.int32)[:, None]

    def body(t, carry):
        out, out_scalars = carry
        b, start, valid = _tile(token_groups, t, tile_rows)
        flat = lax.dynamic_slice(by_token, (start,), (tile_rows,))
        where = jnp.take(dest, flat, mode="clip")
        z = jnp.take(rows_buf, where, axis=0, mode="clip")
        if scale is not None:
            z = (z.astype(jnp.float32) * jnp.take(
                scale.reshape(-1), flat, mode="clip")[:, None]
                 ).astype(rows_buf.dtype)
        # 0/1: row r of the tile belongs to token i of the block
        mine = (flat // e_held - b * block == in_block) & valid
        part = _dot(mine.astype(z.dtype), z, ((1,), (0,)))
        at = (b * block, 0)
        out = lax.dynamic_update_slice(
            out, lax.dynamic_slice(out, at, (block, d)) + part, at)
        if scalars_buf is not None:
            value = jnp.take(scalars_buf, where, mode="clip")
            expert = flat[:, None] % e_held == jnp.arange(e_held)
            part = _dot(jnp.where(mine, value, 0.0),
                        expert.astype(jnp.float32), ((1,), (0,)),
                        precision=lax.Precision.HIGHEST)
            out_scalars = lax.dynamic_update_slice(
                out_scalars, lax.dynamic_slice(
                    out_scalars, at, (block, e_held)) + part, at)
        return out, out_scalars

    out, out_scalars = lax.fori_loop(
        0, token_groups.tile_ends[-1], body,
        (jnp.zeros((blocks * block, d), jnp.float32),
         jnp.zeros((blocks * block, e_held) if scalars_buf is not None
                   else (), jnp.float32)))
    return out[:n], (out_scalars[:n] if scalars_buf is not None else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped_ffn(sizes, x, weight, plan, w_gate, w_up, w_down):
    """``y[n] = sum_e weight[n, e] * FFN_e(x[n])`` over the assignments
    of ``plan``, a tile of one expert's rows at a time; as many tiles
    as the routing needs, so no row is dropped and none is computed
    that no expert got (beyond the padding of each expert's last tile).
    """
    return _grouped_ffn_fwd(sizes, x, weight, plan, w_gate, w_up, w_down)[0]


def _grouped_ffn_fwd(sizes, x, weight, plan, w_gate, w_up, w_down):
    n, e_held = weight.shape
    tile_rows, _, buffer_rows = sizes

    def body(t, buf):
        e, tok, valid = _expert_tile(plan, t, n, tile_rows)
        with jax.named_scope("hvtpu:moe.dispatch"):
            xt = _gather_rows(x, tok, valid)
        with jax.named_scope("hvtpu:moe.experts"):
            a = _dot(xt, _expert(w_gate, e), ((1,), (0,)))
            b = _dot(xt, _expert(w_up, e), ((1,), (0,)))
            h = (jax.nn.silu(a) * b).astype(x.dtype)
            yt = _dot(h, _expert(w_down, e), ((1,), (0,)))
            return lax.dynamic_update_slice(
                buf, yt.astype(x.dtype), (t * tile_rows, 0))

    buf = lax.fori_loop(0, plan.expert_groups.tile_ends[-1], body,
                        jnp.zeros((buffer_rows, x.shape[1]), x.dtype))
    with jax.named_scope("hvtpu:moe.combine"):
        out, _ = _to_tokens(buf, None, weight, plan, sizes, n, e_held)
    return out.astype(x.dtype), (x, weight, plan, w_gate, w_up, w_down)


def _grouped_ffn_bwd(sizes, res, g):
    x, weight, plan, w_gate, w_up, w_down = res
    n, e_held = weight.shape
    tile_rows, _, buffer_rows = sizes

    def add_to_expert(acc, e, update):
        return lax.dynamic_update_index_in_dim(
            acc, _expert(acc, e) + update, e, axis=0)

    def body(t, carry):
        dx_buf, dweight_buf, dw_gate, dw_up, dw_down = carry
        e, tok, valid = _expert_tile(plan, t, n, tile_rows)
        with jax.named_scope("hvtpu:moe.dispatch"):
            xt = _gather_rows(x, tok, valid)
            gt = _gather_rows(g, tok, valid)
            wt = jnp.take(weight, tok * e_held + e, mode="clip")
        with jax.named_scope("hvtpu:moe.experts"):
            wg, wu, wd = (_expert(w, e) for w in (w_gate, w_up, w_down))
            a = _dot(xt, wg, ((1,), (0,)))
            b = _dot(xt, wu, ((1,), (0,)))
            sig = jax.nn.sigmoid(a)
            s = a * sig
            h = s * b
            dh = _dot(gt, wd, ((1,), (1,)))        # before the weighting
            dwt = jnp.sum(dh * h, axis=-1)
            dh = dh * wt[:, None]
            da = (dh * b * sig * (1.0 + a * (1.0 - sig))).astype(x.dtype)
            db = (dh * s).astype(x.dtype)
            dy = (gt.astype(jnp.float32) * wt[:, None]).astype(x.dtype)
            dw_down = add_to_expert(
                dw_down, e, _dot(h.astype(x.dtype), dy, ((0,), (0,))))
            dw_gate = add_to_expert(dw_gate, e, _dot(xt, da, ((0,), (0,))))
            dw_up = add_to_expert(dw_up, e, _dot(xt, db, ((0,), (0,))))
            dxt = (_dot(da, wg, ((1,), (1,)))
                   + _dot(db, wu, ((1,), (1,))))
            at = t * tile_rows
            dx_buf = lax.dynamic_update_slice(
                dx_buf, dxt.astype(x.dtype), (at, 0))
            dweight_buf = lax.dynamic_update_slice(dweight_buf, dwt, (at,))
        return dx_buf, dweight_buf, dw_gate, dw_up, dw_down

    dx_buf, dweight_buf, dw_gate, dw_up, dw_down = lax.fori_loop(
        0, plan.expert_groups.tile_ends[-1], body,
        (jnp.zeros((buffer_rows, x.shape[1]), x.dtype),
         jnp.zeros((buffer_rows,), jnp.float32),
         *(jnp.zeros(w.shape, jnp.float32)
           for w in (w_gate, w_up, w_down))))
    with jax.named_scope("hvtpu:moe.combine"):
        dx, dweight = _to_tokens(dx_buf, dweight_buf, None, plan, sizes, n,
                                 e_held)
    return (dx.astype(x.dtype), dweight.astype(weight.dtype), None,
            dw_gate.astype(w_gate.dtype), dw_up.astype(w_up.dtype),
            dw_down.astype(w_down.dtype))


_grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


def dropless_topk_moe(
    x: jax.Array,
    gate_w: jax.Array,
    expert_params: Any,
    *,
    top_k: int,
    num_experts: int,
    first_expert: int,
    renormalise: bool,
) -> Tuple[jax.Array, dict]:
    """The part of a top-k expert layer that the experts held here give.

    The layer is told which experts it holds: ``expert_params`` are the
    SiLU-gated weights ``{"w_gate", "w_up": [E_held, D, F], "w_down":
    [E_held, F, D]}`` of experts ``first_expert`` to ``first_expert +
    E_held`` of ``num_experts``.  Every token is routed over all
    ``num_experts`` in f32 (``gate_w`` is ``[D, num_experts]``), keeps
    its ``top_k`` largest probabilities (divided by their sum if
    ``renormalise``), and the assignments whose expert is held here are
    sorted by expert, gathered, multiplied a group at a time and added
    back weighted (``_plan`` says how, without a scatter).  What the experts held elsewhere would add is left
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
    e_held = expert_params["w_gate"].shape[0]
    if not 0 <= first_expert <= num_experts - e_held:
        raise ValueError(
            f"experts {first_expert} to {first_expert + e_held} are not "
            f"among {num_experts}")
    with jax.named_scope("hvtpu:moe.route"):
        logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        top_p, top_i = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        if renormalise:
            top_p = top_p / top_p.sum(axis=-1, keepdims=True)
        held = first_expert + jnp.arange(e_held)
        chosen = top_i[:, :, None] == held                   # [N, k, E_held]
        weight = jnp.sum(jnp.where(chosen, top_p[:, :, None], 0.0), axis=1)
    with jax.named_scope("hvtpu:moe.dispatch"):
        plan, sizes = _plan(chosen.any(axis=1), top_k,
                            min(_TILE_ROWS, x.shape[0]))
    y = _grouped_ffn(
        sizes, x, weight, plan, *(expert_params[k].astype(x.dtype)
                                  for k in ("w_gate", "w_up", "w_down")))
    return y, {"rows_per_expert": plan.expert_groups.counts,
               "experts": top_i}
