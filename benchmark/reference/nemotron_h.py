"""Nemotron-H's stack of one-mixer layers (Nemotron 3 Nano 30B-A3B)
under the causal next-token loss on packed documents, written plainly:
``jax.numpy`` in float32, the state-space recurrence one position at a
time (a sequential ``lax.scan`` over time: no chunks, no duality), the
attention mask dense, every expert over every token with a 0/1 choice,
every product at ``jax.default_matmul_precision("highest")``.  No
kernels, no tiling, no ``custom_vjp``, nothing of the program.

On one row ``ids`` ``[T]`` with ``segment`` ``[T]`` (the document's
index at every position); ``first_t`` is true where a document starts:

* ``h = E[ids]`` (not scaled); every layer ``h = h + mixer(RMSNorm(h))``,
  one norm, one mixer; ``logits = RMSNorm(h) W_head`` (untied).
* ``M``, Mamba-2: ``[z, xBC, dt] = u W_in``; ``xBC_t = silu(b + sum_k
  w_k xBC_{t-K+1+k})`` over the taps that stay inside the document;
  ``x`` in ``H`` heads, ``B`` and ``C`` in ``G`` groups of the state's
  size, head ``h`` reading group ``h // (H / G)``; ``delta = softplus(dt
  + dt_bias)``, ``A = -exp(A_log)``; ``H_t = a_t H_{t-1} + delta_t x_t
  (x) B_t`` with ``a_t = 0`` at a document's first position and
  ``exp(delta_t A)`` elsewhere; ``y_t = H_t C_t + D x_t``; ``out = W_out
  (w * GroupRMSNorm(y * silu(z)))``, each of the ``G`` groups of ``inner
  / G`` channels by its own mean square (gate before norm).
* ``*``, attention: q, k, v, o without bias or positions; scores ``q.k
  / sqrt(head_dim)``; a query sees the keys at or before it in its own
  document; a query head reads key/value head ``head // (query heads /
  key/value heads)``.
* ``E``, experts: ``s = sigmoid(u W_r)`` over all the router's experts;
  the ``top_k`` largest of ``s + b`` are chosen (``b`` the selection
  bias); their weights are ``s`` without ``b``, divided by their sum
  (``norm_topk_prob``), times ``routed_scaling_factor``; expert ``i`` is
  ``W_down,i relu(W_up,i u) ** 2``; ``out = sum_i w_i E_i(u) + S(u)``
  with ``S`` a shared expert of the same form.  The sum runs over the
  experts *held* (the parameters hold ``w_up [E_held, D, F]``: experts
  ``first_expert`` to ``first_expert + E_held`` of the router's): what
  the others would add is left out.  With every expert held it is the
  whole layer.
* loss: ``sum_t w_t CE(logits_t, ids_{t+1}) / sum w`` over the batch.

Departures from the published model, each the configuration's: the
embedding and the head may hold a slice of the vocabulary's rows (ids
and loss over the slice); ``delta`` is not clamped; no group-limited
routing (``n_group = topk_group = 1``); ``1e-20`` is not added to the
weights' sum (six sigmoids in f32 never come near it).

To fit a chip at the published widths a caller may ask for blocks:
``time_block`` positions of the recurrence, ``query_block`` queries of
the attention and every layer are then recomputed in the backward pass
(``jax.checkpoint``); the numbers are the same.  Rows are run one by
one.

The parameter tree is the program's: ``embed [V, D]``, ``head [D, V]``,
``final_norm [D]``, ``layers`` a list with one entry for every run of
neighbouring layers of one kind, its leaves stacked on a leading axis
(``layer_groups`` below says which runs a pattern makes).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

_HIGHEST = jax.default_matmul_precision("highest")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameters' shapes do not say."""
    pattern: str                 # "M" | "*" | "E", a layer each
    num_heads: int
    num_kv_heads: int
    ssm_heads: int
    ssm_groups: int
    first_expert: int
    top_k: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rms_norm_eps: float
    time_block: Optional[int] = None
    query_block: Optional[int] = None
    recompute_layers: bool = False


def layer_groups(pattern):
    """``[(letter, layers), ...]``: the runs of neighbours of one kind."""
    groups = []
    for letter in pattern:
        if groups and groups[-1][0] == letter:
            groups[-1][1] += 1
        else:
            groups.append([letter, 1])
    return [tuple(g) for g in groups]


def layers_of(params, sizes: Sizes):
    """(letter, that layer's parameters), a layer at a time."""
    for (letter, n), stacked in zip(layer_groups(sizes.pattern),
                                    params["layers"]):
        for i in range(n):
            yield letter, jax.tree_util.tree_map(lambda a: a[i], stacked)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def first_of_a_document(segment):
    return jnp.concatenate([jnp.ones((1,), bool),
                            segment[1:] != segment[:-1]])


def recurrence(x, delta, a_head, b_in, c_out, first, time_block=None):
    """``y_t = H_t C_t`` one position at a time.  ``x`` ``[T, H, P]``,
    ``delta`` ``[T, H]``, ``a_head`` ``[H]``, ``b_in``, ``c_out`` ``[T,
    G, N]`` (head ``h`` reads group ``h // (H / G)``), ``first`` bool
    ``[T]``."""
    per_group = x.shape[1] // b_in.shape[1]

    def position(state, at):
        x_t, delta_t, b_t, c_t, first_t = at
        b_t, c_t = (jnp.repeat(a, per_group, axis=0) for a in (b_t, c_t))
        a_t = jnp.where(first_t, 0.0, jnp.exp(delta_t * a_head))
        state = (a_t[:, None, None] * state
                 + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    t = x.shape[0]
    state = jnp.zeros((*x.shape[1:], b_in.shape[-1]), jnp.float32)
    inputs = (x, delta, b_in, c_out, first)
    if not time_block or time_block >= t:
        return jax.lax.scan(position, state, inputs)[1]
    if t % time_block:
        raise ValueError(f"{t} positions are no whole blocks of {time_block}")
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape(t // time_block, time_block, *a.shape[1:]),
        inputs)
    y = jax.lax.scan(
        jax.checkpoint(lambda s, block: jax.lax.scan(position, s, block)),
        state, blocks)[1]
    return y.reshape(t, *y.shape[2:])


def mamba_mixer(p, u, segment, sizes: Sizes):
    with _HIGHEST:
        t = u.shape[0]
        inner, heads = p["gate_norm"].shape[0], sizes.ssm_heads
        groups = sizes.ssm_groups
        state = (p["conv_w"].shape[1] - inner) // (2 * groups)
        z, xbc, dt = jnp.split(u @ p["in_proj"],
                               [inner, inner + p["conv_w"].shape[1]], axis=-1)
        taps = p["conv_w"].shape[0]
        conv = jnp.broadcast_to(p["conv_b"], xbc.shape)
        for back in range(taps):
            if back >= t:
                break
            earlier = jnp.concatenate(
                [jnp.zeros((back, xbc.shape[1])), xbc[:t - back]])
            inside = jnp.concatenate(
                [jnp.zeros((back,), bool),
                 segment[back:] == segment[:t - back]])
            conv = conv + jnp.where(
                inside[:, None], earlier, 0.0) * p["conv_w"][taps - 1 - back]
        xbc = jax.nn.silu(conv)
        x, b_in, c_out = jnp.split(
            xbc, [inner, inner + groups * state], axis=-1)
        x = x.reshape(t, heads, inner // heads)
        y = recurrence(
            x, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
            b_in.reshape(t, groups, state), c_out.reshape(t, groups, state),
            first_of_a_document(segment), sizes.time_block)
        y = (y + p["D"][:, None] * x).reshape(t, inner)
        gated = (y * jax.nn.silu(z)).reshape(t, groups, inner // groups)
        y = rms_norm(gated, p["gate_norm"].reshape(groups, -1),
                     sizes.rms_norm_eps).reshape(t, inner)
        return y @ p["out_proj"]


def dense_mask(segment):
    """bool ``[T, T]``: may query ``i`` see key ``j``."""
    at = jnp.arange(segment.shape[0])
    return (at[None, :] <= at[:, None]) & (
        segment[:, None] == segment[None, :])


def attention_mixer(p, u, segment, sizes: Sizes):
    with _HIGHEST:
        t = u.shape[0]
        hd = p["wq"].shape[1] // sizes.num_heads
        q = (u @ p["wq"]).reshape(t, sizes.num_heads, hd)
        k = (u @ p["wk"]).reshape(t, sizes.num_kv_heads, hd)
        v = (u @ p["wv"]).reshape(t, sizes.num_kv_heads, hd)
        rep = sizes.num_heads // sizes.num_kv_heads
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        mask = dense_mask(segment)

        def rows(q_rows, mask_rows):
            s = jnp.einsum("qhd,khd->hqk", q_rows, k) * hd ** -0.5
            s = jnp.where(mask_rows[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        if sizes.query_block and sizes.query_block < t:
            # a block of queries at a time, one after the other (a loop
            # the compiler cannot run side by side: sixteen blocks'
            # scores at once are 14 GB at the published widths)
            step = sizes.query_block
            if t % step:
                raise ValueError(
                    f"{t} positions are no whole blocks of {step}")
            o = jax.lax.map(
                lambda block: jax.checkpoint(rows)(*block),
                (q.reshape(t // step, step, *q.shape[1:]),
                 mask.reshape(t // step, step, t))).reshape(q.shape)
        else:
            o = rows(q, mask)
        return o.reshape(t, -1) @ p["wo"]


def relu2_expert(u, w_up, w_down):
    with _HIGHEST:
        return jnp.square(jax.nn.relu(u @ w_up)) @ w_down


def routing_weights(p, u, sizes: Sizes):
    """``[T, E]``: a token's weight of every expert of the router, zero
    for the experts it did not choose."""
    with _HIGHEST:
        s = jax.nn.sigmoid(u @ p["router"])
    # the top_k largest of s + b, the lower index first among equals
    order = jnp.argsort(-(s + p["router_bias"]), axis=-1, stable=True)
    choice = jnp.sum(jax.nn.one_hot(
        order[:, :sizes.top_k], s.shape[-1], dtype=s.dtype), axis=1)
    w = choice * s
    if sizes.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sizes.routed_scaling_factor * w


def expert_mixer(p, u, segment, sizes: Sizes):
    """The experts held (``p["w_up"]``'s leading axis, from
    ``first_expert``), every one over every token, and the shared
    expert."""
    del segment
    w = routing_weights(p, u, sizes)
    y = relu2_expert(u, p["shared_up"], p["shared_down"])
    for e in range(p["w_up"].shape[0]):
        y = y + w[:, sizes.first_expert + e, None] * relu2_expert(
            u, p["w_up"][e], p["w_down"][e])
    return y


_MIXER = {"M": mamba_mixer, "*": attention_mixer, "E": expert_mixer}


def layer(letter, p, h, segment, sizes: Sizes):
    return h + _MIXER[letter](
        p, rms_norm(h, p["norm"], sizes.rms_norm_eps), segment, sizes)


def hidden_states(params, ids, segment, sizes: Sizes):
    """One row ``ids`` ``[T]`` through every layer."""
    h = params["embed"][ids]
    for letter, p in layers_of(params, sizes):
        run = lambda p, h, letter=letter: layer(letter, p, h, segment, sizes)
        h = (jax.checkpoint(run) if sizes.recompute_layers else run)(p, h)
    return h


def logits_of(params, hidden, sizes: Sizes):
    with _HIGHEST:
        return rms_norm(hidden, params["final_norm"], sizes.rms_norm_eps
                        ) @ params["head"]


def row_loss_sum(params, ids, segment, w, sizes: Sizes):
    """One row's ``sum_t w_t CE(logits_t, ids_{t+1})``."""
    logp = jax.nn.log_softmax(logits_of(
        params, hidden_states(params, ids, segment, sizes), sizes))
    ce = -jnp.take_along_axis(logp, jnp.roll(ids, -1)[:, None], axis=-1)[:, 0]
    return jnp.sum(w.astype(jnp.float32) * ce)


def loss(params, batch, sizes: Sizes):
    """The batch's weighted mean, a row at a time."""
    total = sum(
        row_loss_sum(params, batch["x"][i], batch["segment"][i],
                     batch["w"][i], sizes)
        for i in range(batch["x"].shape[0]))
    return total / jnp.sum(batch["w"].astype(jnp.float32))


def loss_and_gradient(params, batch, sizes: Sizes):
    """``(loss, gradient tree)``, a row at a time: one jitted program,
    run once a row, each row's gradient fetched to the host and added
    up there (at the published widths a chip holds one beside the
    parameters, not two)."""
    import numpy as np

    weight = float(np.sum(np.asarray(batch["w"], np.float32)))
    one = jax.jit(jax.value_and_grad(
        lambda p, x, segment, w: row_loss_sum(p, x, segment, w, sizes)
        / weight))
    total, grads = 0.0, None
    for i in range(batch["x"].shape[0]):
        value, g = one(params, batch["x"][i], batch["segment"][i],
                       batch["w"][i])
        total = total + float(value)
        g = jax.tree_util.tree_map(np.asarray, g)
        grads = g if grads is None else jax.tree_util.tree_map(
            np.add, grads, g)
    return total, grads
