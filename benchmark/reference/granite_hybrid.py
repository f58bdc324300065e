"""Granite 4.0-H's hybrid decoder under the causal next-token loss on
packed documents, written plainly: ``jax.numpy`` in float32, the
state-space recurrence one position at a time (a sequential
``lax.scan`` over time: no chunks, no duality), the attention mask
dense, every product at ``jax.default_matmul_precision("highest")``.
Nothing of the program.

On one row ``ids`` ``[T]`` with ``segment`` ``[T]`` (the document's
index at every position); ``first_t`` is true where a document starts:

* ``h = embedding_multiplier * E[ids]``; every layer ``h = h +
  residual_multiplier * mixer(RMSNorm(h))``, then ``h = h +
  residual_multiplier * W_out (silu(g) * v)``, ``[g, v] = W_in
  RMSNorm(h)``; ``logits = RMSNorm(h) E^T / logits_scaling``.
* Mamba-2 mixer: ``[z, xBC, dt] = W_in u``; ``xBC_t = silu(b + sum_k
  w_k xBC_{t-K+1+k})`` over the taps that stay inside the document;
  ``[x, B, C] = xBC``; ``delta = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; ``H_t = a_t H_{t-1} + delta_t x_t (x) B_t`` with ``a_t
  = 0`` at a document's first position and ``exp(delta_t A)`` elsewhere;
  ``y_t = H_t C_t + D x_t``; ``out = W_out RMSNorm(y * silu(z))``, the
  norm over all inner channels.
* attention: q, k, v, o without bias or positions; scores ``q.k *
  attention_multiplier``; a query sees the keys at or before it in its
  own document; a query head reads key/value head ``head // (query
  heads / key/value heads)``.
* loss: ``sum_t w_t CE(logits_t, ids_{t+1}) / sum w`` over the batch.

Departures from the published model, each the configuration's: the
embedding may hold a slice of the vocabulary's rows (ids and loss over
the slice); ``delta`` is not clamped (``time_step_limit`` (0, inf)).

To fit a chip at the published widths a caller may ask for blocks:
``time_block`` positions of the recurrence, ``query_block`` queries of
the attention and every layer are then recomputed in the backward pass
(``jax.checkpoint``); the numbers are the same.  Rows are run one by
one.

The parameter tree is the program's: ``embed [V, D]``, ``final_norm
[D]``, ``layers`` a list with one entry for every run of neighbouring
layers of one kind, its leaves stacked on a leading axis (``layer_groups``
below says which runs ``layer_types`` makes).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_HIGHEST = jax.default_matmul_precision("highest")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameters' shapes do not say."""
    layer_types: Tuple[str, ...]
    num_heads: int
    num_kv_heads: int
    ssm_heads: int
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    rms_norm_eps: float
    time_block: Optional[int] = None
    query_block: Optional[int] = None
    recompute_layers: bool = False


def layer_groups(layer_types):
    """``[(kind, layers), ...]``: the runs of neighbours of one kind."""
    groups = []
    for kind in layer_types:
        if groups and groups[-1][0] == kind:
            groups[-1][1] += 1
        else:
            groups.append([kind, 1])
    return [tuple(g) for g in groups]


def layers_of(params, sizes: Sizes):
    """(kind, that layer's parameters), a layer at a time."""
    for (kind, n), stacked in zip(layer_groups(sizes.layer_types),
                                  params["layers"]):
        for i in range(n):
            yield kind, jax.tree_util.tree_map(lambda a: a[i], stacked)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def first_of_a_document(segment):
    return jnp.concatenate([jnp.ones((1,), bool),
                            segment[1:] != segment[:-1]])


def recurrence(x, delta, a_head, b_in, c_out, first, time_block=None):
    """``y_t = H_t C_t`` one position at a time.  ``x`` ``[T, H, P]``,
    ``delta`` ``[T, H]``, ``a_head`` ``[H]``, ``b_in``, ``c_out`` ``[T,
    N]``, ``first`` bool ``[T]``."""
    def position(state, at):
        x_t, delta_t, b_t, c_t, first_t = at
        a_t = jnp.where(first_t, 0.0, jnp.exp(delta_t * a_head))
        state = (a_t[:, None, None] * state
                 + (delta_t[:, None] * x_t)[:, :, None] * b_t)
        return state, jnp.sum(state * c_t, axis=-1)

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
        state = (p["conv_w"].shape[1] - inner) // 2
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
        x, b_in, c_out = jnp.split(xbc, [inner, inner + state], axis=-1)
        x = x.reshape(t, heads, inner // heads)
        y = recurrence(
            x, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
            b_in, c_out, first_of_a_document(segment), sizes.time_block)
        y = (y + p["D"][:, None] * x).reshape(t, inner)
        y = rms_norm(y * jax.nn.silu(z), p["gate_norm"], sizes.rms_norm_eps)
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
            s = jnp.einsum("qhd,khd->hqk", q_rows, k) * (
                sizes.attention_multiplier)
            s = jnp.where(mask_rows[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        if sizes.query_block and sizes.query_block < t:
            step = sizes.query_block
            o = jnp.concatenate([
                jax.checkpoint(rows)(q[a:a + step], mask[a:a + step])
                for a in range(0, t, step)])
        else:
            o = rows(q, mask)
        return o.reshape(t, -1) @ p["wo"]


_MIXER = {"mamba": mamba_mixer, "attention": attention_mixer}


def layer(kind, p, h, segment, sizes: Sizes):
    with _HIGHEST:
        h = h + sizes.residual_multiplier * _MIXER[kind](
            p, rms_norm(h, p["norm1"], sizes.rms_norm_eps), segment, sizes)
        g, v = jnp.split(
            rms_norm(h, p["norm2"], sizes.rms_norm_eps) @ p["mlp_in"], 2,
            axis=-1)
        return h + sizes.residual_multiplier * (
            (jax.nn.silu(g) * v) @ p["mlp_out"])


def hidden_states(params, ids, segment, sizes: Sizes):
    """One row ``ids`` ``[T]`` through every layer."""
    h = sizes.embedding_multiplier * params["embed"][ids]
    for kind, p in layers_of(params, sizes):
        run = lambda p, h, kind=kind: layer(kind, p, h, segment, sizes)
        h = (jax.checkpoint(run) if sizes.recompute_layers else run)(p, h)
    return h


def logits_of(params, hidden, sizes: Sizes):
    with _HIGHEST:
        return rms_norm(hidden, params["final_norm"], sizes.rms_norm_eps
                        ) @ params["embed"].T / sizes.logits_scaling


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
