"""Kimi Linear's stack (Kimi-Linear-48B-A3B; arXiv:2510.26692) under the
causal next-token loss on packed documents, written plainly:
``jax.numpy`` in float32, the delta rule one position at a time (a
sequential ``lax.scan`` over time: no chunks, no triangular system), the
attention mask dense, every held expert over every token with a 0/1
choice, every product at ``jax.default_matmul_precision("highest")``.
No kernels, no tiling, no ``custom_vjp``, nothing of the program.

On one row ``ids`` ``[T]`` with ``segment`` ``[T]`` (the document's
index at every position); ``first_t`` is true where a document starts:

* ``h = E[ids]``; every layer ``h = h + mixer(RMSNorm(h))`` and then ``h
  = h + ffn(RMSNorm(h))``; ``logits = RMSNorm(h) W_head`` (untied).
* ``kda``: ``[q~, k~, v~]_t = silu(sum_j w_j ([W_q, W_k, W_v] x)_{t-K+1+j})``
  over the taps that stay inside the document (no bias); in heads of
  ``d`` channels ``q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(d)``, ``k = k~ /
  sqrt(|k~|^2 + 1e-6)``; ``g_t = -exp(A_log_h) softplus(W_f_up W_f_down
  x_t + dt_bias)``, a log-decay for every channel of every head; ``beta_t
  = sigmoid(x_t W_beta)``, one a head; with ``S`` a head's ``d x d``
  state, zero before a document's first position:
  ``S' = Diag(exp g_t) S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T
  k_t)^T`` (which is ``(I - beta_t k_t k_t^T) S' + beta_t k_t v_t^T``),
  ``o_t = S_t^T q_t``; ``y = W_o [RMSNorm_head(o_t) * sigmoid(W_g_up
  W_g_down x_t)]``, the norm over a head's channels with one scale vector
  for all heads.
* ``mla``: ``[c, k_pe] = x W_kva``; ``c = RMSNorm(c)``; ``[k_nope,h ;
  v_h] = c W_kvb,h``; ``k_h = [k_nope,h ; k_pe]`` (``k_pe`` the same for
  every head, nothing rotated); ``q_h = x W_q,h``; scores ``q_h . k_h /
  sqrt(key width)``; a query sees the keys at or before it in its own
  document; ``y = W_o concat_h(softmax(s) v_h)``.
* ``dense``: ``W_down (silu(W_gate u) * W_up u)``.
* ``experts``: ``s = sigmoid(u W_r)`` over all the router's experts; the
  ``top_k`` largest of ``s + b`` are chosen (``b`` the selection bias);
  their weights are ``s`` without ``b``, divided by their sum
  (``renormalise``), times ``routed_scaling_factor``; an expert and the
  shared expert are SwiGLU; ``out = sum_i w_i E_i(u) + S(u)``.  The sum
  runs over the experts *held* (``w_up [E_held, D, F]``: experts
  ``first_expert`` to ``first_expert + E_held`` of the router's): what
  the others would add is left out.  With every expert held it is the
  whole layer.
* loss: ``sum_t w_t CE(logits_t, ids_{t+1}) / sum w`` over the batch.

Departures from the published model, each the configuration's: the
embedding and the head may hold a slice of the vocabulary's rows (ids
and loss over the slice); one routing group.

To fit a chip at the published widths a caller may ask for blocks:
``time_block`` positions of the recurrence, ``query_block`` queries of
the attention and every layer are then recomputed in the backward pass
(``jax.checkpoint``), the blocks through ``lax.scan`` / ``lax.map``, one
after the other; the numbers are the same.  Rows are run one by one.

The parameter tree is the program's: ``embed [V, D]``, ``head [D, V]``,
``final_norm [D]``, ``layers`` a list with one entry for every run of
neighbouring layers of one kind (``layer_groups``), its leaves stacked
on a leading axis; ``w_qkv`` and ``conv_w`` hold q, k and v side by
side.
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
    mixers: Tuple[str, ...]      # "kda" | "mla", a layer each
    ffns: Tuple[str, ...]        # "dense" | "experts", a layer each
    kda_heads: int
    mla_heads: int
    qk_nope_head_dim: int
    first_expert: int
    top_k: int
    renormalise: bool
    routed_scaling_factor: float
    rms_norm_eps: float
    time_block: Optional[int] = None
    query_block: Optional[int] = None
    recompute_layers: bool = False


def layer_groups(sizes: Sizes):
    """``[(mixer, ffn, layers), ...]``: the runs of neighbours of one
    kind."""
    groups = []
    for kind in zip(sizes.mixers, sizes.ffns):
        if groups and groups[-1][0] == kind:
            groups[-1][1] += 1
        else:
            groups.append([kind, 1])
    return [(*kind, n) for kind, n in groups]


def layers_of(params, sizes: Sizes):
    """(mixer, ffn, that layer's parameters), a layer at a time."""
    for (mixer, ffn, n), stacked in zip(layer_groups(sizes),
                                        params["layers"]):
        for i in range(n):
            yield mixer, ffn, jax.tree_util.tree_map(
                lambda a: a[i], stacked)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def first_of_a_document(segment):
    return jnp.concatenate([jnp.ones((1,), bool),
                            segment[1:] != segment[:-1]])


def delta_rule(q, k, v, g, beta, first, time_block=None):
    """``o_t = S_t^T q_t`` one position at a time.  ``q``, ``k`` ``[T,
    H, K]``, ``v`` ``[T, H, V]``, ``g`` ``[T, H, K]``, ``beta`` ``[T,
    H]``, ``first`` bool ``[T]``."""

    def position(state, at):
        q_t, k_t, v_t, g_t, beta_t, first_t = at
        state = jnp.where(first_t, 0.0, jnp.exp(g_t)[:, :, None] * state)
        read = jnp.sum(state * k_t[:, :, None], axis=1)          # S'^T k
        state = state + (beta_t[:, None] * k_t)[:, :, None] * (
            v_t - read)[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    t = q.shape[0]
    state = jnp.zeros((*k.shape[1:], v.shape[-1]), jnp.float32)
    inputs = (q, k, v, g, beta, first)
    if not time_block or time_block >= t:
        return jax.lax.scan(position, state, inputs)[1]
    if t % time_block:
        raise ValueError(f"{t} positions are no whole blocks of {time_block}")
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape(t // time_block, time_block, *a.shape[1:]),
        inputs)
    o = jax.lax.scan(
        jax.checkpoint(lambda s, block: jax.lax.scan(position, s, block)),
        state, blocks)[1]
    return o.reshape(t, *o.shape[2:])


def conv_inside_documents(x, w, segment):
    """``sum_j w_j x_{t-K+1+j}`` over the taps that stay inside ``t``'s
    document; ``x`` ``[T, C]``, ``w`` ``[K, C]``."""
    t, taps = x.shape[0], w.shape[0]
    y = jnp.zeros(x.shape, jnp.float32)
    for back in range(min(taps, t)):
        earlier = jnp.concatenate(
            [jnp.zeros((back, x.shape[1])), x[:t - back]])
        inside = jnp.concatenate(
            [jnp.zeros((back,), bool), segment[back:] == segment[:t - back]])
        y = y + jnp.where(inside[:, None], earlier, 0.0) * w[taps - 1 - back]
    return y


def unit_length(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_mixer(p, u, segment, sizes: Sizes):
    with _HIGHEST:
        t, heads = u.shape[0], sizes.kda_heads
        q, k, v = jnp.split(jax.nn.silu(conv_inside_documents(
            u @ p["w_qkv"], p["conv_w"], segment)).reshape(
                t, 3 * heads, -1), 3, axis=1)
        hd = q.shape[-1]
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            u @ p["f_down"] @ p["f_up"] + p["dt_bias"]).reshape(t, heads, hd)
        o = delta_rule(
            unit_length(q) * hd ** -0.5, unit_length(k), v, g,
            jax.nn.sigmoid(u @ p["b_proj"]), first_of_a_document(segment),
            sizes.time_block)
        gate = jax.nn.sigmoid(u @ p["g_down"] @ p["g_up"])
        y = rms_norm(o, p["head_norm"], sizes.rms_norm_eps) * gate.reshape(
            t, heads, hd)
        return y.reshape(t, -1) @ p["wo"]


def dense_mask(segment):
    """bool ``[T, T]``: may query ``i`` see key ``j``."""
    at = jnp.arange(segment.shape[0])
    return (at[None, :] <= at[:, None]) & (
        segment[:, None] == segment[None, :])


def mla_keys_and_values(p, u, sizes: Sizes):
    """``k`` ``[T, H, own + shared]`` and ``v`` ``[T, H, V]``."""
    with _HIGHEST:
        t, heads = u.shape[0], sizes.mla_heads
        rank = p["kv_norm"].shape[0]
        both = u @ p["w_kva"]
        latent = rms_norm(both[:, :rank], p["kv_norm"], sizes.rms_norm_eps)
        shared = both[:, rank:]                     # one for all the heads
        own_and_v = (latent @ p["w_kvb"]).reshape(t, heads, -1)
        own = own_and_v[..., :sizes.qk_nope_head_dim]
        k = jnp.concatenate(
            [own, jnp.repeat(shared[:, None, :], heads, axis=1)], axis=-1)
        return k, own_and_v[..., sizes.qk_nope_head_dim:]


def mla_mixer(p, u, segment, sizes: Sizes):
    with _HIGHEST:
        t, heads = u.shape[0], sizes.mla_heads
        k, v = mla_keys_and_values(p, u, sizes)
        q = (u @ p["wq"]).reshape(t, heads, k.shape[-1])
        mask = dense_mask(segment)

        def rows(q_rows, mask_rows):
            s = jnp.einsum("qhd,khd->hqk", q_rows, k) * k.shape[-1] ** -0.5
            s = jnp.where(mask_rows[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        if sizes.query_block and sizes.query_block < t:
            step = sizes.query_block
            if t % step:
                raise ValueError(
                    f"{t} positions are no whole blocks of {step}")
            o = jax.lax.map(
                lambda block: jax.checkpoint(rows)(*block),
                (q.reshape(t // step, step, *q.shape[1:]),
                 mask.reshape(t // step, step, t)))
            o = o.reshape(t, heads, -1)
        else:
            o = rows(q, mask)
        return o.reshape(t, -1) @ p["wo"]


def swiglu(u, w_gate, w_up, w_down):
    with _HIGHEST:
        return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def dense_ffn(p, u, sizes: Sizes):
    del sizes
    return swiglu(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"])


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
    if sizes.renormalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sizes.routed_scaling_factor * w


def expert_ffn(p, u, sizes: Sizes):
    """The experts held (``p["w_up"]``'s leading axis, from
    ``first_expert``), every one over every token, one after the other,
    and the shared expert."""
    w = routing_weights(p, u, sizes)
    held = p["w_up"].shape[0]
    weights = jax.lax.dynamic_slice_in_dim(
        w, sizes.first_expert, held, axis=1).T              # [E_held, T]
    routed = jax.lax.map(
        lambda e: e[0][:, None] * swiglu(u, *e[1:]),
        (weights, p["w_gate"], p["w_up"], p["w_down"]))
    return jnp.sum(routed, axis=0) + swiglu(
        u, p["shared_gate"], p["shared_up"], p["shared_down"])


_MIXER = {"kda": kda_mixer, "mla": mla_mixer}


def layer(mixer, ffn, p, h, segment, sizes: Sizes):
    h = h + _MIXER[mixer](
        p, rms_norm(h, p["norm1"], sizes.rms_norm_eps), segment, sizes)
    u = rms_norm(h, p["norm2"], sizes.rms_norm_eps)
    return h + (dense_ffn if ffn == "dense" else expert_ffn)(p, u, sizes)


def hidden_states(params, ids, segment, sizes: Sizes):
    """One row ``ids`` ``[T]`` through every layer."""
    h = params["embed"][ids]
    for mixer, ffn, p in layers_of(params, sizes):
        run = lambda p, h, mixer=mixer, ffn=ffn: layer(
            mixer, ffn, p, h, segment, sizes)
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
