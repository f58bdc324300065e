"""LFM2-8B-A1B's stack (``lfm2_moe``) under the causal next-token loss on
packed documents, written plainly: ``jax.numpy`` in float32, the short
convolution as explicit taps that stop at each document's first
position, the attention mask dense with positions that restart at every
document's start, every held expert over every token with a 0/1
choice, every product at ``jax.default_matmul_precision("highest")``.
No kernels, no tiling, no ``custom_vjp``, nothing of the program.

On one row ``ids`` ``[T]`` with ``segment`` ``[T]`` (the document's
index at every position); ``start_t`` is the position of the first token
of ``t``'s document, ``pos_t = t - start_t``:

* ``h = E[ids]``; every layer ``h = h + mixer(RMSNorm(h))`` and then ``h
  = h + ffn(RMSNorm(h))``; ``logits = RMSNorm(h) W_head`` (the family's
  ``embedding_norm``; the head untied).  Every norm has eps
  ``rms_norm_eps`` and a scale.
* ``conv`` (HF ``Lfm2MoeShortConv``): ``[b; c; x] = u W_in``; ``z_t =
  sum_{j=0}^{K-1} w_{K-1-j} (b x)_{t-j}`` over the ``j`` with ``t - j >=
  start_t`` (a document convolved by itself, zeros before its start);
  ``y = (c z) W_out``.  No bias, no activation.
* ``attention`` (HF ``Lfm2MoeAttention``): ``q = u W_q``, ``k = u W_k``,
  ``v = u W_v`` in heads of ``head_dim``; ``q`` and ``k`` through an
  RMSNorm a head (``q_layernorm``, ``k_layernorm``); both rotated by
  ``pos`` (rotate-half, inverse frequencies ``theta ** (-2 i / d)``);
  query head ``h`` reads key/value head ``h // (H / G)``; scores ``q . k
  / sqrt(head_dim)``; a query sees the keys at or before it in its own
  document; ``y = concat_h(softmax(s) v_h) W_o``.
* ``dense``: ``W_down (silu(W_gate u) * W_up u)`` (HF ``w2``, ``w1``,
  ``w3``).
* ``experts`` (HF ``Lfm2MoeSparseMoeBlock.route_tokens_to_experts``):
  ``s = sigmoid(u W_r)`` over all the router's experts; the ``top_k``
  largest of ``s + b`` are chosen (``b`` the expert bias); their weights
  are ``s`` without ``b``, divided by ``their sum + renormalise_eps``,
  times ``routed_scaling_factor``; ``out = sum_i w_i E_i(u)``, each
  ``E_i`` SwiGLU, no shared expert.  The sum runs over the experts
  *held* (``w_up [E_held, D, F]``: experts ``first_expert`` to
  ``first_expert + E_held`` of the router's): what the others would add
  is left out.  With every expert held it is the whole layer.
* loss: ``sum_t w_t CE(logits_t, ids_{t+1}) / sum w`` over the batch.

Departures from the published model, each the configuration's: the
embedding and the head hold a slice of the vocabulary's rows (ids and
loss over the slice); the experts held are a range of the router's;
the head is untied from the embedding.

To fit a chip at the published widths a caller may ask for blocks:
``query_block`` queries of the attention and every layer are then
recomputed in the backward pass (``jax.checkpoint``), the blocks through
``lax.map``, one after the other; the numbers are the same.  Rows are
run one by one.

The parameter tree is the program's: ``embed [V, D]``, ``head [D, V]``,
``final_norm [D]``, ``layers`` a list with one entry for every run of
neighbouring layers of one kind (``layer_groups``), its leaves stacked
on a leading axis; ``in_proj`` holds ``b``, ``c`` and ``x`` side by
side, in that order.
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
    mixers: Tuple[str, ...]      # "conv" | "attention", a layer each
    ffns: Tuple[str, ...]        # "dense" | "experts", a layer each
    num_heads: int
    num_kv_heads: int
    rope_theta: float
    first_expert: int
    top_k: int
    renormalise: bool
    renormalise_eps: float
    routed_scaling_factor: float
    rms_norm_eps: float
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


def document_starts(segment):
    """int ``[T]``: the position of the first token of each position's
    document."""
    at = jnp.arange(segment.shape[0])
    first = jnp.concatenate([jnp.ones((1,), bool),
                             segment[1:] != segment[:-1]])
    return jax.lax.cummax(jnp.where(first, at, 0))


def short_conv(bx, w, segment):
    """``z_t = sum_j w_{K-1-j} bx_{t-j}`` over the taps ``j`` that stay
    inside ``t``'s document; ``bx`` ``[T, C]``, ``w`` ``[K, C]``."""
    t, taps = bx.shape[0], w.shape[0]
    at = jnp.arange(t)
    start = document_starts(segment)
    z = jnp.zeros(bx.shape, jnp.float32)
    for back in range(taps):
        earlier = jnp.concatenate([jnp.zeros((back, bx.shape[1])),
                                   bx[:t - back]])[:t]
        z = z + jnp.where((at - back >= start)[:, None], earlier,
                          0.0) * w[taps - 1 - back]
    return z


def conv_mixer(p, u, segment, sizes: Sizes):
    del sizes
    with _HIGHEST:
        b, c, x = jnp.split(u @ p["in_proj"], 3, axis=-1)
        return (c * short_conv(b * x, p["conv_w"], segment)) @ p["out_proj"]


def rotate(x, positions, theta):
    """``x`` ``[T, heads, d]`` rotated by ``positions`` ``[T]``
    (rotate-half: channel ``i`` pairs with ``i + d/2``)."""
    half = x.shape[-1] // 2
    inverse = 1.0 / theta ** (jnp.arange(0, 2 * half, 2) / (2 * half))
    angle = (positions[:, None] * inverse)[:, None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def dense_mask(segment):
    """bool ``[T, T]``: may query ``i`` see key ``j``."""
    at = jnp.arange(segment.shape[0])
    return (at[None, :] <= at[:, None]) & (
        segment[:, None] == segment[None, :])


def attention_operands(p, u, positions, sizes: Sizes):
    """``q`` ``[T, H, d]``, ``k`` and ``v`` ``[T, G, d]``, ``q`` and ``k``
    normed a head and rotated by ``positions``."""
    with _HIGHEST:
        t = u.shape[0]
        q = (u @ p["wq"]).reshape(t, sizes.num_heads, -1)
        k = (u @ p["wk"]).reshape(t, sizes.num_kv_heads, -1)
        v = (u @ p["wv"]).reshape(t, sizes.num_kv_heads, -1)
        q = rotate(rms_norm(q, p["q_norm"], sizes.rms_norm_eps), positions,
                   sizes.rope_theta)
        k = rotate(rms_norm(k, p["k_norm"], sizes.rms_norm_eps), positions,
                   sizes.rope_theta)
        return q, k, v


def softmax_attention(q, k, v, segment, query_block=None):
    """Query head ``h`` over key/value head ``h // (H / G)``, a dense
    mask, scores times ``d ** -0.5``; ``[T, H, d]``."""
    with _HIGHEST:
        t, heads, d = q.shape
        group = heads // k.shape[1]
        k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
        mask = dense_mask(segment)

        def rows(q_rows, mask_rows):
            s = jnp.einsum("qhd,khd->hqk", q_rows, k) * d ** -0.5
            s = jnp.where(mask_rows[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        if not query_block or query_block >= t:
            return rows(q, mask)
        if t % query_block:
            raise ValueError(
                f"{t} positions are no whole blocks of {query_block}")
        o = jax.lax.map(
            lambda block: jax.checkpoint(rows)(*block),
            (q.reshape(t // query_block, query_block, heads, d),
             mask.reshape(t // query_block, query_block, t)))
        return o.reshape(t, heads, d)


def attention_mixer(p, u, segment, sizes: Sizes):
    t = u.shape[0]
    positions = jnp.arange(t) - document_starts(segment)
    o = softmax_attention(*attention_operands(p, u, positions, sizes),
                          segment, sizes.query_block)
    with _HIGHEST:
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
    chosen = order[:, :sizes.top_k]
    weights = jnp.take_along_axis(s, chosen, axis=-1)
    if sizes.renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + sizes.renormalise_eps)
    weights = sizes.routed_scaling_factor * weights
    return jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=s.dtype)
                   * weights[..., None], axis=1)


def expert_ffn(p, u, sizes: Sizes):
    """The experts held (``p["w_up"]``'s leading axis, from
    ``first_expert``), every one over every token, one after the
    other."""
    w = routing_weights(p, u, sizes)
    held = p["w_up"].shape[0]
    weights = jax.lax.dynamic_slice_in_dim(
        w, sizes.first_expert, held, axis=1).T              # [E_held, T]
    routed = jax.lax.map(
        lambda e: e[0][:, None] * swiglu(u, *e[1:]),
        (weights, p["w_gate"], p["w_up"], p["w_down"]))
    return jnp.sum(routed, axis=0)


_MIXER = {"conv": conv_mixer, "attention": attention_mixer}


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
