"""SDAR's sparse decoder under the block-diffusion objective, written
plainly: ``jax.numpy`` in float32, the mask dense, a Python loop over
the experts, every product at ``jax.default_matmul_precision
("highest")``.  No tiles, no sort, no grouped product, nothing of the
program.

Per layer, on ``x`` (``P`` positions of one sequence), ``u =
RMSNorm(x)``:

* attention: ``q = u Wq``, ``k = u Wk``, ``v = u Wv``; RMSNorm over the
  128 of every query and key head (learned scale); rotate-half RoPE at
  the position's index; scores ``q.k / sqrt(128) + M``, softmax, ``o =
  concat(P v) Wo``; ``x' = x + o``.  A query head reads key/value head
  ``head // (query heads / key/value heads)``.
* experts: ``u' = RMSNorm(x')``; ``p = softmax(u' Wr)`` over all the
  router's outputs; ``S`` the ``top_k`` largest; ``w_e = p_e / sum_S
  p`` (``norm_topk_prob``); ``y = sum_{e in S} w_e (SiLU(u' Wg_e) *
  u' Wu_e) Wd_e``; output ``x' + y``.
* head: ``logits = RMSNorm(x_L) W_head``, untied.

Block diffusion: the model runs on ``xt ++ x`` (2T positions, both
halves at RoPE positions ``0..T-1``); ``blk(i) = (i mod T) // B``; a
noised query ``i < T`` sees noised keys of its own block and clean keys
of earlier blocks, a clean query sees clean keys of its own and earlier
blocks.  ``loss = 1/T sum_{i<T} w_i CE(logits_i, x_i)``, labels not
shifted, mean over the batch's sequences.

Departures from the published model, each the configuration's: the
parameters may be a *share* — some of the heads, experts ``first_expert``
onwards of those the router scores, some rows of the vocabulary — and
then what the absent heads and experts would add is left out, here as
in the program; no auxiliary loss; ``w`` comes with the batch.

The parameter tree is the program's (``layers`` stacked on a leading
axis): ``embed [V, D]``, ``head [D, V]``, ``final_norm [D]``, ``layers``
``{attn_norm, moe_norm [L, D]; wq [L, D, H*hd]; wk, wv [L, D, G*hd];
wo [L, H*hd, D]; q_norm, k_norm [L, hd]; router [L, D, E]; w_gate, w_up
[L, E_held, D, F]; w_down [L, E_held, F, D]}``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

_HIGHEST = jax.default_matmul_precision("highest")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameters' shapes do not say."""
    head_dim: int
    num_experts: int          # the router's outputs
    first_expert: int         # the first of the experts held
    top_k: int
    norm_topk_prob: bool
    rope_theta: float
    rms_norm_eps: float
    block_length: int
    mask_token_id: int


def check_share(params, sizes: Sizes, *, experts_held: int, heads_held,
                vocab_held: int) -> None:
    """The parameters are the share the caller means: ``experts_held``
    experts, ``heads_held = (query heads, key/value heads)``,
    ``vocab_held`` rows of the vocabulary."""
    layers = params["layers"]
    found = {
        "experts_held": layers["w_gate"].shape[1],
        "heads_held": (layers["wq"].shape[2] // sizes.head_dim,
                       layers["wk"].shape[2] // sizes.head_dim),
        "vocab_held": params["embed"].shape[0]}
    asked = {"experts_held": experts_held, "heads_held": tuple(heads_held),
             "vocab_held": vocab_held}
    if found != asked:
        raise ValueError(f"the parameters hold {found}, not {asked}")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, positions, theta):
    """``x`` ``[P, heads, head_dim]``, rotate-half."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def dense_mask(seq_len: int, block_length: int):
    """bool ``[2T, 2T]``: may query ``i`` see key ``j``."""
    i = jnp.arange(2 * seq_len)[:, None]
    j = jnp.arange(2 * seq_len)[None, :]
    bi, bj = (i % seq_len) // block_length, (j % seq_len) // block_length
    noised_q, noised_k = i < seq_len, j < seq_len
    return jnp.where(
        noised_q,
        jnp.where(noised_k, bj == bi, bj < bi),
        jnp.where(noised_k, False, bj <= bi))


def attention_part(p, x, sizes: Sizes, query_block=None):
    """What the heads in ``p`` add to ``x`` ``[2T, D]``."""
    with _HIGHEST:
        seq_len, hd = x.shape[0] // 2, sizes.head_dim
        u = rms_norm(x, p["attn_norm"], sizes.rms_norm_eps)
        positions = jnp.arange(2 * seq_len) % seq_len
        q = (u @ p["wq"]).reshape(2 * seq_len, -1, hd)
        k = (u @ p["wk"]).reshape(2 * seq_len, -1, hd)
        v = (u @ p["wv"]).reshape(2 * seq_len, -1, hd)
        q = rope(rms_norm(q, p["q_norm"], sizes.rms_norm_eps), positions,
                 sizes.rope_theta)
        k = rope(rms_norm(k, p["k_norm"], sizes.rms_norm_eps), positions,
                 sizes.rope_theta)
        rep = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        mask = dense_mask(seq_len, sizes.block_length)

        def rows(q_rows, mask_rows):
            s = jnp.einsum("qhd,khd->hqk", q_rows, k) / jnp.sqrt(
                jnp.float32(hd))
            s = jnp.where(mask_rows[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        # a block of queries at a time, so that the scores of 16,384
        # positions fit: the same numbers, recomputed in the backward pass
        step = query_block or 2 * seq_len
        o = jnp.concatenate([
            jax.checkpoint(rows)(q[a:a + step], mask[a:a + step])
            for a in range(0, 2 * seq_len, step)])
        return o.reshape(2 * seq_len, -1) @ p["wo"]


def route(p, x, sizes: Sizes):
    """The ``top_k`` experts of every position and their weights, after
    the attention part was added to ``x``."""
    with _HIGHEST:
        u = rms_norm(x, p["moe_norm"], sizes.rms_norm_eps)
        probs = jax.nn.softmax(u @ p["router"], axis=-1)
        top_p, top_i = jax.lax.top_k(probs, sizes.top_k)
        if sizes.norm_topk_prob:
            top_p = top_p / top_p.sum(axis=-1, keepdims=True)
        return u, top_p, top_i


def expert_part(p, x, sizes: Sizes):
    """What the experts in ``p`` (``first_expert`` onwards) add."""
    with _HIGHEST:
        u, top_p, top_i = route(p, x, sizes)
        y = jnp.zeros_like(x)
        for e in range(p["w_gate"].shape[0]):
            w = jnp.sum(jnp.where(top_i == sizes.first_expert + e, top_p,
                                  0.0), axis=-1)
            h = jax.nn.silu(u @ p["w_gate"][e]) * (u @ p["w_up"][e])
            y = y + w[:, None] * (h @ p["w_down"][e])
        return y


def layer_params(params, i):
    return jax.tree_util.tree_map(lambda a: a[i], params["layers"])


def hidden_states(params, ids, sizes: Sizes, query_block=None):
    """One sequence ``ids`` ``[2T]`` through every layer; also the
    experts every position chose, ``[layers, 2T, top_k]``."""
    x = params["embed"][ids]
    chosen = []

    @jax.checkpoint
    def layer(x, p):
        x = x + attention_part(p, x, sizes, query_block)
        return x + expert_part(p, x, sizes), route(p, x, sizes)[2]

    for i in range(params["layers"]["wq"].shape[0]):
        x, top_i = layer(x, layer_params(params, i))
        chosen.append(top_i)
    return x, jnp.stack(chosen)


def logits_of(params, hidden, sizes: Sizes):
    with _HIGHEST:
        return rms_norm(hidden, params["final_norm"],
                        sizes.rms_norm_eps) @ params["head"]


def sequence_loss(params, x, mask, w, sizes: Sizes, query_block=None):
    """One sequence's ``1/T sum_i w_i CE(logits_i, x_i)``, and the
    experts chosen."""
    seq_len = x.shape[0]
    noised = jnp.where(mask != 0, sizes.mask_token_id, x)
    hidden, chosen = hidden_states(
        params, jnp.concatenate([noised, x]), sizes, query_block)
    logp = jax.nn.log_softmax(logits_of(params, hidden[:seq_len], sizes))
    ce = -jnp.take_along_axis(logp, x[:, None], axis=-1)[:, 0]
    return jnp.sum(w.astype(jnp.float32) * ce) / seq_len, chosen


def loss(params, batch, sizes: Sizes, query_block=None):
    """Mean over the batch's sequences, one at a time."""
    rows = batch["x"].shape[0]
    return sum(
        sequence_loss(params, batch["x"][i], batch["mask"][i],
                      batch["w"][i], sizes, query_block)[0]
        for i in range(rows)) / rows


def loss_and_gradient(params, batch, sizes: Sizes, query_block=None):
    """``(loss, gradient tree, experts chosen [B, layers, 2T, top_k])``,
    a sequence at a time: one jitted program, run once per sequence,
    the gradients added up."""
    one = jax.jit(jax.value_and_grad(
        lambda p, x, mask, w: sequence_loss(p, x, mask, w, sizes,
                                            query_block), has_aux=True))
    rows = batch["x"].shape[0]
    total, grads, chosen = 0.0, None, []
    for i in range(rows):
        (value, top_i), g = one(params, batch["x"][i], batch["mask"][i],
                                batch["w"][i])
        total = total + value / rows
        g = jax.tree_util.tree_map(lambda a: a / rows, g)
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
        chosen.append(top_i)
    return total, grads, jnp.stack(chosen)
