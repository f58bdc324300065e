"""Ouro's looped decoder under the expected loss over its exits on packed
documents, written plainly: ``jax.numpy`` in float32, a Python ``for``
over the passes and over the layers, the attention mask dense, RoPE
written out, an exit's logits whole, every product at
``jax.default_matmul_precision("highest")``.  Nothing of the program.

On one row ``ids`` ``[T]`` with ``segment`` ``[T]`` (the document's
index at every position), ``N*`` an RMSNorm with its own scale:

* ``h = E[ids]``; a pass is every layer in turn and then ``Nf``, and
  the next pass starts from ``Nf``'s output; the same layers and the
  same ``Nf`` in each of the ``total_ut_steps`` passes.
* a layer: ``a = h + N2(Attn(N1(h)))``, ``out = a + N4(MLP(N3(a)))``;
  ``MLP(u) = W_out (silu(g) * v)``, ``[g, v] = W_in u``.
* attention: q, k, v, o without bias; rotate-half RoPE on q and k at
  the positions counted along the row; scores ``q.k / sqrt(head_dim)``;
  a query sees the keys at or before it in its own document; a query
  head reads key/value head ``head // (query heads / key/value heads)``.
* after pass ``t``: ``logits_t = h_t W_head``, ``lambda_t = sigmoid(w_g
  . h_t + b_g)``; ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` and the
  last exit takes what is left.
* loss: ``sum_pos w [sum_t p_t CE_t - beta H(p)] / sum_pos w`` over the
  batch, ``CE_t`` the cross-entropy of ``logits_t`` against the next
  token, ``H(p) = -sum_t p_t log p_t``.

To fit a chip at the published widths a caller may ask for blocks:
``query_block`` queries of the attention at a time, and then every
block, every layer and every exit's logits are recomputed in the
backward pass (``jax.checkpoint``); the numbers are the same.  Rows are
run one by one.

The parameter tree is the program's: ``embed [V, D]``, ``head [D, V]``,
``final_norm [D]``, ``gate_w [D]``, ``gate_b [1]`` and ``layers``, whose
leaves are stacked on a leading axis: ``norm1`` … ``norm4 [L, D]``,
``wq``, ``wk``, ``wv [L, D, heads x head_dim]``, ``wo``, ``mlp_in [L, D,
2 F]`` (``W_gate`` beside ``W_up``), ``mlp_out [L, F, D]``.
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
    num_heads: int
    num_kv_heads: int
    rope_theta: float
    rms_norm_eps: float
    total_ut_steps: int
    entropy_weight: float
    query_block: Optional[int] = None


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotate(x, theta):
    """Rotate-half RoPE of ``x`` ``[T, heads, head_dim]`` at positions
    0 … T - 1: channel ``i`` of the first half and of the second turn
    together by ``position * theta ** (-i / half)``."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * (
        theta ** (-jnp.arange(half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, u, segment, sizes: Sizes):
    with _HIGHEST:
        t = u.shape[0]
        hd = p["wq"].shape[1] // sizes.num_heads
        q = rotate((u @ p["wq"]).reshape(t, sizes.num_heads, hd),
                   sizes.rope_theta)
        k = rotate((u @ p["wk"]).reshape(t, sizes.num_kv_heads, hd),
                   sizes.rope_theta)
        v = (u @ p["wv"]).reshape(t, sizes.num_kv_heads, hd)
        rep = sizes.num_heads // sizes.num_kv_heads
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        at = jnp.arange(t)

        def rows(q_rows, at_rows, segment_rows):
            """A block of queries against every key, under the dense
            mask of the block's rows."""
            mask = (at[None, :] <= at_rows[:, None]) & (
                segment_rows[:, None] == segment[None, :])
            s = jnp.einsum("qhd,khd->hqk", q_rows, k) / jnp.sqrt(
                jnp.float32(hd))
            s = jnp.where(mask[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        if sizes.query_block and sizes.query_block < t:
            blocks = jax.tree_util.tree_map(
                lambda a: a.reshape(-1, sizes.query_block, *a.shape[1:]),
                (q, at, segment))
            o = jax.lax.map(lambda block: jax.checkpoint(rows)(*block),
                            blocks).reshape(t, -1)
        else:
            o = rows(q, at, segment).reshape(t, -1)
        return o @ p["wo"]


def layer(p, h, segment, sizes: Sizes):
    with _HIGHEST:
        eps = sizes.rms_norm_eps
        a = h + rms_norm(
            attention(p, rms_norm(h, p["norm1"], eps), segment, sizes),
            p["norm2"], eps)
        g, v = jnp.split(rms_norm(a, p["norm3"], eps) @ p["mlp_in"], 2,
                         axis=-1)
        return a + rms_norm((jax.nn.silu(g) * v) @ p["mlp_out"],
                            p["norm4"], eps)


def cross_entropy(head, h, label):
    with _HIGHEST:
        logp = jax.nn.log_softmax(h @ head)
    return -jnp.take_along_axis(logp, label[:, None], axis=-1)[:, 0]


def exits(params, ids, segment, sizes: Sizes):
    """One row: ``(lambda [passes, T], CE [passes, T])``, an entry an
    exit."""
    recompute = jax.checkpoint if sizes.query_block else (lambda f: f)
    layers = params["layers"]
    count = layers["wq"].shape[0]
    label = jnp.roll(ids, -1)
    h = params["embed"][ids]
    gates, ces = [], []
    for _ in range(sizes.total_ut_steps):
        for i in range(count):
            p = jax.tree_util.tree_map(lambda a: a[i], layers)
            h = recompute(lambda p, h: layer(p, h, segment, sizes))(p, h)
        h = rms_norm(h, params["final_norm"], sizes.rms_norm_eps)
        gates.append(jax.nn.sigmoid(h @ params["gate_w"]
                                    + params["gate_b"][0]))
        ces.append(recompute(cross_entropy)(params["head"], h, label))
    return jnp.stack(gates), jnp.stack(ces)


def exit_distribution(gates):
    """``p [passes, T]`` from ``lambda [passes, T]``: ``p_t = lambda_t
    prod_{j<t} (1 - lambda_j)``; the last exit takes what is left."""
    p, left = [], jnp.ones_like(gates[0])
    for gate in gates[:-1]:
        p.append(gate * left)
        left = left * (1.0 - gate)
    return jnp.stack(p + [left])


def row_loss_sum(params, ids, segment, w, sizes: Sizes):
    """One row's ``sum_pos w [sum_t p_t CE_t - beta H(p)]``."""
    gates, ce = exits(params, ids, segment, sizes)
    p = exit_distribution(gates)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.sum(w.astype(jnp.float32) * (
        jnp.sum(p * ce, axis=0) - sizes.entropy_weight * entropy))


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
    up there."""
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
