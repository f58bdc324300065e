"""Parts the decoders of ``models/`` share: the norm, the rotary
embedding, the mask value, the causal convolution cut at document starts,
the SwiGLU feed-forward part and the weighted next-token loss.  Pure functions; nothing here knows
which model calls it."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# what a masked score is set to: finite, so that a row of a tile pair
# in which it sees no key gives exp(0) and no NaN; the first real key
# of the row wipes that out again (exp(_MASKED - m) == 0)
_MASKED = -1e30


def rms_norm(x, scale, eps):
    """In f32 over the last axis, back in ``x``'s type."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rope(x, positions, theta):
    """Rotate-half rotary embedding of ``[..., P, heads, head_dim]`` at
    ``positions`` ``[P]``, computed in f32."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def causal_conv(x, w, b, segment):
    """Depthwise convolution over time of ``x`` ``[B, T, C]`` with taps
    ``w`` ``[K, C]`` (the last tap at the position itself) and bias
    ``b``, in f32.  A tap that reaches before the row's or the
    document's start reads zero.  ``x`` and ``segment`` are padded once
    at the front and every tap is a window of the same length into
    them (a pad a tap made four passes of one; PERF.md, the hybrid
    cell)."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    before = jnp.pad(segment, ((0, 0), (taps - 1, 0)), constant_values=-1)
    y = jnp.broadcast_to(b, x.shape).astype(jnp.float32)
    for tap in range(taps):
        same = before[:, tap:tap + t] == segment
        y = y + jnp.where(
            same[..., None], padded[:, tap:tap + t].astype(jnp.float32),
            0.0) * w[tap]
    return y


def swiglu(u, w_gate, w_up, w_down):
    """``W_down (silu(W_gate u) * W_up u)``, the two first products
    summed and gated in f32, the gated rows back in ``u``'s type."""
    dtype = u.dtype
    hidden = (jax.nn.silu(jnp.dot(u, w_gate.astype(dtype),
                                  preferred_element_type=jnp.float32))
              * jnp.dot(u, w_up.astype(dtype),
                        preferred_element_type=jnp.float32)).astype(dtype)
    return hidden @ w_down.astype(dtype)


def weighted_next_token_cross_entropy(logits, batch):
    """The mean over the batch's weighted positions of ``w_t CE(logits_t,
    x_{t+1})``, from f32 ``logits`` ``[B, T, V]``."""
    with jax.named_scope("hvtpu:lm_head"):
        label = jnp.roll(batch["x"], -1, axis=1)
        ce = (jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, label[..., None], axis=-1)[..., 0])
        w = batch["w"].astype(jnp.float32)
        return jnp.sum(w * ce) / jnp.sum(w)
