"""Quantized (int8-wire) allreduce — EQuARX-style (PAPERS.md:
"EQuARX: Efficient Quantized AllReduce in XLA", arXiv:2506.17615).

A plain ``psum`` cannot carry block-quantized int8: summing codes
quantized against different per-rank scales is meaningless and int8
accumulation overflows.  EQuARX therefore quantizes *per hop* inside
the collective.  At the JAX level we express the same structure as the
two-phase allreduce XLA itself uses:

  1. **reduce-scatter phase** — ``all_to_all`` the int8-quantized
     shards (each rank's chunk c quantized with that rank's scale,
     scales ride alongside as fp32 per-block sidecars), then each rank
     dequantizes the N received chunks and sums them in fp32 — wire
     bytes: 1 B/elt instead of 4 (plus 4/BLOCK scale overhead);
  2. **allgather phase** — the reduced chunk is re-quantized and
     ``all_gather``-ed, again 1 B/elt on the wire.

Total wire ≈ 2·(N-1)/N bytes/elt vs 8·(N-1)/N for fp32 psum — the same
~4× saving as EQuARX, with one quantization error per phase (two total),
matching the paper's error model.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import ring as _ring

BLOCK = 512


def _quantize(x, *, key=None):
    """x: (..., k) fp32 → int8 codes + fp32 per-block scales.

    ``key`` (a jax PRNG key) switches to stochastic rounding:
    ``floor(x/s + u)``, u ~ U[0,1), which is unbiased (E[q·s] = x) so
    quantisation noise cancels instead of accumulating when the codes
    feed a summation across ranks."""
    n = x.shape[-1]
    pad = (-n) % BLOCK
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    blocks = x.reshape(x.shape[:-1] + (-1, BLOCK))
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0
    safe = jnp.where(scale == 0, 1.0, scale)
    scaled = blocks / safe
    if key is not None:
        u = jax.random.uniform(key, scaled.shape, jnp.float32)
        q = jnp.clip(jnp.floor(scaled + u), -127, 127).astype(jnp.int8)
    else:
        q = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dither_key(flat, axis_name):
    """Traced PRNG key for stochastic rounding: folds the rank index
    (decorrelates dither across ranks — the property cross-rank error
    cancellation needs) and a fold of the payload bits (varies the
    dither per step under jit, where a Python-level seed would bake
    into the compiled program as a constant)."""
    bits = lax.bitcast_convert_type(flat.astype(jnp.float32), jnp.int32)
    key = jax.random.fold_in(jax.random.key(0x51DE), lax.axis_index(axis_name))
    return jax.random.fold_in(key, jnp.sum(bits).astype(jnp.uint32))


def quantized_allreduce(tensor, *, axis_name: str, average: bool = False,
                        stochastic: bool = False):
    """int8-wire allreduce of a float tensor inside shard_map/jit.

    The tensor is flattened and padded so each participant owns an
    equal chunk.  Returns fp32 (caller casts back).

    ``stochastic=True`` rounds with a traced per-(rank, payload) PRNG
    key (see :func:`_dither_key`) so the per-rank quantisation errors
    are independent and cancel ~√N-style in the phase-1 summation
    instead of adding coherently — the error model EQuARX assumes.

    ``HVTPU_QUANTIZED_RING=1`` routes through the Pallas per-hop
    requantizing ring kernel instead (ops/ring.py — the EQuARX
    algorithm proper, requantizing on every hop rather than once per
    phase).  The kernel runs on a TPU (or the interpreter in tests)
    and raises anywhere else — asking for the ring never silently runs
    this XLA path.  The ring kernel rounds deterministically, so
    ``stochastic=True`` keeps the XLA path — the documented
    unbiased-dither semantics win over the ring opt-in.
    """
    n_ranks = lax.axis_size(axis_name)
    if (os.environ.get("HVTPU_QUANTIZED_RING", "0") == "1"
            and n_ranks > 1 and not stochastic):
        return _ring.ring_allreduce(
            tensor, axis_name=axis_name, average=average, quantized=True)
    orig_shape = tensor.shape
    orig_dtype = tensor.dtype
    flat = tensor.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    chunk = -(-n // n_ranks)  # ceil
    pad = chunk * n_ranks - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n_ranks, chunk)

    key = _dither_key(flat, axis_name) if stochastic else None

    # Phase 1: reduce-scatter with int8 wire.  Stochastic rounding
    # matters HERE: the N dequantized contributions are summed, so
    # independent per-rank dither cancels while deterministic rounding
    # bias adds coherently.
    q, scale = _quantize(chunks, key=key)      # (N, chunk/B, B) int8 + scales
    q_recv = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
    s_recv = lax.all_to_all(scale, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
    # q_recv: (N, chunk/B, B) — contribution of every rank to MY chunk.
    deq = q_recv.astype(jnp.float32) * s_recv
    reduced = jnp.sum(deq, axis=0)             # (chunk/B, B) fp32

    # Phase 2: allgather with int8 wire.  (Stochastic rounding here
    # keeps the result unbiased over steps; the error is common to all
    # ranks either way since each chunk is quantized once by its owner.)
    scale2 = jnp.max(jnp.abs(reduced), axis=-1, keepdims=True) / 127.0
    safe2 = jnp.where(scale2 == 0, 1.0, scale2)
    scaled2 = reduced / safe2
    if key is not None:
        u2 = jax.random.uniform(
            jax.random.fold_in(key, 1), scaled2.shape, jnp.float32
        )
        q2 = jnp.clip(jnp.floor(scaled2 + u2), -127, 127).astype(jnp.int8)
    else:
        q2 = jnp.clip(jnp.round(scaled2), -127, 127).astype(jnp.int8)
    q_all = lax.all_gather(q2, axis_name)      # (N, chunk/B, B)
    s_all = lax.all_gather(scale2.astype(jnp.float32), axis_name)
    deq_all = (q_all.astype(jnp.float32) * s_all).reshape(n_ranks, -1)
    # trim per-chunk block padding before concatenating ranks' chunks
    out = deq_all[:, :chunk].reshape(-1)[:n]

    if average:
        out = out / n_ranks
    return out.reshape(orig_shape).astype(
        orig_dtype if jnp.issubdtype(orig_dtype, jnp.floating) else jnp.float32
    )
