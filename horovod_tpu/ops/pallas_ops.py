"""Pallas TPU kernels for the data-plane hot ops.

TPU-native re-expression of the reference's hand-written device kernels
(``horovod/common/ops/cuda/cuda_kernels.cu``: the batched
scale-buffer fp16/fp32 kernels used around fused collectives, and the
pack/unpack memcpys of ``collective_operations.cc
MemcpyInFusionBuffer/MemcpyOutFusionBuffer``).  On TPU the XLA compiler
already fuses most elementwise work, so these kernels target the two
places where an explicit kernel still wins:

* ``fused_scale_cast`` — one-pass ``cast(x * scale)`` over a flat
  fusion buffer: a single HBM read + write at the *output* width even
  when scale forces an f32 intermediate (XLA sometimes materialises the
  f32 product when the producer/consumer live in different fusions —
  e.g. across a collective boundary, exactly where this runs).
* ``quantize_int8_blocks`` / ``dequantize_int8_blocks`` — per-block
  absmax int8 (de)quantisation for the EQuARX-style quantized-wire
  allreduce (comm/quantized.py), with optional stochastic rounding via
  the on-core PRNG (cuda_kernels.cu's scale kernels have no TPU analog
  in XLA's standard fusion set for the rounding path).

Which implementation runs is decided by what the process can observe:
on a TPU backend the Pallas kernel (compiled by Mosaic), on any other
backend the numerically-identical XLA twin, so callers never need to
branch.  ``HVTPU_PALLAS=0`` selects the twin on a TPU as well; float16
buffers always take it (Mosaic has no f16 vector type on v5e).
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger("horovod_tpu")

# Lane width of the VPU / MXU; last-dim tiles are always 128 wide.
_LANES = 128
# Rows per grid step for the flat-buffer kernels: 256 rows x 128 lanes
# x 4 B = 128 KiB per operand block in VMEM — small enough to double
# buffer, large enough to saturate HBM bandwidth.
_TILE_ROWS = 256


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pallas_mode() -> Tuple[bool, bool]:
    """(use_pallas, interpret).  HVTPU_PALLAS=0 disables the kernels
    entirely; HVTPU_PALLAS_INTERPRET=1 forces the Pallas path in
    interpreter mode so CPU tests execute the real kernel bodies."""
    if os.environ.get("HVTPU_PALLAS", "1") == "0":
        return False, False
    if os.environ.get("HVTPU_PALLAS_INTERPRET", "0") == "1":
        return True, True
    return _on_tpu(), False


def _mosaic_dtype(dtype) -> bool:
    """False for element types the Mosaic compiler cannot load or
    store on the chip (v5e has no float16 vector type)."""
    return jnp.dtype(dtype) != jnp.float16


def _pad_to_grid(flat, rows_mult: int) -> Tuple[jax.Array, int, int]:
    """Pad a 1-D buffer and reshape to (rows, _LANES) with rows a
    multiple of ``rows_mult``; returns (2-D view, rows, original n)."""
    n = flat.shape[0]
    per_block = rows_mult * _LANES
    padded = ((n + per_block - 1) // per_block) * per_block
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    rows = padded // _LANES
    return flat.reshape(rows, _LANES), rows, n


def _split_rows(rows: int) -> Tuple[int, int]:
    """(main_rows, rem_rows): full _TILE_ROWS tiles + one remainder.

    Keeps padding at the _QROWS granularity (1024 elements — the wire
    block) instead of padding every buffer up to a full 256-row tile,
    which would inflate small tensors' wire size up to 32x.  The
    remainder runs as a second single-program pallas call with
    full-array blocks (Mosaic allows sub-(8,128) blocks only when they
    equal the whole array)."""
    rem = rows % _TILE_ROWS
    return rows - rem, rem


# ----------------------------------------------------------------------
# fused scale + cast
# ----------------------------------------------------------------------


def _scale_cast_kernel(scale_ref, x_ref, out_ref):
    # scale lives in SMEM as (1, 1); the multiply runs in f32 and the
    # narrowing cast happens in-register before the VMEM write, so HBM
    # sees only in-dtype reads and out-dtype writes.
    s = scale_ref[0, 0]
    out_ref[:] = (x_ref[:].astype(jnp.float32) * s).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def _scale_cast_xla(flat, scale, out_dtype):
    return (flat.astype(jnp.float32) * scale).astype(out_dtype)


def fused_scale_cast(flat, scale, out_dtype=None):
    """``cast(flat * scale)`` in one pass over a flat buffer.

    Parity: the scale-buffer CUDA kernels the reference launches around
    fused collectives for prescale/postscale
    (``horovod/common/ops/cuda/cuda_kernels.cu``, dispatched from
    ``ScaleBuffer`` in gpu_operations.cc).

    Args:
      flat: 1-D array (any float/int dtype).
      scale: python float or 0-D array.
      out_dtype: output dtype (defaults to ``flat.dtype``).
    """
    out_dtype = jnp.dtype(out_dtype or flat.dtype)
    use, interp = _pallas_mode()
    if (not use or flat.ndim != 1
            or not (_mosaic_dtype(flat.dtype) and _mosaic_dtype(out_dtype))):
        return _scale_cast_xla(jnp.asarray(flat), float(scale), out_dtype)

    x2, rows, n = _pad_to_grid(jnp.asarray(flat), _QROWS)
    scale_arr = jnp.full((1, 1), scale, jnp.float32)

    def call(x_part, part_rows, tile):
        return pl.pallas_call(
            _scale_cast_kernel,
            grid=(part_rows // tile,),
            interpret=interp,
            in_specs=[
                pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((tile, _LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((tile, _LANES), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((part_rows, _LANES), out_dtype),
        )(scale_arr, x_part)

    main, rem = _split_rows(rows)
    parts = []
    if main:
        parts.append(call(x2[:main], main, _TILE_ROWS))
    if rem:
        parts.append(call(x2[main:], rem, rem))
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return out.reshape(-1)[:n]


# ----------------------------------------------------------------------
# int8 block quantize / dequantize
# ----------------------------------------------------------------------

# Quantisation block = one (8, 128) f32 tile = 1024 elements; each
# block carries one f32 absmax scale (0.4% wire overhead).
_QROWS = 8
QBLOCK = _QROWS * _LANES


def block_scale_inv(xg, axis: int = 1):
    """Shared absmax-block quantisation formula: (scale, inv) for
    blocks laid along ``axis`` of f32 ``xg``.  THE single definition —
    the Pallas kernel, the XLA twin, and the ring kernel's per-hop
    requantization (ops/ring.py) must stay bit-identical, so they all
    call this."""
    absmax = jnp.max(jnp.abs(xg), axis=axis, keepdims=True)
    # single multiply (not /127): a division invites per-fusion
    # strength-reduction ulp drift between lowerings
    scale = absmax * jnp.float32(1.0 / 127.0)
    inv = jnp.where(scale > 0.0,
                    1.0 / jnp.where(scale > 0.0, scale, 1.0), 0.0)
    return scale, inv


def _quantize_kernel(seed_ref, x_ref, q_ref, scale_ref, *, stochastic,
                     tile):
    i = pl.program_id(0)
    if stochastic:
        pltpu.prng_seed(seed_ref[0] + i)
    x = x_ref[:].astype(jnp.float32)              # (tile, 128)
    # per-(8,128)-tile absmax: reduce within each group of _QROWS rows
    g = tile // _QROWS
    xg = x.reshape(g, _QROWS * _LANES)
    scale, inv = block_scale_inv(xg)
    scaled = xg * inv
    if stochastic:
        # pltpu.stochastic_round only targets bf16/fp8; integer
        # stochastic rounding is floor(x + u), u ~ U[0,1) from the
        # on-core PRNG (top 24 bits -> exact f32 uniform): unbiased,
        # E[q] = x, so quantisation noise cancels across summed ranks.
        bits = pltpu.bitcast(
            pltpu.prng_random_bits(scaled.shape), jnp.uint32)
        # route via int32 (Mosaic has no uint32->f32 cast); >>9 keeps
        # 23 bits, safely positive in int32
        u = ((bits >> 9).astype(jnp.int32).astype(jnp.float32)
             * jnp.float32(1.0 / (1 << 23)))
        q = jnp.clip(jnp.floor(scaled + u), -127, 127).astype(jnp.int8)
    else:
        q = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)
    q_ref[:] = q.reshape(tile, _LANES)
    scale_ref[:] = scale


def _dequantize_kernel(q_ref, scale_ref, out_ref, *, tile):
    g = tile // _QROWS
    q = q_ref[:].astype(jnp.float32).reshape(g, _QROWS * _LANES)
    out = q * scale_ref[:]
    out_ref[:] = out.reshape(tile, _LANES).astype(out_ref.dtype)


def _quantize_xla(flat):
    x2, rows, n = _pad_to_grid(flat.astype(jnp.float32), _QROWS)
    g = rows // _QROWS
    xg = x2.reshape(g, QBLOCK)
    scale, inv = block_scale_inv(xg)
    q = jnp.clip(jnp.round(xg * inv), -127, 127).astype(jnp.int8)
    return q.reshape(rows, _LANES), scale, n


def quantize_int8_blocks(flat, *, stochastic: bool = False,
                         seed=0):
    """Block-absmax int8 quantisation of a flat f32/bf16 buffer.

    Returns ``(codes, scales, n)``: codes ``(rows, 128) int8`` (rows a
    multiple of 8, zero-padded), scales ``(rows/8, 1) f32`` — one per
    1024-element block — and the original element count ``n``.

    ``stochastic=True`` uses the on-core PRNG for unbiased rounding
    (recommended when the quantized wire feeds a summation, as in the
    EQuARX reduce-scatter phase — rounding bias accumulates over ranks).
    """
    flat = jnp.asarray(flat)
    use, interp = _pallas_mode()
    if stochastic and (interp or not use):
        # the on-core PRNG exists only in the Mosaic-compiled kernel:
        # neither the test interpreter nor the XLA twin has one
        logger.warning(
            "quantize_int8_blocks: stochastic rounding needs the on-core "
            "PRNG of the compiled TPU kernel; rounding to nearest here")
        stochastic = False
    if not use or flat.ndim != 1:
        q, scale, n = _quantize_xla(flat)
        return q, scale, n

    # keep the native width into the kernel (the in-register cast in
    # the body handles f32 accumulation) — a host-side astype would
    # materialize a full f32 copy of the buffer in HBM first; only
    # dtypes Mosaic cannot load (f16, f64, ...) pre-cast
    if flat.dtype not in (jnp.float32, jnp.bfloat16):
        flat = flat.astype(jnp.float32)
    x2, rows, n = _pad_to_grid(flat, _QROWS)

    def call(x_part, part_rows, tile, seed_val):
        g_per_tile = tile // _QROWS
        # seed_val may be a traced scalar (see compression._stochastic_seed)
        seed_arr = jnp.asarray(seed_val, jnp.int32).reshape(1)
        return pl.pallas_call(
            functools.partial(_quantize_kernel, stochastic=stochastic,
                              tile=tile),
            grid=(part_rows // tile,),
            interpret=interp,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((tile, _LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((tile, _LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((g_per_tile, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((part_rows, _LANES), jnp.int8),
                jax.ShapeDtypeStruct((part_rows // _QROWS, 1),
                                     jnp.float32),
            ),
        )(seed_arr, x_part)

    main, rem = _split_rows(rows)
    qs, ss = [], []
    if main:
        q, s = call(x2[:main], main, _TILE_ROWS, seed)
        qs.append(q)
        ss.append(s)
    if rem:
        # distinct seed stream for the remainder program
        q, s = call(x2[main:], rem, rem, seed + main // _TILE_ROWS + 1)
        qs.append(q)
        ss.append(s)
    if len(qs) == 1:
        return qs[0], ss[0], n
    return jnp.concatenate(qs), jnp.concatenate(ss), n


def dequantize_int8_blocks(q, scale, n: int, dtype=jnp.float32):
    """Inverse of :func:`quantize_int8_blocks` → 1-D array of length n."""
    q = jnp.asarray(q)
    scale = jnp.asarray(scale)
    rows = q.shape[0]
    use, interp = _pallas_mode()
    if not use or rows % _QROWS != 0:
        g = rows // _QROWS
        out = (q.astype(jnp.float32).reshape(g, QBLOCK) * scale)
        return out.reshape(-1)[:n].astype(dtype)

    def call(q_part, s_part, part_rows, tile):
        g_per_tile = tile // _QROWS
        return pl.pallas_call(
            functools.partial(_dequantize_kernel, tile=tile),
            grid=(part_rows // tile,),
            interpret=interp,
            in_specs=[
                pl.BlockSpec((tile, _LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((g_per_tile, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((tile, _LANES), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((part_rows, _LANES), dtype),
        )(q_part, s_part)

    main, rem = _split_rows(rows)
    parts = []
    if main:
        parts.append(call(q[:main], scale[: main // _QROWS], main,
                          _TILE_ROWS))
    if rem:
        parts.append(call(q[main:], scale[main // _QROWS:], rem, rem))
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return out.reshape(-1)[:n]
