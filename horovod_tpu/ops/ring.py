"""Hand-rolled ICI ring collectives as Pallas TPU kernels.

The reference's data plane is NCCL's ring algorithms
(``horovod/common/ops/nccl_operations.cc`` — ``ncclAllReduce`` et al.
run ring reduce-scatter + ring all-gather over NVLink).  On TPU, XLA's
own collectives already lower to tuned ICI rings, so these kernels are
NOT the default data plane; they exist for the cases XLA cannot
express:

* ``ring_allreduce(..., quantized=True)`` — the true EQuARX design
  (PAPERS.md, arXiv:2506.17615): int8 codes + per-block scales cross
  the wire on EVERY hop.  Reduce-scatter hops dequantize → f32
  accumulate → requantize (values change per hop); all-gather hops
  relay each owner's codes VERBATIM (store-and-forward), so every
  rank dequantizes identical bytes and the output is bit-equal across
  ranks — the allreduce contract.  The XLA-level approximation in
  comm/quantized.py must round-trip through ``all_to_all``/
  ``all_gather``; here the quantize lives inside the transfer loop,
  which is the actual paper algorithm (1 B/elt wire on all 2(N-1)
  hops).
* A reference implementation of the ring protocol itself (entry
  barrier, double buffering, per-slot DMA semaphore accounting).

Protocol (one direction around the ring): every kernel opens with a
neighbour barrier on the ``collective_id`` barrier semaphore — a rank
signals its left and right neighbours and waits for both — so no rank
DMAs into a neighbour that has not entered the kernel (its scratch
buffers and semaphores do not exist before that).  Each device then
holds a 2-slot VMEM comm buffer; step ``i`` RDMAs slot ``i%2`` to the
right neighbour's slot ``(i+1)%2`` with per-slot send / recv
semaphores, so a slot is never written while its previous transfer is
in flight.  Reduce-scatter accumulates the received chunk with the
local contribution in place; after N-1 steps rank r owns the
fully-reduced chunk (r+1)%N, and a second N-1-step ring gathers them.

Shapes: kernels operate on f32 ``(N*CH, 128)`` buffers (CH rows per
rank) held whole in VMEM, so one call carries at most
``_MAX_CHUNK_ROWS`` rows per rank under an explicit
``vmem_limit_bytes``; ``ring_allreduce`` flattens/pads arbitrary float
tensors and maps the kernel over slices of that size.

Where they run: compiled by Mosaic on a TPU; under the Pallas TPU
interpreter (``HVTPU_PALLAS_INTERPRET=1``, which simulates the remote
DMAs and semaphores across the shard_map devices) in CPU tests — keep
those small: the interpreter parks one host thread per device in a
callback, and a buffer over the CPU client's 100 KiB inline-copy limit
then waits for a pool thread that may not exist.  Anywhere else — and
for anything the kernels cannot do — the entry points raise; they
never substitute an XLA collective.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ops import _LANES, _pallas_mode, block_scale_inv

# The quantized ring carries one (8, 128) f32 tile of scales per chunk:
# a 1.6% sidecar on the int8 codes of a full 2048-row chunk.
_SCALE_ROWS = 8
# Per-rank chunk rows are a multiple of the f32 tile height.
_CHUNK_ROW_QUANTUM = 8
# Largest per-rank chunk one kernel call carries: 2048 rows x 128 lanes
# x 4 B = 1 MiB.  The f32 kernel's VMEM footprint is (2N+5) chunks —
# 13 MiB on the four-chip host — and Mosaic unrolls whole-chunk vector
# ops, so larger chunks buy compile time, not bandwidth.
_MAX_CHUNK_ROWS = 2048
# Ceiling for the explicit scoped-VMEM request (v5e has 128 MiB).
_VMEM_CEILING = 96 * 1024 * 1024


def _interpret_arg(what: str):
    """``interpret=`` for the ring ``pallas_call``s: False (compiled) on
    a TPU, the TPU interpreter under ``HVTPU_PALLAS_INTERPRET=1``.
    Raises where the kernels cannot run."""
    use, interp = _pallas_mode()
    if not use:
        raise RuntimeError(
            f"{what}: the Pallas ring kernels run on a TPU (or under "
            "HVTPU_PALLAS_INTERPRET=1 in CPU tests); here the platform "
            f"is {jax.default_backend()!r} with HVTPU_PALLAS="
            f"{os.environ.get('HVTPU_PALLAS', '1')!r}. Use lax.psum / "
            "lax.all_gather for an XLA collective.")
    return pltpu.InterpretParams() if interp else False


def _compiler_params(collective_id: int, vmem_bytes: int):
    """Side-effecting collective kernel with its own barrier semaphore
    and a scoped-VMEM request sized from its buffers (25% + 2 MiB of
    headroom for Mosaic's own temporaries)."""
    limit = vmem_bytes + vmem_bytes // 4 + (2 << 20)
    if limit > _VMEM_CEILING:
        raise ValueError(
            f"ring kernel needs {limit} B of VMEM, over the "
            f"{_VMEM_CEILING} B ceiling: its footprint grows with the "
            "ring length; lower _MAX_CHUNK_ROWS for a ring this long")
    return pltpu.CompilerParams(
        has_side_effects=True, collective_id=collective_id,
        vmem_limit_bytes=limit)


def _neighbors(axis_name):
    my_id = lax.axis_index(axis_name)
    n = lax.axis_size(axis_name)
    return my_id, n, lax.rem(my_id - 1 + n, n), lax.rem(my_id + 1, n)


def _signal(sem, axis_name, device):
    pltpu.semaphore_signal(
        sem, inc=1, device_id={axis_name: device},
        device_id_type=pltpu.DeviceIdType.MESH)


def _entry_barrier(axis_name, left, right):
    """Both ring neighbours are inside this kernel (their comm buffers
    and semaphores are live) before anything is sent to them."""
    barrier = pltpu.get_barrier_semaphore()
    _signal(barrier, axis_name, left)
    _signal(barrier, axis_name, right)
    pltpu.semaphore_wait(barrier, 2)


def _ring_hop(i, base, n, axis_name, left, right, ack_sem, copies):
    """One double-buffered ring hop of the buffers in ``copies`` — a
    list of ``(comm_ref, send_sem, recv_sem)`` moved in lockstep —
    from slot ``base + i%2`` to the right neighbour's slot
    ``base + (i+1)%2``.  The semaphore protocol lives ONLY here.
    Returns the recv slot."""
    send_slot = base + lax.rem(i, 2)
    recv_slot = base + lax.rem(i + 1, 2)

    # Backpressure: my step-i RDMA writes the right neighbour's
    # comm[recv_slot], which was THEIR send buffer at step i-1 — wait
    # for their ACK that the slot is free.  Without this a rank running
    # ahead stomps a slower neighbour's unsent data (ring skew is
    # unbounded: each rank only waits on its own semaphores).
    @pl.when(i >= 1)
    def _():
        pltpu.semaphore_wait(ack_sem.at[recv_slot], 1)

    rdmas = [
        pltpu.make_async_remote_copy(
            src_ref=comm.at[send_slot],
            dst_ref=comm.at[recv_slot],
            send_sem=send_sem.at[send_slot],
            recv_sem=recv_sem.at[recv_slot],
            device_id={axis_name: right},
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        for comm, send_sem, recv_sem in copies
    ]
    for rdma in rdmas:
        rdma.start()
    for rdma in rdmas:
        rdma.wait()

    # my send buffer is dead -> tell the LEFT neighbour (who writes it
    # at their next step); skip after the last step that could consume
    # it, or the count leaks past kernel exit
    @pl.when(i < n - 2)
    def _():
        _signal(ack_sem.at[send_slot], axis_name, left)

    return recv_slot


# ----------------------------------------------------------------------
# ring all-gather
# ----------------------------------------------------------------------


def _allgather_kernel(local_ref, out_ref, comm_ref, send_sem, recv_sem,
                      ack_sem, *, axis_name):
    my_id, n, left, right = _neighbors(axis_name)
    ch = local_ref.shape[0]
    _entry_barrier(axis_name, left, right)
    out_ref[pl.ds(pl.multiple_of(my_id * ch, ch), ch), :] = local_ref[:]
    comm_ref[0] = local_ref[:]

    def step(i, _):
        src_dev = lax.rem(my_id - i - 1 + 2 * n, n)
        recv_slot = _ring_hop(i, 0, n, axis_name, left, right, ack_sem,
                              [(comm_ref, send_sem, recv_sem)])
        out_ref[pl.ds(pl.multiple_of(src_dev * ch, ch), ch), :] = (
            comm_ref[recv_slot])
        return 0

    lax.fori_loop(0, n - 1, step, 0)


def ring_allgather_2d(local, *, axis_name: str):
    """All-gather a per-rank ``(CH, 128)`` f32 block into ``(N*CH, 128)``
    via the Pallas ring.  Must run inside shard_map over ``axis_name``;
    ``CH`` is a multiple of 8 and at most ``_MAX_CHUNK_ROWS``."""
    n = lax.axis_size(axis_name)
    ch, lanes = local.shape
    if lanes != _LANES or ch % 8 or not 0 < ch <= _MAX_CHUNK_ROWS:
        raise ValueError(
            f"ring_allgather_2d takes a (CH, {_LANES}) block with CH a "
            f"multiple of 8 up to {_MAX_CHUNK_ROWS}; got {local.shape}")
    interp = _interpret_arg("ring_allgather_2d")
    chunk_bytes = ch * _LANES * 4
    return pl.pallas_call(
        functools.partial(_allgather_kernel, axis_name=axis_name),
        out_shape=jax.ShapeDtypeStruct((n * ch, _LANES), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, ch, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
        ],
        # distinct collective_id per kernel entry point: concurrent
        # collective kernels sharing a barrier semaphore is documented
        # as a correctness hazard (allgather=0, allreduce=1, quant=2)
        compiler_params=_compiler_params(0, (n + 3) * chunk_bytes),
        interpret=interp,
    )(local.astype(jnp.float32))


# ----------------------------------------------------------------------
# ring allreduce (reduce-scatter phase + all-gather phase)
# ----------------------------------------------------------------------


def _allreduce_kernel(x_ref, out_ref, comm_ref, send_sem, recv_sem,
                      ack_sem, *, axis_name):
    """x_ref: (N*CH, 128) local contributions; out_ref: (N*CH, 128)
    reduced result (same on every rank afterwards)."""
    my_id, n, left, right = _neighbors(axis_name)
    ch = x_ref.shape[0] // n
    copies = [(comm_ref, send_sem, recv_sem)]

    def chunk_rows(c):
        return pl.ds(pl.multiple_of(c * ch, ch), ch)

    _entry_barrier(axis_name, left, right)

    # ---- phase 1: ring reduce-scatter ------------------------------
    # comm starts with my contribution to chunk my_id's ring walk.
    comm_ref[0] = x_ref[chunk_rows(my_id), :]

    def rs_step(i, _):
        chunk = lax.rem(my_id - i - 1 + 2 * n, n)  # chunk received now
        recv_slot = _ring_hop(i, 0, n, axis_name, left, right, ack_sem,
                              copies)
        # accumulate my contribution in place; this slot is next step's
        # send buffer
        comm_ref[recv_slot] = (
            comm_ref[recv_slot] + x_ref[chunk_rows(chunk), :])
        return 0

    lax.fori_loop(0, n - 1, rs_step, 0)

    # I now hold the fully-reduced chunk (my_id+1)%N in slot (n-1)%2.
    reduced = comm_ref[(n - 1) % 2]
    out_ref[chunk_rows(right), :] = reduced

    # ---- phase 2: ring all-gather of reduced chunks ----------------
    # DISJOINT slot pair (2,3) + matching semaphores: a rank ahead of
    # its neighbour may start phase 2 while the neighbour still waits on
    # its last phase-1 receive — sharing slots would let the phase-2
    # RDMA overwrite that in-flight phase-1 buffer.
    comm_ref[2] = reduced

    def ag_step(i, _):
        src_dev = lax.rem(my_id - i - 1 + 2 * n, n)
        src_chunk = lax.rem(src_dev + 1, n)   # chunk owned by src_dev
        recv_slot = _ring_hop(i, 2, n, axis_name, left, right, ack_sem,
                              copies)
        out_ref[chunk_rows(src_chunk), :] = comm_ref[recv_slot]
        return 0

    lax.fori_loop(0, n - 1, ag_step, 0)


def _quantize_block(x):
    """(CH, 128) f32 -> int8 codes (CH, 128) + one (8, 128) f32 tile of
    scales: scale[s, l] is the absmax/127 of lane l over the rows
    r = s (mod 8), so the reduction runs across whole vector tiles and
    the scales come out as one lane-dense tile per chunk (a (g, 1)
    column of per-block scales cannot be DMA-sliced: Mosaic pads each
    to a full 128-lane row).  Same scale formula as pallas_ops."""
    xg = x.reshape(x.shape[0] // _SCALE_ROWS, _SCALE_ROWS, _LANES)
    scale, inv = block_scale_inv(xg, axis=0)
    q = jnp.clip(jnp.round(xg * inv), -127, 127).astype(jnp.int8)
    return q.reshape(x.shape), scale[0]


def _dequantize_block(q, scale):
    deq = q.astype(jnp.float32).reshape(
        q.shape[0] // _SCALE_ROWS, _SCALE_ROWS, _LANES) * scale
    return deq.reshape(q.shape)


def _quantized_allreduce_kernel(x_ref, out_ref, qcomm_ref, scomm_ref,
                                acc_ref, send_sem, recv_sem,
                                ssend_sem, srecv_sem, ack_sem,
                                *, axis_name):
    """Per-hop requantizing ring allreduce: EVERY transfer carries int8
    codes + f32 per-strip scales; accumulation stays f32."""
    my_id, n, left, right = _neighbors(axis_name)
    ch = x_ref.shape[0] // n
    # one ACK covers the lockstep codes+scales pair
    copies = [(qcomm_ref, send_sem, recv_sem),
              (scomm_ref, ssend_sem, srecv_sem)]

    def chunk_rows(c):
        return pl.ds(pl.multiple_of(c * ch, ch), ch)

    _entry_barrier(axis_name, left, right)

    # ---- phase 1: reduce-scatter with per-hop requantization -------
    acc_ref[:] = x_ref[chunk_rows(my_id), :]

    def rs_step(i, _):
        chunk = lax.rem(my_id - i - 1 + 2 * n, n)
        send_slot = lax.rem(i, 2)
        q, s = _quantize_block(acc_ref[:])
        qcomm_ref[send_slot] = q
        scomm_ref[send_slot] = s
        recv_slot = _ring_hop(i, 0, n, axis_name, left, right, ack_sem,
                              copies)
        incoming = _dequantize_block(
            qcomm_ref[recv_slot], scomm_ref[recv_slot])
        acc_ref[:] = incoming + x_ref[chunk_rows(chunk), :]
        return 0

    lax.fori_loop(0, n - 1, rs_step, 0)

    # ---- phase 2: all-gather, store-and-forward --------------------
    # The reduced chunk values do NOT change in this phase, so each
    # chunk is quantized exactly ONCE (by its owner) and the int8
    # codes + scales are relayed VERBATIM around the ring (slot pair
    # (2,3): see _allreduce_kernel).  Every rank therefore dequantizes
    # identical bytes — the output is bit-equal on all ranks (the
    # allreduce contract) and the quantization error does not grow
    # with ring distance.  The owner likewise keeps the dequantized
    # form of the codes it put on the wire, not its raw f32
    # accumulator.
    q0, s0 = _quantize_block(acc_ref[:])
    qcomm_ref[2] = q0
    scomm_ref[2] = s0
    out_ref[chunk_rows(right), :] = _dequantize_block(q0, s0)

    def ag_step(i, _):
        src_dev = lax.rem(my_id - i - 1 + 2 * n, n)
        src_chunk = lax.rem(src_dev + 1, n)
        # relay only — no quantize: received codes land in recv_slot
        # == next step's send_slot, so they are forwarded verbatim
        recv_slot = _ring_hop(i, 2, n, axis_name, left, right, ack_sem,
                              copies)
        out_ref[chunk_rows(src_chunk), :] = _dequantize_block(
            qcomm_ref[recv_slot], scomm_ref[recv_slot])
        return 0

    lax.fori_loop(0, n - 1, ag_step, 0)


def _ring_allreduce_2d(x2, *, axis_name: str, quantized: bool, interp):
    """One kernel call over an ``(N*CH, 128)`` f32 slice."""
    n = lax.axis_size(axis_name)
    rows = x2.shape[0]
    ch = rows // n
    chunk_bytes = ch * _LANES * 4
    if quantized:
        kernel = functools.partial(
            _quantized_allreduce_kernel, axis_name=axis_name
        )
        scratch = [
            pltpu.VMEM((4, ch, _LANES), jnp.int8),
            pltpu.VMEM((4, _SCALE_ROWS, _LANES), jnp.float32),
            pltpu.VMEM((ch, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((4,)),
            pltpu.SemaphoreType.DMA((4,)),
            pltpu.SemaphoreType.DMA((4,)),
            pltpu.SemaphoreType.DMA((4,)),
            pltpu.SemaphoreType.REGULAR((4,)),
        ]
        # x + out + acc in f32, four int8 code slots (one f32 chunk),
        # four scale slots of up to one (8, 128) f32 tile
        vmem = (2 * n + 2) * chunk_bytes + 4 * 4096
    else:
        kernel = functools.partial(_allreduce_kernel, axis_name=axis_name)
        scratch = [
            pltpu.VMEM((4, ch, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((4,)),
            pltpu.SemaphoreType.DMA((4,)),
            pltpu.SemaphoreType.REGULAR((4,)),
        ]
        vmem = (2 * n + 4) * chunk_bytes
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
        compiler_params=_compiler_params(2 if quantized else 1, vmem),
        interpret=interp,
    )(x2)


def ring_allreduce(tensor, *, axis_name: str, average: bool = False,
                   quantized: bool = False):
    """Ring allreduce (sum) of an arbitrary float tensor inside
    shard_map, accumulated in f32.

    ``quantized=True`` sends int8 codes + a tile of strip scales on
    every hop (per-hop requantization — the EQuARX algorithm proper).

    Buffers larger than one kernel call's VMEM share are reduced slice
    by slice.  Raises off a TPU (see the module docstring) and for
    non-float tensors, whose sums the f32 ring would round.
    """
    n = lax.axis_size(axis_name)
    if not jnp.issubdtype(tensor.dtype, jnp.floating):
        raise TypeError(
            f"ring_allreduce reduces in float32; a {tensor.dtype} tensor "
            "would lose exactness past 2^24 — use lax.psum")
    if n == 1:
        return tensor  # a ring of one: sum and mean are the identity
    interp = _interpret_arg("ring_allreduce")

    flat = tensor.reshape(-1).astype(jnp.float32)
    size = flat.shape[0]
    # every rank owns an equal (CH, 128) chunk of each slice, CH a
    # multiple of the tile quantum and at most _MAX_CHUNK_ROWS
    ch_total = -(-size // (n * _LANES))
    slices = -(-ch_total // _MAX_CHUNK_ROWS)
    ch = -(-ch_total // slices)
    ch = -(-ch // _CHUNK_ROW_QUANTUM) * _CHUNK_ROW_QUANTUM
    padded = slices * n * ch * _LANES
    if padded != size:
        flat = jnp.pad(flat, (0, padded - size))
    x3 = flat.reshape(slices, n * ch, _LANES)

    call = functools.partial(_ring_allreduce_2d, axis_name=axis_name,
                             quantized=quantized, interp=interp)
    red = call(x3[0])[None] if slices == 1 else lax.map(call, x3)
    out = red.reshape(-1)[:size]
    if average:
        out = out / n
    return out.reshape(tensor.shape).astype(tensor.dtype)
