"""DistributedOptimizer: gradient averaging woven into the optimizer.

Parity surface: ``horovod/torch/optimizer.py`` (``_DistributedOptimizer``
— per-parameter hooks firing async allreduce during backward,
``synchronize()`` before ``step()``, ``backward_passes_per_step`` local
aggregation, ``op=Average/Sum/Adasum``, compression,
``gradient_predivide_factor``) and the TF ``DistributedOptimizer`` /
``DistributedGradientTape`` (horovod/tensorflow/__init__.py).

TPU-native design: the torch version needs hooks because gradients
materialize one at a time during eager backward, and a background thread
overlaps their reduction with remaining compute.  Under jit, XLA's
latency-hiding scheduler already overlaps the fused-bucket ``psum``s
with the backward computation — so the whole hook machinery collapses
into a gradient transformation: ``DistributedOptimizer(tx)`` is an
``optax.GradientTransformation`` that bucket-fuses and allreduces the
gradient tree (one wire-cast + one psum per bucket, deterministic
order — the FusionBufferManager semantics) before handing it to the
wrapped optimizer.  Inside jit/shard_map it lowers to ICI collectives;
outside it falls back to the eager process-level data plane.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from ..comm import eager as eager_comm
from ..comm.compression import NoneCompressor
from ..comm.fusion import fused_tree_allreduce, plan_buckets
from ..comm.reduce_ops import ReduceOp, normalize_op
from ..core import state as core_state
from ..core.exceptions import HorovodInternalError
from ..obs import metrics as obs_metrics

_M_NONFINITE = obs_metrics.counter(
    "hvtpu_optimizer_nonfinite_skips_total",
    "Optimizer updates guarded because the REDUCED gradients carried "
    "non-finite values (coordinated across ranks: every rank sees the "
    "same reduced tensors, so every rank skips/zeros/aborts together).")


def _nonfinite_action() -> str:
    """``HVTPU_NONFINITE_ACTION``: what every rank does, together, when
    the reduced gradients carry NaN/inf — skip (default) | zero |
    abort | off.

    The decision is *piggybacked on the gradient allreduce*: IEEE
    non-finites propagate through sum/average reduction, so checking
    the REDUCED gradients is a coordinated test — all ranks see the
    identical reduced tensors and reach the identical verdict with no
    extra collective.  This is what prevents the classic desync where
    one rank's local overflow makes it skip a step its peers apply."""
    v = os.environ.get("HVTPU_NONFINITE_ACTION", "skip").strip().lower()
    if v in ("", "skip"):
        return "skip"
    if v in ("off", "none", "disable", "disabled"):
        return "off"
    if v in ("zero", "abort"):
        return v
    raise ValueError(
        "HVTPU_NONFINITE_ACTION must be one of skip|zero|abort|off, "
        f"got {v!r}")


def _tree_finite(tree):
    """Scalar all-leaves-finite flag (traced-safe; integer leaves are
    finite by construction and skipped)."""
    flags = [
        jnp.all(jnp.isfinite(leaf))
        for leaf in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact)
    ]
    if not flags:
        return jnp.asarray(True)
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_and(out, f)
    return out


def _zero_nonfinite(tree):
    """Replace non-finite elements with zeros (float leaves only)."""
    return jax.tree_util.tree_map(
        lambda leaf: (
            jnp.where(jnp.isfinite(leaf), leaf, jnp.zeros_like(leaf))
            if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact)
            else leaf
        ),
        tree,
    )


def allreduce_gradients(
    grads,
    *,
    axis_name: Optional[str] = None,
    op=None,
    average=None,
    compression=NoneCompressor,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    fusion_threshold_bytes: Optional[int] = None,
    process_set=None,
):
    """Fused allreduce of a gradient pytree.

    ``axis_name`` set → in-jit SPMD reduction over that mesh axis (the
    hot path).  ``axis_name=None`` → eager process-level reduction, with
    the same deterministic bucket plan so both paths agree with the
    reference's fused execution order (Controller::FuseResponses).
    """
    rop = normalize_op(op, average)
    st = core_state.global_state()
    # The tuner only participates when it actually chose the threshold —
    # an explicit fusion_threshold_bytes must neither be overridden nor
    # feed scores for candidates that were never in effect.  Restricted
    # to SINGLE-PROCESS worlds: at P>1 the eager controller owns tuning
    # (rank 0 scores, result broadcast in the ResponseList); a per-rank
    # tuner here would diverge ranks' bucket plans (different flattened
    # shapes for the same named collective) and double-count bytes on
    # rank 0.
    use_autotune = (
        fusion_threshold_bytes is None
        and st.initialized and st.autotuner is not None
        and axis_name is None and st.size == 1
    )
    if fusion_threshold_bytes is None:
        if use_autotune:
            # Autotuned threshold (eager path only: the jit path's fusion
            # is a compile-time constant, so retuning it would recompile
            # per candidate).  Parity: ParameterManager adjusting
            # HOROVOD_FUSION_THRESHOLD online.
            fusion_threshold_bytes = st.autotuner.current[0]
        elif st.initialized and st.config:
            fusion_threshold_bytes = st.config.fusion_threshold_bytes
        else:
            fusion_threshold_bytes = 64 * 1024 * 1024

    if axis_name is not None:
        groups = None
        if process_set is not None:
            ps = process_set
            if isinstance(ps, int):
                ps = core_state.require_init(
                    "process_set collectives"
                ).process_set_table.get(ps)
            groups = ps.device_groups()
        return fused_tree_allreduce(
            grads,
            axis_name=axis_name,
            threshold_bytes=fusion_threshold_bytes,
            op=rop,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            compression=compression,
            groups=groups,
        )

    # Eager path: bucket leaves deterministically, one eager allreduce
    # per fused flat buffer.
    from ..comm.packing import pack_flat, unpack_flat

    leaves_with_paths = jax.tree_util.tree_leaves_with_path(grads)
    names = [jax.tree_util.keystr(p) for p, _ in leaves_with_paths]
    leaves = [l for _, l in leaves_with_paths]
    treedef = jax.tree_util.tree_structure(grads)
    plan = plan_buckets(names, leaves, fusion_threshold_bytes)
    out = [None] * len(leaves)
    total_bytes = 0
    for k, bucket in enumerate(plan.buckets):
        if rop == ReduceOp.ADASUM:
            # Adasum's dot-product correction is per-tensor (reference:
            # tensor_counts in adasum.h DispatchFusedAllreduce keeps
            # segment boundaries inside the fused buffer); the eager
            # data plane has no segment support, so execute unfused —
            # results must not depend on the fusion threshold.
            for e in bucket:
                out[e.index] = eager_comm.allreduce(
                    leaves[e.index],
                    op=rop,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor,
                    compression=compression,
                    process_set=process_set,
                    name=f"adasum.{e.name}",
                )
                total_bytes += e.nbytes
            continue
        flat, _ = pack_flat([leaves[e.index] for e in bucket])
        red = eager_comm.allreduce(
            flat,
            op=rop,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            compression=compression,
            process_set=process_set,
            name=f"allreduce.bucket_{k}",
        )
        total_bytes += sum(e.nbytes for e in bucket)
        specs = [(e.shape, e.dtype, e.size) for e in bucket]
        for e, o in zip(bucket, unpack_flat(red, specs)):
            out[e.index] = o
    if use_autotune:
        st.autotuner.record_step(total_bytes)
    # Step telemetry for the eager reduction path (the jit path's
    # update is traced once, so its host loop reports via
    # metrics.note_step directly).
    obs_metrics.note_step()
    return jax.tree_util.tree_unflatten(treedef, out)


def ShardedDistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    axis_name: str,
    average: bool = True,
    compression=NoneCompressor,
) -> optax.GradientTransformation:
    """ZeRO-1-style sharded optimizer: reduce-scatter the gradients,
    run the inner optimizer on this rank's 1/N shard of the flattened
    parameter vector, then all-gather the updates.

    Post-parity TPU extension (SURVEY.md §2.7 lists sharded optimizers
    as absent from the reference; its ``reducescatter`` primitive —
    ``EnqueueTensorReducescatter`` — is exactly the ZeRO building
    block).  Optimizer state lives at 1/N per device: for Adam on a
    P-parameter model this drops per-device state from 2P to 2P/N.
    Wire cost per step is the same as allreduce (reduce_scatter +
    all_gather is how XLA lowers a large psum anyway).

    Both ``init`` and ``update`` must run inside ``jax.shard_map`` over
    ``axis_name`` (they call ``lax.axis_index``); init the state with a
    jitted shard_map too, using ``P(axis_name)``-sharded out_specs so
    the shards actually live distributed.

    Restriction: the inner optimizer must be *elementwise* (sgd,
    momentum, adam(w), rmsprop, ...) — the shard is a flat slice that
    ignores tensor boundaries, so per-tensor-structure transforms
    (adafactor's factored moments, per-leaf masks) are not supported.
    """
    from jax import lax as _lax

    from ..comm import spmd as _spmd
    from ..comm.packing import pack_flat, unpack_flat
    from ..comm.spmd import _is_int8

    if _is_int8(compression):
        # int8's per-block scales don't survive a raw summed wire (the
        # same guard spmd.allreduce and the eager controller apply);
        # the quantized path needs per-hop requantization, which the
        # reduce_scatter here does not do.
        raise ValueError(
            "ShardedDistributedOptimizer does not support int8 "
            "compression; use fp16/bf16"
        )

    def _flatten(tree):
        leaves_with_paths = jax.tree_util.tree_leaves_with_path(tree)
        leaves = [l for _, l in leaves_with_paths]
        flat, specs = pack_flat(leaves)
        return flat, specs, jax.tree_util.tree_structure(tree)

    def _shard_bounds(n_total, n_ranks):
        chunk = -(-n_total // n_ranks)  # ceil
        return chunk, chunk * n_ranks - n_total

    def init_fn(params):
        flat, _, _ = _flatten(params)
        n = _lax.axis_size(axis_name)
        chunk, pad = _shard_bounds(flat.shape[0], n)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        idx = _lax.axis_index(axis_name)
        mine = _lax.dynamic_slice_in_dim(flat, idx * chunk, chunk)
        return optimizer.init(mine)

    def update_fn(grads, state, params=None, **extra):
        # the scopes of DistributedOptimizer's jit path, so that a
        # device profile of either optimizer reads alike
        with jax.named_scope("hvtpu:exchange.pack"):
            gflat, specs, treedef = _flatten(grads)
            n = _lax.axis_size(axis_name)
            chunk, pad = _shard_bounds(gflat.shape[0], n)
            if pad:
                gflat = jnp.pad(gflat, (0, pad))
        with jax.named_scope("hvtpu:exchange.reduce"):
            # wire compression rides the reduce_scatter like the fused
            # allreduce path's compressors
            wire, cctx = compression.compress(gflat)
            gshard = _spmd.reducescatter(
                wire.reshape(n, chunk), axis_name=axis_name,
                op=ReduceOp.AVERAGE if average else ReduceOp.SUM,
            ).reshape(chunk)
            gshard = compression.decompress(gshard, cctx)
        pshard = None
        if params is not None:
            with jax.named_scope("hvtpu:exchange.pack"):
                pflat, _, _ = _flatten(params)
                if pad:
                    pflat = jnp.pad(pflat, (0, pad))
                idx = _lax.axis_index(axis_name)
                pshard = _lax.dynamic_slice_in_dim(pflat, idx * chunk, chunk)
        with jax.named_scope("hvtpu:optimizer.update"):
            upd_shard, new_state = optimizer.update(
                gshard.astype(gflat.dtype), state, pshard, **extra
            )
        with jax.named_scope("hvtpu:exchange.reduce"):
            full = _spmd.allgather(upd_shard, axis_name=axis_name)
        with jax.named_scope("hvtpu:exchange.unpack"):
            full = full.reshape(-1)
            if pad:
                full = full[:-pad]
            outs = unpack_flat(full, specs)
        return jax.tree_util.tree_unflatten(treedef, outs), new_state

    return optax.GradientTransformation(init_fn, update_fn)


class _DistOptState(NamedTuple):
    inner: optax.OptState
    acc: optax.Updates          # local gradient accumulator
    step_in_cycle: jnp.ndarray  # int32 counter for backward_passes_per_step


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    axis_name: Optional[str] = None,
    op=None,
    average=None,
    compression=NoneCompressor,
    backward_passes_per_step: int = 1,
    average_aggregated_gradients: bool = True,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    gradient_predivide_factor: float = 1.0,
    fusion_threshold_bytes: Optional[int] = None,
    process_set=None,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer with distributed gradient reduction.

    Matches the reference's knob set: ``op``, ``compression``,
    ``backward_passes_per_step`` (local aggregation: the collective fires
    every N-th update; in between, updates are zero and the inner
    optimizer state is untouched, like the reference's skipped
    synchronize), ``gradient_predivide_factor`` (splits the averaging
    divisor across pre/post scaling exactly as horovod/torch/optimizer.py
    does).
    """
    rop = normalize_op(op, average)
    pre, post = prescale_factor, postscale_factor
    if gradient_predivide_factor != 1.0:
        if rop != ReduceOp.AVERAGE:
            raise ValueError(
                "gradient_predivide_factor requires op=Average"
            )
        # Reference semantics: divide by predivide before the sum and by
        # (size / predivide) after; we fold the first into prescale and
        # let the Average op handle 1/size, compensating in postscale.
        pre = pre / gradient_predivide_factor
        post = post * gradient_predivide_factor

    def reduce_tree(grads):
        return allreduce_gradients(
            grads,
            axis_name=axis_name,
            op=rop,
            compression=compression,
            prescale_factor=pre,
            postscale_factor=post,
            fusion_threshold_bytes=fusion_threshold_bytes,
            process_set=process_set,
        )

    nonfinite = _nonfinite_action()

    def guarded_update(reduced, inner_state, params, extra):
        """Run the wrapped optimizer under the coordinated non-finite
        guard: the verdict is computed on the REDUCED gradients (the
        allreduce already propagated any rank's NaN/inf to every
        rank), so all ranks skip/zero/abort the step together."""
        if axis_name is None:
            # Eager path: concrete arrays, Python control flow.
            if nonfinite != "off" and not bool(_tree_finite(reduced)):
                _M_NONFINITE.inc()
                if nonfinite == "abort":
                    raise HorovodInternalError(
                        "non-finite reduced gradients; aborting the "
                        "step on every rank "
                        "(HVTPU_NONFINITE_ACTION=abort)")
                if nonfinite == "skip":
                    return (
                        jax.tree_util.tree_map(jnp.zeros_like, reduced),
                        inner_state,
                    )
                reduced = _zero_nonfinite(reduced)
            return optimizer.update(reduced, inner_state, params, **extra)
        # In-jit the flag is traced: skip rides lax.cond.  abort cannot
        # raise from compiled code and degrades to a coordinated skip,
        # and the counter only advances on the eager path — both
        # documented in docs/robustness.md.  The named scopes put the
        # guard's and the update's device time under their own names in a
        # profile (docs/observability.md); around the whole cond, so that
        # both branches carry the update's.
        if nonfinite == "zero":
            with jax.named_scope("hvtpu:optimizer.guard"):
                reduced = _zero_nonfinite(reduced)
        if nonfinite in ("off", "zero"):
            with jax.named_scope("hvtpu:optimizer.update"):
                return optimizer.update(
                    reduced, inner_state, params, **extra)
        with jax.named_scope("hvtpu:optimizer.guard"):
            finite = _tree_finite(reduced)

        def _apply(_):
            return optimizer.update(reduced, inner_state, params, **extra)

        def _skip(_):
            return (jax.tree_util.tree_map(jnp.zeros_like, reduced),
                    inner_state)

        with jax.named_scope("hvtpu:optimizer.update"):
            return jax.lax.cond(finite, _apply, _skip, None)

    if backward_passes_per_step == 1:

        def init_fn(params):
            return optimizer.init(params)

        def update_fn(grads, state, params=None, **extra):
            reduced = reduce_tree(grads)
            return guarded_update(reduced, state, params, extra)

        return optax.GradientTransformation(init_fn, update_fn)

    n_acc = backward_passes_per_step

    def init_fn(params):
        return _DistOptState(
            inner=optimizer.init(params),
            acc=jax.tree_util.tree_map(jnp.zeros_like, params),
            step_in_cycle=jnp.zeros((), jnp.int32),
        )

    def update_fn(grads, state, params=None, **extra):
        if axis_name is None:
            return accumulate(grads, state, params, extra)
        # in-jit the accumulator's add and clear are the update's too;
        # the exchange and the guard keep their own, inner scopes
        with jax.named_scope("hvtpu:optimizer.update"):
            return accumulate(grads, state, params, extra)

    def accumulate(grads, state, params, extra):
        acc = jax.tree_util.tree_map(jnp.add, state.acc, grads)
        count = state.step_in_cycle + 1

        def at_boundary(_):
            g = acc
            if average_aggregated_gradients:
                g = jax.tree_util.tree_map(lambda t: t / n_acc, g)
            reduced = reduce_tree(g)
            # Guarded: a skipped boundary still clears the accumulator
            # (the poisoned aggregation is discarded identically on
            # every rank; the inner state stays untouched).
            upd, inner = guarded_update(reduced, state.inner, params, extra)
            zeroed = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return upd, _DistOptState(inner, zeroed, jnp.zeros((), jnp.int32))

        def mid_cycle(_):
            upd = jax.tree_util.tree_map(jnp.zeros_like, grads)
            return upd, _DistOptState(state.inner, acc, count)

        if axis_name is None:
            # Eager path: Python control flow on a concrete counter.
            if int(count) == n_acc:
                return at_boundary(None)
            # local aggregation only — no collective fired this call
            # (parity: the reference's skipped synchronize)
            obs_metrics.counter(
                "hvtpu_optimizer_skipped_steps_total",
                "Updates that only accumulated locally "
                "(backward_passes_per_step aggregation).",
            ).inc()
            return mid_cycle(None)
        # In-jit: the boundary test must be static-friendly; the cycle
        # counter is a traced value, so use lax.cond.  Collectives
        # execute unconditionally inside at_boundary's branch — XLA
        # requires both branches to be collective-free or the predicate
        # to be replicated; it is (same counter on every device).
        return jax.lax.cond(count == n_acc, at_boundary, mid_cycle, None)

    return optax.GradientTransformation(init_fn, update_fn)
