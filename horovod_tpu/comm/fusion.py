"""Tensor fusion: bucketed flat-buffer collectives.

Parity surface: ``horovod/common/fusion_buffer_manager.cc``
(``FusionBufferManager::InitializeBuffer/GetBuffer``) and the fusion
step of the controller (``Controller::FuseResponses``): small tensors
are packed into one flat buffer so each cycle issues one collective
instead of hundreds, with a deterministic packing order identical on
every rank.

TPU-native re-expression: the "buffer" is not a persistent allocation we
memcpy around — inside jit, the flatten/concat/cast and the unpack are
XLA ops that fuse with the producing/consuming computation in HBM, and
the single ``psum`` per bucket rides ICI.  What we keep from the
reference is the *semantics*: deterministic ordering (sorted tensor
names, as ``FuseResponses`` orders responses), a byte threshold
(``HVTPU_FUSION_THRESHOLD``), and one collective per bucket.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .compression import NoneCompressor
from .reduce_ops import ReduceOp, normalize_op


@dataclasses.dataclass(frozen=True)
class BucketEntry:
    name: str
    index: int          # position in the original flat list
    shape: Tuple[int, ...]
    dtype: Any
    size: int           # element count
    nbytes: int


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    buckets: Tuple[Tuple[BucketEntry, ...], ...]

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)


def plan_buckets(
    names: Sequence[str],
    leaves: Sequence[Any],
    threshold_bytes: int,
) -> BucketPlan:
    """Greedy size-bounded bucketing in deterministic (sorted-name) order.

    A tensor larger than the threshold gets its own bucket (the reference
    does the same: responses above the fusion threshold go alone).
    """
    entries = []
    for i, (name, leaf) in enumerate(zip(names, leaves)):
        shape = tuple(leaf.shape)
        dtype = jnp.asarray(leaf).dtype if not hasattr(leaf, "dtype") else leaf.dtype
        size = 1
        for d in shape:
            size *= d
        nbytes = size * jnp.dtype(dtype).itemsize
        entries.append(BucketEntry(name, i, shape, dtype, size, nbytes))
    entries.sort(key=lambda e: e.name)

    buckets: List[List[BucketEntry]] = []
    cur: List[BucketEntry] = []
    cur_bytes = 0
    for e in entries:
        if cur and cur_bytes + e.nbytes > threshold_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(e)
        cur_bytes += e.nbytes
        if e.nbytes > threshold_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    return BucketPlan(tuple(tuple(b) for b in buckets))


def plan_for_tree(tree, threshold_bytes: int) -> Tuple[BucketPlan, Any]:
    """Bucket plan for a pytree of tensors; names come from the treedef
    paths, so ordering is deterministic across ranks for identical trees
    (the analog of the reference keying fusion on tensor names).
    """
    leaves_with_paths = jax.tree_util.tree_leaves_with_path(tree)
    names = [jax.tree_util.keystr(path) for path, _ in leaves_with_paths]
    leaves = [leaf for _, leaf in leaves_with_paths]
    treedef = jax.tree_util.tree_structure(tree)
    return plan_buckets(names, leaves, threshold_bytes), treedef


def fused_tree_allreduce(
    tree,
    *,
    axis_name: str,
    threshold_bytes: int,
    op: Optional[ReduceOp] = None,
    average: Optional[bool] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    compression=NoneCompressor,
    groups: Optional[List[List[int]]] = None,
    plan: Optional[BucketPlan] = None,
):
    """Allreduce every leaf of a pytree with bucketed fusion, inside jit.

    This is the gradient hot path used by ``DistributedOptimizer``: one
    flatten + one wire-cast + one ``psum`` per bucket.  Returns a tree of
    the same structure.
    """
    rop = normalize_op(op, average)
    leaves_with_paths = jax.tree_util.tree_leaves_with_path(tree)
    names = [jax.tree_util.keystr(p) for p, _ in leaves_with_paths]
    leaves = [l for _, l in leaves_with_paths]
    treedef = jax.tree_util.tree_structure(tree)
    if plan is None:
        plan = plan_buckets(names, leaves, threshold_bytes)

    if rop not in (ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.ADASUM):
        raise ValueError("fused_tree_allreduce supports Sum/Average/Adasum")

    from . import spmd
    from .packing import pack_flat, unpack_flat

    out_leaves: List[Any] = [None] * len(leaves)
    # The named scopes are compile-time metadata (the ``op_name`` of
    # every instruction made under them): a device profile then says what
    # the pack, the reduction and the unpack of the buckets cost a step
    # (docs/observability.md).
    for bucket in plan.buckets:
        with jax.named_scope("hvtpu:exchange.pack"):
            flat, _ = pack_flat([leaves[e.index] for e in bucket])
        # Per-tensor segment boundaries keep Adasum's dot products
        # per-tensor inside the fused buffer (reference: tensor_counts
        # in adasum.h DispatchFusedAllreduce) — results must not depend
        # on the fusion threshold.
        segments = []
        off = 0
        for e in bucket:
            segments.append((off, e.size))
            off += e.size
        # spmd.allreduce handles op routing (incl. the Adasum+groups and
        # int8 rejection paths) so fused and unfused semantics agree.
        with jax.named_scope("hvtpu:exchange.reduce"):
            red = spmd.allreduce(
                flat,
                axis_name=axis_name,
                op=rop,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                compression=compression,
                groups=groups,
                adasum_segments=segments if rop == ReduceOp.ADASUM else None,
            )
        specs = [(e.shape, e.dtype, e.size) for e in bucket]
        with jax.named_scope("hvtpu:exchange.unpack"):
            outs = unpack_flat(red, specs)
        for e, out in zip(bucket, outs):
            out_leaves[e.index] = out

    return jax.tree_util.tree_unflatten(treedef, out_leaves)
