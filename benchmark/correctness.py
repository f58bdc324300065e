"""The comparisons that decide ``correct``, with their tolerances.

A run is correct only if every check here passes (``run.py`` lists
them).  Each returns ``(ok, what it saw)`` so that a failure says which
one and by how much.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

# Steps taken through the system before the window: its warm-up, and
# the losses the first-loss band is held to.
WARMUP_STEPS = 3

# Elements of every leaf of an update that the exchange check compares
# (evenly strided over the flattened leaf).
SAMPLE_PER_LEAF = 4096

# The exchange, alone: the same synthetic gradient trees (one per chip,
# standard normal from the seed, the parameters' own shapes, so the
# fusion plan is the real one) through ``hvt.DistributedOptimizer`` and
# through ``pmean`` + the plain optimizer, from the same parameters and
# optimizer state; ||u_sys - u_ref|| / ||u_ref|| over the sampled
# elements of the two updates.  Both sides hold the same f32 numbers and
# differ only in the order four of them are summed in: measured exactly 0
# on one chip and 4.8e-8 to 4.9e-8 on four, in all 58 chip runs of PR 22
# (PERF.md, findings).  A gradient cast to bf16 on the wire measured
# 2.3e-3 at toy width, fp16 4e-4 and int8 1.1e-2
# (benchmark/tests/test_reference.py), so each fails this at least fortyfold.
EXCHANGE_RTOL = 1e-5

# Whole steps through the system and through the plain reference
# (benchmark/reference/) are compared where that can be tight: in f32 at
# toy width, benchmark/tests/test_reference.py, below 1e-5.  On the chip
# it cannot.  The two step programs are compiled apart, the compiler
# places the bf16 roundings of the backward pass differently in each, and
# BatchNorm's backward pass subtracts nearly equal numbers: after three
# steps ResNet-50's two updates differed by 6e-4 to 5.6e-2 on the chip
# (VGG-16's by 1.2e-6 to 2.9e-3; PERF.md, findings of PR 22).  A bound
# loose enough for that (it was 0.25) caught nothing the check above
# does not, and the second step program cost every ResNet-50 run 8 s of
# set-up; the two steps share the model's ``apply`` and differ in the
# exchange alone, which the check above holds to 1e-5.  So the harness
# does not run it.

# The first loss from random weights sits near ln(classes): the last
# BatchNorm scale of every ResNet block starts at zero and VGG's logits
# start small, so the softmax is close to uniform.  (The smoke's band.)
FIRST_LOSS_BELOW, FIRST_LOSS_ABOVE = 1.5, 3.0


def _sample(tree):
    import jax
    import jax.numpy as jnp

    parts = []
    for leaf in jax.tree_util.tree_leaves(tree):
        flat = leaf.reshape(-1)
        stride = max(1, flat.shape[0] // SAMPLE_PER_LEAF)
        parts.append(flat[::stride][:SAMPLE_PER_LEAF].astype(jnp.float32))
    return jnp.concatenate(parts)


def relative_distance(got, want, start=None) -> float:
    """||got - want|| / ||want - start|| on host vectors (``start``
    defaults to zero); infinite when ``want`` did not move."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    moved = np.linalg.norm(want if start is None
                           else want - np.asarray(start, np.float64))
    if moved == 0.0:
        return math.inf
    return float(np.linalg.norm(got - want) / moved)


def make_exchange_probe(mesh, axis_name: str, tx, reduce_grads):
    """One jitted program: ``(key, params, opt_state)`` -> the sampled
    update ``tx`` makes of per-chip synthetic gradients drawn from
    ``key``, which ``reduce_grads`` sees first.  The system's side
    passes its own optimizer and the identity; the reference's passes
    the plain one and ``data_parallel_sgd.average_over``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    def probe(key, params, opt_state):
        key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
        leaves, treedef = jax.tree_util.tree_flatten(params)
        bounds = np.cumsum([0] + [x.size for x in leaves])
        flat = jax.random.normal(key, (bounds[-1],), jnp.float32)
        grads = jax.tree_util.tree_unflatten(treedef, [
            flat[a:b].reshape(x.shape).astype(x.dtype)
            for a, b, x in zip(bounds, bounds[1:], leaves)])
        updates, _ = tx.update(reduce_grads(grads), opt_state, params)
        return _sample(updates)

    return jax.jit(jax.shard_map(
        probe, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False))


def exchange_agrees(system, reference) -> Tuple[bool, Dict[str, float]]:
    distance = relative_distance(system, reference)
    return (distance <= EXCHANGE_RTOL,
            {"exchange_distance": distance, "exchange_rtol": EXCHANGE_RTOL})


def first_loss_in_band(loss: float, expected: float) -> bool:
    return (expected - FIRST_LOSS_BELOW < loss
            < expected + FIRST_LOSS_ABOVE)


def count_not_finite(losses: Sequence[float]) -> int:
    return sum(1 for x in losses if not math.isfinite(x))


def batch_is_spread(batch: Dict[str, object], devices,
                    rows_per_chip: int) -> Tuple[bool, List[str]]:
    """Every array of the batch has one shard of ``rows_per_chip`` rows
    on each device."""
    wrong = []
    for name, arr in batch.items():
        shards = arr.addressable_shards
        on = {s.device for s in shards}
        rows = {s.data.shape[0] for s in shards}
        if on != set(devices) or rows != {rows_per_chip}:
            wrong.append(
                f"{name}: shards on {len(on)} of {len(devices)} devices "
                f"with leading sizes {sorted(rows)}")
    return not wrong, wrong


def make_replica_digest(mesh, axis_name: str):
    """One jitted program: a replicated tree -> one uint32 per device,
    the wrapping sum of every leaf's bits on that device.  Replicas that
    drifted apart by one bit anywhere give different digests."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def digest(tree):
        acc = jnp.zeros((), jnp.uint32)
        for leaf in jax.tree_util.tree_leaves(tree):
            bits = jax.lax.bitcast_convert_type(
                leaf.astype(jnp.float32), jnp.uint32)
            acc = acc + jnp.sum(bits, dtype=jnp.uint32)
        return acc[None]

    return jax.jit(jax.shard_map(
        digest, mesh=mesh, in_specs=(P(),), out_specs=P(axis_name),
        check_vma=False))


def replicas_bit_equal(digests) -> bool:
    import numpy as np

    digests = np.asarray(digests)
    return bool((digests == digests[0]).all())
