"""Pod-shape training: P processes × D local devices, ONE global mesh.

The deployment shape of a real TPU pod (e.g. v5e-256 = 64 hosts × 4
chips): every process runs the SAME jitted training step over the
global ``hvt.world_mesh()`` (multi-controller JAX), each providing its
locally-addressable shards.  The jit/SPMD path uses ALL P×D devices;
``hvt.rank()``/``size()`` stay process-granularity (one Horovod rank =
one process, exactly like the reference's one-rank-per-GPU model, with
D chips per rank instead of one).

Run (2 processes × 4 virtual CPU devices = an 8-device global mesh):

    hvtpurun -np 2 --cpu-devices 4 python examples/pod_train.py

On a TPU host, drop ``--cpu-devices``.  ``hvtpurun -np 4`` on a
four-chip host is the Horovod shape — four ranks, one chip each (the
launcher hands local rank r chip r through the environment libtpu
reads) — and ``-np 1`` is one process driving all four.  Either way
the mesh spans every chip, and the eager plane (``hvt.allreduce``)
spans the ranks.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvt


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=256, help="global batch")
    args = p.parse_args()

    hvt.init()
    mesh = hvt.world_mesh()
    n_dev = mesh.devices.size
    print(f"rank {hvt.rank()}/{hvt.size()} owns "
          f"{[str(d) for d in jax.local_devices()]}", flush=True)
    if hvt.rank() == 0:
        print(f"pod: {hvt.size()} processes x "
              f"{jax.local_device_count()} local devices = "
              f"{n_dev}-device world mesh", flush=True)

    # The eager plane is process-granularity: one tensor per rank.
    total = hvt.allreduce(
        jnp.full((1024,), hvt.rank() + 1.0), op=hvt.Sum, name="pod.hello")
    want = hvt.size() * (hvt.size() + 1) / 2
    assert np.asarray(total).tolist() == [want] * 1024, (total[:4], want)

    # Deterministic synthetic data; every process generates the full
    # array and contributes only the shards it owns.
    rng = np.random.RandomState(0)
    W0 = (rng.randn(64, 8) * 0.1).astype(np.float32)
    X = rng.randn(args.batch, 64).astype(np.float32)
    Y = rng.randn(args.batch, 8).astype(np.float32)

    repl = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("world"))
    w = jax.make_array_from_callback(W0.shape, repl, lambda i: W0[i])
    x = jax.make_array_from_callback(X.shape, rows, lambda i: X[i])
    y = jax.make_array_from_callback(Y.shape, rows, lambda i: Y[i])

    opt = hvt.DistributedOptimizer(
        optax.sgd(0.1, momentum=0.9), axis_name="world"
    )

    def step(w, s, xs, ys):
        def loss_fn(w):
            return jnp.mean((xs @ w - ys) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(w)
        updates, s = opt.update(g, s, w)
        return optax.apply_updates(w, updates), s, \
            jax.lax.pmean(loss, "world")

    sstep = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P("world"), P("world")),
        out_specs=(P(), P(), P()), check_vma=False,
    ))
    s = jax.jit(
        opt.init,
        out_shardings=jax.tree_util.tree_map(
            lambda _: repl, jax.eval_shape(opt.init, w)
        ),
    )(w)

    first = last = None
    for i in range(args.steps):
        w, s, loss = sstep(w, s, x, y)
        val = float(np.asarray(loss.addressable_data(0)))
        first = val if first is None else first
        last = val
    assert last < first, (first, last)
    if hvt.rank() == 0:
        print(f"eager allreduce across {hvt.size()} ranks = {want:g}; "
              f"loss {first:.4f} -> {last:.4f} over {args.steps} steps "
              f"on {n_dev} devices; ranks consistent "
              f"({hvt.size()} ranks)", flush=True)


if __name__ == "__main__":
    main()
