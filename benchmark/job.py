"""The path every cell drives: the README quick start, as
``chip_smoke.TrainJob`` writes it and nothing else.

``hvt.world_mesh()`` -> ``hvt.DistributedOptimizer(optax.sgd(...),
axis_name="world")`` inside ``jax.jit(jax.shard_map(one_step, ...),
donate_argnums=(0, 1, 2))``, one optimizer step per dispatch, a fresh
global batch every step from ``hvt.data.ElasticDataLoader`` over a host
pool placed by an explicit ``device_put`` transform.  Every
configuration shares every line of it; what differs comes in through
the builder's ``Workload`` and the traffic mix's file.
"""

from __future__ import annotations

from typing import Any, Dict


def make_optimizer(spec: Dict[str, Any]):
    """The plain optax optimizer a configuration's file asks for; the
    train job wraps it, the reference uses it as it is."""
    import optax

    if spec["name"] != "sgd":
        raise ValueError(f"unknown optimizer {spec['name']!r}")
    return optax.sgd(spec["learning_rate"], momentum=spec["momentum"])


def make_step(mesh, loss_fn, tx):
    """``step(params, model_state, opt_state, batch)`` ->
    ``(params, model_state, opt_state, loss)``: replicated state, the
    batch split over ``world``, the state's buffers donated."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    def one_step(params, model_state, opt_state, batch):
        (loss, model_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, model_state, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, model_state, opt_state, jax.lax.pmean(loss, "world")

    return jax.jit(
        jax.shard_map(
            one_step, mesh=mesh, in_specs=(P(), P(), P(), P("world")),
            out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))


class TrainJob:
    """State, input stream and step of one data-parallel job over every
    local chip."""

    def __init__(self, workload, config: Dict[str, Any],
                 traffic: Dict[str, Any], seed: int):
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        import horovod_tpu as hvt

        self.workload = workload
        self.mesh = hvt.world_mesh()
        self.n_dev = hvt.num_devices()
        self.batch_per_chip = traffic["batch_per_chip"]
        self.global_batch = self.batch_per_chip * self.n_dev
        self.replicated = NamedSharding(self.mesh, P())
        self.batch_sharding = NamedSharding(self.mesh, P("world"))
        self.seed = seed

        feed = traffic["feed"]
        pool = workload.make_pool(
            np.random.default_rng(seed),
            feed["host_pool_batches"] * self.global_batch, feed["dtype"])
        self._example = pool["x"][:2]

        def place(batch):
            return {k: jax.device_put(v, self.batch_sharding)
                    for k, v in batch.items()}

        # device_put=False + an explicit placing transform: a failed
        # transfer fails the prefetch, it is not retried on the host
        self.loader = hvt.data.ElasticDataLoader(
            hvt.data.ArraySource(pool), batch_size=self.global_batch,
            shuffle=feed["reshuffle"], seed=seed, device_put=False,
            transform=place, name="benchmark")
        self.batches = self.loader.stream()

        compression = getattr(hvt.Compression, traffic["compression"])
        self.plain_tx = make_optimizer(config["optimizer"])
        self.tx = hvt.DistributedOptimizer(
            self.plain_tx, axis_name="world", compression=compression)
        # one jitted program: eager init would compile per parameter
        self._init = jax.jit(
            lambda key, rows: self._fresh(self.tx, key, rows),
            out_shardings=self.replicated)
        self.step = make_step(self.mesh, workload.loss_fn, self.tx)

    def _fresh(self, tx, key, rows):
        params, model_state = self.workload.init(key, rows)
        return params, model_state, tx.init(params)

    def fresh_state(self):
        """(params, model_state, opt_state) from the seed, replicated,
        made on the device in one call; the same every time."""
        import jax

        return self._init(jax.random.PRNGKey(self.seed), self._example)

    def close(self):
        self.loader.close()
