"""Synchronous data-parallel training, written plainly.

What ``hvt.DistributedOptimizer`` has to equal: every worker computes
the gradient of the loss on its shard of the batch, the gradients are
averaged over the workers leaf by leaf (``jax.lax.pmean``), and the
plain optax optimizer applies the average.  No fusion buckets, no wire
cast, no non-finite guard.  It takes the same ``loss_fn`` and so the
same model ``apply``: the layer under test here is the exchange and the
optimizer wrapper, not the model (``PERF.md``, open questions).

The harness holds the system to ``average_over`` + the plain optimizer
on synthetic gradients in every run; ``make_step`` is the whole step,
compared with the system's where that can be tight: in f32 at toy width
(``benchmark/correctness.py`` says why not on the chip).
"""

from __future__ import annotations


def average_over(axis_name: str):
    """``grads -> grads``: each leaf's mean over the workers."""
    import jax

    return lambda grads: jax.lax.pmean(grads, axis_name)


def make_step(mesh, axis_name: str, loss_fn, tx):
    """``step(params, model_state, opt_state, batch)`` ->
    ``(params, model_state, opt_state, loss)`` over ``mesh``, with
    replicated state and the batch split over ``axis_name``."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    def one_step(params, model_state, opt_state, batch):
        (loss, model_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, model_state, batch)
        grads = average_over(axis_name)(grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, model_state, opt_state, jax.lax.pmean(loss, axis_name)

    return jax.jit(
        jax.shard_map(
            one_step, mesh=mesh,
            in_specs=(P(), P(), P(), P(axis_name)),
            out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))
