"""The plain reference and the comparison that decides ``correct``, at
toy width on four virtual CPU devices."""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import cells, correctness
from benchmark.reference import data_parallel_sgd


@pytest.fixture(scope="module")
def hvt():
    import horovod_tpu as hvt

    hvt.init()
    yield hvt
    hvt.shutdown()


def test_the_reference_does_not_import_the_program():
    directory = os.path.join(cells.HERE, "reference")
    for name in os.listdir(directory):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(directory, name)) as f:
            tree = ast.parse(f.read())
        imported = {a.name for n in ast.walk(tree)
                    if isinstance(n, ast.Import) for a in n.names}
        imported |= {n.module for n in ast.walk(tree)
                     if isinstance(n, ast.ImportFrom)}
        assert not any(m and m.split(".")[0] == "horovod_tpu"
                       for m in imported), name


PARAMS = {"kernel": jnp.ones((300, 70)), "bias": jnp.zeros((70,)),
          "wide": jnp.full((5000,), 0.5)}


def _probe(hvt, tx, reduce_grads, key=3):
    return np.asarray(correctness.make_exchange_probe(
        hvt.world_mesh(), "world", tx, reduce_grads)(
            jax.random.PRNGKey(key), PARAMS, tx.init(PARAMS)))


@pytest.mark.parametrize("wire, agrees", [
    ("none", True), ("bf16", False), ("fp16", False), ("int8", False)])
def test_a_cast_on_the_wire_fails_the_exchange_check(hvt, wire, agrees):
    plain = optax.sgd(0.1, momentum=0.9)
    want = _probe(hvt, plain, data_parallel_sgd.average_over("world"))
    tx = hvt.DistributedOptimizer(
        plain, axis_name="world",
        compression=getattr(hvt.Compression, wire))
    got = _probe(hvt, tx, lambda g: g)
    ok, seen = correctness.exchange_agrees(got, want)
    assert ok is agrees, seen
    if not agrees:
        # not by a hair: the tolerance sits far below any cast's error
        assert seen["exchange_distance"] > 30 * correctness.EXCHANGE_RTOL


def test_the_probe_gives_every_chip_its_own_gradients(hvt):
    plain = optax.sgd(1.0)
    averaged = _probe(hvt, plain, lambda g: jax.lax.pmean(g, "world"))
    # the mean of four independent standard normals has deviation 1/2
    assert np.std(averaged) == pytest.approx(0.5, rel=0.05)
    again = _probe(hvt, plain, lambda g: jax.lax.pmean(g, "world"))
    other = _probe(hvt, plain, lambda g: jax.lax.pmean(g, "world"), key=4)
    assert np.array_equal(averaged, again)
    assert not np.array_equal(averaged, other)


def _loss(params, model_state, batch):
    pred = jnp.tanh(batch["x"] @ params["kernel"] + params["bias"])
    return jnp.mean((pred.sum(-1) - batch["y"]) ** 2), model_state


def test_three_whole_steps_agree_in_f32_and_a_wrong_rate_does_not(hvt):
    """Where nothing is rounded to bf16 the whole step can be held as
    tightly as the exchange; on the chip it cannot (correctness.py)."""
    from benchmark.job import make_step

    mesh, plain = hvt.world_mesh(), optax.sgd(0.1, momentum=0.9)
    rng = np.random.default_rng(0)
    params = {"kernel": jnp.asarray(rng.standard_normal((16, 8)) * 0.1,
                                    jnp.float32),
              "bias": jnp.zeros((8,), jnp.float32)}
    batches = [{"x": jnp.asarray(rng.standard_normal((32, 16)), jnp.float32),
                "y": jnp.asarray(rng.standard_normal((32,)), jnp.float32)}
               for _ in range(correctness.WARMUP_STEPS)]

    def flat(tree):
        return np.concatenate(
            [np.ravel(x) for x in jax.tree_util.tree_leaves(tree)])

    def walk(step, tx):
        state, losses = (params, {}, tx.init(params)), []
        state = jax.tree_util.tree_map(jnp.copy, state)  # steps donate
        for batch in batches:
            *state, loss = step(*state, batch)
            losses.append(float(loss))
        return flat(state[0]), losses

    start = flat(params)
    want, want_losses = walk(
        data_parallel_sgd.make_step(mesh, "world", _loss, plain), plain)
    tx = hvt.DistributedOptimizer(plain, axis_name="world")
    got, got_losses = walk(make_step(mesh, _loss, tx), tx)
    assert correctness.relative_distance(got, want, start) < 1e-5
    assert got_losses == pytest.approx(want_losses, rel=1e-6)

    hasty = hvt.DistributedOptimizer(
        optax.sgd(0.2, momentum=0.9), axis_name="world")
    got, _ = walk(make_step(mesh, _loss, hasty), hasty)
    assert correctness.relative_distance(got, want, start) > 0.5


def test_first_loss_band_and_counts():
    assert correctness.first_loss_in_band(6.95, np.log(1000))
    assert not correctness.first_loss_in_band(12.0, np.log(1000))
    assert not correctness.first_loss_in_band(float("nan"), np.log(1000))
    assert correctness.count_not_finite([1.0, float("inf"), float("nan")]) == 2
    assert correctness.relative_distance([1.0], [1.0], [1.0]) == float("inf")


def test_replica_digest_tells_one_flipped_bit(hvt):
    mesh = hvt.world_mesh()
    digest = correctness.make_replica_digest(mesh, "world")
    tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((5,))}
    assert correctness.replicas_bit_equal(digest(tree))
    assert not correctness.replicas_bit_equal(np.array([7, 7, 8, 7]))
