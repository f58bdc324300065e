"""``parallel.moe.dropless_topk_moe``: the chip's share of a top-k
expert layer, against a plain loop over the experts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import moe

N, D, F, E = 50, 16, 12, 16


def _weights(seed, held):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (N, D))
    gate_w = jax.random.normal(ks[1], (D, E))
    experts = {
        "w_gate": 0.3 * jax.random.normal(ks[2], (held, D, F)),
        "w_up": 0.3 * jax.random.normal(ks[3], (held, D, F)),
        "w_down": 0.3 * jax.random.normal(ks[4], (held, F, D))}
    return x, gate_w, experts


def plain(x, gate_w, experts, *, top_k, first_expert, renormalise=True):
    """Every held expert over every token, weighted by the router."""
    top_p, top_i = jax.lax.top_k(jax.nn.softmax(x @ gate_w, -1), top_k)
    if renormalise:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(experts["w_gate"].shape[0]):
        w = jnp.sum(jnp.where(top_i == first_expert + e, top_p, 0.0), -1)
        h = jax.nn.silu(x @ experts["w_gate"][e]) * (x @ experts["w_up"][e])
        y = y + w[:, None] * (h @ experts["w_down"][e])
    return y


def layer(x, gate_w, experts, *, top_k, first_expert, renormalise=True):
    return moe.dropless_topk_moe(
        x, gate_w, experts, top_k=top_k, num_experts=E,
        first_expert=first_expert, renormalise=renormalise)


def _close(got, want, rtol=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert np.linalg.norm(got - want) <= rtol * np.linalg.norm(want)


@pytest.mark.parametrize("tile_rows", [4, 8, 512])
@pytest.mark.parametrize("first_expert, held, top_k, renormalise", [
    (0, 4, 3, True), (8, 4, 3, True), (12, 4, 8, False), (0, 16, 2, True)])
def test_the_share_equals_the_plain_loop(monkeypatch, tile_rows,
                                         first_expert, held, top_k,
                                         renormalise):
    monkeypatch.setattr(moe, "_TILE_ROWS", tile_rows)
    x, gate_w, experts = _weights(first_expert + held, held)
    kwargs = dict(top_k=top_k, first_expert=first_expert,
                  renormalise=renormalise)
    y, routing = jax.jit(lambda *a: layer(*a, **kwargs))(x, gate_w, experts)
    _close(y, plain(x, gate_w, experts, **kwargs))
    top_i = np.asarray(routing["experts"])
    assert top_i.shape == (N, top_k)
    want_rows = [(top_i == first_expert + e).sum() for e in range(held)]
    assert routing["rows_per_expert"].tolist() == want_rows
    target = jax.random.normal(jax.random.PRNGKey(9), (N, D))
    got = jax.grad(lambda *a: jnp.sum(layer(*a, **kwargs)[0] * target),
                   argnums=(0, 1, 2))(x, gate_w, experts)
    want = jax.grad(lambda *a: jnp.sum(plain(*a, **kwargs) * target),
                    argnums=(0, 1, 2))(x, gate_w, experts)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(g, w)


@pytest.mark.parametrize("to_one_expert", [False, True])
def test_nothing_is_dropped_when_the_router_sends_every_token_here(
        monkeypatch, to_one_expert):
    """A rigged router: every token's top-k lie among the held experts
    (or its first choice is one and the same expert), so the layer gets
    top_k rows a token, eight times an even routing's, and still equals
    the plain loop."""
    monkeypatch.setattr(moe, "_TILE_ROWS", 8)
    first_expert, held, top_k = 8, 4, 3
    x, gate_w, experts = _weights(3, held)
    x = x.at[:, 0].set(10.0)
    pull = jnp.array([3.0, 0.0, 0.0, 0.0] if to_one_expert
                     else [4.0, 4.1, 4.2, 4.3])
    gate_w = gate_w.at[0, first_expert:first_expert + held].set(pull)
    kwargs = dict(top_k=top_k, first_expert=first_expert)
    y, routing = layer(x, gate_w, experts, **kwargs)
    rows = np.asarray(routing["rows_per_expert"])
    if to_one_expert:
        assert rows[0] == N
    else:
        assert rows.sum() == N * top_k
    _close(y, plain(x, gate_w, experts, **kwargs))


def test_a_share_no_token_chose_adds_nothing():
    x, gate_w, experts = _weights(4, 2)
    x = jnp.abs(x)
    gate_w = gate_w.at[:, 6:8].set(-50.0)
    y, routing = layer(x, gate_w, experts, top_k=3, first_expert=6)
    assert routing["rows_per_expert"].tolist() == [0, 0]
    assert not np.asarray(y).any()


def test_the_shares_add_up_to_the_whole_layer(monkeypatch):
    monkeypatch.setattr(moe, "_TILE_ROWS", 8)
    x, gate_w, experts = _weights(5, E)
    whole = plain(x, gate_w, experts, top_k=4, first_expert=0)
    parts = [
        layer(x, gate_w, {k: v[first:first + 4] for k, v in experts.items()},
              top_k=4, first_expert=first)[0]
        for first in range(0, E, 4)]
    _close(sum(parts), whole)


def test_experts_beyond_the_router_are_refused():
    x, gate_w, experts = _weights(6, 4)
    with pytest.raises(ValueError, match="not among"):
        layer(x, gate_w, experts, top_k=2, first_expert=13)


def test_bfloat16_rows_come_back_in_bfloat16():
    x, gate_w, experts = _weights(7, 4)
    y, _ = layer(x.astype(jnp.bfloat16), gate_w, experts, top_k=3,
                 first_expert=4)
    assert y.dtype == jnp.bfloat16
    want = plain(x, gate_w, experts, top_k=3, first_expert=4)
    _close(y.astype(jnp.float32), want, rtol=0.05)
