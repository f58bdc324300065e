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


def _rigged(routing, held, first_expert, dtype):
    """Inputs whose router is rigged: ``uneven`` (as the weights fall,
    but nobody chooses the last held expert: the rows a reader finds
    for it lie past everything written), ``one_expert`` (every token's
    first choice is the first held expert: its count, N = 50, is a
    whole number of tiles of 5 and of 10), ``all_here`` (every choice
    is a held expert: the buffer's worst case) and ``none_here`` (no
    choice is)."""
    x, gate_w, experts = _weights(11, held)
    here = slice(first_expert, first_expert + held)
    x = x.at[:, 0].set(10.0)
    if routing == "uneven":
        gate_w = gate_w.at[0, first_expert + held - 1].set(-50.0)
    else:
        gate_w = gate_w.at[0, here].set(jnp.array({
            "one_expert": [3.0, 0.0, 0.0, 0.0],
            "all_here": [4.0, 4.1, 4.2, 4.3],
            "none_here": [-50.0] * 4}[routing]))
    return x.astype(dtype), gate_w, experts


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tile_rows", [5, 10])
@pytest.mark.parametrize("routing", ["uneven", "one_expert", "all_here",
                                     "none_here"])
def test_rows_of_the_buffers_that_nobody_wrote_are_never_used(
        monkeypatch, routing, tile_rows, dtype):
    """The row buffers, and the arrays the way back writes block by
    block, are allocated, not cleared (on the CPU
    ``lax.empty`` happens to give zeros, which would hide a reader that
    trusts them).  Filled with NaN they give the forward result and all
    five gradients of buffers filled with zeros, bit for bit."""
    monkeypatch.setattr(moe, "_TILE_ROWS", tile_rows)
    first_expert, held, top_k = 8, 4, 3
    args = _rigged(routing, held, first_expert, dtype)
    target = jax.random.normal(jax.random.PRNGKey(9), (N, D))
    seen = {}

    def run(fill):
        def buffer(rows, width, dtype, near):
            seen[fill] = seen.get(fill, 0) + 1
            return jnp.full((rows, width), fill, dtype)
        monkeypatch.setattr(moe, "_row_buffer", buffer)

        def loss(x, gate_w, experts):
            y, routing = layer(x, gate_w, experts, top_k=top_k,
                               first_expert=first_expert)
            return jnp.sum(y.astype(jnp.float32) * target), (y, routing)
        (_, (y, routing)), grads = jax.value_and_grad(
            loss, (0, 1, 2), has_aux=True)(*args)
        return routing["rows_per_expert"], [y] + jax.tree_util.tree_leaves(
            grads)

    rows, clean = run(0.0)
    _, poisoned = run(jnp.nan)
    # forward's and backward's, in expert order and in token order
    assert seen == {0.0: 4, jnp.nan: 4}
    rows = np.asarray(rows)
    assert {"uneven": 0 < rows.sum() < N * top_k and len(set(rows)) > 1,
            "one_expert": rows[0] == N and N % tile_rows == 0,
            "all_here": rows.sum() == N * top_k,
            "none_here": rows.sum() == 0}[routing]
    assert len(clean) == 6
    for got, want in zip(poisoned, clean):
        assert np.isfinite(np.asarray(got, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("where, custom_calls", [
    ("tpu", 4), ("tpu_without_pallas", 0), ("tpu_float16", 0), ("cpu", 0)])
def test_lowered_for_a_tpu_the_row_buffers_are_plain_custom_calls(
        monkeypatch, where, custom_calls):
    """On a TPU each of the four allocations (forward's and backward's,
    in expert order and in token order) is a kernel that does nothing,
    a ``tpu_custom_call`` named for what it is, under the layer's
    scopes, and with no kernel metadata, which XLA would print over
    several lines where ``benchmark/scopes.py`` cannot follow; without
    Pallas, for a type Mosaic cannot load, and off the TPU it is
    ``lax.empty``."""
    import re

    from horovod_tpu.ops import pallas_ops

    monkeypatch.delenv("HVTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)
    if where == "tpu_without_pallas":
        monkeypatch.setenv("HVTPU_PALLAS", "0")
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: where != "cpu")
    dtype = jnp.float16 if where == "tpu_float16" else jnp.bfloat16
    x, gate_w, experts = _weights(5, 4)

    def loss(x, gate_w, experts):
        return jnp.sum(layer(x.astype(dtype), gate_w, experts, top_k=3,
                             first_expert=8)[0].astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).trace(
        x, gate_w, experts).lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)
    found = re.findall(
        r"stablehlo\.custom_call @tpu_custom_call.* loc\((#loc\d+)\)$", text,
        re.MULTILINE)
    assert len(found) == custom_calls
    scopes = set()
    for line, loc in zip(re.findall(
            r"stablehlo\.custom_call @tpu_custom_call.*", text), found):
        assert 'kernel_name = "hvtpu_moe_row_buffer"' in line
        assert "kernel_metadata" not in line.replace(
            'kernel_metadata = "{}"', "")
        scopes.update(re.findall(
            r"hvtpu:moe\.\w+", re.search(
                "^" + loc + r" = loc\(\"([^\"]*)\"", text, re.MULTILINE)[1]))
    assert scopes == ({"hvtpu:moe.dispatch", "hvtpu:moe.combine"}
                      if custom_calls else set())


def test_a_blocks_list_whether_or_not_token_and_row_share_a_sort_key():
    """``_by_block``: a block's assignments at the front of its list,
    each with its token in the block and its row of the buffer; sorted
    as one int32 key where both fit, as a key and a payload where the
    buffer has too many rows for that."""
    rng = np.random.default_rng(0)
    hit = rng.random((3, 6, 4)) < 0.3       # blocks, tokens a block, experts
    dest = rng.permutation(hit.size).reshape(hit.shape).astype(np.int32)
    for rows_of_buffer in (100, 2 ** 30):
        tokens, rows = moe._by_block(jnp.asarray(hit), jnp.asarray(dest), 5,
                                     rows_of_buffer)
        assert tokens.shape == rows.shape == (3, 25)    # 24: 5 tiles of 5
        for b in range(3):
            count = hit[b].sum()
            assert sorted(zip(np.asarray(tokens)[b, :count].tolist(),
                              np.asarray(rows)[b, :count].tolist())) == sorted(
                (t, dest[b, t, e]) for t, e in zip(*np.nonzero(hit[b])))
            # no entry past them stands for a token of the block
            assert (np.asarray(tokens)[b, count:] >= 6).all()


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
