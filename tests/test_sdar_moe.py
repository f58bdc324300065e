"""``parallel.moe.dropless_topk_moe``: the chip's share of a top-k
expert layer, against a plain loop over the experts."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import moe

N, D, F, E = 50, 16, 12, 16


def _weights(seed, held, n=N, d=D, f=F):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, d))
    gate_w = jax.random.normal(ks[1], (d, E))
    experts = {
        "w_gate": 0.3 * jax.random.normal(ks[2], (held, d, f)),
        "w_up": 0.3 * jax.random.normal(ks[3], (held, d, f)),
        "w_down": 0.3 * jax.random.normal(ks[4], (held, f, d))}
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


def _rigged(routing, held, first_expert, dtype, **sizes):
    """Inputs whose router is rigged: ``uneven`` (as the weights fall,
    but nobody chooses the last held expert: the rows a reader finds
    for it lie past everything written), ``one_expert`` (every token's
    first choice is the first held expert: its count, N = 50, is a
    whole number of tiles of 5 and of 10), ``all_here`` (every choice
    is a held expert: the buffer's worst case) and ``none_here`` (no
    choice is)."""
    x, gate_w, experts = _weights(11, held, **sizes)
    here = slice(first_expert, first_expert + held)
    # the rigged coordinate outweighs the others' sum at any width
    x = x.at[:, 0].set(10.0 * (x.shape[1] / D) ** 0.5)
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
    # in expert order the forward pass's rows and their weights, the
    # backward pass's rows, cotangents and weights; in token order one
    # a pass (what the products write is theirs to allocate)
    assert seen == {0.0: 7, jnp.nan: 7}
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
    ("tpu", 7), ("tpu_without_pallas", 0), ("tpu_float16", 2), ("cpu", 0)])
def test_lowered_for_a_tpu_the_row_buffers_are_plain_custom_calls(
        monkeypatch, where, custom_calls):
    """On a TPU each of the seven allocations (in expert order the
    forward pass's rows and their weights, the backward pass's rows,
    cotangents and weights; in token order one a pass) is a kernel that
    does nothing, a ``tpu_custom_call`` named for what it is, under the
    layer's scopes, and with no kernel metadata, which XLA would print
    over several lines where ``benchmark/scopes.py`` cannot follow;
    without Pallas, for a type Mosaic cannot load (float16 rows: the
    two buffers of float32 weights stay kernels), and off the TPU it is
    ``lax.empty``.  Widths of 16 are none the grouped kernels take, so
    the products here are ``lax.ragged_dot``'s."""
    import re

    from horovod_tpu.ops import backend

    monkeypatch.delenv("HVTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)
    if where == "tpu_without_pallas":
        monkeypatch.setenv("HVTPU_PALLAS", "0")
    monkeypatch.setattr(backend, "on_tpu", lambda: where != "cpu")
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
    assert scopes == {7: {"hvtpu:moe.dispatch", "hvtpu:moe.combine"},
                      2: {"hvtpu:moe.dispatch"}, 0: set()}[custom_calls]


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


# -- the grouped kernels (ops/grouped_ffn.py), interpreted ------------------

WIDE = dict(n=64, d=128, f=256)     # widths the kernels take


@pytest.fixture
def grouped(monkeypatch):
    """The kernels under the interpreter, a gather's tile of 32 rows
    and a visit of 16: 64 tokens, top-3 of 4 held, a buffer of 192."""
    monkeypatch.setenv("HVTPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)
    monkeypatch.setattr(moe, "_TILE_ROWS", 32)
    monkeypatch.setattr(moe, "_PRODUCT_ROWS", 16)


def _hits(routing):
    """Which of 4 held experts each of 64 tokens chose, at most 3."""
    n = WIDE["n"]
    token = np.arange(n)[:, None]
    expert = np.arange(4)[None, :]
    rng = np.random.default_rng(3)
    hit = {
        # 32 rows an expert: every expert ends on a tile of 32
        "even": (expert == token % 4) | (expert == (token + 1) % 4),
        "an_expert_without_rows": (rng.random((n, 4)) < 0.4) & (expert != 1),
        # 3 of 4 for every token: the buffer's worst case, all 192 rows
        "every_token_here": expert != token % 4,
        # 19, 30, 7 and 23 rows: no expert ends where a tile does
        "off_every_tile": np.concatenate([
            token < 19, token < 30, (40 <= token) & (token < 47),
            token >= 41], axis=1),
        "nobody_here": np.zeros((n, 4), bool),
    }[routing]
    return np.where(hit.sum(1, keepdims=True) > 3, expert != 0, hit)


def _dense(x, weight, hit, w_gate, w_up, w_down):
    """``sum_e weight[n, e] FFN_e(x[n])`` over the hits, every expert
    over every token."""
    y = jnp.zeros_like(x)
    for e in range(hit.shape[1]):
        h = jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])
        y = y + jnp.where(hit[:, e], weight[:, e], 0.0)[:, None] * (
            h @ w_down[e])
    return y


@pytest.mark.parametrize("poisoned", [False, True])
@pytest.mark.parametrize("routing", [
    "even", "an_expert_without_rows", "every_token_here", "off_every_tile",
    "nobody_here"])
def test_the_grouped_kernels_equal_every_expert_over_every_token(
        monkeypatch, grouped, routing, poisoned):
    """``_grouped_ffn`` through the Pallas kernels against the dense
    sum, the result and the gradients of ``x``, ``weight`` and the
    three weights, over routings whose groups end on every tile, on
    none, leave an expert without rows, fill the buffer to its last
    row, or send nothing.  ``poisoned``: every row of the buffers that
    no assignment owns is NaN before the products, and everything
    comes out finite and as from buffers of zeros, bit for bit."""
    x, _, experts = _weights(21, 4, **WIDE)
    hit = _hits(routing)
    counts = hit.sum(0)
    assert {"even": (counts == 32).all(),
            "an_expert_without_rows": counts[1] == 0 < counts[0],
            "every_token_here": counts.sum() == moe.buffer_rows(64, 3, 4),
            "off_every_tile": (np.cumsum(counts) % 16 != 0).all(),
            "nobody_here": counts.sum() == 0}[routing], counts
    weight = jax.random.uniform(jax.random.PRNGKey(2), hit.shape)
    target = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    names = ("w_gate", "w_up", "w_down")

    def ours(x, weight, *w):
        plan, sizes = moe._plan(jnp.asarray(hit), 3)
        return moe._grouped_ffn((*sizes, "grouped"), x, weight, plan,
                                tuple(w))

    def run(f):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(f(*a) * target), (0, 1, 2, 3, 4)))(
                x, weight, *(experts[k] for k in names))

    assert moe.products_path(x.dtype, 128, 256, 64, 3, 4) == "grouped"
    got = jax.tree_util.tree_leaves(run(ours))
    want = jax.tree_util.tree_leaves(run(
        lambda x, weight, *w: _dense(x, weight, jnp.asarray(hit), *w)))
    assert len(got) == 6
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        if routing == "nobody_here":
            assert not np.asarray(g).any() and not np.asarray(w).any()
        else:
            _close(g, w)
    if poisoned:
        monkeypatch.setattr(
            moe, "_row_buffer", lambda rows, width, dtype, near: jnp.full(
                (rows, width), jnp.nan, dtype))
        for g, clean in zip(jax.tree_util.tree_leaves(run(ours)), got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(clean))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("routing", ["uneven", "one_expert", "all_here",
                                     "none_here"])
def test_through_the_kernels_the_layer_equals_the_plain_loop(
        grouped, routing, dtype):
    """``dropless_topk_moe`` with its products in the kernels, rigged
    routers and all, against the plain loop: the result, the rows each
    expert got, and the gradients of the tokens, the router and the
    three weights; bfloat16 rows against the plain loop in float32."""
    first_expert, held, top_k = 8, 4, 3
    before = moe.metrics.REGISTRY.counter(
        "hvtpu_moe_products_total").value(path="grouped")
    x, gate_w, experts = _rigged(routing, held, first_expert, dtype, **WIDE)
    kwargs = dict(top_k=top_k, first_expert=first_expert)
    target = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def run(f, x):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(f(*a).astype(jnp.float32) * target),
            (0, 1, 2)))(x, gate_w, experts)

    got = jax.tree_util.tree_leaves(
        run(lambda *a: layer(*a, **kwargs)[0], x))
    want = jax.tree_util.tree_leaves(
        run(lambda *a: plain(*a, **kwargs), x.astype(jnp.float32)))
    assert moe.metrics.REGISTRY.counter(
        "hvtpu_moe_products_total").value(path="grouped") == before + 1
    rows = np.asarray(layer(x, gate_w, experts, **kwargs)[1][
        "rows_per_expert"])
    n = WIDE["n"]
    assert {"uneven": 0 < rows.sum() < n * top_k and rows[-1] == 0,
            "one_expert": rows[0] == n,
            "all_here": rows.sum() == n * top_k,
            "none_here": rows.sum() == 0}[routing]
    assert len(got) == 6
    for g, w in zip(got, want):
        g = np.asarray(g, np.float32)
        assert np.isfinite(g).all()
        if routing == "none_here":
            assert not g.any()
        else:
            _close(g, w, rtol=1e-5 if dtype == jnp.float32 else 0.05)


@pytest.mark.parametrize("counts", [
    [0, 0, 0, 0], [5, 0, 70, 0], [16, 16, 16, 16], [0, 0, 0, 96],
    [1, 1, 1, 93], [31, 33, 0, 17]])
@pytest.mark.parametrize("empty_groups", [False, True])
def test_a_walk_visits_every_tile_of_every_group_once(counts, empty_groups):
    """``ops.grouped_ffn.visits``: group after group, every tile that
    holds rows of the group and no other; a group without rows is
    visited only if asked, and then names the tile the walk is at, so
    that no block is fetched or written for it."""
    from horovod_tpu.ops import grouped_ffn

    rows, tile_rows = 96, 16
    walk = grouped_ffn.visits(jnp.asarray(counts, jnp.int32), rows,
                              tile_rows, empty_groups)
    assert walk.offsets.tolist() == [0] + np.cumsum(counts).tolist()
    assert walk.group.shape == walk.tile.shape == (rows // tile_rows + 3,)
    made = list(zip(walk.group[:int(walk.count)].tolist(),
                    walk.tile[:int(walk.count)].tolist()))
    want, at = [], 0
    for g, count in enumerate(counts):
        start, end = at, at + count
        at = end
        if count:
            want += [(g, t) for t in range(start // tile_rows,
                                           (end - 1) // tile_rows + 1)]
        elif empty_groups:
            want.append((g, max(start - 1, 0) // tile_rows))
    assert made == want
    assert ((0 <= np.asarray(walk.tile))
            & (np.asarray(walk.tile) < rows // tile_rows)).all()


@pytest.mark.parametrize("where, dtype, width, tokens, path", [
    ("tpu", jnp.bfloat16, 128, 1024, "grouped"),
    ("tpu", jnp.float32, 512, 32768, "grouped"),
    # a group's f32 weights twice over and sums once crowd the tiles out
    ("tpu", jnp.float32, 4096, 32768, "ragged_dot"),
    ("tpu", jnp.bfloat16, 64, 1024, "ragged_dot"),      # the rehearsals
    ("tpu", jnp.float16, 128, 1024, "ragged_dot"),
    ("tpu", jnp.bfloat16, 128, 8, "ragged_dot"),        # no whole tile
    ("tpu_without_pallas", jnp.bfloat16, 128, 1024, "ragged_dot"),
    ("cpu", jnp.bfloat16, 128, 1024, "ragged_dot")])
def test_how_the_products_run_is_read_off_the_backend_and_the_shapes(
        monkeypatch, where, dtype, width, tokens, path):
    from horovod_tpu.ops import backend

    monkeypatch.delenv("HVTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)
    if where == "tpu_without_pallas":
        monkeypatch.setenv("HVTPU_PALLAS", "0")
    monkeypatch.setattr(backend, "on_tpu", lambda: where != "cpu")
    assert moe.products_path(dtype, width, 768, tokens, 8, 16) == path
    assert moe.products_path(dtype, 2048, width, tokens, 8, 16) == path


@pytest.mark.parametrize("where, dtype, d, f, path, blocked", [
    # the Kimi Linear cell's: three weights and three f32 sums twice over
    # are 81 MiB, three weights twice and the sums once 54
    ("interpret", jnp.bfloat16, 2304, 1024, "grouped", True),
    ("tpu", jnp.bfloat16, 2304, 1024, "grouped", True),
    # the transformer cell's: whole, 54 MiB, the kernel of before
    ("tpu", jnp.bfloat16, 2048, 768, "grouped", False),
    ("tpu", jnp.float32, 2048, 768, "grouped", True),
    # the LFM2 cell's: 126 MiB whole, 84 in blocks, the most the rule
    # allows
    ("tpu", jnp.bfloat16, 2048, 1792, "grouped", True),
    ("tpu", jnp.float32, 2304, 1024, "grouped", True),      # 81 in blocks
    # beyond the blocked rule too: 108 and 162 MiB of its 84
    ("tpu", jnp.bfloat16, 2304, 2048, "ragged_dot", None),
    ("tpu", jnp.float32, 2304, 2048, "ragged_dot", None),
    ("tpu_without_pallas", jnp.bfloat16, 2304, 1024, "ragged_dot", True),
    ("cpu", jnp.bfloat16, 2304, 1024, "ragged_dot", True)])
def test_which_backward_kernel_a_gated_shape_takes(
        monkeypatch, where, dtype, d, f, path, blocked):
    """``products_path`` of gated experts over the Kimi Linear cell's
    16,384 tokens, top-8 of 8 held, and which backward kernel the
    grouped path takes there (``grouped_ffn._blocked``): the group's
    whole inner width where its weights and sums fit VMEM twice over,
    else blocks of it where the weights twice and the sums once do;
    ``lax.ragged_dot`` where neither, on the CPU and with
    ``HVTPU_PALLAS=0``."""
    from horovod_tpu.ops import backend, grouped_ffn

    monkeypatch.delenv("HVTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)
    if where == "interpret":
        monkeypatch.setenv("HVTPU_PALLAS_INTERPRET", "1")
    if where == "tpu_without_pallas":
        monkeypatch.setenv("HVTPU_PALLAS", "0")
    monkeypatch.setattr(backend, "on_tpu", lambda: where.startswith(
        "tpu"))
    assert moe.products_path(dtype, d, f, 16384, 8, 8, "gated") == path
    assert grouped_ffn._blocked(dtype, d, f, 3) is blocked


@pytest.mark.parametrize("kernel", ["whole", "blocked"])
def test_the_backward_kernel_a_layer_was_built_with_is_counted(
        monkeypatch, grouped, kernel):
    """``hvtpu_moe_backward_kernel_total{kernel=}``: one a trace of a
    backward pass through the grouped kernels, under the kernel the
    shapes take (128 x 256 in float32 is whole, and blocked with
    ``_VMEM_FOR_A_GROUP`` at 0); none for a forward pass alone, nor on
    the ``ragged_dot`` path."""
    from horovod_tpu.ops import grouped_ffn

    if kernel == "blocked":
        monkeypatch.setattr(grouped_ffn, "_VMEM_FOR_A_GROUP", 0)
    assert grouped_ffn._blocked(jnp.float32, 128, 256, 3) is (
        kernel == "blocked")
    counter = moe.metrics.REGISTRY.counter("hvtpu_moe_backward_kernel_total")
    x, gate_w, experts = _weights(21, 4, **WIDE)

    def traced(grad):
        def f(x):
            return jnp.sum(layer(x, gate_w, experts, top_k=3,
                                 first_expert=8)[0])
        before = {k: counter.value(kernel=k) for k in ("whole", "blocked")}
        jax.jit(jax.grad(f) if grad else f).lower(x)
        return {k: counter.value(kernel=k) - before[k]
                for k in ("whole", "blocked")}

    assert traced(grad=True) == {"whole": float(kernel == "whole"),
                                 "blocked": float(kernel == "blocked")}
    assert traced(grad=False) == {"whole": 0.0, "blocked": 0.0}
    monkeypatch.setenv("HVTPU_PALLAS", "0")
    assert traced(grad=True) == {"whole": 0.0, "blocked": 0.0}


# -- the other routing rule and the other form of expert ---------------------
# (``models/hybrid_moe.py``'s: sigmoid scores chosen by score plus a
# selection bias; two weights with a squared ReLU between)

SCALE = 2.5


def _ungated(seed, held, n=N):
    x, gate_w, experts = _weights(seed, held, n=n)
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(seed + 100), (E,))
    return x, 0.5 * gate_w, {k: experts[k] for k in ("w_up", "w_down")}, bias


def plain_sigmoid(x, gate_w, experts, bias, *, top_k, first_expert,
                  renormalise=True, scale=SCALE):
    """Every held expert over every token with a 0/1 choice: the choice
    by ``s + b``, the weights ``s`` without ``b``."""
    s = jax.nn.sigmoid(x @ gate_w)
    order = jnp.argsort(-(s + bias), axis=-1, stable=True)[:, :top_k]
    w = jnp.sum(jax.nn.one_hot(order, E), axis=1) * s
    if renormalise:
        w = w / w.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(experts["w_up"].shape[0]):
        h = jnp.square(jax.nn.relu(x @ experts["w_up"][e]))
        y = y + scale * w[:, first_expert + e, None] * (
            h @ experts["w_down"][e])
    return y


def sigmoid_layer(x, gate_w, experts, bias, *, top_k, first_expert,
                  renormalise=True, scale=SCALE):
    return moe.dropless_topk_moe(
        x, gate_w, experts, top_k=top_k, num_experts=E,
        first_expert=first_expert, renormalise=renormalise,
        selection_bias=bias, scale=scale)


@pytest.mark.parametrize("tile_rows", [4, 512])
@pytest.mark.parametrize("first_expert, held, top_k, renormalise", [
    (0, 4, 3, True), (8, 4, 6, True), (12, 4, 8, False), (0, 16, 2, True)])
def test_sigmoid_routed_relu2_experts_equal_the_plain_loop(
        monkeypatch, tile_rows, first_expert, held, top_k, renormalise):
    """The result, the rows, and the gradients of ``x``, the router and
    both weights; the selection bias gets exact zeros."""
    monkeypatch.setattr(moe, "_TILE_ROWS", tile_rows)
    args = _ungated(first_expert + held, held)
    kwargs = dict(top_k=top_k, first_expert=first_expert,
                  renormalise=renormalise)
    y, routing = jax.jit(lambda *a: sigmoid_layer(*a, **kwargs))(*args)
    _close(y, plain_sigmoid(*args, **kwargs))
    top_i = np.asarray(routing["experts"])
    want_rows = [(top_i == first_expert + e).sum() for e in range(held)]
    assert routing["rows_per_expert"].tolist() == want_rows
    target = jax.random.normal(jax.random.PRNGKey(9), (N, D))
    got = jax.grad(
        lambda *a: jnp.sum(sigmoid_layer(*a, **kwargs)[0] * target),
        argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(
        lambda *a: jnp.sum(plain_sigmoid(*a, **kwargs) * target),
        argnums=(0, 1, 2, 3))(*args)
    assert not np.any(got[3]) and not np.any(want[3])
    for g, w in zip(jax.tree_util.tree_leaves(got[:3]),
                    jax.tree_util.tree_leaves(want[:3])):
        _close(g, w)


def test_the_selection_bias_changes_the_choice_and_not_the_weights():
    """A bias that lifts the held experts into every token's choice:
    every token now comes here, and a chosen expert's weight is still
    its score over the chosen scores' sum, times the scale, as if the
    bias were not there."""
    first_expert, held, top_k = 8, 4, 4
    x, gate_w, experts, bias = _ungated(7, held)
    lifted = bias.at[first_expert:first_expert + held].add(10.0)
    before = sigmoid_layer(x, gate_w, experts, bias, top_k=top_k,
                           first_expert=first_expert)[1]
    y, after = sigmoid_layer(x, gate_w, experts, lifted, top_k=top_k,
                             first_expert=first_expert)
    assert before["rows_per_expert"].sum() < N * top_k
    assert after["rows_per_expert"].tolist() == [N] * held
    s = jax.nn.sigmoid(x @ gate_w)[:, first_expert:first_expert + held]
    w = SCALE * s / s.sum(-1, keepdims=True)
    want = sum(w[:, e, None] * (jnp.square(jax.nn.relu(
        x @ experts["w_up"][e])) @ experts["w_down"][e])
        for e in range(held))
    _close(y, want)


def test_the_scale_multiplies_the_routed_part():
    x, gate_w, experts, bias = _ungated(5, 4)
    kwargs = dict(top_k=3, first_expert=8)
    one = sigmoid_layer(x, gate_w, experts, bias, scale=1.0, **kwargs)[0]
    _close(sigmoid_layer(x, gate_w, experts, bias, **kwargs)[0],
           SCALE * one)


def _plain_relu2(x, gate_w, experts, *, top_k, first_expert):
    """Softmax-routed ungated experts, every held one over every token."""
    top_p, top_i = jax.lax.top_k(jax.nn.softmax(x @ gate_w, -1), top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(experts["w_up"].shape[0]):
        w = jnp.sum(jnp.where(top_i == first_expert + e, top_p, 0.0), -1)
        y = y + w[:, None] * (jnp.square(jax.nn.relu(
            x @ experts["w_up"][e])) @ experts["w_down"][e])
    return y


@pytest.mark.parametrize("routing", ["uneven", "one_expert", "all_here",
                                     "none_here"])
def test_relu2_experts_on_rigged_routings(monkeypatch, routing):
    """An expert without rows, one expert with a row of every token,
    every row here and none, on the ``ragged_dot`` body (which the CPU,
    float16 and widths off the 128 lanes still run), softmax-routed:
    the form and the rule are chosen apart."""
    monkeypatch.setattr(moe, "_TILE_ROWS", 8)
    first_expert, held, top_k = 8, 4, 3
    x, gate_w, experts = _rigged(routing, held, first_expert, jnp.float32)
    experts = {k: experts[k] for k in ("w_up", "w_down")}
    assert moe.products_path(x.dtype, D, F, N, top_k, held,
                             "relu2") == "ragged_dot"

    plain_relu2 = functools.partial(_plain_relu2, top_k=top_k,
                                    first_expert=first_expert)

    def ours(x, gate_w, experts):
        return layer(x, gate_w, experts, top_k=top_k,
                     first_expert=first_expert)

    y, seen = ours(x, gate_w, experts)
    rows = np.asarray(seen["rows_per_expert"])
    assert {"uneven": rows[-1] == 0 < rows.sum() < N * top_k,
            "one_expert": rows[0] == N,
            "all_here": rows.sum() == N * top_k,
            "none_here": rows.sum() == 0}[routing]
    want = plain_relu2(x, gate_w, experts)
    if routing == "none_here":
        assert not np.any(y) and not np.any(want)
    else:
        _close(y, want)
    target = jax.random.normal(jax.random.PRNGKey(9), (N, D))
    got = jax.grad(lambda *a: jnp.sum(ours(*a)[0] * target),
                   argnums=(0, 1, 2))(x, gate_w, experts)
    wanted = jax.grad(lambda *a: jnp.sum(plain_relu2(*a) * target),
                      argnums=(0, 1, 2))(x, gate_w, experts)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(wanted)):
        if np.any(w):
            _close(g, w, rtol=2e-5)
        else:
            assert not np.any(g)


def test_the_grouped_kernels_take_both_forms(monkeypatch):
    """... and of the ungated one an inner width that is no whole
    number of vectors too (the layer fills it), where the gated
    kernels want whole vectors."""
    from horovod_tpu.ops import backend

    monkeypatch.setattr(backend, "pallas_mode", lambda: (True, True))
    for form in ("gated", "relu2"):
        assert moe.products_path(jnp.bfloat16, 128, 256, 64, 3, 4,
                                 form) == "grouped"
    assert moe.products_path(jnp.bfloat16, 128, 320, 64, 3, 4,
                             "relu2") == "grouped"
    assert moe.products_path(jnp.bfloat16, 128, 320, 64, 3, 4,
                             "gated") == "ragged_dot"
    assert moe.products_path(jnp.bfloat16, 192, 256, 64, 3, 4,
                             "relu2") == "ragged_dot"


# -- the blocked backward kernel, both forms, interpreted ----------------------

@pytest.fixture
def inner_blocks_of_128(monkeypatch):
    """An inner width of 256 is two blocks, one of 320 is filled to 384
    and is three; and a gated group whose weights and sums fit VMEM
    whole takes the blocked kernel all the same."""
    from horovod_tpu.ops import grouped_ffn

    monkeypatch.setattr(grouped_ffn, "_F_BLOCK", 128)
    monkeypatch.setattr(grouped_ffn, "_VMEM_FOR_A_GROUP", 0)
    assert grouped_ffn.inner_block(grouped_ffn.padded_width(320)) == 128
    assert grouped_ffn._blocked(jnp.bfloat16, 128, 256, 3)


def _dense_relu2(x, weight, hit, w_up, w_down):
    y = jnp.zeros_like(x)
    for e in range(hit.shape[1]):
        h = jnp.square(jax.nn.relu(x @ w_up[e]))
        y = y + jnp.where(hit[:, e], weight[:, e], 0.0)[:, None] * (
            h @ w_down[e])
    return y


# (form, inner width): an ungated case is named by its width alone
FORMS = [pytest.param("relu2", 256, id="256"),
         pytest.param("relu2", 320, id="320"),
         pytest.param("gated", 256, id="gated-256")]


@pytest.mark.parametrize("form, f", FORMS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("routing", [
    "even", "an_expert_without_rows", "every_token_here", "off_every_tile",
    "nobody_here"])
def test_the_ungated_kernels_equal_every_expert_over_every_token(
        monkeypatch, grouped, inner_blocks_of_128, routing, dtype, form, f):
    """``_grouped_ffn`` through the blocked backward kernel against the
    dense sum: the result and the gradients of ``x``, ``weight`` and
    every weight, the inner width walked in two blocks (256) or, of two
    weights, filled from 320 to 384 and walked in three, where the
    filled columns' gradients are exact zeros; three weights (the gated
    form) or two; bfloat16 rows against the dense sum in float32.  Then
    again with every row of the buffers that no assignment owns NaN
    before the products: everything comes out finite and as from
    buffers of zeros, bit for bit."""
    x, _, experts = _weights(21, 4, n=64, d=128, f=f)
    hit = _hits(routing)
    weight = jax.random.uniform(jax.random.PRNGKey(2), hit.shape)
    target = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    if form == "gated":
        weights, dense = tuple(experts[k] for k in (
            "w_gate", "w_up", "w_down")), _dense
    else:
        weights = moe._in_whole_vectors(experts["w_up"], experts["w_down"])
        dense = _dense_relu2
        assert weights[0].shape[2] == weights[1].shape[1] == {
            256: 256, 320: 384}[f]

    def ours(x, weight, *w):
        plan, sizes = moe._plan(jnp.asarray(hit), 3)
        return moe._grouped_ffn((*sizes, "grouped"), x.astype(dtype), weight,
                                plan, tuple(a.astype(dtype) for a in w))

    def run(f):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(f(*a).astype(jnp.float32) * target),
            tuple(range(2 + len(weights)))))(x, weight, *weights)

    assert moe.products_path(dtype, 128, f, 64, 3, 4, form) == "grouped"
    got = jax.tree_util.tree_leaves(run(ours))
    want = jax.tree_util.tree_leaves(run(
        lambda x, weight, *w: dense(x, weight, jnp.asarray(hit), *w)))
    assert len(got) == 3 + len(weights)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        if routing == "nobody_here":
            assert not np.asarray(g).any() and not np.asarray(w).any()
        else:               # the loss is a sum that cancels
            _close(g, w, rtol=(1e-5 if dtype == jnp.float32 else 0.05)
                   * (1 if np.ndim(g) else 10))
    assert not np.asarray(got[-2])[:, :, f:].any()
    assert not np.asarray(got[-1])[:, f:, :].any()
    monkeypatch.setattr(
        moe, "_row_buffer", lambda rows, width, dtype, near: jnp.full(
            (rows, width), jnp.nan, dtype))
    for g, clean in zip(jax.tree_util.tree_leaves(run(ours)), got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(clean))


@pytest.mark.parametrize("dtype, form", [
    pytest.param(dtype, form, id=jnp.dtype(dtype).name + (
        "-gated" if form == "gated" else ""))
    for form in ("relu2", "gated") for dtype in (jnp.float32, jnp.bfloat16)])
@pytest.mark.parametrize("routing", ["uneven", "one_expert", "all_here",
                                     "none_here"])
def test_through_the_kernels_the_ungated_layer_equals_the_plain_loop(
        monkeypatch, grouped, inner_blocks_of_128, routing, dtype, form):
    """``dropless_topk_moe`` with the blocked backward kernel, rigged
    routers and all, against the plain loop: the result, the rows each
    expert got and the gradients of the tokens, the router and every
    weight; and against the same layer on the ``ragged_dot`` body,
    whose arithmetic the kernels keep (in bfloat16 a router rigged to
    one expert has a gradient of differences that cancel: both bodies
    are a fifth off the float32 loop there, and a part in a hundred
    thousand off each other).  Two-weight experts 320 wide (the layer
    fills the width to 384 and the gradients come back 320 wide), or
    gated ones 256 wide."""
    first_expert, held, top_k = 8, 4, 3
    f = 320 if form == "relu2" else 256
    counters = {
        name: moe.metrics.REGISTRY.counter(name).value(**label)
        for name, label in (("hvtpu_moe_products_total", {"path": "grouped"}),
                            ("hvtpu_moe_experts_form_total", {"form": form}))}
    x, gate_w, experts = _rigged(routing, held, first_expert, dtype,
                                 n=64, d=128, f=f)
    if form == "relu2":
        experts = {k: experts[k] for k in ("w_up", "w_down")}
        plain_loop = functools.partial(_plain_relu2, top_k=top_k,
                                       first_expert=first_expert)
    else:
        plain_loop = functools.partial(plain, top_k=top_k,
                                       first_expert=first_expert)
    kwargs = dict(top_k=top_k, first_expert=first_expert)
    target = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def run(f, x):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(f(*a).astype(jnp.float32) * target),
            (0, 1, 2)))(x, gate_w, experts)

    got = jax.tree_util.tree_leaves(
        run(lambda *a: layer(*a, **kwargs)[0], x))
    want = jax.tree_util.tree_leaves(
        run(plain_loop, x.astype(jnp.float32)))
    assert moe.metrics.REGISTRY.counter("hvtpu_moe_products_total").value(
        path="grouped") == counters["hvtpu_moe_products_total"] + 1
    assert moe.metrics.REGISTRY.counter(
        "hvtpu_moe_experts_form_total").value(
            form=form) == counters["hvtpu_moe_experts_form_total"] + 1
    rows = np.asarray(layer(x, gate_w, experts, **kwargs)[1][
        "rows_per_expert"])
    assert {"uneven": 0 < rows.sum() < 64 * top_k and rows[-1] == 0,
            "one_expert": rows[0] == 64,
            "all_here": rows.sum() == 64 * top_k,
            "none_here": rows.sum() == 0}[routing]
    assert [g.shape for g in got[3:]] == [
        w.shape for w in jax.tree_util.tree_leaves(experts)]
    assert len(got) == 3 + len(experts)
    monkeypatch.setenv("HVTPU_PALLAS", "0")
    assert moe.products_path(dtype, 128, f, 64, top_k, held,
                             form) == "ragged_dot"
    ragged = jax.tree_util.tree_leaves(
        run(lambda *a: layer(*a, **kwargs)[0], x))
    for i, (g, w, r) in enumerate(zip(got, want, ragged)):
        g = np.asarray(g, np.float32)
        assert np.isfinite(g).all()
        if routing == "none_here":
            assert not g.any()
            continue
        _close(g, np.asarray(r, np.float32), rtol=1e-4)
        rigged_router = (i == 2 and dtype == jnp.bfloat16
                         and routing == "one_expert")
        _close(g, w, rtol=1e-5 if dtype == jnp.float32
               else 0.3 if rigged_router else 0.05)


@pytest.mark.parametrize("where, dtype, d, f, path", [
    ("tpu", jnp.bfloat16, 2688, 1856, "grouped"),       # the one-mixer cell
    ("tpu", jnp.bfloat16, 128, 320, "grouped"),
    ("tpu", jnp.float32, 1024, 1024, "grouped"),
    # both f32 weights twice and both sums crowd the tiles out
    ("tpu", jnp.float32, 2688, 1856, "ragged_dot"),
    ("tpu", jnp.bfloat16, 4096, 4096, "ragged_dot"),
    ("tpu", jnp.float16, 2688, 1856, "ragged_dot"),
    ("tpu", jnp.bfloat16, 64, 32, "ragged_dot"),        # the rehearsals
    ("tpu", jnp.bfloat16, 192, 256, "ragged_dot"),      # D off the lanes
    ("tpu_without_pallas", jnp.bfloat16, 2688, 1856, "ragged_dot"),
    ("cpu", jnp.bfloat16, 2688, 1856, "ragged_dot")])
def test_how_the_ungated_products_run_is_read_off_the_backend_and_the_shapes(
        monkeypatch, where, dtype, d, f, path):
    from horovod_tpu.ops import backend

    monkeypatch.delenv("HVTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)
    if where == "tpu_without_pallas":
        monkeypatch.setenv("HVTPU_PALLAS", "0")
    monkeypatch.setattr(backend, "on_tpu", lambda: where != "cpu")
    assert moe.products_path(dtype, d, f, 16384, 6, 8, "relu2") == path
