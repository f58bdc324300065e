"""The Pallas kernels behind ``models.attention.tiled_attention`` and
``models.attention.causal_document_attention``
(``ops/flash_attention.py``) on the CPU, under the Pallas interpreter:
against the XLA tiles they replace, against a dense masked softmax and
against the library's splash-attention kernel with the same computed
mask; their schedule against ``allowed`` itself; with a mask that is
data (document ids) and heads of 64; which path runs; and how the
kernels appear in a program lowered for a TPU."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import attention as attn
from horovod_tpu.obs import metrics
from horovod_tpu.ops import backend, flash_attention

HD = 128
KERNELS = ("hvtpu_flash_attention_fwd", "hvtpu_flash_attention_dq",
           "hvtpu_flash_attention_dkv")


def distance(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def calls(path):
    return metrics.REGISTRY.counter(
        "hvtpu_attention_calls_total").value(path=path)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("HVTPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)


def blocks(monkeypatch, q, kv):
    monkeypatch.setattr(attn, "_FLASH_BLOCK_Q", q)
    monkeypatch.setattr(attn, "_FLASH_BLOCK_KV", kv)


def operands(seq_len, heads, kv_heads, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (2, 2 * seq_len, heads, HD)
    kv = (2, 2 * seq_len, kv_heads, HD)
    return (jax.random.normal(ks[0], shape).astype(dtype),
            jax.random.normal(ks[1], kv).astype(dtype),
            jax.random.normal(ks[2], kv).astype(dtype),
            jax.random.normal(ks[3], shape).astype(dtype))


def with_gradients(attention, q, k, v, target):
    """``(out, dq, dk, dv)`` of ``sum(attention * target)``."""
    def loss(q, k, v):
        out = attention(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * target), out

    grads, out = jax.jit(jax.grad(loss, (0, 1, 2), has_aux=True))(q, k, v)
    return (out, *grads)


def dense_attention(q, k, v, seq_len, block_length):
    pos = np.arange(2 * seq_len)
    seen = jnp.asarray(
        attn.allowed(pos[:, None], pos[None, :], seq_len, block_length))
    k, v = (jnp.repeat(a, q.shape[2] // a.shape[2], axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def splash_attention(q, k, v, seq_len, block_length, block):
    """The library's kernels in the interpreter, their block-sparse
    walk made from ``allowed`` by the library's own classifier."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash, splash_attention_mask as masks)

    class BlockDiffusionMask(masks._ComputableMask):
        def __init__(self):
            super().__init__(
                (2 * seq_len, 2 * seq_len),
                lambda q_ids, kv_ids: attn.allowed(
                    q_ids, kv_ids, seq_len, block_length))

        def __eq__(self, other):
            return isinstance(other, type(self))

        def __hash__(self):
            return hash((type(self), seq_len, block_length))

    group = q.shape[2] // k.shape[2]
    kernel = splash.make_splash_mqa_single_device(
        masks.MultiHeadMask([BlockDiffusionMask()] * group),
        block_sizes=splash.BlockSizes(
            block_q=block, block_kv=block, block_q_dkv=block,
            block_kv_dkv=block, block_q_dq=block, block_kv_dq=block),
        interpret=True)
    b, positions, _, hd = q.shape
    # a (sequence, key/value head) at a time: [B, G, R, P, hd]
    grouped = (q / np.sqrt(hd)).reshape(b, positions, -1, group, hd)
    out = jax.vmap(jax.vmap(kernel))(
        grouped.transpose(0, 2, 3, 1, 4), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3))
    return out.transpose(0, 3, 1, 2, 4).reshape(q.shape)


# (seq_len, block_q, block_kv): one tile a half; several; a tile larger
# than the half; key blocks larger than the queries'
TILINGS = [(128, 128, 128), (256, 128, 128), (128, 512, 512),
           (256, 128, 256)]


@pytest.mark.parametrize("kv_heads", [1, 2])
@pytest.mark.parametrize("block_length", [4, 32])
@pytest.mark.parametrize("seq_len, block_q, block_kv", TILINGS)
def test_kernels_equal_the_xla_tiles_and_the_dense_mask(
        interpreted, monkeypatch, kv_heads, block_length, seq_len, block_q,
        block_kv):
    blocks(monkeypatch, block_q, block_kv)
    q, k, v, target = operands(seq_len, 2 * kv_heads, kv_heads)

    def tiled(q, k, v):
        return attn.tiled_attention(q, k, v, block_length=block_length,
                                    tile=128)

    before = calls("pallas"), calls("xla")
    got = with_gradients(tiled, q, k, v, target)
    assert (calls("pallas"), calls("xla")) == (before[0] + 1, before[1])
    monkeypatch.setenv("HVTPU_PALLAS", "0")
    in_xla = with_gradients(lambda *a: tiled(*a), q, k, v, target)
    assert (calls("pallas"), calls("xla")) == (before[0] + 1, before[1] + 1)
    dense = with_gradients(
        lambda *a: dense_attention(*a, seq_len, block_length), q, k, v,
        target)
    for name, g, x, d in zip(("out", "dq", "dk", "dv"), got, in_xla, dense):
        assert distance(g, x) < 1e-5, name
        assert distance(g, d) < 1e-5, name


@pytest.mark.parametrize("kv_heads, block_length", [(1, 4), (2, 32)])
def test_kernels_equal_the_librarys_splash_attention(
        interpreted, monkeypatch, kv_heads, block_length):
    seq_len = 256
    blocks(monkeypatch, 128, 128)
    q, k, v, target = operands(seq_len, 2 * kv_heads, kv_heads, seed=3)
    got = with_gradients(
        lambda *a: attn.tiled_attention(*a, block_length=block_length,
                                        tile=128), q, k, v, target)
    want = with_gradients(
        lambda *a: splash_attention(*a, seq_len, block_length, 128),
        q, k, v, target)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert distance(g, w) < 1e-5, name


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_bf16_kernels_differ_from_f32_by_the_rounding_of_their_products(
        interpreted, monkeypatch, kv_heads):
    """The same bf16 operands through the bf16 kernels and, widened,
    through the f32 XLA tiles: what is left is the rounding of ``p`` and
    ``ds`` to bf16 before the second products (2**-9 an element) and of
    the results."""
    seq_len, block_length = 256, 4
    blocks(monkeypatch, 128, 128)
    q, k, v, target = operands(seq_len, 2 * kv_heads, kv_heads,
                               jnp.bfloat16, seed=5)

    def tiled(q, k, v):
        return attn.tiled_attention(q, k, v, block_length=block_length,
                                    tile=128)

    got = with_gradients(tiled, q, k, v, target)
    assert all(g.dtype == jnp.bfloat16 for g in got)
    monkeypatch.setenv("HVTPU_PALLAS", "0")
    want = with_gradients(lambda *a: tiled(*a), *(
        a.astype(jnp.float32) for a in (q, k, v)), target)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert distance(g, w) < 6e-3, name


def test_the_forward_kernel_hands_the_backward_out_before_its_rounding(
        interpreted):
    """``delta = sum(d_out * out)`` cancels against ``dp``; it is made
    from the f32 ``out``, as XLA makes it for its own tiles."""
    seq_len = 128
    schedule = flash_attention.pair_schedule(
        attn.tile_work(seq_len, 4, 128, 128), 128, 128)

    def forward(dtype):
        q, k, v, _ = operands(seq_len, 2, 1, dtype, seed=7)
        return flash_attention.forward(
            q, k, v, schedule, lambda a, b: attn.allowed(a, b, seq_len, 4),
            scale=HD ** -0.5, mask_value=attn._MASKED, interpret=True)

    out, _, exact = forward(jnp.bfloat16)
    assert (out.dtype, exact.dtype) == (jnp.bfloat16, jnp.float32)
    assert jnp.array_equal(exact.astype(jnp.bfloat16), out)
    assert not jnp.array_equal(exact, out.astype(jnp.float32))
    out, _, exact = forward(jnp.float32)
    assert exact is out


@pytest.mark.parametrize("seq_len, block_length, tile_q, tile_k", [
    (64, 4, 16, 16), (64, 4, 16, 32), (64, 32, 8, 16), (60, 5, 16, 8),
    (24, 1, 16, 16), (24, 24, 16, 8), (32, 16, 16, 16), (32, 32, 16, 16)])
def test_the_tables_are_allowed_block_by_block(seq_len, block_length,
                                               tile_q, tile_k):
    def positions(tile):
        n = -(-seq_len // tile)
        pos = np.full((2, n * tile), -1)             # padding
        pos[0, :seq_len] = np.arange(seq_len)
        pos[1, :seq_len] = seq_len + np.arange(seq_len)
        return pos.reshape(2 * n, tile)

    q_pos, k_pos = positions(tile_q), positions(tile_k)
    want = np.zeros((len(q_pos), len(k_pos)), np.int8)
    for i, qp in enumerate(q_pos):
        for j, kp in enumerate(k_pos):
            seen = (attn.allowed(qp[:, None], kp[None, :], seq_len,
                                 block_length)
                    & (qp[:, None] >= 0) & (kp[None, :] >= 0))
            want[i, j] = 2 if seen.all() else 1 if seen.any() else 0
    got = attn.tile_work(seq_len, block_length, tile_q, tile_k)
    assert np.array_equal(got, want)
    if seq_len % tile_q == 0 and seq_len % tile_k == 0:
        schedule = flash_attention.pair_schedule(got, tile_q, tile_k)
        for table, own in ((schedule.by_query, 0), (schedule.by_key, 1)):
            assert {(i, j): kind for i, j, kind in table.T} == {
                (i, j): want[i, j] for i, j in zip(*np.nonzero(want))}
            assert np.all(np.diff(table[own]) >= 0)    # a tile's pairs in a row


def test_the_cells_schedule():
    """8,192 tokens, block length 4, blocks of 512: 288 of 1,024 pairs,
    the 16 diagonal ones of each of three quadrants partial, at most 17
    key blocks a query block and 32 query blocks a key block."""
    work = attn.tile_work(8192, 4, 512, 512)
    assert work.shape == (32, 32)
    assert np.count_nonzero(work) == 288
    assert np.count_nonzero(work == flash_attention.PARTIAL) == 48
    assert np.count_nonzero(work == flash_attention.FULL) == 240
    assert np.count_nonzero(work, axis=1).max() == 17
    assert np.count_nonzero(work, axis=0).max() == 32
    assert not work[16:, :16].any()
    partial = np.argwhere(work == flash_attention.PARTIAL)
    assert np.array_equal(partial[:, 0] % 16, partial[:, 1] % 16)
    cell = attn.BlockDiffusionMask(8192, 4)
    schedule = attn._flash_schedule(cell, 512, 512)
    assert schedule.pairs == 288
    assert attn._flash_schedule(cell, 512, 512) is schedule   # memoised
    # keys fetched 1,024 at a time: every pair on the diagonal partial
    assert np.count_nonzero(attn.tile_work(8192, 4, 512, 1024)) == 160


@pytest.mark.parametrize("seq_len, block_length, tile", [
    (24, 1, 16), (24, 4, 16), (24, 24, 16), (30, 5, 8), (64, 4, 16),
    (8192, 4, 512)])
def test_the_xla_rows_read_the_key_tiles_that_hold_work(
        seq_len, block_length, tile):
    """``key_ranges``, the XLA path's walk: under the block-diffusion
    mask a tile of queries reads the keys of exactly the key tiles where
    ``tile_work`` is not 0, in order and none twice (a half's last tile
    is shorter, nothing is padded); under the document mask of as many
    positions, the tile at ``a`` reads the keys ``[0, a + tile)``."""
    n = -(-seq_len // tile)
    first = np.concatenate([np.arange(n) * tile,
                            seq_len + np.arange(n) * tile])
    end = np.minimum(first + tile, np.repeat([seq_len, 2 * seq_len], n))
    work = attn.tile_work(seq_len, block_length, tile, tile) > 0
    walk = attn.key_ranges(attn.BlockDiffusionMask(seq_len, block_length),
                           tile)
    assert [queries for queries, _ in walk] == list(zip(first, end))
    for row, (_, ranges) in zip(work, walk):
        read = np.concatenate([np.arange(lo, hi) for lo, hi in ranges])
        assert np.array_equal(read, np.concatenate([
            np.arange(lo, hi) for lo, hi in zip(first[row], end[row])]))
    positions = 2 * seq_len
    assert attn.key_ranges(attn.DocumentMask(positions), tile) == tuple(
        ((a, min(a + tile, positions)), ((0, min(a + tile, positions)),))
        for a in range(0, positions, tile))


@pytest.mark.parametrize("seq_len, head_dim, why", [
    (256, 32, "a quarter of a vector's lanes a head"),
    (192, 128, "a half no block divides"),
    (136, 128, "not whole blocks of 128")])
def test_other_shapes_take_the_xla_path(interpreted, monkeypatch, seq_len,
                                        head_dim, why):
    blocks(monkeypatch, 128, 128)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2 * seq_len, 2, head_dim))
    k = jax.random.normal(ks[1], (1, 2 * seq_len, 1, head_dim))
    v = jax.random.normal(ks[2], (1, 2 * seq_len, 1, head_dim))
    before = calls("pallas"), calls("xla")
    out = jax.jit(lambda *a: attn.tiled_attention(
        *a, block_length=4, tile=128))(q, k, v)
    assert (calls("pallas"), calls("xla")) == (before[0], before[1] + 1), why
    assert distance(out, dense_attention(q, k, v, seq_len, 4)) < 1e-5


def test_heads_of_64_take_the_kernels_two_to_a_block(interpreted,
                                                    monkeypatch):
    """Heads of half a vector's lanes, which ``supports`` used to
    refuse: two key/value heads share a 128-lane block."""
    blocks(monkeypatch, 128, 128)
    seq_len = 128
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q, target = (jax.random.normal(k, (2, 2 * seq_len, 4, 64))
                 for k in ks[:2])
    k, v = (jax.random.normal(k_, (2, 2 * seq_len, 2, 64)) for k_ in ks[2:])
    before = calls("pallas"), calls("xla")
    got = with_gradients(lambda *a: attn.tiled_attention(
        *a, block_length=4, tile=128), q, k, v, target)
    assert (calls("pallas"), calls("xla")) == (before[0] + 1, before[1])
    dense = with_gradients(
        lambda *a: dense_attention(*a, seq_len, 4), q, k, v, target)
    for name, g, d in zip(("out", "dq", "dk", "dv"), got, dense):
        assert distance(g, d) < 1e-5, name
    # an odd number of key/value heads of 64 fills no whole block
    assert not flash_attention.supports(64, jnp.float32, 256, 128, 128, 3)
    assert flash_attention.supports(64, jnp.float32, 256, 128, 128, 8)
    assert flash_attention.supports(128, jnp.float32, 256, 128, 128, 3)


# -- a mask that is data: models.attention.causal_document_attention -------

# where the documents after the first start, a row each; blocks of 128
PACKINGS = {
    "a boundary on a block's edge": [[128, 384], [256]],
    "boundaries inside blocks": [[100, 300], [7, 450]],
    "one document a row": [[], []],
    "a document of 16 tokens": [[200, 216], [496]],
}


def segments(boundaries, positions):
    segment = np.zeros((len(boundaries), positions), np.int32)
    for row, starts in zip(segment, boundaries):
        for start in starts:
            row[start:] += 1
    return segment


def document_operands(head_dim, group, positions=512, kv_heads=2, seed=11,
                      value_dim=None):
    """``q``, ``k``, ``v`` and a target like the result: ``v`` and the
    result ``value_dim`` wide, where that is given."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    value_dim = value_dim or head_dim
    heads = group * kv_heads
    return (jax.random.normal(ks[0], (2, positions, heads, head_dim)),
            jax.random.normal(ks[1], (2, positions, kv_heads, head_dim)),
            jax.random.normal(ks[2], (2, positions, kv_heads, value_dim)),
            jax.random.normal(ks[3], (2, positions, heads, value_dim)))


def kernels_against_xla_tiles(monkeypatch, head_dim, group, packing,
                              skipping, kv_heads=2, value_dim=None):
    monkeypatch.setattr(attn, "_FLASH_BLOCK_Q", 128)
    monkeypatch.setattr(attn, "_FLASH_BLOCK_KV", 128)
    if not skipping:
        live = attn.live_pairs
        monkeypatch.setattr(
            attn, "live_pairs", lambda *a: jnp.ones_like(live(*a)))
    segment = jnp.asarray(segments(PACKINGS[packing], 512))
    q, k, v, target = document_operands(head_dim, group, kv_heads=kv_heads,
                                        value_dim=value_dim)

    def layer(q, k, v):
        return attn.causal_document_attention(
            q, k, v, segment, scale=0.125, tile=128)

    before = calls("pallas"), calls("xla")
    got = with_gradients(layer, q, k, v, target)
    assert (calls("pallas"), calls("xla")) == (before[0] + 1, before[1])
    monkeypatch.setenv("HVTPU_PALLAS", "0")
    in_xla = with_gradients(lambda *a: layer(*a), q, k, v, target)
    assert (calls("pallas"), calls("xla")) == (before[0] + 1, before[1] + 1)
    for name, g, x in zip(("out", "dq", "dk", "dv"), got, in_xla):
        assert distance(g, x) < 1e-5, name


@pytest.mark.parametrize("skipping", [True, False],
                         ids=["pairs skipped", "every pair run"])
@pytest.mark.parametrize("packing", sorted(PACKINGS))
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_with_document_ids_the_kernels_equal_the_xla_tiles(
        interpreted, monkeypatch, head_dim, group, packing, skipping):
    """``causal_document_attention`` through the kernels (the document
    ids their mask's data, the pairs without a common document skipped
    or not) against its own XLA tiles: ``out`` and every gradient."""
    kernels_against_xla_tiles(
        monkeypatch, head_dim, group, packing, skipping)


def block_heads_noted():
    noted = metrics.REGISTRY.gauge("hvtpu_attention_block_heads")
    return noted.value(kind="query"), noted.value(kind="key_value")


@pytest.mark.parametrize("kv_heads, block", [(16, 8), (6, 6)],
                         ids=["sixteen heads", "six heads"])
@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_sixteen_key_value_heads_of_one_query_head_each(
        interpreted, monkeypatch, packing, kv_heads, block):
    """The looped decoder's shape (``models.looped``: multi-head
    attention, 16 key/value heads of 128 with one query head a group,
    document ids): the kernels walk them eight to a block (six heads go
    six to one), and a head's results do not depend on which heads
    share its block: the same heads in as many calls of one head each
    are equal bit for bit, forward and the three gradients."""
    kernels_against_xla_tiles(
        monkeypatch, 128, 1, packing, skipping=True, kv_heads=kv_heads)
    segment = jnp.asarray(segments(PACKINGS[packing], 512))
    q, k, v, target = document_operands(128, 1, kv_heads=kv_heads)
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)

    @jax.jit
    def layer(q, k, v, target):
        def loss(q, k, v):
            out = attn.causal_document_attention(
                q, k, v, segment, scale=0.125, tile=128)
            return jnp.sum(out * target), out

        grads, out = jax.grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)

    together = layer(q, k, v, target)
    assert block_heads_noted() == (block, block)
    alone = [layer(*(a[:, :, h:h + 1] for a in (q, k, v, target)))
             for h in range(kv_heads)]
    assert block_heads_noted() == (1, 1)
    for name, got, want in zip(("out", "dq", "dk", "dv"), together,
                               zip(*alone)):
        assert np.array_equal(got, jnp.concatenate(want, axis=2)), name


@pytest.mark.parametrize("kv_heads", [1, 2],
                         ids=["one key/value head", "two key/value heads"])
@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_sixteen_query_heads_on_one_key_value_head(
        interpreted, monkeypatch, packing, kv_heads):
    """The one-mixer stack's shape (``models.hybrid_moe``: 32 query heads
    of 128 on 2 key/value heads, document ids): a group above eight is
    not divided, so a grid step walks all sixteen query heads of one
    key/value head against the one key/value block it fetched; ``out``,
    ``dq`` and ``dk``/``dv`` (summed over the sixteen) against the XLA
    tiles."""
    kernels_against_xla_tiles(
        monkeypatch, 128, 16, packing, skipping=True, kv_heads=kv_heads)
    assert block_heads_noted() == (16, 1)


def head_widths_noted():
    noted = metrics.REGISTRY.gauge("hvtpu_attention_head_width")
    return noted.value(kind="key"), noted.value(kind="value")


@pytest.mark.parametrize("packing", sorted(PACKINGS))
@pytest.mark.parametrize("widths, kv_heads, group, block", [
    ((192, 128), 8, 1, (8, 8)), ((192, 128), 2, 2, (4, 2)),
    ((256, 128), 3, 1, (3, 3)), ((64, 128), 2, 1, (2, 2)),
    ((128, 64), 4, 2, (8, 4))],
    ids=lambda case: str(case).replace(" ", ""))
def test_a_key_width_apart_from_the_value_width(
        interpreted, monkeypatch, packing, widths, kv_heads, group, block):
    """Latent attention's shape (``models.kimi_linear``: keys of 128 +
    64 = 192, values of 128, a key/value head a query head), and other
    pairs of widths: ``q`` and ``k`` are cut by the one width, ``v``, the
    result and its cotangent by the other; a head of 192 is three halves
    of a 128-lane vector, so the key/value heads go two to a block at
    least.  ``out`` and the three gradients against the XLA tiles, and
    what the kernels note of their shapes."""
    hd, hdv = widths
    kernels_against_xla_tiles(
        monkeypatch, hd, group, packing, skipping=True, kv_heads=kv_heads,
        value_dim=hdv)
    assert block_heads_noted() == block
    assert head_widths_noted() == widths


def test_the_widths_the_kernels_take():
    takes = flash_attention.supports
    assert takes(192, jnp.bfloat16, 256, 128, 128, 32, 128)
    # an odd number of key/value heads of 192 fills no whole block
    assert not takes(192, jnp.bfloat16, 256, 128, 128, 3, 128)
    assert takes(256, jnp.float32, 256, 128, 128, 3, 128)
    assert not takes(96, jnp.float32, 256, 128, 128, 4, 128)
    assert not takes(128, jnp.float32, 256, 128, 128, 4, 32)
    # one width a head, given once or twice, is one answer
    for hd, kv_heads in ((64, 2), (64, 3), (128, 3), (32, 4)):
        assert takes(hd, jnp.float32, 256, 128, 128, kv_heads) == takes(
            hd, jnp.float32, 256, 128, 128, kv_heads, hd)
    assert flash_attention.block_heads(32, 32, 192, 128) == (8, 8)
    assert flash_attention.block_heads(32, 32, 128, 128) == (
        flash_attention.block_heads(32, 32, 128))


@pytest.mark.parametrize("heads, kv_heads, hd, block", [
    (4, 1, 128, (4, 1)),        # the transformer cell: as before
    (32, 2, 128, (16, 1)),      # the one-mixer stack: a group is whole
    (32, 8, 64, (8, 2)),        # the hybrid cell: as before
    (16, 16, 128, (8, 8)),      # the looped cell
    (32, 4, 128, (8, 1)),       # a group of eight fills a step
    (6, 6, 128, (6, 6)), (10, 10, 128, (5, 5)), (7, 7, 128, (7, 7)),
    (20, 10, 128, (4, 2)), (12, 6, 64, (4, 2)), (6, 6, 64, (6, 6)),
    (16, 2, 64, (16, 2)), (1, 1, 128, (1, 1)), (2, 1, 256, (2, 1))],
    ids=lambda case: str(case).replace(" ", ""))
def test_a_blocks_heads_are_chosen_from_the_shapes(heads, kv_heads, hd,
                                                   block):
    """The fewest key/value heads that fill whole vectors, and more
    while their query heads number at most eight and they divide the
    key/value heads evenly (a group above eight goes whole, one
    key/value head a step); ``lse`` is laid out by it."""
    assert flash_attention.block_heads(heads, kv_heads, hd) == block
    query, key_value = block
    assert (key_value * hd) % 128 == 0 and kv_heads % key_value == 0
    assert query == key_value * (heads // kv_heads)
    schedule = attn._flash_schedule(attn.DocumentMask(256), 128, 128)
    q = jax.ShapeDtypeStruct((2, 256, heads, hd), jnp.float32)
    kv = jax.ShapeDtypeStruct((2, 256, kv_heads, hd), jnp.float32)
    out, lse, exact = jax.eval_shape(
        lambda q, k, v: flash_attention.forward(
            q, k, v, schedule, attn._seen, scale=1.0, mask_value=-1e30,
            interpret=True, ids=jnp.zeros((2, 256), jnp.int32)), q, kv, kv)
    assert out.shape == q.shape
    assert lse.shape == (2, heads // query, 256, query)


def walks(packing, first_skipped=False):
    """For both walks over a packing's rows: the table, the rows' flags
    in its order, and the pairs held."""
    live = np.array(attn.live_pairs(segments(PACKINGS[packing], 512),
                                    128, 128))
    if first_skipped:          # no walk of a causal mask: a caller's own
        live[:, 0, 0] = 0
    schedule = attn._flash_schedule(attn.DocumentMask(512), 128, 128)
    for own, table in enumerate((schedule.by_query, schedule.by_key)):
        flags = live[:, table[0], table[1]]
        yield own, table, flags, np.asarray(
            flash_attention.held_pairs(jnp.asarray(flags)))


@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_a_skipped_pair_holds_the_last_live_pairs_blocks(packing):
    """Every live pair names itself and every skipped pair the last
    live pair the walk passed, so the blocks fetched along a row's walk
    number its live pairs and no more, in both orders; the index maps
    of the operands that change along the walk read their tile through
    it, the accumulating tile's and the results' from the table."""
    for own, table, flags, held in walks(packing):
        assert flags[:, 0].all()            # a walk starts on the diagonal
        at = np.arange(table.shape[1])
        for row_flags, row_held in zip(flags != 0, held):
            assert np.array_equal(row_held[row_flags], at[row_flags])
            for i in at[~row_flags]:
                assert row_held[i] == at[:i][row_flags[:i]].max()
            fetched = 1 + np.count_nonzero(np.diff(table[1 - own, row_held]))
            assert fetched <= row_flags.sum()
            assert 1 + np.count_nonzero(np.diff(row_held)) == row_flags.sum()
        q = jnp.zeros((2, 512, 4, 128))
        ks = flash_attention._Kernels.of(
            q, q, q, attn._flash_schedule(attn.DocumentMask(512), 128, 128),
            attn._seen, 1.0, -1e30,
            True, jnp.zeros((2, 512), jnp.int32), jnp.asarray(flags))
        through_held = {
            0: {"narrow", "key_ids", "key_ids_row"},
            1: {"wide", "row", "column", "query_ids", "query_ids_row"}}[own]
        where = {      # an index of batch b, block of heads 7 and tile t
            "wide": lambda b, t: (b, t, 7), "narrow": lambda b, t: (b, t, 7),
            "column": lambda b, t: (b, 7, t, 0),
            "row": lambda b, t: (b, 7, 0, t),
            "query_ids": lambda b, t: (b, t, 0),
            "key_ids": lambda b, t: (b, t, 0),
            "query_ids_row": lambda b, t: (b, 0, t),
            "key_ids_row": lambda b, t: (b, 0, t)}
        for kind, index_of in where.items():
            spec = ks.operand(kind, own)[1]
            keys = int("key" in kind or kind == "narrow")
            for b in range(2):
                for i in at:
                    pair = held[b, i] if kind in through_held else i
                    assert spec.index_map(b, 7, i, table, flags, held) == (
                        index_of(b, table[keys, pair])), (kind, own)


@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_a_walk_that_starts_on_a_skipped_pair_holds_the_first_live_one(
        packing):
    for own, table, flags, held in walks(packing, first_skipped=True):
        first = np.argmax(flags != 0, axis=1)
        assert (first > 0).all()
        for row_held, row_first in zip(held, first):
            assert (row_held[:row_first + 1] == row_first).all()


def test_in_bf16_the_document_kernels_differ_by_their_products_rounding(
        interpreted, monkeypatch):
    """As for the block-diffusion mask: heads of 64 in bf16 against the
    f32 XLA tiles on the same operands, widened."""
    monkeypatch.setattr(attn, "_FLASH_BLOCK_Q", 128)
    monkeypatch.setattr(attn, "_FLASH_BLOCK_KV", 128)
    segment = jnp.asarray(segments(PACKINGS["boundaries inside blocks"], 512))
    q, k, v, target = document_operands(64, 4, seed=13)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))

    def layer(q, k, v):
        return attn.causal_document_attention(
            q, k, v, segment, scale=0.125, tile=128)

    got = with_gradients(layer, q, k, v, target)
    assert all(g.dtype == jnp.bfloat16 for g in got)
    monkeypatch.setenv("HVTPU_PALLAS", "0")
    want = with_gradients(lambda *a: layer(*a), *(
        a.astype(jnp.float32) for a in (q, k, v)), target)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert distance(g, w) < 6e-3, name


@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_a_pair_is_skipped_only_where_no_query_sees_a_key(packing):
    """``live_pairs`` against the mask itself, block pair by block pair
    of the causal triangle: it may keep a pair that holds nothing (it
    knows the ranges of the ids, not the ids), never drop one that
    holds something; with documents in order it is exact."""
    segment = segments(PACKINGS[packing], 512)
    live = attn.live_pairs(segment, 128, 128)
    assert live.shape == (2, 4, 4) and live.dtype == np.int32
    pos = np.arange(512)
    seen = np.asarray(attn._seen(pos[None, :, None], pos[None, None, :],
                               segment[:, :, None], segment[:, None, :]))
    holds = seen.reshape(2, 4, 128, 4, 128).any(axis=(2, 4))
    schedule = attn._flash_schedule(attn.DocumentMask(512), 128, 128)
    assert schedule.pairs == 10 and np.all(
        schedule.by_query[2] == flash_attention.PARTIAL)
    qi, kj = schedule.by_query[:2]
    assert np.array_equal(live[:, qi, kj] != 0, holds[:, qi, kj])
    assert not holds[:, np.triu_indices(4, 1)[0],
                     np.triu_indices(4, 1)[1]].any()
    # ids in no order: a range that straddles another keeps the pair
    shuffled = np.where(segment == 0, 5, segment)
    kept = attn.live_pairs(shuffled, 128, 128)
    same = (shuffled[:, :, None] == shuffled[:, None, :]).reshape(
        2, 4, 128, 4, 128).any(axis=(2, 4))
    assert np.all(kept[same] == 1)


def test_the_hosts_loop_counts_the_pairs_run_and_skipped(monkeypatch):
    monkeypatch.setattr(attn, "_FLASH_BLOCK_Q", 128)
    monkeypatch.setattr(attn, "_FLASH_BLOCK_KV", 128)
    pairs = metrics.REGISTRY.counter("hvtpu_attention_pairs_total")
    before = pairs.value(kind="run"), pairs.value(kind="skipped")
    attn.note_attention_pairs(segments([[128, 384], []], 512))
    # row 0: documents of blocks {0}, {1, 2}, {3}: 1 + 3 + 1 pairs;
    # row 1: one document, the whole triangle of 10
    assert pairs.value(kind="run") - before[0] == 5 + 10
    assert pairs.value(kind="skipped") - before[1] == 5


def test_without_ids_the_kernels_are_built_as_if_there_were_none(
        interpreted):
    """The transformer cell's call: one prefetched table and the three
    operands, nothing for ids or for pairs to skip.  With ids two more
    operands; with pairs to skip their flags and the pairs held, both
    prefetched, and nothing else."""
    seq_len = 128
    schedule = flash_attention.pair_schedule(
        attn.tile_work(seq_len, 4, 128, 128), 128, 128)
    q, k, v, _ = operands(seq_len, 2, 1)

    def call_of(**data):
        jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention.forward(
            q, k, v, schedule,
            (lambda a, b, *ids: attn.allowed(a, b, seq_len, 4)),
            scale=HD ** -0.5, mask_value=attn._MASKED, interpret=True,
            **data))(q, k, v)
        call, = (e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
        return (call.params["grid_mapping"].num_index_operands,
                len(call.invars))

    assert call_of() == (1, 4)
    ids = jnp.zeros(q.shape[:2], jnp.int32)
    assert call_of(ids=ids) == (1, 6)
    assert call_of(ids=ids, live=jnp.ones((2, 2, 2), jnp.int32)) == (3, 8)


def test_without_pallas_the_xla_path_runs(monkeypatch):
    """No interpreter and no TPU: what every CPU run of the model takes."""
    monkeypatch.delenv("HVTPU_PALLAS_INTERPRET", raising=False)
    q, k, v, _ = operands(128, 2, 1)
    before = calls("pallas"), calls("xla")
    jax.jit(lambda *a: attn.tiled_attention(
        *a, block_length=4, tile=128)).lower(q, k, v)
    assert (calls("pallas"), calls("xla")) == (before[0], before[1] + 1)


def lowered_for_a_tpu(monkeypatch, loss, *shapes):
    """``jax.grad(loss)`` lowered for a TPU, the kernels compiled."""
    monkeypatch.delenv("HVTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    return jax.jit(jax.grad(loss, (0, 1, 2))).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


def three_plain_custom_calls(text):
    found = re.findall(r"stablehlo\.custom_call @tpu_custom_call.*", text)
    assert len(found) == 3
    for name, line in zip(KERNELS, sorted(
            found, key=lambda l: KERNELS.index(next(
                n for n in KERNELS if n in l)))):
        assert f'kernel_name = "{name}"' in line
        assert "kernel_metadata" not in line.replace(
            'kernel_metadata = "{}"', "")
    assert text.count("hvtpu:attention") >= 3


def test_lowered_for_a_tpu_the_kernels_are_three_plain_custom_calls(
        monkeypatch):
    """The join the benchmark's roofline depends on (``benchmark/scopes
    .py`` reads an instruction as one line): each kernel a
    ``tpu_custom_call`` under the scope, named for what it is, and none
    with kernel metadata, which XLA would print over several lines."""
    q, k, v, target = (jax.ShapeDtypeStruct(a.shape, jnp.bfloat16)
                       for a in operands(1024, 4, 1))

    def loss(q, k, v, target):
        out = attn.tiled_attention(q, k, v, block_length=4, tile=512)
        return jnp.sum(out.astype(jnp.float32) * target)

    three_plain_custom_calls(lowered_for_a_tpu(monkeypatch, loss, q, k, v,
                                               target))


def test_under_the_document_mask_the_same_three_calls(monkeypatch):
    """The document mask through the same wrapper, at latent attention's
    heads (keys of 192 on values of 128, a key/value head a query head),
    the ids its data: the same three kernels, each once."""
    q, k = (jax.ShapeDtypeStruct((2, 1024, 8, 192), jnp.bfloat16)
            for _ in range(2))
    v, target = (jax.ShapeDtypeStruct((2, 1024, 8, 128), jnp.bfloat16)
                 for _ in range(2))
    segment = jnp.asarray(segments(PACKINGS["boundaries inside blocks"],
                                   1024))

    def loss(q, k, v, target):
        out = attn.causal_document_attention(q, k, v, segment,
                                             scale=192 ** -0.5)
        return jnp.sum(out.astype(jnp.float32) * target)

    three_plain_custom_calls(lowered_for_a_tpu(monkeypatch, loss, q, k, v,
                                               target))
