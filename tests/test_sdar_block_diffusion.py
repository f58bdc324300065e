"""``models.block_diffusion`` at toy widths in f32 against the plain
reference (``benchmark/reference/sdar_block_diffusion.py``, the one
reference: the chip comparison uses the same file)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import data_parallel_sgd  # noqa: E402
from benchmark.reference import sdar_block_diffusion as ref  # noqa: E402
from horovod_tpu.models import block_diffusion as bd  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402

T = 24          # not a multiple of the tile
TOY = bd.BlockDiffusionConfig(
    vocab_size=50, hidden_size=32, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=8, expert_width=16, num_experts=8,
    experts_held=4, first_expert=2, top_k=3, norm_topk_prob=True,
    rope_theta=1e4, rms_norm_eps=1e-6, block_length=4,
    compute_dtype="float32")


def sizes_of(cfg):
    return ref.Sizes(
        head_dim=cfg.head_dim, num_experts=cfg.num_experts,
        first_expert=cfg.first_expert, top_k=cfg.top_k,
        norm_topk_prob=cfg.norm_topk_prob, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps, block_length=cfg.block_length,
        mask_token_id=cfg.mask_token_id)


def toy_params(cfg, seed=1, scale=3.0):
    """Matrices three times the initialiser's, so that attention and the
    experts move the residual stream by as much as it holds."""
    params = bd.init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree_util.tree_map(
        lambda a: a * scale if a.ndim > 1 and a.shape[-1] != cfg.vocab_size
        else a, params)


def toy_batch(cfg, rows, seq_len=T, seed=0):
    rng = np.random.default_rng(seed)
    blocks = -(-seq_len // cfg.block_length)
    x = rng.integers(0, cfg.vocab_size - 1, (rows, seq_len), dtype=np.int32)
    t = rng.uniform(0.05, 1.0, (rows, blocks)).repeat(
        cfg.block_length, axis=1)[:, :seq_len]
    mask = rng.uniform(size=(rows, seq_len)) < t
    return {"x": jnp.asarray(x), "mask": jnp.asarray(mask, jnp.int8),
            "w": jnp.asarray(np.where(mask, 1.0 / t, 0.0), jnp.float32)}


def distance(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(moe, "_TILE_ROWS", 8)   # several tiles an expert
    monkeypatch.setattr(bd, "_ATTENTION_TILE", 16)   # ... and a sequence


@pytest.mark.parametrize("block_length", [1, 4, T])
def test_loss_and_every_gradient_leaf_equal_the_reference(block_length):
    cfg = dataclasses.replace(TOY, block_length=block_length)
    params, batch = toy_params(cfg), toy_batch(cfg, rows=3)
    (loss, state), grads = jax.jit(jax.value_and_grad(
        lambda p: bd.block_diffusion_loss(p, batch, cfg), has_aux=True))(
            params)
    want_loss, want_grads, chosen = ref.loss_and_gradient(
        params, batch, sizes_of(cfg), query_block=16)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(want_grads)):
        assert distance(got, want) < 1e-5, jax.tree_util.keystr(path)
    # the routing's counts are the reference's choices, counted
    chosen = np.asarray(chosen)                     # [B, L, 2T, k]
    for layer in range(cfg.num_layers):
        want_rows = [(chosen[:, layer] == cfg.first_expert + e).sum()
                     for e in range(cfg.experts_held)]
        assert state["moe_rows_per_expert"][layer].tolist() == want_rows


def test_the_reference_in_query_blocks_is_the_reference():
    params, batch = toy_params(TOY), toy_batch(TOY, rows=2)
    whole = ref.loss(params, batch, sizes_of(TOY))
    in_blocks = ref.loss(params, batch, sizes_of(TOY), query_block=16)
    assert float(whole) == pytest.approx(float(in_blocks), rel=1e-6)


def dense_attention(q, k, v, seq_len, block_length):
    pos = np.arange(2 * seq_len)
    seen = bd.allowed(pos[:, None], pos[None, :], seq_len, block_length)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("seq_len, block_length, tile", [
    (24, 1, 16), (24, 4, 16), (24, 24, 16), (20, 4, 8), (16, 4, 512)])
def test_tiled_attention_equals_the_dense_mask(seq_len, block_length, tile):
    ks = jax.random.split(jax.random.PRNGKey(seq_len + block_length), 4)
    q = 2 * jax.random.normal(ks[0], (2, 2 * seq_len, 4, 8))
    k = 2 * jax.random.normal(ks[1], (2, 2 * seq_len, 2, 8))
    v = jax.random.normal(ks[2], (2, 2 * seq_len, 2, 8))
    target = jax.random.normal(ks[3], q.shape)

    def tiled(q, k, v):
        return bd.tiled_attention(q, k, v, block_length=block_length,
                                  tile=tile)

    def dense(q, k, v):
        return dense_attention(q, k, v, seq_len, block_length)

    assert distance(jax.jit(tiled)(q, k, v), dense(q, k, v)) < 1e-5
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(tiled(*a) * target), (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(dense(*a) * target), (0, 1, 2)))(q, k, v)
    for g, w in zip(got, want):
        assert distance(g, w) < 1e-5


def test_the_programs_mask_is_the_references():
    for seq_len, block_length in [(24, 1), (24, 4), (24, 24), (30, 5)]:
        pos = np.arange(2 * seq_len)
        assert np.array_equal(
            bd.allowed(pos[:, None], pos[None, :], seq_len, block_length),
            np.asarray(ref.dense_mask(seq_len, block_length)))
    # block 1: causal over the clean half, each noised token alone
    seen = bd.allowed(np.arange(8)[:, None], np.arange(8)[None, :], 4, 1)
    assert np.array_equal(seen[4:, 4:], np.tril(np.ones((4, 4), bool)))
    assert np.array_equal(seen[:4, :4], np.eye(4, dtype=bool))
    assert np.array_equal(seen[:4, 4:], np.tril(np.ones((4, 4), bool), -1))
    assert not seen[4:, :4].any()


@pytest.mark.parametrize("seq_len, block_length, tile", [
    (24, 1, 16), (24, 4, 16), (24, 24, 16), (30, 5, 8), (64, 4, 16)])
def test_the_runs_are_the_tile_pairs_that_hold_work(seq_len, block_length,
                                                    tile):
    n = -(-seq_len // tile)
    pos = np.full((2, n * tile), 10 ** 6)            # padding sees nothing
    pos[0, :seq_len] = np.arange(seq_len)
    pos[1, :seq_len] = seq_len + np.arange(seq_len)
    pos = pos.reshape(2 * n, tile)
    work = {
        (qi, kj) for qi in range(2 * n) for kj in range(2 * n)
        if (bd.allowed(pos[qi][:, None], pos[kj][None, :], seq_len,
                       block_length)
            & (pos[qi][:, None] < 10 ** 6) & (pos[kj][None, :] < 10 ** 6)
            ).any()}
    pairs = [(qi, k_lo + qi - q_lo)
             for q_lo, q_hi, k_lo in bd.tile_runs(seq_len, block_length, tile)
             for qi in range(q_lo, q_hi)]
    assert len(pairs) == len(set(pairs))
    assert set(pairs) == work


def test_the_cells_tiles():
    """8,192 tokens, block 4, tile 512: 288 of 1,024 tile pairs hold
    work (28 %), in 32 runs, and nothing of the fourth quadrant."""
    runs = bd.tile_runs(8192, 4, 512)
    assert sum(q_hi - q_lo for q_lo, q_hi, _ in runs) == 288
    assert len(runs) == 32
    assert not any(q_lo >= 16 and k_lo < 16 for q_lo, _, k_lo in runs)


# -- the tie of the share to the model --------------------------------------

UNCUT = dataclasses.replace(
    TOY, num_heads=16, num_kv_heads=4, experts_held=8, first_expert=0,
    vocab_size=48, num_layers=1)
CHIPS = 8


def share_of(params, chip):
    """What chip ``chip`` of 8 holds of the uncut parameters: two query
    heads and the key/value head of their group (two chips hold the
    same one), one expert, six rows of the vocabulary."""
    hd = UNCUT.head_dim
    q_cols = slice(2 * chip * hd, 2 * (chip + 1) * hd)
    kv_cols = slice(chip // 2 * hd, (chip // 2 + 1) * hd)
    rows = slice(6 * chip, 6 * (chip + 1))
    layers = dict(params["layers"])
    layers.update(
        wq=layers["wq"][:, :, q_cols], wo=layers["wo"][:, q_cols],
        wk=layers["wk"][:, :, kv_cols], wv=layers["wv"][:, :, kv_cols],
        **{name: layers[name][:, chip:chip + 1]
           for name in ("w_gate", "w_up", "w_down")})
    return dict(params, layers=layers, embed=params["embed"][rows],
                head=params["head"][:, rows])


def test_the_eight_shares_add_up_to_the_uncut_layer_and_logits():
    params = toy_params(UNCUT, seed=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (2 * T, UNCUT.hidden_size))
    sizes = sizes_of(UNCUT)
    whole = ref.layer_params(params, 0)
    want_attention = ref.attention_part(whole, x, sizes)
    want_experts = ref.expert_part(whole, x, sizes)
    want_logits = ref.logits_of(params, x, sizes)
    attention, experts, logits = 0.0, 0.0, []
    for chip in range(CHIPS):
        cfg = dataclasses.replace(
            UNCUT, num_heads=2, num_kv_heads=1, experts_held=1,
            first_expert=chip, vocab_size=6)
        held = share_of(params, chip)
        p = ref.layer_params(held, 0)
        attention += bd.attention_part(cfg, p, x[None])[0]
        experts += bd.expert_part(cfg, p, x[None])[0][0]
        logits.append(bd.logits_of(held, x[None], cfg)[0])
        # the reference, given the same share, gives the same part
        share_sizes = dataclasses.replace(sizes, first_expert=chip)
        ref.check_share(held, share_sizes, experts_held=1,
                        heads_held=(2, 1), vocab_held=6)
        assert distance(bd.expert_part(cfg, p, x[None])[0][0],
                        ref.expert_part(p, x, share_sizes)) < 1e-5
    assert distance(attention, want_attention) < 1e-5
    assert distance(experts, want_experts) < 1e-5
    assert distance(jnp.concatenate(logits, axis=-1), want_logits) < 1e-5
    with pytest.raises(ValueError, match="hold"):
        ref.check_share(params, sizes, experts_held=1, heads_held=(2, 1),
                        vocab_held=6)


# -- the normal path ---------------------------------------------------------

@pytest.mark.parametrize("n_dev", [1, 4])
def test_one_distributed_optimizer_step_equals_the_plain_references(
        hvt, n_dev):
    """The step ``benchmark/job.make_step`` builds around
    ``hvt.DistributedOptimizer``, on one and on four devices, against
    plain data-parallel SGD around the same loss and against one step
    of SGD on the plain model's own gradient."""
    from jax.sharding import Mesh

    from benchmark.job import make_step

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("world",))
    plain = optax.sgd(0.05, momentum=0.9)
    params, batch = toy_params(TOY), toy_batch(TOY, rows=4)

    def loss_fn(params, model_state, batch):
        del model_state
        return bd.block_diffusion_loss(params, batch, TOY)

    state0 = {"moe_rows_per_expert": jnp.zeros(
        (TOY.num_layers, TOY.experts_held), jnp.int32)}

    def one(step, tx):
        state = jax.tree_util.tree_map(
            jnp.copy, (params, state0, tx.init(params)))
        return step(*state, batch)

    tx = hvt.DistributedOptimizer(plain, axis_name="world")
    got_params, got_state, got_opt, got_loss = one(
        make_step(mesh, loss_fn, tx), tx)
    want_params, _, _, want_loss = one(
        data_parallel_sgd.make_step(mesh, "world", loss_fn, plain), plain)
    ref_loss, ref_grads, _ = ref.loss_and_gradient(
        params, batch, sizes_of(TOY))
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert float(got_loss) == pytest.approx(float(ref_loss), rel=1e-6)
    assert got_state["moe_rows_per_expert"].shape == (2, 4)
    # after one step from zero momentum the momentum is the gradient
    momentum = optax.tree_utils.tree_get(got_opt, "trace")
    for (path, got), want, before, got_grad, grad in zip(
            jax.tree_util.tree_leaves_with_path(got_params),
            *(jax.tree_util.tree_leaves(t)
              for t in (want_params, params, momentum, ref_grads))):
        name = jax.tree_util.keystr(path)
        moved = np.asarray(got) - np.asarray(before)
        assert distance(
            moved, np.asarray(want) - np.asarray(before)) < 1e-5, name
        assert distance(got_grad, grad) < 1e-5, name


def test_the_spans_are_in_the_compiled_steps_metadata():
    params, batch = toy_params(TOY), toy_batch(TOY, rows=1)
    text = jax.jit(jax.grad(
        lambda p: bd.block_diffusion_loss(p, batch, TOY)[0])).lower(
            params).compile().as_text()
    for scope in ("hvtpu:attention", "hvtpu:moe.route", "hvtpu:moe.dispatch",
                  "hvtpu:moe.experts", "hvtpu:moe.combine", "hvtpu:lm_head"):
        assert scope in text, scope


def test_the_share_of_the_published_model_counts_391_million():
    cfg = bd.BlockDiffusionConfig(
        vocab_size=18992, hidden_size=2048, num_layers=4, num_heads=4,
        num_kv_heads=1, head_dim=128, expert_width=768, num_experts=128,
        experts_held=16, first_expert=48, top_k=8, norm_topk_prob=True,
        rope_theta=1e6, rms_norm_eps=1e-6, block_length=4)
    shapes = jax.eval_shape(
        lambda key: bd.init_params(key, cfg), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 391334912
    assert cfg.mask_token_id == 18991


def test_the_router_starts_balanced_over_the_chips_of_a_layer():
    """Eight near-copies of one random matrix: every token's top-8 hold
    one expert of each chip's range, whatever the token (the [MASK]
    tokens of a batch share one embedding), whatever the seed."""
    cfg = dataclasses.replace(TOY, num_experts=32, experts_held=4, top_k=8,
                              num_layers=1)
    tokens = jax.random.normal(jax.random.PRNGKey(0), (512, cfg.hidden_size))
    for seed in range(3):
        router = bd.init_params(jax.random.PRNGKey(seed), cfg)[
            "layers"]["router"][0]
        assert router.shape == (cfg.hidden_size, 32)
        chips = np.asarray(jax.lax.top_k(tokens @ router, 8)[1]) // 4
        per_chip = np.stack([(chips == c).sum(axis=1) for c in range(8)])
        assert np.abs(per_chip.sum(axis=1) - 512).max() < 16
        assert (per_chip == 1).mean() > 0.9
    with pytest.raises(ValueError, match="do not divide"):
        bd.init_params(jax.random.PRNGKey(0),
                       dataclasses.replace(cfg, experts_held=5))


def test_routing_counters():
    from horovod_tpu.obs import metrics

    before = metrics.snapshot().get(
        "hvtpu_moe_local_rows_total", {"values": {"": 0.0}})["values"][""]
    # another file's test may have said a buffer's size in this process
    share_before = metrics.snapshot().get("hvtpu_moe_buffer_live_share")
    metrics.note_moe_routing(np.array([[10, 10, 10, 10], [4, 4, 4, 28]]))
    snap = metrics.snapshot()
    assert snap["hvtpu_moe_rows_per_expert"]["values"][""] == pytest.approx(
        2.8)
    assert (snap["hvtpu_moe_local_rows_total"]["values"][""] - before
            == pytest.approx(80.0))
    # nobody said its size
    assert snap.get("hvtpu_moe_buffer_live_share") == share_before
    metrics.note_moe_routing(np.zeros((4,)))        # a step nobody came
    assert metrics.snapshot()["hvtpu_moe_rows_per_expert"]["values"][
        ""] == 0.0


def test_the_share_of_the_row_buffer_a_routing_uses(monkeypatch):
    """``hvtpu_moe_buffer_live_share``: the fullest layer's rows over
    the rows ``parallel.moe`` keeps for the worst routing; an eighth in
    the benchmark's cell at an even routing."""
    from horovod_tpu.obs import metrics
    from horovod_tpu.parallel import moe

    # the worst case in whole tiles, and no padding between the experts
    assert moe.buffer_rows(50, 3, 4) == 19 * 8 >= 50 * 3
    assert moe.buffer_rows(50, 8, 4) == 25 * 8 == 50 * 4    # 4 held of top-8
    monkeypatch.setattr(moe, "_TILE_ROWS", 512)     # as a job has it
    assert moe.buffer_rows(50, 3, 4) == 3 * 50      # a tile of 50
    rows = moe.buffer_rows(32768, 8, 16)
    assert rows == 32768 * 8 == 512 * 512
    metrics.note_moe_routing(
        np.array([[2048] * 16, [1024] * 16]), buffer_rows=rows)
    gauge = metrics.snapshot()["hvtpu_moe_buffer_live_share"]
    assert gauge["values"][""] == pytest.approx(0.125)
    assert gauge["type"] == "gauge"
    assert gauge["help"].startswith(
        "Rows the experts held here got over the rows of the buffer")
    metrics.note_moe_routing(np.full((16,), 16384), buffer_rows=rows)
    assert metrics.snapshot()["hvtpu_moe_buffer_live_share"]["values"][
        ""] == pytest.approx(1.0)
