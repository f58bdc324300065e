"""``models/hybrid_ssm.py`` (Mamba-2 mixers and NoPE attention by a
``layer_types`` list, packed documents) against the plain reference
``benchmark/reference/granite_hybrid.py`` (float32, the recurrence one
position at a time, a dense mask), on seeded random weights at toy size
on the CPU.  Both sides compute in float32 at ``highest``; the stated
tolerance is what two orders of summing the same f32 products leave."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import granite_hybrid as ref  # noqa: E402
from horovod_tpu.models import hybrid_ssm as hs  # noqa: E402
from horovod_tpu.obs import metrics  # noqa: E402

# relative L2 distance of a loss or a gradient leaf, f32 against f32
RTOL = 2e-5

TOY = hs.HybridSSMConfig(
    vocab_size=96, hidden_size=32, layer_types=("mamba", "attention", "mamba"),
    mlp_width=48, num_heads=4, num_kv_heads=2, head_dim=8,
    attention_multiplier=0.125, ssm_heads=4, ssm_head_dim=16, ssm_state=8,
    conv_width=4, chunk_size=16, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0, rms_norm_eps=1e-5,
    compute_dtype="float32")


def sizes_of(cfg, **blocks):
    return ref.Sizes(
        layer_types=cfg.layer_types, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, ssm_heads=cfg.ssm_heads,
        attention_multiplier=cfg.attention_multiplier,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling, rms_norm_eps=cfg.rms_norm_eps,
        **blocks)


def lively(params):
    """``init_params`` with the first projection ten times larger: at
    hidden 32 normal(0.02) leaves ``x``, ``B`` and ``C`` so small that
    the recurrence adds nothing a comparison could see."""
    for group in params["layers"]:
        if "in_proj" in group:
            group["in_proj"] = 10.0 * group["in_proj"]
    return params


def params_of(cfg, seed=0):
    return lively(hs.init_params(jax.random.PRNGKey(seed), cfg))


def batch_of(boundaries, seq_len, seed=0, vocab=96):
    """Rows whose documents start at 0 and at ``boundaries[row]``."""
    rows = len(boundaries)
    rng = np.random.default_rng(seed)
    segment = np.zeros((rows, seq_len), np.int32)
    for row, starts in zip(segment, boundaries):
        for start in starts:
            row[start:] += 1
    w = np.zeros((rows, seq_len), np.float32)
    w[:, :-1] = segment[:, 1:] == segment[:, :-1]
    return {"x": rng.integers(0, vocab, (rows, seq_len), dtype=np.int32),
            "segment": segment, "w": w}


def distance(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def system(cfg, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: hs.next_token_loss(p, batch, cfg)))(params)


def assert_trees_close(got, want, rtol=RTOL):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert np.linalg.norm(np.asarray(w)) > 0, jax.tree_util.keystr(path)
        assert distance(g, w) < rtol, jax.tree_util.keystr(path)


# -- the whole model against the reference -----------------------------------

# chunk 16: 20 and 50 fall inside chunks, the chunk boundaries 16, 32, 48
# inside documents; 32 is a document boundary on a chunk boundary
PACKINGS = {
    "boundaries_inside_chunks": [[20, 50], [33]],
    "boundary_on_a_chunk_boundary": [[32], [16, 48]],
    "one_document_a_row": [[], []],
    "documents_shorter_than_the_convolution": [[1, 3, 4, 7], [61, 63]],
}


@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_loss_and_every_gradient_leaf_agree_with_the_reference(packing):
    batch = batch_of(PACKINGS[packing], 64)
    params = params_of(TOY)
    loss, grads = system(TOY, params, batch)
    ref_loss, ref_grads = ref.loss_and_gradient(params, batch, sizes_of(TOY))
    assert abs(float(loss) - float(ref_loss)) < RTOL * float(ref_loss)
    assert_trees_close(grads, ref_grads)


@pytest.mark.parametrize("layer_types", [
    ("attention", "mamba", "mamba"), ("mamba", "mamba", "attention"),
    ("mamba", "attention", "attention", "mamba"), ("mamba",)])
def test_the_layers_follow_layer_types(layer_types):
    cfg = dataclasses.replace(TOY, layer_types=layer_types)
    params = params_of(cfg, seed=3)
    assert [len(jax.tree_util.tree_leaves(g)) for g in params["layers"]] == [
        12 if kind == "mamba" else 8
        for kind, _ in hs.layer_groups(layer_types)]
    batch = batch_of([[20, 50]], 64, seed=3)
    loss, grads = system(cfg, params, batch)
    ref_loss, ref_grads = ref.loss_and_gradient(params, batch, sizes_of(cfg))
    assert abs(float(loss) - float(ref_loss)) < RTOL * float(ref_loss)
    assert_trees_close(grads, ref_grads)


def test_another_order_of_the_same_layers_is_another_model():
    batch = batch_of([[20]], 64)
    params = params_of(TOY)
    swapped = dataclasses.replace(
        TOY, layer_types=("attention", "mamba", "mamba"))
    moved = {**params, "layers": [params["layers"][1], jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b]), params["layers"][0],
        params["layers"][2])]}
    with jax.default_matmul_precision("highest"):
        one = hs.hidden_states(params, batch["x"], TOY, batch["segment"])
        other = hs.hidden_states(moved, batch["x"], swapped, batch["segment"])
    assert distance(other, one) > 1e-3


def test_layer_groups_are_the_runs_of_one_kind():
    published = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert hs.layer_groups(published) == [
        ("mamba", 5), ("attention", 1), ("mamba", 4)]
    assert hs.layer_groups(published) == ref.layer_groups(published)
    assert hs.layer_groups(published * 4)[2:4] == [
        ("mamba", 9), ("attention", 1)]
    with pytest.raises(ValueError, match="'mamba' or 'attention'"):
        hs.layer_groups(["mamba", "moe"])


def test_a_row_of_one_document_is_the_model_without_segment():
    batch = batch_of([[]], 64)
    params = params_of(TOY)
    with jax.default_matmul_precision("highest"):
        with_segment = hs.hidden_states(
            params, batch["x"], TOY, batch["segment"])
        without = hs.hidden_states(params, batch["x"], TOY)
    assert np.array_equal(np.asarray(with_segment), np.asarray(without))


def test_a_packed_row_is_its_documents_run_one_by_one():
    starts = [0, 20, 50, 64]
    batch = batch_of([starts[1:-1]], 64)
    params = params_of(TOY)
    with jax.default_matmul_precision("highest"):
        packed = np.asarray(hs.hidden_states(
            params, batch["x"], TOY, batch["segment"]))
        for a, b in zip(starts, starts[1:]):
            alone = np.asarray(hs.hidden_states(
                params, batch["x"][:, a:b], TOY))
            assert distance(packed[:, a:b], alone) < 1e-6, (a, b)


def test_a_document_cannot_see_the_one_before_it():
    """Other tokens in the first document leave the second's hidden
    states where they were: state, convolution and attention all stop.
    (To rounding, not bit for bit: a chunk's cumulative sums run over
    both documents before their differences are taken.)"""
    batch = batch_of([[20]], 64)
    params = params_of(TOY)
    other = batch["x"].copy()
    other[:, :20] = (other[:, :20] + 1) % 96
    with jax.default_matmul_precision("highest"):
        run = jax.jit(lambda x: hs.hidden_states(
            params, x, TOY, batch["segment"]))
        a, b = np.asarray(run(batch["x"])), np.asarray(run(other))
    assert distance(a[:, 20:], b[:, 20:]) < 1e-6
    assert distance(a[:, :20], b[:, :20]) > 1e-2


def test_chunks_of_256_and_of_64_give_the_same_answer():
    wide = dataclasses.replace(TOY, layer_types=("mamba", "mamba"))
    batch = batch_of([[100, 300, 301]], 512)
    params = params_of(wide, seed=1)
    results = {}
    for chunk in (256, 64, 512):
        cfg = dataclasses.replace(wide, chunk_size=chunk)
        results[chunk] = system(cfg, params, batch)
    for chunk in (64, 512):
        assert abs(float(results[chunk][0]) - float(results[256][0])) < (
            RTOL * float(results[256][0]))
        # 512 positions summed in another order: five times the room
        assert_trees_close(results[chunk][1], results[256][1], rtol=1e-4)


def test_the_reference_in_blocks_is_the_reference():
    """``time_block``, ``query_block`` and recomputed layers, which the
    comparison on the chip needs to fit, change no number."""
    batch = batch_of([[20, 50]], 64)
    params = params_of(TOY)
    plain = ref.loss_and_gradient(params, batch, sizes_of(TOY))
    blocks = ref.loss_and_gradient(params, batch, sizes_of(
        TOY, time_block=16, query_block=16, recompute_layers=True))
    assert abs(float(plain[0]) - float(blocks[0])) < 1e-6 * float(plain[0])
    assert_trees_close(blocks[1], plain[1], rtol=1e-5)


# -- the cut ties to the model -----------------------------------------------

def test_the_cut_is_the_first_layers_and_a_slice_of_the_vocabulary():
    """The uncut toy model: two periods of (mamba, attention, mamba)
    and 4 x 24 rows of vocabulary.  The cut one holds the first period
    and rows 24-47: its hidden states are the uncut model's after three
    layers, and its loss is the uncut reference's with the logits
    outside the slice left out."""
    whole = dataclasses.replace(TOY, layer_types=TOY.layer_types * 2)
    first, held = 24, 24
    params = params_of(whole, seed=2)
    groups = hs.layer_groups(whole.layer_types)
    assert groups == [("mamba", 1), ("attention", 1), ("mamba", 2),
                      ("attention", 1), ("mamba", 1)]
    cut_cfg = dataclasses.replace(TOY, vocab_size=held)
    cut = {"embed": params["embed"][first:first + held],
           "final_norm": params["final_norm"],
           "layers": [params["layers"][0], params["layers"][1],
                      jax.tree_util.tree_map(lambda a: a[:1],
                                             params["layers"][2])]}
    batch = batch_of([[20, 50]], 64, vocab=held)
    ids = batch["x"] + first                      # the uncut model's ids

    # the reference's hidden states after every layer of the uncut model
    sizes = sizes_of(whole)
    h = sizes.embedding_multiplier * params["embed"][ids[0]]
    after = []
    for kind, p in ref.layers_of(params, sizes):
        h = ref.layer(kind, p, h, batch["segment"][0], sizes)
        after.append(h)
    with jax.default_matmul_precision("highest"):
        hidden = hs.hidden_states(cut, batch["x"], cut_cfg, batch["segment"])
    assert distance(hidden[0], after[2]) < RTOL
    assert distance(hidden[0], after[-1]) > 1e-2

    # the loss over the slice: the uncut head's logits, the rest left out
    logits = ref.logits_of(params, after[2], sizes)[:, first:first + held]
    logp = jax.nn.log_softmax(logits)
    ce = -jnp.take_along_axis(
        logp, jnp.roll(batch["x"][0], -1)[:, None], axis=-1)[:, 0]
    want = float(jnp.sum(batch["w"][0] * ce) / batch["w"].sum())
    loss, _ = system(cut_cfg, cut, batch)
    assert abs(float(loss) - want) < RTOL * want
    assert abs(float(ref.loss(cut, batch, sizes_of(cut_cfg))) - want) < (
        RTOL * want)


# -- the parts ---------------------------------------------------------------

def scan_inputs(seed, rows, t, heads=4, head_dim=8, state=8,
                dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, t, heads, head_dim)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (rows, t, heads))
                ).astype(np.float32)
    a_head = -rng.uniform(1.0, 16.0, heads).astype(np.float32)
    b_in = rng.standard_normal((rows, t, state)).astype(np.float32)
    c_out = rng.standard_normal((rows, t, state)).astype(np.float32)
    d_skip = rng.uniform(0.5, 1.5, heads).astype(np.float32)
    return (jnp.asarray(x, dtype), jnp.asarray(dt), jnp.asarray(a_head),
            jnp.asarray(b_in, dtype), jnp.asarray(c_out, dtype),
            jnp.asarray(d_skip))


def sequential(x, dt, a_head, b_in, c_out, d_skip, segment):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.recurrence(
            x[i], dt[i], a_head, b_in[i], c_out[i],
            ref.first_of_a_document(segment[i])) for i in range(len(x))]
        ) + d_skip[:, None] * x


@pytest.mark.parametrize("t, chunk, starts", [
    (64, 16, [20, 50]), (64, 16, [16, 17]), (50, 16, [3]), (24, 64, [5]),
    (64, 1, [20]), (64, 16, [])])
def test_the_chunked_scan_is_the_recurrence(t, chunk, starts):
    """Inputs of order one and decays from 0.9995 down to e-8 a
    position; 50 positions are no whole chunks of 16, and 24 are less
    than one of 64."""
    operands = scan_inputs(7, 2, t)
    segment = jnp.asarray(batch_of([starts, []], t)["segment"])
    want = sequential(*operands, segment)

    def chunked(*operands):
        with jax.default_matmul_precision("highest"):
            return hs.ssd_scan(*operands, segment, chunk)

    assert distance(jax.jit(chunked)(*operands), want) < 1e-5
    # and its backward pass, through both
    weights = jnp.asarray(np.random.default_rng(8).standard_normal(
        want.shape), jnp.float32)
    got = jax.grad(lambda *o: jnp.sum(chunked(*o) * weights),
                   argnums=(0, 1, 2, 3, 4, 5))(*operands)
    ref_grads = jax.grad(
        lambda *o: jnp.sum(sequential(*o, segment) * weights),
        argnums=(0, 1, 2, 3, 4, 5))(*operands)
    for g, w in zip(got, ref_grads):
        assert distance(g, w) < 1e-4


def test_a_decay_that_underflows_gives_zeros_and_no_nan():
    """``delta A`` of -1,600 a position: every decay is 0 in f32, the
    masked half of a chunk's decays would be ``exp(+25,600)``."""
    x, dt, a_head, b_in, c_out, d_skip = scan_inputs(9, 1, 32)
    segment = jnp.zeros((1, 32), jnp.int32)
    dt = jnp.full_like(dt, 100.0)

    def total(x, dt, a_head):
        return jnp.sum(hs.ssd_scan(
            x, dt, a_head, b_in, c_out, d_skip, segment, 16))

    value, grads = jax.value_and_grad(total, argnums=(0, 1, 2))(x, dt, a_head)
    assert np.isfinite(float(value))
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


def test_in_bfloat16_the_scan_stays_near_the_recurrence():
    operands = scan_inputs(11, 2, 64, dtype=jnp.bfloat16)
    segment = jnp.asarray(batch_of([[20, 50], []], 64)["segment"])
    got = hs.ssd_scan(*operands, segment, 16)
    assert got.dtype == jnp.bfloat16
    want = sequential(*(o.astype(jnp.float32) for o in operands), segment)
    assert distance(got.astype(jnp.float32), want) < 2e-2


@pytest.mark.parametrize("starts", [[], [1, 2], [5, 6, 7, 9]])
def test_the_convolution_reads_zeros_before_a_documents_start(starts):
    rng = np.random.default_rng(4)
    x = rng.standard_normal((1, 12, 3)).astype(np.float32)
    w = rng.standard_normal((4, 3)).astype(np.float32)
    b = rng.standard_normal(3).astype(np.float32)
    segment = batch_of([starts], 12)["segment"]
    want = np.zeros_like(x)
    for t in range(12):
        for back in range(4):
            if t - back >= 0 and segment[0, t - back] == segment[0, t]:
                want[0, t] += w[3 - back] * x[0, t - back]
    got = hs.causal_conv(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
                         jnp.asarray(segment))
    np.testing.assert_allclose(np.asarray(got), want + b, atol=1e-6)


@pytest.mark.parametrize("tile", [8, 24, 64])
def test_the_attention_in_tiles_is_the_dense_masked_softmax(tile):
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((2, 64, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 64, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 64, 2, 8)), jnp.float32)
    segment = jnp.asarray(batch_of([[20, 50], [33]], 64)["segment"])
    with jax.default_matmul_precision("highest"):
        got = hs.causal_document_attention(
            q, k, v, segment, scale=0.125, tile=tile)
        for row in range(2):
            mask = ref.dense_mask(segment[row])
            kk, vv = (jnp.repeat(a[row], 2, axis=1) for a in (k, v))
            s = jnp.einsum("qhd,khd->hqk", q[row], kk) * 0.125
            want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(
                jnp.where(mask[None], s, -jnp.inf), axis=-1), vv)
            assert distance(got[row], want) < 1e-6


def _tiles_of_pr_32(q, k, v, segment, *, scale, tile):
    """``causal_document_attention`` as PR 32 left it, line for line:
    what every backend but a TPU must still run."""
    from jax import lax

    b, t, heads, hd = q.shape
    groups = k.shape[2]
    tile = min(tile, t)

    @jax.checkpoint
    def rows(q_rows, keys, values, seg_q, seg_k, first):
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q_rows, keys,
                       preferred_element_type=jnp.float32) * scale
        at = first + jnp.arange(q_rows.shape[1])
        seen = ((jnp.arange(keys.shape[1])[None, :] <= at[:, None])[None]
                & (seg_q[:, :, None] == seg_k[:, None, :]))
        s = jnp.where(seen[:, None, None], s, -1e30)
        top = lax.optimization_barrier(
            lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
        p = jnp.exp(s - top)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(values.dtype), values,
                          preferred_element_type=jnp.float32
                          ).astype(q_rows.dtype)

    q = q.reshape(b, t, groups, heads // groups, hd)
    out = [rows(q[:, a:a + tile], k[:, :a + tile], v[:, :a + tile],
                segment[:, a:a + tile], segment[:, :a + tile], a)
           for a in range(0, t, tile)]
    return jnp.concatenate(out, axis=1).reshape(b, t, heads, hd)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_on_the_cpu_the_attention_is_pr_32s_function_bit_for_bit(
        monkeypatch, dtype):
    """Shapes the Pallas kernels would take on a TPU (heads of 64, 1,024
    positions): off one, the XLA tiles run, counted as such, and give
    ``out`` and every gradient as they did."""
    monkeypatch.delenv("HVTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)
    rng = np.random.default_rng(9)
    q, target = (jnp.asarray(rng.standard_normal((2, 1024, 4, 64)), dtype)
                 for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((2, 1024, 2, 64)), dtype)
            for _ in range(2))
    segment = jnp.asarray(batch_of([[300, 512, 528], [77]], 1024)["segment"])

    def results(attention):
        def loss(q, k, v):
            out = attention(q, k, v, segment, scale=0.125, tile=256)
            return jnp.sum((out * target).astype(jnp.float32)), out
        grads, out = jax.jit(jax.grad(loss, (0, 1, 2), has_aux=True))(q, k, v)
        return (out, *grads)

    counter = metrics.REGISTRY.counter("hvtpu_attention_calls_total")
    before = counter.value(path="xla"), counter.value(path="pallas")
    got = results(hs.causal_document_attention)
    assert (counter.value(path="xla"), counter.value(path="pallas")) == (
        before[0] + 1, before[1])
    for g, w in zip(got, results(_tiles_of_pr_32)):
        assert g.dtype == w.dtype and jnp.array_equal(g, w)


def test_the_start_is_mamba_2s_own():
    cfg = dataclasses.replace(TOY, ssm_heads=64, ssm_head_dim=2,
                              layer_types=("mamba",) * 4)
    group = hs.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]
    delta = np.asarray(jax.nn.softplus(group["dt_bias"]))
    assert 1e-3 * 0.999 <= delta.min() and delta.max() <= 1e-1 * 1.001
    a = np.exp(np.asarray(group["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert np.all(np.asarray(group["D"]) == 1.0)
    assert np.all(np.asarray(group["conv_b"]) == 0.0)
    taps = np.asarray(group["conv_w"])
    assert taps.shape == (4, 4, cfg.conv_channels)
    assert np.abs(taps).max() <= 0.5 and taps.std() == pytest.approx(
        0.5 / np.sqrt(3), rel=0.05)
    assert np.asarray(group["in_proj"]).std() == pytest.approx(0.02, rel=0.05)
    assert group["in_proj"].shape == (4, 32, 2 * 128 + 2 * 8 + 64)


def test_the_published_widths_give_the_published_counts():
    cfg = hs.HybridSSMConfig(
        vocab_size=12544, hidden_size=2048,
        layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
        mlp_width=8192, num_heads=32, num_kv_heads=8, head_dim=64,
        attention_multiplier=0.015625, ssm_heads=64, ssm_head_dim=64,
        ssm_state=128, conv_width=4, chunk_size=256,
        embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
        rms_norm_eps=1e-5)
    shapes = jax.eval_shape(
        lambda key: hs.init_params(key, cfg), jax.random.PRNGKey(0))
    count = lambda tree: sum(
        x.size for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layers"][0]) == 5 * 76_182_976
    assert count(shapes["layers"][1]) == 60_821_504
    assert count(shapes) == 772_160_448


# -- counters ----------------------------------------------------------------

def _value(name, **labels):
    found = metrics.snapshot()[name]["values"]
    key = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return found.get(key, 0.0)


def test_the_scan_counts_its_chunks_when_a_program_is_traced():
    before = _value("hvtpu_ssm_chunks_total") if (
        "hvtpu_ssm_chunks_total" in metrics.snapshot()) else 0.0
    operands = scan_inputs(1, 3, 40)
    run = jax.jit(lambda *o: hs.ssd_scan(
        *o, jnp.zeros((3, 40), jnp.int32), 16))
    run(*operands)
    run(*operands)                 # traced once: counted once
    assert _value("hvtpu_ssm_chunks_total") - before == 3 * 3


def test_a_packed_batch_is_noted_from_the_hosts_loop():
    metrics.note_packed_batch(np.zeros((2, 8), np.int32))
    assert _value("hvtpu_packed_documents_per_row") == 1.0
    before = _value("hvtpu_ssm_state_resets_total")
    metrics.note_packed_batch(batch_of([[20, 50], [33], []], 64)["segment"])
    assert _value("hvtpu_ssm_state_resets_total") - before == 6
    assert _value("hvtpu_packed_documents_per_row") == 2.0


def test_the_models_package_does_not_import_the_model():
    """The other cells' set-up is imports first."""
    import subprocess

    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, horovod_tpu.models; "
         "print('horovod_tpu.models.hybrid_ssm' in sys.modules)"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.stdout.strip() == "False", out.stderr[-1000:]


# -- B and C in groups (``models/hybrid_moe.py``'s mixers: eight) ------------

from benchmark.reference import nemotron_h as grouped_ref  # noqa: E402


def scan_operands(groups, heads=8, head_dim=4, state=8, seq_len=64, seed=0,
                  group_axis=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bc = (2, seq_len, groups, state) if group_axis else (2, seq_len, state)
    return dict(
        x=jax.random.normal(ks[0], (2, seq_len, heads, head_dim)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (2, seq_len, heads))),
        a_head=-jnp.exp(jax.random.normal(ks[2], (heads,))),
        b_in=jax.random.normal(ks[3], bc), c_out=jax.random.normal(ks[4], bc),
        d_skip=jax.random.normal(ks[5], (heads,)))


def scan_of(ops, segment, chunk=16):
    with jax.default_matmul_precision("highest"):
        return hs.ssd_scan(ops["x"], ops["dt"], ops["a_head"], ops["b_in"],
                           ops["c_out"], ops["d_skip"], segment, chunk)


def recurrence_of(ops, segment):
    """``y`` a position at a time, a row at a time, by the plain
    reference's recurrence (head ``h`` reads group ``h // (H / G)``)."""
    rows = []
    for i in range(segment.shape[0]):
        y = grouped_ref.recurrence(
            ops["x"][i], ops["dt"][i], ops["a_head"], ops["b_in"][i],
            ops["c_out"][i], grouped_ref.first_of_a_document(segment[i]))
        rows.append(y + ops["d_skip"][:, None] * ops["x"][i])
    return jnp.stack(rows)


@pytest.mark.parametrize("packing", sorted(PACKINGS))
@pytest.mark.parametrize("groups", [2, 4])
def test_the_scan_with_groups_equals_the_recurrence(groups, packing):
    """Forward and every operand's gradient, with resets inside a chunk
    and on a chunk's edge (``PACKINGS``, chunks of 16)."""
    segment = jnp.asarray(batch_of(PACKINGS[packing], 64)["segment"])
    ops = scan_operands(groups)
    target = jax.random.normal(jax.random.PRNGKey(7), ops["x"].shape)
    got, got_grads = jax.value_and_grad(
        lambda ops: jnp.sum(scan_of(ops, segment) * target))(ops)
    want, want_grads = jax.value_and_grad(
        lambda ops: jnp.sum(recurrence_of(ops, segment) * target))(ops)
    assert distance(scan_of(ops, segment), recurrence_of(ops, segment)) < RTOL
    assert abs(float(got) - float(want)) < RTOL * abs(float(want))
    for name in ops:
        assert distance(got_grads[name], want_grads[name]) < RTOL, name


def test_a_head_reads_its_own_group():
    """With two groups the second half of the heads reads the second
    group's ``B`` and ``C``: changing those moves their ``y`` alone."""
    segment = jnp.zeros((2, 64), jnp.int32)
    ops = scan_operands(2)
    moved = dict(ops, b_in=ops["b_in"].at[:, :, 1].multiply(2.0))
    one, other = scan_of(ops, segment), scan_of(moved, segment)
    assert np.array_equal(one[:, :, :4], other[:, :, :4])
    assert distance(other[:, :, 4:], one[:, :, 4:]) > 0.1


def _chunk_with_one_group(carry, inputs, a_head, d_skip, causal):
    """``hybrid_ssm._chunk`` as it was while ``B`` and ``C`` had no group
    axis (PR 32's, kept here as the oracle of *bit for bit*)."""
    state, seg_before = carry
    x, dt, b_in, c_out, seg = inputs
    dtype = x.dtype
    cs = jnp.cumsum(dt * a_head, axis=1)
    cs_h = cs.transpose(0, 2, 1)
    seen = ((seg[:, :, None] == seg[:, None, :]) & causal)[:, None]
    log_l = jnp.where(seen, cs_h[..., :, None] - cs_h[..., None, :], 0.0)
    decay = jnp.where(seen, jnp.exp(log_l), 0.0)
    cb = jnp.einsum("bin,bjn->bij", c_out, b_in,
                    preferred_element_type=jnp.float32)
    dtx = x.astype(jnp.float32) * dt[..., None]
    y = jnp.einsum("bhij,bjhp->bihp", (decay * cb[:, None]).astype(dtype),
                   dtx.astype(dtype), preferred_element_type=jnp.float32)
    into = jnp.exp(cs) * (seg == seg_before[:, None])[..., None]
    y = y + into[..., None] * jnp.einsum(
        "bin,bhpn->bihp", c_out, state.astype(dtype),
        preferred_element_type=jnp.float32)
    last, seg_last = cs[:, -1:], seg[:, -1]
    to_end = jnp.exp(last - cs) * (seg == seg_last[:, None])[..., None]
    own = jnp.einsum("bjhp,bjn->bhpn", (dtx * to_end[..., None]).astype(dtype),
                     b_in, preferred_element_type=jnp.float32)
    keep = jnp.exp(last[:, 0]) * (seg_last == seg_before)[:, None]
    state = state * keep[..., None, None] + own
    y = y + d_skip[:, None] * x.astype(jnp.float32)
    return (state, seg_last), y.astype(dtype)


@pytest.mark.parametrize("group_axis", [False, True],
                         ids=["B and C [B, T, N]", "B and C [B, T, 1, N]"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_group_gives_bit_for_bit_what_the_scan_gave_before(
        monkeypatch, dtype, group_axis):
    """``ssd_scan`` with one group, handed ``B`` and ``C`` with the group
    axis or, as before, without: ``y`` and every operand's gradient equal
    the chunk PR 32 wrote to the last bit."""
    segment = jnp.asarray(batch_of(PACKINGS["boundaries_inside_chunks"],
                                   64)["segment"])
    ops = scan_operands(1, group_axis=False)
    ops = {k: v.astype(dtype) if k in ("x", "b_in", "c_out") else v
           for k, v in ops.items()}
    target = jax.random.normal(jax.random.PRNGKey(7), ops["x"].shape)

    def run(ops):
        def loss(ops):
            y = hs.ssd_scan(ops["x"], ops["dt"], ops["a_head"], ops["b_in"],
                            ops["c_out"], ops["d_skip"], segment, 16)
            return jnp.sum(y.astype(jnp.float32) * target), y
        (_, y), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(ops)
        return [y] + [grads[k] for k in sorted(grads)]

    handed = ops if not group_axis else dict(
        ops, b_in=ops["b_in"][:, :, None], c_out=ops["c_out"][:, :, None])
    now = run(handed)
    with monkeypatch.context() as m:
        m.setattr(hs, "_chunk", _chunk_with_one_group)
        before = run(ops)
    for got, want in zip(now, before):
        assert np.array_equal(np.asarray(got).reshape(want.shape),
                              np.asarray(want))


GROUPED_TOY = dataclasses.replace(TOY, ssm_groups=2)


def test_the_gated_norm_is_over_each_groups_channels():
    """The mixer with two groups against the plain reference's
    (``benchmark/reference/nemotron_h.py``): each group's 32 of the 64
    inner channels by its own mean square."""
    cfg = GROUPED_TOY
    p = jax.tree_util.tree_map(lambda a: a[0], hs.init_params(
        jax.random.PRNGKey(2), dataclasses.replace(
            cfg, layer_types=("mamba",)))["layers"][0])
    p["in_proj"] = 10.0 * p["in_proj"]
    p["gate_norm"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(3), p["gate_norm"].shape)
    assert p["conv_w"].shape[1] == cfg.ssm_inner + 2 * 2 * cfg.ssm_state
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 64, cfg.hidden_size))
    segment = jnp.asarray(batch_of(PACKINGS["boundaries_inside_chunks"],
                                   64)["segment"])
    sizes = grouped_ref.Sizes(
        pattern="M", num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        ssm_heads=cfg.ssm_heads, ssm_groups=2, first_expert=0, top_k=1,
        norm_topk_prob=True, routed_scaling_factor=1.0,
        rms_norm_eps=cfg.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        got = hs.mamba_mixer(cfg, p, u, segment)
    want = jnp.stack([grouped_ref.mamba_mixer(p, u[i], segment[i], sizes)
                      for i in range(2)])
    assert distance(got, want) < RTOL
    # a norm over all 64 channels is another function: by more than a
    # rounding, so the comparison above can tell the two apart
    gated = jax.random.normal(jax.random.PRNGKey(5), (64,)) * jnp.repeat(
        jnp.array([1.0, 5.0]), 32)
    by_group = grouped_ref.rms_norm(gated.reshape(2, 32), 1.0, 1e-5)
    over_all = grouped_ref.rms_norm(gated, 1.0, 1e-5)
    assert distance(by_group.reshape(64), over_all) > 0.5
