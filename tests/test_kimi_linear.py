"""``models/kimi_linear.py`` (a mixer and a feed-forward part a layer,
chosen apart: delta-rule linear attention with a decay a channel or
latent attention without positions; a dense SwiGLU MLP or sigmoid-routed
SwiGLU experts beside a shared expert; packed documents) against the
plain reference ``benchmark/reference/kimi_linear.py`` (float32, the
delta rule one position at a time, a dense mask, every held expert over
every token with a 0/1 choice), on seeded random weights at toy size on
the CPU.  Both sides compute in float32 at ``highest``; the stated
tolerance is what two orders of summing the same f32 products leave."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import kimi_linear as ref  # noqa: E402
from horovod_tpu.models import attention as attn  # noqa: E402
from horovod_tpu.models import kimi_linear as kl  # noqa: E402
from horovod_tpu.obs import metrics  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# relative L2 distance of a loss or a gradient leaf, f32 against f32
RTOL = 3e-5
# ... and with the program's activations in bfloat16 against the f32
# reference, at weights three times ``init_params``': the loss read
# 7.5e-5; the leaves 0.02 to 0.41, the largest the last expert layer's
# (its router 0.28, its held experts' 0.26 to 0.41: of the 128 tokens a
# few flip their third choice on the rounded input, and each is a
# hundredth of an expert's sum), the rest 0.18 and under.  A part that
# is wrong, not rounded, reads near 1
BF16_LOSS_RTOL = 5e-4
BF16_LEAF_DISTANCE = 0.6

# the published order of the first five layers, at toy size: 4 of 16
# experts held
TOY = kl.KimiLinearConfig(
    vocab_size=96, hidden_size=32,
    mixers=("kda", "kda", "kda", "mla", "kda"),
    ffns=("dense", "experts", "experts", "experts", "experts"),
    kda_heads=4, kda_head_dim=8, conv_width=4, chunk_size=16,
    mla_heads=4, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, dense_width=48, expert_width=24, shared_width=24,
    num_experts=16, experts_held=4, first_expert=8, top_k=3,
    renormalise=True, routed_scaling_factor=2.446, rms_norm_eps=1e-5,
    compute_dtype="float32")


def sizes_of(cfg, **blocks):
    return ref.Sizes(
        mixers=cfg.mixers, ffns=cfg.ffns, kda_heads=cfg.kda_heads,
        mla_heads=cfg.mla_heads, qk_nope_head_dim=cfg.qk_nope_head_dim,
        first_expert=cfg.first_expert, top_k=cfg.top_k,
        renormalise=cfg.renormalise,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rms_norm_eps=cfg.rms_norm_eps, **blocks)


def lively(params, seed=0, scale=10.0, router=20.0):
    """``init_params`` with the projections ``scale`` times larger (at
    hidden 32 normal(0.02) leaves every product so small that a wrong
    part would hide in the residual stream) and a selection bias that is
    not zero, so that it changes choices."""
    for i, group in enumerate(params["layers"]):
        for name in group:
            if group[name].ndim >= 3 and name not in ("conv_w", "router"):
                group[name] = scale * group[name]
        if "router_bias" in group:
            group["router"] = router * group["router"]
            group["router_bias"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(seed + i), group["router_bias"].shape)
    return params


def params_of(cfg, seed=0, **scales):
    return lively(kl.init_params(jax.random.PRNGKey(seed), cfg), seed,
                  **scales)


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
            lambda p: kl.next_token_loss(p, batch, cfg), has_aux=True))(
                params)


def assert_trees_close(got, want, rtol=RTOL):
    """Every leaf; a leaf the reference's gradient is exact zeros of
    (the selection bias) has to be exact zeros."""
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.any(np.asarray(w)) and not np.any(np.asarray(g))
            continue
        assert np.linalg.norm(np.asarray(w)) > 0, name
        assert distance(g, w) < rtol, name


# -- the whole stack against the reference -----------------------------------

# chunk 16: 20 and 50 fall inside chunks, the chunk boundaries 16, 32, 48
# inside documents; 32 is a document boundary on a chunk boundary
PACKINGS = {
    "boundaries_inside_chunks": [[20, 50], [33]],
    "boundary_on_a_chunk_boundary": [[32], [16, 48]],
    "one_document_a_row": [[], []],
    "documents_of_one_token_and_shorter_than_the_convolution":
        [[1, 2, 4, 7], [61, 63]],
}


@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_loss_and_every_gradient_leaf_agree_with_the_reference(packing):
    batch = batch_of(PACKINGS[packing], 64)
    params = params_of(TOY)
    (loss, state), grads = system(TOY, params, batch)
    ref_loss, ref_grads = ref.loss_and_gradient(params, batch, sizes_of(TOY))
    assert abs(float(loss) - float(ref_loss)) < RTOL * float(ref_loss)
    assert_trees_close(grads, ref_grads)
    rows = np.asarray(state["moe_rows_per_expert"])
    assert rows.shape == (4, 4) and 0 < rows.sum() < 4 * 128 * 3


def test_in_bfloat16_the_model_stays_within_a_band_of_the_reference():
    """bf16 activations over the same f32 parameters: the loss and every
    gradient leaf within ``BF16_*`` of the f32 reference.  (The routers'
    and the held experts' leaves move with the tokens whose third choice
    the rounding flips: the band is theirs.)"""
    cfg = dataclasses.replace(TOY, compute_dtype="bfloat16")
    batch = batch_of(PACKINGS["boundaries_inside_chunks"], 64)
    params = params_of(TOY, scale=3.0, router=3.0)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: kl.next_token_loss(p, batch, cfg), has_aux=True))(params)
    ref_loss, ref_grads = ref.loss_and_gradient(params, batch, sizes_of(TOY))
    assert abs(float(loss) - ref_loss) < BF16_LOSS_RTOL * ref_loss
    assert_trees_close(grads, ref_grads, rtol=BF16_LEAF_DISTANCE)
    worst = max(
        distance(g, w) for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(ref_grads))
        if "router_bias" not in jax.tree_util.keystr(path))
    assert worst > 10 * RTOL      # the type was bfloat16


@pytest.mark.parametrize("mixers, ffns", [
    (("kda",), ("dense",)), (("mla",), ("experts",)),
    (("mla", "kda"), ("dense", "dense")),
    (("kda", "kda", "mla", "mla"), ("experts", "experts", "experts",
                                    "dense"))],
    ids=lambda kinds: "-".join(kinds))
def test_a_layers_mixer_and_feed_forward_part_are_chosen_apart(mixers,
                                                               ffns):
    cfg = dataclasses.replace(TOY, mixers=mixers, ffns=ffns)
    params = params_of(cfg, seed=3)
    groups = kl.layer_groups(mixers, ffns)
    assert [n for *_, n in groups] == [
        g["norm1"].shape[0] for g in params["layers"]]
    assert groups == ref.layer_groups(sizes_of(cfg))
    for (mixer, ffn, _), group in zip(groups, params["layers"]):
        assert ("w_qkv" in group) == (mixer == "kda")
        assert ("w_kva" in group) == (mixer == "mla")
        assert ("mlp_gate" in group) == (ffn == "dense")
        assert ("router" in group) == (ffn == "experts")
    batch = batch_of([[20, 50]], 64)
    (loss, state), grads = system(cfg, params, batch)
    ref_loss, ref_grads = ref.loss_and_gradient(params, batch, sizes_of(cfg))
    assert abs(float(loss) - float(ref_loss)) < RTOL * float(ref_loss)
    assert_trees_close(grads, ref_grads)
    assert state["moe_rows_per_expert"].shape == (ffns.count("experts"), 4)


def test_neighbours_of_one_kind_are_one_run():
    assert kl.layer_groups(TOY.mixers, TOY.ffns) == [
        ("kda", "dense", 1), ("kda", "experts", 2), ("mla", "experts", 1),
        ("kda", "experts", 1)]
    with pytest.raises(ValueError, match="a layer is one of"):
        kl.layer_groups(("kda", "mamba"), ("dense", "dense"))
    with pytest.raises(ValueError, match="a layer is one of"):
        kl.layer_groups(("kda",), ("moe",))
    with pytest.raises(ValueError, match="names a mixer and"):
        kl.layer_groups(("kda", "mla"), ("dense",))


def test_a_document_does_not_see_the_one_before_it():
    """Changing the tokens of the second document changes nothing in the
    first, bit for bit, and the first's tokens nothing in the second
    beyond the last bit: state, convolution and attention stop at the
    boundary.  (Not bit for bit that way round: a chunk's cumulative
    log-decays run on through a boundary inside it, so a difference of
    two of them inside the second document is rounded from sums that
    hold the first's.)"""
    batch = batch_of([[24]], 64)
    params = params_of(TOY)
    with jax.default_matmul_precision("highest"):
        one = kl.hidden_states(params, batch["x"], TOY, batch["segment"])[0]
        later = batch["x"].copy()
        later[0, 24:] = (later[0, 24:] + 1) % 96
        two = kl.hidden_states(params, later, TOY, batch["segment"])[0]
        earlier = batch["x"].copy()
        earlier[0, :24] = (earlier[0, :24] + 1) % 96
        three = kl.hidden_states(params, earlier, TOY, batch["segment"])[0]
    assert np.array_equal(one[0, :24], two[0, :24])
    assert distance(two[0, 24:], one[0, 24:]) > 1e-3
    assert distance(three[0, 24:], one[0, 24:]) < 1e-6
    assert distance(three[0, :24], one[0, :24]) > 1e-3


# -- the delta rule: chunked equals recurrent ---------------------------------

def delta_operands(rows, positions, heads=3, width=16, strength=1.0,
                   seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (rows, positions, heads, width)
    return (ref.unit_length(jax.random.normal(ks[0], shape)) * width ** -0.5,
            ref.unit_length(jax.random.normal(ks[1], shape)),
            jax.random.normal(ks[2], shape),
            -strength * jax.nn.softplus(jax.random.normal(ks[3], shape)),
            jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])),
            jax.random.normal(ks[5], shape))


def recurrent(q, k, v, g, beta, segment):
    return jnp.stack([
        ref.delta_rule(q[i], k[i], v[i], g[i], beta[i],
                       ref.first_of_a_document(segment[i]))
        for i in range(q.shape[0])])


def with_gradients(rule, operands, target):
    out = rule(*operands)
    grads = jax.grad(lambda *a: jnp.sum(rule(*a) * target),
                     tuple(range(5)))(*operands)
    return (out, *grads)


# where the documents after the first start, a row each, in 200 positions
LAYOUTS = {
    "a_document_longer_than_a_chunk": [[150], []],
    "documents_of_one_token": [[70, 71, 72], [199]],
    "starts_inside_chunks_and_on_their_edges": [[5, 64, 100, 128], [32]],
}


@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_chunked_rule_equals_the_recurrence(layout, chunk):
    """``chunked_delta_rule`` against the reference's recurrence a token
    at a time, the result and the gradient of every operand: over chunk
    sizes, document layouts and a length (200) that is no whole number
    of chunks."""
    segment = jnp.asarray(batch_of(LAYOUTS[layout], 200)["segment"])
    *operands, target = delta_operands(2, 200)
    got = with_gradients(
        lambda *a: kl.chunked_delta_rule(*a, segment, chunk), operands,
        target)
    want = with_gradients(lambda *a: recurrent(*a, segment), operands,
                          target)
    for name, g, w in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert distance(g, w) < RTOL, name


@pytest.mark.parametrize("sub_chunk", [1, 4, 16, 64])
def test_strong_decays_stay_finite_and_equal(monkeypatch, sub_chunk):
    """``A = 16`` on long documents: a step decays by up to ``exp(-16
    softplus(.))``, a chunk of 64 by far more than f32 can invert
    (``exp(-G)`` alone is infinite), and the chunked form, which makes
    every decay as a difference of two cumulative sums, equals the
    recurrence, gradients and all, at whatever sub-chunk the pairs are
    cut (``_SUB_CHUNK``: 1 is every pair as a product, 64 every pair a
    channel at a time)."""
    monkeypatch.setattr(kl, "_SUB_CHUNK", sub_chunk)
    segment = jnp.asarray(batch_of([[150], []], 200)["segment"])
    *operands, target = delta_operands(2, 200, strength=16.0, seed=1)
    g = operands[3]
    assert float(jnp.min(g)) < -40.0      # exp(64 x 40) is no f32
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-np.cumsum(
            np.asarray(g[0, :64], np.float32), axis=0))).all()
    got = with_gradients(
        lambda *a: kl.chunked_delta_rule(*a, segment, 64), operands, target)
    want = with_gradients(lambda *a: recurrent(*a, segment), operands,
                          target)
    for name, o, w in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert np.isfinite(np.asarray(o)).all(), name
        assert distance(o, w) < RTOL, name


def test_the_systems_inverse_is_the_inverse_and_its_gradient_the_rule():
    a = jnp.tril(0.2 * jax.random.normal(jax.random.PRNGKey(0),
                                         (3, 2, 32, 32)), -1)
    with jax.default_matmul_precision("highest"):
        inverse = kl.unit_lower_inverse(a)
        assert distance(inverse @ (jnp.eye(32) + a),
                        jnp.broadcast_to(jnp.eye(32), a.shape)) < 1e-5
        target = jax.random.normal(jax.random.PRNGKey(1), a.shape)
        got = jax.grad(lambda a: jnp.sum(
            kl.unit_lower_inverse(a) * target))(a)
        want = jax.grad(lambda a: jnp.sum(
            jnp.linalg.inv(jnp.eye(32) + a) * target))(a)
    assert distance(got, want) < 1e-4
    with pytest.raises(ValueError, match="no power of two"):
        kl.unit_lower_inverse(jnp.zeros((48, 48)))


def counter(name, **labels):
    return metrics.REGISTRY.counter(name).value(**labels)


def test_the_chunks_are_counted_when_a_program_is_traced():
    before = counter("hvtpu_kda_chunks_total")
    *operands, _ = delta_operands(2, 200)
    jax.jit(lambda *a: kl.chunked_delta_rule(
        *a, jnp.zeros((2, 200), jnp.int32), 32)).lower(*operands)
    assert counter("hvtpu_kda_chunks_total") == before + 2 * 7
    assert metrics.REGISTRY.gauge("hvtpu_kda_chunk_size").value() == 32.0


# -- each mixer against the reference ----------------------------------------

def one_layer(cfg, seed):
    return jax.tree_util.tree_map(
        lambda a: a[0], params_of(cfg, seed=seed)["layers"][0])


@pytest.mark.parametrize("mixer", ["kda", "mla"])
@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_a_mixer_agrees_with_the_reference(mixer, packing):
    """One mixer alone on normed inputs: its result, and the gradient of
    its input and of every one of its parameters."""
    cfg = dataclasses.replace(TOY, mixers=(mixer,), ffns=("dense",))
    p = one_layer(cfg, seed=4)
    segment = jnp.asarray(batch_of(PACKINGS[packing], 64)["segment"])
    u, target = jax.random.normal(
        jax.random.PRNGKey(7), (2, 2, 64, cfg.hidden_size))
    program = {"kda": kl.kda_mixer, "mla": kl.mla_mixer}[mixer]
    plain = {"kda": ref.kda_mixer, "mla": ref.mla_mixer}[mixer]

    def got(p, u):
        return program(cfg, p, u, segment)

    def want(p, u):
        return jnp.stack([plain(p, u[i], segment[i], sizes_of(cfg))
                          for i in range(2)])

    with jax.default_matmul_precision("highest"):
        assert distance(got(p, u), want(p, u)) < RTOL
        g = jax.grad(lambda p, u: jnp.sum(got(p, u) * target), (0, 1))(p, u)
    w = jax.grad(lambda p, u: jnp.sum(want(p, u) * target), (0, 1))(p, u)
    assert distance(g[1], w[1]) < RTOL
    used = [n for n in w[0] if np.any(np.asarray(w[0][n]))]
    assert len(used) == {"kda": 11, "mla": 5}[mixer]
    for name in used:
        assert distance(g[0][name], w[0][name]) < RTOL, name


def test_the_shared_key_part_is_one_for_all_the_heads():
    """``k_pe`` is made once a token and laid beside every head's own
    part: the reference's keys hold it in every head, and its gradient
    reaches ``W_kva``'s last columns from every head's scores."""
    cfg = dataclasses.replace(TOY, mixers=("mla",), ffns=("dense",))
    p = one_layer(cfg, seed=2)
    u = jax.random.normal(jax.random.PRNGKey(3), (64, cfg.hidden_size))
    k, v = ref.mla_keys_and_values(p, u, sizes_of(cfg))
    assert k.shape == (64, 4, 12) and v.shape == (64, 4, 8)
    shared = k[:, :, cfg.qk_nope_head_dim:]
    assert np.array_equal(shared, np.broadcast_to(shared[:, :1],
                                                  shared.shape))
    assert distance(k[:, 0, :8], k[:, 1, :8]) > 0.5      # their own differ
    # with the shared columns' weights at zero every score loses the part
    segment = jnp.zeros((1, 64), jnp.int32)
    cut = {**p, "w_kva": p["w_kva"].at[:, cfg.kv_lora_rank:].set(0.0)}
    with jax.default_matmul_precision("highest"):
        whole = kl.mla_mixer(cfg, p, u[None], segment)
        without = kl.mla_mixer(cfg, cut, u[None], segment)
    assert distance(without, whole) > 1e-3
    assert distance(
        without[0], ref.mla_mixer(cut, u, segment[0], sizes_of(cfg))) < RTOL


# -- a key width apart from the value width -----------------------------------

@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("HVTPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_keys_of_192_against_values_of_128_on_both_paths(
        interpreted, monkeypatch, path):
    """``causal_document_attention`` at the published head, 192 / 128,
    through the kernels (interpreted) and through the XLA tiles, against
    the plain softmax with the mask from positions and document ids:
    the result and the three gradients."""
    if path == "xla":
        monkeypatch.setenv("HVTPU_PALLAS", "0")
    monkeypatch.setattr(attn, "_FLASH_BLOCK_Q", 128)
    monkeypatch.setattr(attn, "_FLASH_BLOCK_KV", 128)
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k = (jax.random.normal(key, (2, 256, 4, 192)) for key in ks[:2])
    v, target = (jax.random.normal(key, (2, 256, 4, 128)) for key in ks[2:])
    segment = jnp.asarray(batch_of([[100, 130], [7]], 256)["segment"])

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 192 ** -0.5
        mask = jnp.stack([ref.dense_mask(row) for row in segment])
        s = jnp.where(mask[:, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    def program(q, k, v):
        return attn.causal_document_attention(
            q, k, v, segment, scale=192 ** -0.5, tile=64)

    calls = counter("hvtpu_attention_calls_total", path=path)
    with jax.default_matmul_precision("highest"):
        got = (program(q, k, v), *jax.grad(
            lambda *a: jnp.sum(program(*a) * target), (0, 1, 2))(q, k, v))
        want = (plain(q, k, v), *jax.grad(
            lambda *a: jnp.sum(plain(*a) * target), (0, 1, 2))(q, k, v))
    assert counter("hvtpu_attention_calls_total", path=path) == calls + 2
    assert got[0].shape == (2, 256, 4, 128)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert distance(g, w) < RTOL, name
    if path == "pallas":
        width = metrics.REGISTRY.gauge("hvtpu_attention_head_width")
        assert (width.value(kind="key"), width.value(kind="value")) == (
            192.0, 128.0)


# -- the shares add up --------------------------------------------------------

def test_the_shares_add_up_to_the_whole_layer():
    """A whole layer from its shares: the mixer once, then the routed
    parts that four chips compute, each holding four of the sixteen
    experts and seeing the same tokens, plus the shared expert once,
    equal the uncut reference's layer (every expert held); and the
    dense layer, which no chip cuts, is the reference's as it is."""
    cfg = dataclasses.replace(
        TOY, mixers=("kda",), ffns=("experts",), experts_held=16,
        first_expert=0)
    p = one_layer(cfg, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 128, cfg.hidden_size))
    segment = jnp.asarray(batch_of([[40, 90]], 128)["segment"])
    whole = ref.layer("kda", "experts", p, x[0], segment[0], sizes_of(cfg))
    with jax.default_matmul_precision("highest"):
        mixed = kl.mixer_half(cfg, "kda", p, x, segment)      # counted once
        u = kl.rms_norm(mixed, p["norm2"], cfg.rms_norm_eps)[0]
        shares, rows = [], []
        for first in range(0, 16, 4):
            held = slice(first, first + 4)
            y, routing = moe.dropless_topk_moe(
                u, p["router"],
                {name: p[name][held] for name in ("w_gate", "w_up",
                                                  "w_down")},
                top_k=cfg.top_k, num_experts=16, first_expert=first,
                renormalise=True, selection_bias=p["router_bias"],
                scale=cfg.routed_scaling_factor)
            shares.append(y)
            rows.append(int(routing["rows_per_expert"].sum()))
        shared = kl.swiglu(u, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    assert sum(rows) == 128 * cfg.top_k      # every choice is some chip's
    assert all(0 < r < 128 * cfg.top_k for r in rows)
    assert distance(mixed[0] + sum(shares) + shared, whole) < RTOL
    # one chip's share is what the program's layer and the reference give
    # for that share, and it is not the whole
    one = dataclasses.replace(cfg, experts_held=4, first_expert=4)
    p_one = {**p, **{name: p[name][4:8]
                     for name in ("w_gate", "w_up", "w_down")}}
    want = ref.layer("kda", "experts", p_one, x[0], segment[0],
                     sizes_of(one))
    assert distance(mixed[0] + shares[1] + shared, want) < RTOL
    with jax.default_matmul_precision("highest"):
        got = kl.layer(one, "kda", "experts", p_one, x, segment)[0]
    assert distance(got[0], want) < RTOL
    assert distance(want, whole) > 0.01
    dense = dataclasses.replace(TOY, mixers=("mla",), ffns=("dense",))
    p = one_layer(dense, seed=5)
    with jax.default_matmul_precision("highest"):
        got, none = kl.layer(dense, "mla", "dense", p, x, segment)
    assert none is None
    assert distance(got[0], ref.layer(
        "mla", "dense", p, x[0], segment[0], sizes_of(dense))) < RTOL


def test_the_programs_choices_are_the_references_token_for_token():
    cfg = dataclasses.replace(TOY, mixers=("kda",), ffns=("experts",))
    p = one_layer(cfg, seed=2)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        _, routing = kl.expert_ffn(cfg, p, x)
    assert routing["rows_per_expert"].shape == (4,)
    assert routing["experts"].shape == (128, cfg.top_k)
    weights = ref.routing_weights(
        p, ref.rms_norm(x.reshape(128, -1), p["norm2"], cfg.rms_norm_eps),
        sizes_of(cfg))
    chosen = np.zeros(weights.shape, bool)
    np.put_along_axis(chosen, np.asarray(routing["experts"]), True, axis=1)
    assert np.array_equal(chosen, np.asarray(weights) > 0)
    sums = np.asarray(weights).sum(axis=1)
    assert np.allclose(sums, cfg.routed_scaling_factor, rtol=1e-5)


# -- what the program counts --------------------------------------------------

def test_the_counters_tell_the_rule_the_form_and_the_widths():
    before = {
        "rule": counter("hvtpu_moe_router_total", rule="sigmoid_bias"),
        "form": counter("hvtpu_moe_experts_form_total", form="gated"),
        "path": counter("hvtpu_moe_products_total", path="ragged_dot"),
        "softmax": counter("hvtpu_moe_router_total", rule="softmax"),
        "relu2": counter("hvtpu_moe_experts_form_total", form="relu2"),
        "chunks": counter("hvtpu_kda_chunks_total"),
        "attention": counter("hvtpu_attention_calls_total", path="xla")}
    batch = batch_of([[20]], 64)
    jax.jit(lambda p: kl.next_token_loss(p, batch, TOY)).lower(
        params_of(TOY))
    # four expert layers in three runs: three call sites a trace
    assert counter("hvtpu_moe_router_total",
                   rule="sigmoid_bias") == before["rule"] + 3
    assert counter("hvtpu_moe_experts_form_total",
                   form="gated") == before["form"] + 3
    assert counter("hvtpu_moe_products_total",
                   path="ragged_dot") == before["path"] + 3
    assert counter("hvtpu_moe_router_total",
                   rule="softmax") == before["softmax"]
    assert counter("hvtpu_moe_experts_form_total",
                   form="relu2") == before["relu2"]
    # three runs of KDA layers, one row of four chunks each
    assert counter("hvtpu_kda_chunks_total") == before["chunks"] + 3 * 4
    assert metrics.REGISTRY.gauge("hvtpu_kda_chunk_size").value() == 16.0
    assert counter("hvtpu_attention_calls_total",
                   path="xla") == before["attention"] + 1


def test_the_published_expert_layer_takes_the_grouped_kernels(
        config, interpreted):
    """At the published widths, 2304 x 1024 over 16,384 tokens top-8 of
    the 8 held, the gated experts' products are the grouped kernels
    where kernels compile (here the interpreter), the backward one
    walking the inner width in blocks (a group's three weights and
    three f32 sums do not fit VMEM twice over whole):
    ``hvtpu_moe_products_total{path="grouped"}`` and
    ``hvtpu_moe_backward_kernel_total{kernel="blocked"}`` count the
    layer when a step of it is traced, forward and backward."""
    from benchmark.builders import kimi_linear_lm
    from horovod_tpu.ops import grouped_ffn

    cfg = kimi_linear_lm.model_config(config)
    shapes = jax.eval_shape(
        lambda key: kl.init_params(key, cfg), jax.random.PRNGKey(0))
    p = jax.tree_util.tree_map(      # one layer of a stacked pair
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        shapes["layers"][1])
    x = jax.ShapeDtypeStruct((2, 8192, cfg.hidden_size),
                             jnp.dtype(cfg.compute_dtype))
    assert p["w_gate"].shape == (8, 2304, 1024)
    assert grouped_ffn._blocked(cfg.compute_dtype, 2304, 1024, 3)
    before = {path: counter("hvtpu_moe_products_total", path=path)
              for path in ("grouped", "ragged_dot")}
    blocked = counter("hvtpu_moe_backward_kernel_total", kernel="blocked")
    grads = jax.eval_shape(jax.grad(lambda p, x: jnp.sum(
        kl.expert_ffn(cfg, p, x)[0].astype(jnp.float32))), p, x)
    assert grads["w_down"].shape == (8, 1024, 2304)
    assert counter("hvtpu_moe_products_total",
                   path="grouped") == before["grouped"] + 1
    assert counter("hvtpu_moe_products_total",
                   path="ragged_dot") == before["ragged_dot"]
    assert counter("hvtpu_moe_backward_kernel_total",
                   kernel="blocked") == blocked + 1


def test_the_routing_of_a_step_feeds_note_moe_routing():
    batch = batch_of([[20]], 64)
    (_, state), _ = system(TOY, params_of(TOY), batch)
    metrics.note_moe_routing(
        state["moe_rows_per_expert"],
        buffer_rows=moe.buffer_rows(64, TOY.top_k, TOY.experts_held))
    assert metrics.REGISTRY.gauge("hvtpu_moe_rows_per_expert").value() >= 1.0
    assert 0 < metrics.REGISTRY.gauge(
        "hvtpu_moe_buffer_live_share").value() <= 1.0


def test_the_start_is_the_configurations():
    cfg = dataclasses.replace(TOY, hidden_size=256, kda_head_dim=32)
    params = kl.init_params(jax.random.PRNGKey(0), cfg)
    first = params["layers"][0]
    assert abs(float(jnp.std(first["w_qkv"])) - 0.02) < 2e-3
    assert abs(float(jnp.std(first["wo"])) - 0.02) < 2e-3
    delta = jax.nn.softplus(first["dt_bias"])
    assert float(delta.min()) >= 1e-3 * 0.999
    assert float(delta.max()) <= 1e-1 * 1.001
    rate = jnp.exp(first["A_log"])
    assert first["A_log"].shape == (1, cfg.kda_heads)
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 16.0
    assert float(jnp.abs(first["conv_w"]).max()) <= 0.5
    assert not np.any(params["layers"][1]["router_bias"])
    assert params["head"].shape == (cfg.hidden_size, cfg.vocab_size)


# -- the configuration's file and its builder ---------------------------------

CONFIG = os.path.join(
    ROOT, "benchmark", "configs", "kimi-linear-48b-a3b-5of27.json")
CELL = "kimi-linear-48b-a3b-5of27-t8k-b2"


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


def test_the_file_holds_every_width_as_published(config):
    published = {
        "hidden_size": 2304, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_attention_heads": 32, "num_key_value_heads": 32,
        "intermediate_size": 9216, "moe_intermediate_size": 1024,
        "num_experts_per_token": 8, "routed_scaling_factor": 2.446,
        "num_shared_experts": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "mla_use_nope": True,
        "first_k_dense_replace": 1, "q_lora_rank": None,
        "tie_word_embeddings": False, "rms_norm_eps": 1e-5}
    assert {k: config[k] for k in published} == published
    linear = config["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (32, 128, 4)
    assert (linear["kda_layers"], linear["full_attn_layers"]) == (
        [1, 2, 3, 5], [4])
    assert config["reduced"] == [
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"]
    whole = config["published"]
    assert (whole["num_hidden_layers"], whole["num_experts"],
            whole["vocab_size"]) == (27, 256, 163840)
    full = whole["linear_attn_config"]
    assert len(full["kda_layers"]) == 20 and full["full_attn_layers"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert full["kda_layers"][:4] == linear["kda_layers"]
    assert {k: v for k, v in full.items() if not k.endswith("_layers")} == {
        k: v for k, v in linear.items() if not k.endswith("_layers")}
    assert config["num_hidden_layers"] == 5 and config["num_experts"] == 8
    place = config["deployment"]
    assert place["expert_parallel_chips"] * config["num_experts"] == 256
    assert place["vocabulary_shards"] * config["vocab_size"] == 163840
    assert place["first_vocabulary_row"] == (
        place["vocabulary_shard"] * config["vocab_size"])
    tokens = 2 * config["sequence_length"]
    assert place["rows_an_expert_a_step_here"] == tokens * 8 // 256 == 512
    assert place["rows_an_expert_a_step_in_the_deployment"] == (
        32 * place["rows_an_expert_a_step_here"])
    for key in ("assumed", "rehearsal", "parameters", "source", "why"):
        assert key in config
    assert len(config["why"]) <= 200


def test_the_file_agrees_with_the_catalog(config):
    """Every key of the catalog row's ``config`` under the same key with
    the same value, but the keys of ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert config["source"] == row["source_url"]
    assert all(key in config for key in row["config"])
    differ = {k for k, v in row["config"].items() if config[k] != v}
    assert differ == set(config["reduced"])
    assert config["published"]["linear_attn_config"] == (
        row["config"]["linear_attn_config"])


def test_the_traffic_file_repeats_the_configurations_lengths(config):
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "t8k-b2-packed.json")) as f:
        traffic = json.load(f)
    assert traffic["sequence_length"] == config["sequence_length"]
    assert traffic["document_length"] == config["document_length"]


def test_the_benchmark_names_the_cell_and_its_five_metrics(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        config["name"], "t8k-b2-packed", 1)
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == {"kda_ms_per_step", "kda_delta_roofline",
                    "latent_attention_ms_per_step",
                    "latent_attention_roofline",
                    "gated_experts_ms_per_step"}


@pytest.mark.parametrize("size", ["published", "rehearsal"])
def test_the_builder_counts_the_parameters_the_tree_holds(config, size):
    from benchmark.builders import kimi_linear_lm

    if size == "rehearsal":
        config = {**config, **config["rehearsal"]}
    cfg = kimi_linear_lm.model_config(config)
    shapes = jax.eval_shape(
        lambda key: kl.init_params(key, cfg), jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes))
    assert held == kimi_linear_lm.parameters(config) == config["parameters"]
    assert cfg.mixers == ("kda", "kda", "kda", "mla", "kda")
    assert cfg.ffns == ("dense",) + ("experts",) * 4
    assert cfg.experts_held < cfg.num_experts
    if size == "published":
        assert held == 602_434_432
        assert (cfg.kda_inner, cfg.key_width, cfg.v_head_dim,
                cfg.chunk_size) == (4096, 192, 128, 64)
        first, second = shapes["layers"][:2]
        mixer = sum(int(np.prod(first[n].shape)) for n in (
            "w_qkv", "conv_w", "f_down", "f_up", "dt_bias", "A_log",
            "b_proj", "g_down", "g_up", "head_norm", "wo"))
        assert mixer == 39_514_272
        assert second["w_up"].shape == (2, 8, 2304, 1024)
        latent = shapes["layers"][2]
        assert sum(int(np.prod(latent[n].shape)) for n in (
            "wq", "w_kva", "kv_norm", "w_kvb", "wo")) == 29_114_880


@pytest.mark.parametrize("key, value", [
    ("num_expert_group", 2), ("topk_group", 2), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True), ("num_shared_experts", 2),
    ("mla_use_nope", False), ("q_lora_rank", 1536),
    ("moe_router_activation_func", "softmax")])
def test_the_builder_refuses_what_the_model_does_not_build(config, key,
                                                           value):
    from benchmark.builders import kimi_linear_lm

    with pytest.raises(ValueError, match="models.kimi_linear builds"):
        kimi_linear_lm.model_config({**config, key: value})


def test_the_builder_holds_the_layer_lists_to_the_depth(config):
    from benchmark.builders import kimi_linear_lm

    linear = config["linear_attn_config"]
    for lists in ({"kda_layers": [1, 2, 3]},
                  {"full_attn_layers": [4, 5]},
                  {"kda_layers": [1, 2, 3, 5, 6]}):
        with pytest.raises(ValueError, match="do not name each"):
            kimi_linear_lm.layer_kinds(
                {**config, "linear_attn_config": {**linear, **lists}})
    with pytest.raises(ValueError, match="expert shard's first"):
        kimi_linear_lm.model_config({**config, "deployment": {
            **config["deployment"], "first_expert": 80}})


def test_the_required_work_is_the_issues_arithmetic(config):
    from benchmark import flops_kimi_linear_lm as flops

    pairs = 10_776_285.6        # a head a row, at the law's mean
    macs = flops.forward_macs_per_row(config, pairs)
    per_token = {k: 2 * v / 8192 / 1e6 for k, v in macs.items()}
    assert round(per_token["kda_projections"] / 4, 1) == 78.9
    assert round(per_token["kda_delta"] / 4, 1) == 5.8
    assert round(per_token["mla_projections"], 1) == 58.2
    assert round(per_token["attention"], 1) == 26.9
    assert round(per_token["dense_mlp"], 1) == 127.4
    assert round(per_token["head"], 1) == 94.4
    assert round(per_token["shared_experts"] / 4, 1) == 14.2
    assert round(per_token["router"] / 4, 1) == 1.2
    assert round(per_token["routed_experts"] / 4, 1) == 3.5
    assert flops.held_expert_rows_per_token(config) == 0.25
    assert round(sum(per_token.values())) == 721
    total = flops.train_flops_per_sample(config, pairs)
    assert 2.15e9 < total < 2.17e9
    delta = flops.delta_macs_per_token(config)
    assert delta == 32 * (2 * 64 * 128 + 64 * 256 + 64 * 128 + 3 * 128 * 128)
    assert flops.delta_train_flops_per_step(config, 16384) == (
        6 * delta * 16384 * 4)
    assert flops.delta_train_bytes_per_step(config, 16384) == (
        3 * (3 * 2 * 4096 + 4 * 4096 + 4 * 32) + 2 * 2 * 4096) * 16384 * 4
    assert flops.attention_train_flops_per_step(config, pairs, 2) == (
        pytest.approx(6 * 2 * 32 * pairs * (192 + 128)))
    mixers = per_token["kda_projections"] + per_token["kda_delta"] + (
        per_token["mla_projections"] + per_token["attention"])
    assert round(100 * mixers / sum(per_token.values())) == 59


def test_the_references_blocks_give_the_same_numbers():
    """``time_block``, ``query_block`` and ``recompute_layers`` are how
    the reference fits a chip at the published widths; they change no
    number by more than a reordering of f32 sums."""
    batch = batch_of(PACKINGS["boundaries_inside_chunks"], 64)
    params = params_of(TOY)
    whole_loss, whole = ref.loss_and_gradient(params, batch, sizes_of(TOY))
    loss, grads = ref.loss_and_gradient(params, batch, sizes_of(
        TOY, time_block=16, query_block=16, recompute_layers=True))
    assert abs(loss - whole_loss) < 1e-6 * whole_loss
    assert_trees_close(grads, whole, rtol=1e-5)
    with pytest.raises(ValueError, match="no whole blocks"):
        ref.loss_and_gradient(params, batch, sizes_of(TOY, query_block=48))
