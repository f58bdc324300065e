"""``models/gated_conv.py`` (a mixer and a feed-forward part a layer,
chosen apart: a gated short convolution or grouped-query attention with
normed queries and keys; a dense SwiGLU MLP or sigmoid-routed SwiGLU
experts; packed documents) against the plain reference
``benchmark/reference/lfm2_moe.py`` (float32, explicit taps that stop at
each document's start, a dense mask with positions restarted at every
document's start, every held expert over every token with a 0/1
choice), on seeded random weights at toy size on the CPU.  Both sides
compute in float32 at ``highest``; the stated tolerance is what two
orders of summing the same f32 products leave."""

import dataclasses
import functools
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import lfm2_moe as ref  # noqa: E402
from horovod_tpu.models import gated_conv as gc  # noqa: E402
from horovod_tpu.obs import metrics  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# relative L2 distance of a loss or a gradient leaf, f32 against f32
RTOL = 3e-5

# the published order of the first six layers, at toy size: 4 of 16
# experts held, four a token
TOY = gc.GatedConvConfig(
    vocab_size=96, hidden_size=32,
    mixers=("conv", "conv", "attention", "conv", "conv", "conv"),
    ffns=("dense", "dense", "experts", "experts", "experts", "experts"),
    conv_width=3, num_heads=4, num_kv_heads=2, head_dim=8,
    rope_theta=1e6, dense_width=48, expert_width=24, num_experts=16,
    experts_held=4, first_expert=4, top_k=4, renormalise=True,
    renormalise_eps=1e-6, routed_scaling_factor=1.0, rms_norm_eps=1e-5,
    compute_dtype="float32")


def sizes_of(cfg, **blocks):
    return ref.Sizes(
        mixers=cfg.mixers, ffns=cfg.ffns, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta,
        first_expert=cfg.first_expert, top_k=cfg.top_k,
        renormalise=cfg.renormalise, renormalise_eps=cfg.renormalise_eps,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rms_norm_eps=cfg.rms_norm_eps, **blocks)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "seed", "scale", "router"))
def params_of(cfg, seed=0, scale=10.0, router=20.0):
    """``init_params`` with the projections ``scale`` times larger (at
    hidden 32 normal(0.02) leaves every product so small that a wrong
    part would hide in the residual stream), norm scales that are not
    all 1, and a selection bias that is not zero, so that it changes
    choices."""
    params = gc.init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)
    for i, group in enumerate(params["layers"]):
        for name in group:
            key, sub = jax.random.split(key)
            if group[name].ndim >= 3 and name not in ("conv_w", "router"):
                group[name] = scale * group[name]
            elif name.endswith("norm") or name.startswith("norm"):
                group[name] = 1.0 + 0.3 * jax.random.normal(
                    sub, group[name].shape)
        if "router_bias" in group:
            group["router"] = router * group["router"]
            group["router_bias"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(seed + i), group["router_bias"].shape)
    return params


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


def one_layer(cfg, seed, group=0):
    return jax.tree_util.tree_map(lambda a: a[0],
                                  params_of(cfg, seed)["layers"][group])


def counter(name, **labels):
    return metrics.REGISTRY.counter(name).value(**labels)


# documents that start one, two and three positions apart (5, 6, 8, 11):
# the convolution's taps reach back over a start from each of them
PACKINGS = {
    "starts_one_two_and_three_apart": [[5, 6, 8, 11, 40], [1, 2, 30]],
    "one_document_a_row": [[], []],
}


# -- the gated convolution ----------------------------------------------------

@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_the_conv_mixer_agrees_with_the_reference(packing):
    cfg = dataclasses.replace(TOY, mixers=("conv",), ffns=("dense",))
    p = {name: a for name, a in one_layer(cfg, seed=1).items()
         if name in ("in_proj", "conv_w", "out_proj")}
    segment = jnp.asarray(batch_of(PACKINGS[packing], 64)["segment"])
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 64, cfg.hidden_size))
    target = jax.random.normal(jax.random.PRNGKey(3), u.shape)

    def program(p, u):
        return jnp.sum(gc.conv_mixer(cfg, p, u, segment) * target)

    def plain(p, u):
        return sum(jnp.sum(ref.conv_mixer(p, u[i], segment[i], None)
                           * target[i]) for i in range(2))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(program, (0, 1)))(p, u)
        want = jax.jit(jax.value_and_grad(plain, (0, 1)))(p, u)
        out, plain_out = jax.jit(lambda p, u: (
            gc.conv_mixer(cfg, p, u, segment),
            jnp.stack([ref.conv_mixer(p, u[i], segment[i], None)
                       for i in range(2)])))(p, u)
    assert distance(out, plain_out) < RTOL
    for g, w in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        assert distance(g, w) < RTOL
    if packing == "one_document_a_row":
        return
    # a start is a wall: the first token of a document reads its own
    # b x alone, the second its own and one before it
    b, c, x = jnp.split(u @ p["in_proj"], 3, axis=-1)
    for row, starts in enumerate(PACKINGS[packing]):
        for start in starts:
            lone = c[row, start] * p["conv_w"][-1] * (b * x)[row, start]
            first = (lone @ p["out_proj"])
            assert distance(out[row, start], first) < RTOL


def test_the_taps_are_summed_in_f32_and_the_gates_run_in_the_compute_type():
    c, bx = (jax.random.normal(jax.random.PRNGKey(i), (1, 16, 8),
                               jnp.bfloat16) for i in (0, 1))
    w = jax.random.uniform(jax.random.PRNGKey(2), (3, 8), jnp.float32,
                           -0.5, 0.5)
    segment = jnp.zeros((1, 16), jnp.int32)
    got, z = jax.jit(lambda c, bx, w: (
        gc.short_conv(c, bx, w, segment),
        ref.short_conv(bx[0].astype(jnp.float32), w, segment[0])))(c, bx, w)
    assert got.dtype == jnp.bfloat16
    want = (c[0] * z.astype(jnp.bfloat16)).astype(jnp.float32)
    assert np.allclose(np.asarray(got[0], np.float32), want, rtol=8e-3,
                       atol=1e-6)


# -- the attention layer ------------------------------------------------------

def test_row_positions_give_the_scores_of_positions_restarted_a_document():
    """The program rotates by a position's place in the row, the
    reference by its place in its document: inside a document the two
    differ by a constant, so the attention's result is the same up to
    rounding, and the mixer is the reference's."""
    cfg = dataclasses.replace(TOY, mixers=("attention",), ffns=("dense",))
    p = one_layer(cfg, seed=4)
    segment = jnp.asarray(batch_of([[20, 45], [7]], 64)["segment"])
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 64, cfg.hidden_size))
    restarted = jnp.stack([jnp.arange(64) - ref.document_starts(s)
                           for s in segment])

    @jax.jit
    def both(p, u):
        row = gc.attention(cfg, *gc.attention_operands(
            cfg, p, u, jnp.arange(64)), segment)
        own = jnp.stack([gc.attention(cfg, *gc.attention_operands(
            cfg, p, u[i:i + 1], restarted[i]), segment[i:i + 1])[0]
            for i in range(2)])
        unturned = gc.attention(cfg, *gc.attention_operands(
            cfg, p, u, jnp.zeros((64,), jnp.int32)), segment)
        plain = jnp.stack([ref.attention_mixer(
            p, u[i], segment[i], sizes_of(cfg)) for i in range(2)])
        return row, own, unturned, gc.attention_mixer(cfg, p, u,
                                                      segment), plain

    with jax.default_matmul_precision("highest"):
        row, own, unturned, mixer, plain = both(p, u)
    assert distance(row, own) < RTOL
    assert distance(mixer, plain) < RTOL
    # the rotation is there: without it the result is another
    assert distance(unturned, row) > 1e-2


def test_queries_and_keys_are_normed_a_head_before_they_turn():
    cfg = dataclasses.replace(TOY, mixers=("attention",), ffns=("dense",))
    p = one_layer(cfg, seed=6)
    u = jax.random.normal(jax.random.PRNGKey(7), (1, 16, cfg.hidden_size))
    q, k, _ = jax.jit(lambda p, u: gc.attention_operands(
        cfg, p, u, jnp.zeros((16,), jnp.int32)))(p, u)
    assert q.shape == (1, 16, 4, 8) and k.shape == (1, 16, 2, 8)
    for got, scale in ((q, p["q_norm"]), (k, p["k_norm"])):
        unscaled = np.asarray(got) / np.asarray(scale)
        rms = np.sqrt(np.mean(unscaled ** 2, axis=-1))
        assert np.allclose(rms, 1.0, atol=1e-3)


# -- the whole stack against the reference -----------------------------------

def test_loss_and_every_gradient_leaf_agree_with_the_reference():
    batch = batch_of(PACKINGS["starts_one_two_and_three_apart"], 64)
    params = params_of(TOY)
    with jax.default_matmul_precision("highest"):
        (loss, state), grads = jax.jit(jax.value_and_grad(
            lambda p: gc.next_token_loss(p, batch, TOY), has_aux=True))(
                params)
    ref_loss, ref_grads = ref.loss_and_gradient(params, batch, sizes_of(TOY))
    assert abs(float(loss) - ref_loss) < RTOL * ref_loss
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.any(np.asarray(w)) and not np.any(np.asarray(g))
            continue
        assert np.linalg.norm(np.asarray(w)) > 0, name
        assert distance(g, w) < RTOL, name
    rows = np.asarray(state["moe_rows_per_expert"])
    assert rows.shape == (4, 4) and 0 < rows.sum() < 4 * 128 * 4


def test_the_routing_divides_by_the_sum_plus_the_familys_epsilon():
    """Scores of sigmoid(-20) (2e-9 each) make ``sum + 1e-6`` a hundredth
    of the bare sum's weights: the program's layer is the reference's
    with the epsilon, and without it (the default, what the Kimi Linear
    and Nemotron layers take) another."""
    cfg = dataclasses.replace(TOY, mixers=("conv",), ffns=("experts",))
    p = one_layer(cfg, seed=8)
    u = jax.random.normal(jax.random.PRNGKey(9), (128, cfg.hidden_size))
    u = u.at[:, 0].set(1.0)
    p = {**p, "router": jnp.zeros_like(p["router"]).at[0].set(-20.0),
         "router_bias": jnp.zeros_like(p["router_bias"]).at[4:8].set(1.0)}
    experts = {name: p[name] for name in ("w_gate", "w_up", "w_down")}

    @jax.jit
    def layers(p, u):
        return [moe.dropless_topk_moe(
            u, p["router"], experts, top_k=4, num_experts=16,
            first_expert=4, renormalise=True,
            selection_bias=p["router_bias"], renormalise_eps=eps)[0]
            for eps in (1e-6, 0.0)] + [ref.expert_ffn(p, u, sizes_of(cfg))]

    with jax.default_matmul_precision("highest"):
        with_eps, bare, want = layers(p, u)
    assert distance(with_eps, want) < RTOL
    assert 0.005 < float(jnp.linalg.norm(with_eps)
                         / jnp.linalg.norm(bare)) < 0.012
    assert str(jax.make_jaxpr(lambda u: moe.dropless_topk_moe(
        u, p["router"], experts, top_k=4, num_experts=16, first_expert=4,
        renormalise=True, selection_bias=p["router_bias"])[0])(u)) == str(
            jax.make_jaxpr(lambda u: moe.dropless_topk_moe(
                u, p["router"], experts, top_k=4, num_experts=16,
                first_expert=4, renormalise=True,
                selection_bias=p["router_bias"], renormalise_eps=0.0)[0])(u))


def test_the_shares_add_up_to_the_whole_layer():
    """A whole layer from its shares: the mixer once, then the routed
    parts that four chips compute, each holding four of the sixteen
    experts and seeing the same tokens, equal the uncut reference's
    layer (every expert held); one chip's share is the reference's for
    that share.  (The dense layers, which no chip cuts, are the
    reference's in the whole model's test.)"""
    cfg = dataclasses.replace(
        TOY, mixers=("conv",), ffns=("experts",), experts_held=16,
        first_expert=0)
    p = one_layer(cfg, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 128, cfg.hidden_size))
    segment = jnp.asarray(batch_of([[40, 90]], 128)["segment"])
    one = dataclasses.replace(cfg, experts_held=4, first_expert=4)
    held_names = ("w_gate", "w_up", "w_down")

    @jax.jit
    def everything(p, x):
        whole = ref.layer("conv", "experts", p, x[0], segment[0],
                          sizes_of(cfg))
        mixed = gc.mixer_half(cfg, "conv", p, x, segment)     # counted once
        u = gc.rms_norm(mixed, p["norm2"], cfg.rms_norm_eps)[0]
        shares, rows = [], []
        for first in range(0, 16, 4):
            held = slice(first, first + 4)
            y, routing = moe.dropless_topk_moe(
                u, p["router"], {name: p[name][held] for name in held_names},
                top_k=cfg.top_k, num_experts=16, first_expert=first,
                renormalise=True, selection_bias=p["router_bias"],
                scale=cfg.routed_scaling_factor,
                renormalise_eps=cfg.renormalise_eps)
            shares.append(y)
            rows.append(routing["rows_per_expert"].sum())
        p_one = {**p, **{name: p[name][4:8] for name in held_names}}
        want = ref.layer("conv", "experts", p_one, x[0], segment[0],
                         sizes_of(one))
        got = gc.layer(one, "conv", "experts", p_one, x, segment)[0]
        return whole, mixed[0], shares, rows, want, got[0]

    with jax.default_matmul_precision("highest"):
        whole, mixed, shares, rows, want, got = everything(p, x)
    rows = [int(r) for r in rows]
    assert sum(rows) == 128 * cfg.top_k      # every choice is some chip's
    assert all(0 < r < 128 * cfg.top_k for r in rows)
    assert distance(mixed + sum(shares), whole) < RTOL
    # one chip's share is what the program's layer and the reference
    # give for that share, and it is not the whole
    assert distance(mixed + shares[1], want) < RTOL
    assert distance(got, want) < RTOL
    assert distance(want, whole) > 0.01


def test_neighbours_of_one_kind_are_one_run():
    assert gc.layer_groups(TOY.mixers, TOY.ffns) == [
        ("conv", "dense", 2), ("attention", "experts", 1),
        ("conv", "experts", 3)]
    assert gc.layer_groups(TOY.mixers, TOY.ffns) == ref.layer_groups(
        sizes_of(TOY))
    with pytest.raises(ValueError, match="a layer is one of"):
        gc.layer_groups(("conv", "mamba"), ("dense", "dense"))
    with pytest.raises(ValueError, match="names a mixer and"):
        gc.layer_groups(("conv", "attention"), ("dense",))


def test_the_counters_tell_the_convolution_the_rule_and_the_paths():
    before = {
        "conv": counter("hvtpu_short_conv_calls_total", path="xla"),
        "rule": counter("hvtpu_moe_router_total", rule="sigmoid_bias"),
        "form": counter("hvtpu_moe_experts_form_total", form="gated"),
        "path": counter("hvtpu_moe_products_total", path="ragged_dot"),
        "attention": counter("hvtpu_attention_calls_total", path="xla")}
    batch = batch_of([[20]], 64)
    jax.jit(lambda p: gc.next_token_loss(p, batch, TOY)).lower(
        params_of(TOY))
    # two runs of convolution layers, two of expert layers: a call site
    # a run and a trace
    assert counter("hvtpu_short_conv_calls_total",
                   path="xla") == before["conv"] + 2
    assert metrics.REGISTRY.gauge("hvtpu_short_conv_taps").value() == 3.0
    assert counter("hvtpu_moe_router_total",
                   rule="sigmoid_bias") == before["rule"] + 2
    assert counter("hvtpu_moe_experts_form_total",
                   form="gated") == before["form"] + 2
    assert counter("hvtpu_moe_products_total",
                   path="ragged_dot") == before["path"] + 2
    assert counter("hvtpu_attention_calls_total",
                   path="xla") == before["attention"] + 1


def test_the_start_is_the_configurations():
    cfg = dataclasses.replace(TOY, hidden_size=64, head_dim=16)
    params = jax.jit(lambda key: gc.init_params(key, cfg))(
        jax.random.PRNGKey(0))
    first, attention = params["layers"][0], params["layers"][1]
    assert abs(float(jnp.std(first["in_proj"])) - 0.02) < 2e-3
    assert first["conv_w"].shape == (2, 3, 64)
    assert float(jnp.abs(first["conv_w"]).max()) <= 3 ** -0.5
    assert not np.any(attention["router_bias"])
    assert np.all(np.asarray(attention["q_norm"]) == 1.0)
    assert params["head"].shape == (cfg.hidden_size, cfg.vocab_size)


def test_the_references_blocks_give_the_same_numbers():
    """``query_block`` and ``recompute_layers`` are how the reference
    fits a chip at the published widths; they change no number by more
    than a reordering of f32 sums."""
    cfg = dataclasses.replace(TOY, mixers=("attention",),
                              ffns=("experts",))
    batch = batch_of(PACKINGS["starts_one_two_and_three_apart"], 64)
    params = params_of(cfg)
    whole_loss, whole = ref.loss_and_gradient(params, batch, sizes_of(cfg))
    loss, grads = ref.loss_and_gradient(params, batch, sizes_of(
        cfg, query_block=16, recompute_layers=True))
    assert abs(loss - whole_loss) < 1e-6 * whole_loss
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(whole)):
        assert np.linalg.norm(w) == 0 or distance(g, w) < 1e-5
    with pytest.raises(ValueError, match="no whole blocks"):
        ref.loss_and_gradient(params, batch, sizes_of(cfg, query_block=48))


# -- the configuration's file and its builder ---------------------------------

CONFIG = os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b-6of24.json")
CELL = "lfm2-8b-a1b-6of24-t8k-b2"


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


def test_the_file_holds_every_width_as_published(config):
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 8, "intermediate_size": 7168,
        "moe_intermediate_size": 1792, "num_experts_per_tok": 4,
        "conv_L_cache": 3, "conv_bias": False, "num_dense_layers": 2,
        "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": 1, "rope_theta": 1000000,
        "norm_eps": 1e-5, "model_type": "lfm2_moe"}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv"]
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    whole = config["published"]
    assert (whole["num_hidden_layers"], whole["num_experts"],
            whole["vocab_size"]) == (24, 32, 65536)
    assert whole["layer_types"][:6] == config["layer_types"]
    assert whole["layer_types"].count("conv") == 18
    place = config["deployment"]
    assert place["expert_parallel_chips"] * config["num_experts"] == 32
    assert place["vocabulary_shards"] * config["vocab_size"] == 65536
    assert place["first_vocabulary_row"] == (
        place["vocabulary_shard"] * config["vocab_size"])
    tokens = 2 * config["sequence_length"]
    assert place["rows_an_expert_a_step_here"] == tokens * 4 // 32 == 2048
    assert place["rows_an_expert_a_step_in_the_deployment"] == (
        4 * place["rows_an_expert_a_step_here"])
    for key in ("assumed", "rehearsal", "parameters", "source", "why"):
        assert key in config
    assert len(config["why"]) <= 200


# LiquidAI/LFM2-8B-A1B's config.json, the keys that say something about
# its shape, as published
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention"]
    + ["conv", "conv", "conv", "full_attention"] * 4
    + ["conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}


def test_the_file_agrees_with_the_published_config(config):
    """Every key of the published ``config.json`` under the same key with
    the same value, but the keys of ``reduced``, whose published values
    are under ``published``."""
    assert config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert len(PUBLISHED["layer_types"]) == 24
    differ = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differ == set(config["reduced"])
    assert {k: PUBLISHED[k] for k in config["reduced"]} == {
        k: config["published"][k] for k in config["reduced"]}


def test_the_traffic_file_repeats_the_configurations_lengths(config):
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "t8k-b2-packed.json")) as f:
        traffic = json.load(f)
    assert traffic["sequence_length"] == config["sequence_length"]
    assert traffic["document_length"] == config["document_length"]


def test_the_benchmark_names_the_cell_and_its_two_metrics(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        config["name"], "t8k-b2-packed", 1)
    mine = {m["name"]: m["layer"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == {"short_conv_ms_per_step": "conv",
                    "short_conv_roofline": "kernels"}


@pytest.mark.parametrize("size", ["published", "rehearsal"])
def test_the_builder_counts_the_parameters_the_tree_holds(config, size):
    from benchmark.builders import lfm2_moe_lm

    if size == "rehearsal":
        config = {**config, **config["rehearsal"]}
    cfg = lfm2_moe_lm.model_config(config)
    shapes = jax.eval_shape(
        lambda key: gc.init_params(key, cfg), jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes))
    assert held == lfm2_moe_lm.parameters(config) == config["parameters"]
    assert cfg.mixers == ("conv", "conv", "attention", "conv", "conv",
                          "conv")
    assert cfg.ffns == ("dense",) * 2 + ("experts",) * 4
    assert (cfg.renormalise_eps, cfg.experts_held < cfg.num_experts) == (
        1e-6, True)
    if size == "published":
        assert held == 602_202_368
        assert (cfg.head_dim, cfg.first_expert, cfg.num_experts) == (
            64, 8, 32)
        conv, attention = shapes["layers"][0], shapes["layers"][1]
        assert sum(int(np.prod(conv[n].shape[1:])) for n in (
            "in_proj", "conv_w", "out_proj")) == 16_783_360
        assert sum(int(np.prod(attention[n].shape[1:])) for n in (
            "wq", "wk", "wv", "wo", "q_norm", "k_norm")) == 10_485_888
        assert shapes["layers"][2]["w_up"].shape == (3, 8, 2048, 1792)


def test_the_published_expert_layer_takes_the_grouped_kernels(
        config, monkeypatch):
    """At the published widths, 2048 x 1792 over 16,384 tokens top-4 of
    the 8 held, the experts' products are the grouped kernels where
    kernels compile (here the interpreter), the backward one walking the
    inner width in blocks with a group's three weights twice over and
    their f32 sums once in VMEM (84 MiB, the most the rule allows):
    ``hvtpu_moe_products_total{path="grouped"}`` and
    ``hvtpu_moe_backward_kernel_total{kernel="blocked"}`` count the
    layer when a step of it is traced, forward and backward."""
    from benchmark.builders import lfm2_moe_lm
    from horovod_tpu.ops import grouped_ffn

    monkeypatch.setenv("HVTPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)
    cfg = lfm2_moe_lm.model_config(config)
    shapes = jax.eval_shape(
        lambda key: gc.init_params(key, cfg), jax.random.PRNGKey(0))
    p = jax.tree_util.tree_map(      # one layer of a stacked run
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        shapes["layers"][2])
    x = jax.ShapeDtypeStruct((2, 8192, cfg.hidden_size),
                             jnp.dtype(cfg.compute_dtype))
    assert p["w_gate"].shape == (8, 2048, 1792)
    assert grouped_ffn._blocked(cfg.compute_dtype, 2048, 1792, 3)
    before = {"grouped": counter("hvtpu_moe_products_total", path="grouped"),
              "blocked": counter("hvtpu_moe_backward_kernel_total",
                                 kernel="blocked")}
    grads = jax.eval_shape(jax.grad(lambda p, x: jnp.sum(
        gc.expert_ffn(cfg, p, x)[0].astype(jnp.float32))), p, x)
    assert grads["w_down"].shape == (8, 1792, 2048)
    assert counter("hvtpu_moe_products_total",
                   path="grouped") == before["grouped"] + 1
    assert counter("hvtpu_moe_backward_kernel_total",
                   kernel="blocked") == before["blocked"] + 1


@pytest.mark.parametrize("key, value", [
    ("conv_bias", True), ("use_expert_bias", False),
    ("norm_topk_prob", False), ("tie_word_embeddings", True),
    ("model_type", "lfm2")])
def test_the_builder_refuses_what_the_model_does_not_build(config, key,
                                                           value):
    from benchmark.builders import lfm2_moe_lm

    with pytest.raises(ValueError, match="models.gated_conv builds"):
        lfm2_moe_lm.model_config({**config, key: value})


def test_the_builder_holds_the_layer_list_to_the_depth(config):
    from benchmark.builders import lfm2_moe_lm

    with pytest.raises(ValueError, match="layer_types names"):
        lfm2_moe_lm.layer_kinds({**config, "num_hidden_layers": 7})
    with pytest.raises(ValueError, match="layer_types names"):
        lfm2_moe_lm.layer_kinds({**config, "layer_types": [
            "conv"] * 5 + ["sliding_attention"]})
    with pytest.raises(ValueError, match="expert shard's first"):
        lfm2_moe_lm.model_config({**config, "deployment": {
            **config["deployment"], "first_expert": 4}})


def test_the_required_work_is_the_issues_arithmetic(config):
    from benchmark import flops_lfm2_moe_lm as flops

    macs = flops.forward_macs_per_row(config, 0.0)
    per_token = {k: v / 8192 for k, v in macs.items()}
    total = sum(per_token.values())
    assert round(total / 1e6) == 260
    shares = {k: round(100 * v / total, 1) for k, v in per_token.items()}
    assert shares["conv_projections"] == 32.2
    assert shares["dense_mlp"] == 33.8
    assert shares["routed_experts"] == 16.9
    assert shares["head"] == 12.9
    assert shares["attention_projections"] == 4.0
    assert flops.held_expert_rows_per_token(config) == 1.0
    tokens = 16384
    assert flops.short_conv_train_flops_per_step(config, tokens) == (
        (6 * 3 + 6) * 2048 * tokens * 5)
    assert flops.short_conv_train_bytes_per_step(config, tokens) == (
        11 * 2 * 2048 * tokens * 5)
    pairs = 1e7
    assert flops.forward_macs_per_row(config, pairs)["attention"] == (
        32 * pairs * 2 * 64)
