"""``models/hybrid_moe.py`` (one mixer a layer by a pattern of ``M``,
``*`` and ``E``: Mamba-2 with B/C groups, attention without positions,
sigmoid-routed ungated experts with a shared expert; packed documents)
against the plain reference ``benchmark/reference/nemotron_h.py``
(float32, the recurrence one position at a time, a dense mask, every
expert over every token with a 0/1 choice), on seeded random weights at
toy size on the CPU.  Both sides compute in float32 at ``highest``; the
stated tolerance is what two orders of summing the same f32 products
leave."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import nemotron_h as ref  # noqa: E402
from horovod_tpu.models import hybrid_moe as hm  # noqa: E402
from horovod_tpu.obs import metrics  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# relative L2 distance of a loss or a gradient leaf, f32 against f32
RTOL = 2e-5

# the pattern's order of the three kinds, as the published one starts:
# M E M E M * E M E, at toy size 2 groups and 4 of 16 experts held
TOY = hm.HybridMoEConfig(
    vocab_size=96, hidden_size=32, pattern="MEM*E", num_heads=4,
    num_kv_heads=2, head_dim=8, ssm_heads=4, ssm_head_dim=16, ssm_state=8,
    ssm_groups=2, conv_width=4, chunk_size=16, expert_width=24,
    shared_width=48, num_experts=16, experts_held=4, first_expert=8,
    top_k=3, norm_topk_prob=True, routed_scaling_factor=2.5,
    rms_norm_eps=1e-5, rescale_depth=52, compute_dtype="float32")


def sizes_of(cfg, **blocks):
    return ref.Sizes(
        pattern=cfg.pattern, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, ssm_heads=cfg.ssm_heads,
        ssm_groups=cfg.ssm_groups, first_expert=cfg.first_expert,
        top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rms_norm_eps=cfg.rms_norm_eps, **blocks)


def lively(params, seed=0):
    """``init_params`` with the first projection ten times larger (at
    hidden 32 normal(0.02) leaves ``x``, ``B`` and ``C`` so small that
    the recurrence adds nothing a comparison could see), the residual
    branches' last matrices as large as the others, and a selection bias
    that is not zero, so that it changes choices."""
    for i, group in enumerate(params["layers"]):
        if "in_proj" in group:
            group["in_proj"] = 10.0 * group["in_proj"]
        for name in ("out_proj", "wo", "w_down", "shared_down"):
            if name in group:
                group[name] = 52 ** 0.5 * group[name]
        if "router_bias" in group:
            group["router"] = 20.0 * group["router"]
            group["router_bias"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(seed + i), group["router_bias"].shape)
    return params


def params_of(cfg, seed=0):
    return lively(hm.init_params(jax.random.PRNGKey(seed), cfg), seed)


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
            lambda p: hm.next_token_loss(p, batch, cfg), has_aux=True))(
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
    "documents_shorter_than_the_convolution": [[1, 3, 4, 7], [61, 63]],
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
    assert rows.shape == (2, 4) and 0 < rows.sum() < 2 * 128 * 3


@pytest.mark.parametrize("pattern", [
    "MEMEM*EME", "*ME", "EEM", "MM**E", "E", "M*"])
def test_the_layers_follow_the_pattern(pattern):
    cfg = dataclasses.replace(TOY, pattern=pattern)
    params = params_of(cfg, seed=3)
    leaves = {"M": 9, "*": 5, "E": 7}
    assert [len(jax.tree_util.tree_leaves(g)) for g in params["layers"]] == [
        leaves[letter] for letter, _ in hm.layer_groups(pattern)]
    assert [jax.tree_util.tree_leaves(g)[0].shape[0]
            for g in params["layers"]] == [
                n for _, n in hm.layer_groups(pattern)]
    batch = batch_of([[20, 50]], 64, seed=3)
    (loss, state), grads = system(cfg, params, batch)
    ref_loss, ref_grads = ref.loss_and_gradient(params, batch, sizes_of(cfg))
    assert abs(float(loss) - float(ref_loss)) < RTOL * float(ref_loss)
    assert_trees_close(grads, ref_grads)
    assert state["moe_rows_per_expert"].shape == (pattern.count("E"), 4)


def test_a_letter_that_is_no_kind_is_refused():
    with pytest.raises(ValueError, match="a layer is one of"):
        hm.layer_groups("ME-M")
    assert hm.layer_groups("MEMEM*EME") == [
        ("M", 1), ("E", 1), ("M", 1), ("E", 1), ("M", 1), ("*", 1),
        ("E", 1), ("M", 1), ("E", 1)]
    assert hm.layer_groups("MMEE*") == [("M", 2), ("E", 2), ("*", 1)]


def test_a_document_does_not_see_the_one_before_it():
    """Tokens of the second document changed: the first document's
    hidden states do not move (state, convolution and attention stop at
    the start; the expert layers work a token at a time)."""
    batch = batch_of([[24]], 64)
    params = params_of(TOY)
    other = dict(batch, x=batch["x"].copy())
    other["x"][0, 24:] = (other["x"][0, 24:] + 1) % 96
    with jax.default_matmul_precision("highest"):
        one = hm.hidden_states(params, batch["x"], TOY, batch["segment"])[0]
        two = hm.hidden_states(params, other["x"], TOY, other["segment"])[0]
    assert np.array_equal(one[0, :24], two[0, :24])
    assert distance(two[0, 24:], one[0, 24:]) > 1e-3


def test_the_embedding_is_plain_and_the_head_is_untied():
    params = params_of(TOY)
    assert params["head"].shape == (TOY.hidden_size, TOY.vocab_size)
    batch = batch_of([[20]], 64)
    (_, _), grads = system(TOY, params, batch)
    assert distance(grads["head"].T, grads["embed"]) > 0.5
    no_layers = dataclasses.replace(TOY, pattern="")
    hidden, rows = hm.hidden_states(
        {**params, "layers": []}, batch["x"], no_layers)
    assert np.array_equal(hidden, params["embed"][batch["x"]])
    assert rows.shape == (0, 4)


def test_the_start_is_the_configurations():
    """The residual branches' last matrices are smaller by sqrt(depth);
    ``dt_bias`` is the inverse softplus of a delta between the
    configuration's limits; the selection bias starts at zero."""
    cfg = dataclasses.replace(TOY, hidden_size=256, pattern="M*E")
    params = hm.init_params(jax.random.PRNGKey(0), cfg)
    mamba, attention, experts = params["layers"]
    for small, plain in ((mamba["out_proj"], mamba["in_proj"]),
                         (attention["wo"], attention["wq"]),
                         (experts["w_down"], experts["w_up"]),
                         (experts["shared_down"], experts["shared_up"])):
        assert abs(float(jnp.std(small)) * 52 ** 0.5 - 0.02) < 2e-3
        assert abs(float(jnp.std(plain)) - 0.02) < 2e-3
    delta = jax.nn.softplus(mamba["dt_bias"])
    assert float(delta.min()) >= 1e-3 * 0.999
    assert float(delta.max()) <= 1e-1 * 1.001
    assert not np.any(experts["router_bias"])
    unscaled = hm.init_params(
        jax.random.PRNGKey(0), dataclasses.replace(cfg, rescale_depth=0))
    assert abs(float(jnp.std(unscaled["layers"][0]["out_proj"])) - 0.02
               ) < 2e-3


# -- the shares add up --------------------------------------------------------

def test_the_shares_add_up_to_the_whole_layer():
    """The routed parts that four chips compute, each holding four of
    the sixteen experts and seeing the same tokens, plus the shared
    expert once, equal the uncut reference's whole layer (every expert
    held: ``benchmark/reference/nemotron_h.expert_mixer``)."""
    cfg = dataclasses.replace(TOY, pattern="E", experts_held=16,
                              first_expert=0)
    p = jax.tree_util.tree_map(
        lambda a: a[0], params_of(cfg, seed=5)["layers"][0])
    u = jax.random.normal(jax.random.PRNGKey(6), (128, cfg.hidden_size))
    whole = ref.expert_mixer(p, u, None, sizes_of(cfg))
    with jax.default_matmul_precision("highest"):
        shares, rows = [], []
        for first in range(0, 16, 4):
            held = slice(first, first + 4)
            y, routing = moe.dropless_topk_moe(
                u, p["router"],
                {"w_up": p["w_up"][held], "w_down": p["w_down"][held]},
                top_k=cfg.top_k, num_experts=16, first_expert=first,
                renormalise=True, selection_bias=p["router_bias"],
                scale=cfg.routed_scaling_factor)
            shares.append(y)
            rows.append(int(routing["rows_per_expert"].sum()))
        shared = hm.relu2_expert(u, p["shared_up"], p["shared_down"])
    assert sum(rows) == 128 * cfg.top_k      # every choice is some chip's
    assert all(0 < r < 128 * cfg.top_k for r in rows)
    assert distance(sum(shares) + shared, whole) < RTOL
    # and one chip's share is what the reference gives for that share
    one = ref.expert_mixer(
        {**p, "w_up": p["w_up"][4:8], "w_down": p["w_down"][4:8]}, u, None,
        dataclasses.replace(sizes_of(cfg), first_expert=4))
    assert distance(shares[1] + shared, one) < RTOL
    assert distance(one, whole) > 0.1


def test_the_expert_layer_is_the_routed_share_and_the_shared_expert():
    cfg = dataclasses.replace(TOY, pattern="E")
    params = params_of(cfg, seed=2)
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"][0])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        got, routing = hm.expert_layer(cfg, p, x)
    want = jnp.stack([ref.layer("E", p, x[i], None, sizes_of(cfg))
                      for i in range(2)])
    assert distance(got, want) < RTOL
    rows = routing["rows_per_expert"]
    assert rows.shape == (4,) and int(rows.sum()) > 0
    assert routing["experts"].shape == (128, cfg.top_k)
    # the program's choices are the reference's, token for token
    weights = ref.routing_weights(
        p, ref.rms_norm(x.reshape(128, -1), p["norm"], cfg.rms_norm_eps),
        sizes_of(cfg))
    chosen = np.zeros(weights.shape, bool)
    np.put_along_axis(chosen, np.asarray(routing["experts"]), True, axis=1)
    assert np.array_equal(chosen, np.asarray(weights) > 0)


# -- what the program counts --------------------------------------------------

def counter(name, **labels):
    return metrics.REGISTRY.counter(name).value(**labels)


def test_the_counters_tell_the_rule_the_form_and_the_groups():
    before = {
        "rule": counter("hvtpu_moe_router_total", rule="sigmoid_bias"),
        "form": counter("hvtpu_moe_experts_form_total", form="relu2"),
        "path": counter("hvtpu_moe_products_total", path="ragged_dot"),
        "softmax": counter("hvtpu_moe_router_total", rule="softmax"),
        "gated": counter("hvtpu_moe_experts_form_total", form="gated")}
    batch = batch_of([[20]], 64)
    jax.jit(lambda p: hm.next_token_loss(p, batch, TOY)).lower(
        params_of(TOY))
    # two expert layers in two runs of one: two call sites a trace
    assert counter("hvtpu_moe_router_total",
                   rule="sigmoid_bias") == before["rule"] + 2
    assert counter("hvtpu_moe_experts_form_total",
                   form="relu2") == before["form"] + 2
    assert counter("hvtpu_moe_products_total",
                   path="ragged_dot") == before["path"] + 2
    assert counter("hvtpu_moe_router_total",
                   rule="softmax") == before["softmax"]
    assert counter("hvtpu_moe_experts_form_total",
                   form="gated") == before["gated"]
    assert metrics.REGISTRY.gauge("hvtpu_ssm_groups").value() == 2.0


def test_the_routing_of_a_step_feeds_note_moe_routing():
    batch = batch_of([[20]], 64)
    (_, state), _ = system(TOY, params_of(TOY), batch)
    metrics.note_moe_routing(
        state["moe_rows_per_expert"],
        buffer_rows=moe.buffer_rows(64, TOY.top_k, TOY.experts_held))
    assert metrics.REGISTRY.gauge("hvtpu_moe_rows_per_expert").value() >= 1.0
    assert 0 < metrics.REGISTRY.gauge(
        "hvtpu_moe_buffer_live_share").value() <= 1.0


# -- the configuration's file and its builder ---------------------------------

CONFIG = os.path.join(
    ROOT, "benchmark", "configs", "nemotron-3-nano-30b-a3b-9of52.json")


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


def test_the_file_holds_every_width_as_published(config):
    published = {
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "mlp_hidden_act": "relu2", "tie_word_embeddings": False}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    full = config["published"]["hybrid_override_pattern"]
    assert (len(full), full.count("M"), full.count("E"),
            full.count("*")) == (52, 23, 23, 6)
    assert full.startswith(config["hybrid_override_pattern"])
    assert config["hybrid_override_pattern"] == "MEMEM*EME"
    assert config["num_hidden_layers"] == 9
    place = config["deployment"]
    assert place["expert_parallel_chips"] * config["n_routed_experts"] == 128
    assert place["vocabulary_shards"] * config["vocab_size"] == 131072
    assert place["first_vocabulary_row"] == (
        place["vocabulary_shard"] * config["vocab_size"])
    tokens = 2 * config["sequence_length"]
    assert place["rows_an_expert_a_step_here"] == tokens * 6 // 128
    assert place["rows_an_expert_a_step_in_the_deployment"] == (
        16 * place["rows_an_expert_a_step_here"])
    for key in ("assumed", "rehearsal", "parameters", "source"):
        assert key in config


def test_the_file_agrees_with_the_catalog(config):
    """Every number of the catalog row's ``config`` under the same key,
    but the keys of ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert config["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differ == set(config["reduced"])


def test_the_traffic_file_repeats_the_configurations_lengths(config):
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "t8k-b2-packed.json")) as f:
        traffic = json.load(f)
    assert traffic["sequence_length"] == config["sequence_length"]
    assert traffic["document_length"] == config["document_length"]


@pytest.mark.parametrize("size", ["published", "rehearsal"])
def test_the_builder_counts_the_parameters_the_tree_holds(config, size):
    from benchmark.builders import hybrid_moe_lm

    if size == "rehearsal":
        config = {**config, **config["rehearsal"]}
    cfg = hybrid_moe_lm.model_config(config)
    shapes = jax.eval_shape(
        lambda key: hm.init_params(key, cfg), jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes))
    assert held == hybrid_moe_lm.parameters(config) == config["parameters"]
    if size == "published":
        assert held == 666_963_456
        assert (cfg.ssm_groups, cfg.conv_channels, cfg.ssm_inner) == (
            8, 6144, 4096)
    else:
        # three kinds of layer, at least two groups, fewer experts held
        # than routed over
        assert set(cfg.pattern) == {"M", "*", "E"}
        assert cfg.ssm_groups >= 2 and cfg.experts_held < cfg.num_experts


@pytest.mark.parametrize("key, value", [
    ("n_group", 2), ("topk_group", 2), ("mlp_hidden_act", "silu"),
    ("tie_word_embeddings", True), ("n_shared_experts", 2)])
def test_the_builder_refuses_what_the_model_does_not_build(config, key,
                                                           value):
    from benchmark.builders import hybrid_moe_lm

    with pytest.raises(ValueError, match="models.hybrid_moe builds"):
        hybrid_moe_lm.model_config({**config, key: value})


def test_the_required_work_is_the_issues_arithmetic(config):
    from benchmark import flops_hybrid_moe_lm as flops

    pairs = 10_776_285.6        # a head a row, at the law's mean
    macs = flops.forward_macs_per_row(config, pairs)
    per_token = {k: 2 * v / 8192 / 1e6 for k, v in macs.items()}
    assert round(per_token["ssm_projections"] / 4, 1) == 77.4
    assert round(per_token["shared_experts"] / 4, 1) == 39.9
    assert round(per_token["head"], 1) == 88.1
    assert round(per_token["attention_projections"], 1) == 46.8
    assert round(per_token["routed_experts"], 1) == 29.9
    assert flops.held_expert_rows_per_token(config) == 0.375
    total = flops.train_flops_per_sample(config, pairs)
    assert 2.0e9 < total < 2.03e9
    assert flops.expert_train_flops_per_step(config, 2) == pytest.approx(
        6 * 2 * 16384 * 0.375 * 2688 * 1856 * 4)
    scan = flops.scan_macs_per_token(config)
    assert scan == 128 * 128 * 8 + 128 * 4096 + 2 * 128 * 4096
    assert flops.scan_train_bytes_per_step(config, 16384) == (
        5 * 2 * 4096 + 3 * (2 * 2048 + 4 * 64)) * 16384 * 4


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
    assert_trees_close(grads, whole, rtol=1e-6)
    with pytest.raises(ValueError, match="no whole blocks"):
        ref.loss_and_gradient(params, batch, sizes_of(TOY, query_block=48))
