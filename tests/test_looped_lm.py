"""``models/looped.py`` (one stack of layers walked ``total_ut_steps``
times with shared weights, an exit after every pass, the expected loss
over the exits on packed documents) against the plain reference
``benchmark/reference/ouro_looped.py`` (float32, Python loops over
passes and layers, a dense mask) and against an unrolled stack that
holds a copy of the weights for every use, on seeded random weights at
toy size on the CPU.  In float32 both sides compute at ``highest``; the
stated tolerance is what two orders of summing the same f32 products
leave."""

import dataclasses
import json
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import flops_looped_lm as flops  # noqa: E402
from benchmark.reference import ouro_looped as ref  # noqa: E402
from horovod_tpu.models import looped  # noqa: E402
from horovod_tpu.models.layers import rms_norm  # noqa: E402
from horovod_tpu.obs import metrics  # noqa: E402

# relative L2 distance of a loss or a gradient leaf, f32 against f32
RTOL = 1e-5
# and with bf16 products against the f32 reference: activations and
# weights rounded to 8 bits (0.4 %) through 8 layer uses; read 0.008 to
# 0.021 on these weights
BF16_BAND = 0.06

TOY = looped.LoopedConfig(
    vocab_size=96, hidden_size=32, num_layers=2, mlp_width=48, num_heads=4,
    num_kv_heads=4, head_dim=8, rope_theta=1e6, rms_norm_eps=1e-6,
    total_ut_steps=4, entropy_weight=0.1, compute_dtype="float32")


def sizes_of(cfg, **blocks):
    return ref.Sizes(
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        total_ut_steps=cfg.total_ut_steps,
        entropy_weight=cfg.entropy_weight, **blocks)


def params_of(cfg, seed=0):
    """``init_params`` with a gate ten times larger, so that the exits'
    probabilities differ from position to position."""
    params = looped.init_params(jax.random.PRNGKey(seed), cfg)
    return {**params, "gate_w": 10.0 * params["gate_w"]}


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
        (loss, _), grads = jax.jit(jax.value_and_grad(
            lambda p: looped.expected_exit_loss(p, batch, cfg),
            has_aux=True))(params)
    return loss, grads


def assert_trees_close(got, want, rtol=RTOL):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert np.linalg.norm(np.asarray(w)) > 0, jax.tree_util.keystr(path)
        assert distance(g, w) < rtol, jax.tree_util.keystr(path)


def exits_of(cfg, params, batch):
    """``CE_t`` ``[passes, B, T]`` of every exit, a token each."""
    label = jnp.roll(batch["x"], -1, axis=1)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda x: jax.vmap(
            lambda h: looped.exit_cross_entropy(params["head"], h, label))(
                looped.hidden_states_by_pass(
                    params, x, cfg, batch["segment"])))(batch["x"]))


# -- the whole model against the reference -----------------------------------

PACKINGS = {
    "three_documents_and_two": [[20, 50], [33]],
    "one_document_a_row": [[], []],
    "documents_of_one_and_two_tokens": [[1, 3, 4, 7], [61, 63]],
}


@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_loss_and_every_gradient_leaf_agree_with_the_reference(packing):
    batch = batch_of(PACKINGS[packing], 64)
    params = params_of(TOY)
    loss, grads = system(TOY, params, batch)
    ref_loss, ref_grads = ref.loss_and_gradient(params, batch, sizes_of(TOY))
    assert abs(float(loss) - ref_loss) < RTOL * ref_loss
    assert_trees_close(grads, ref_grads)


def test_with_bf16_products_it_stays_in_a_band_around_the_reference():
    cfg = dataclasses.replace(TOY, compute_dtype="bfloat16")
    batch = batch_of(PACKINGS["three_documents_and_two"], 64)
    params = params_of(cfg)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: looped.expected_exit_loss(p, batch, cfg),
        has_aux=True))(params)
    ref_loss, ref_grads = ref.loss_and_gradient(params, batch, sizes_of(cfg))
    assert abs(float(loss) - ref_loss) < 5e-4 * ref_loss
    assert_trees_close(grads, ref_grads, rtol=BF16_BAND)
    assert all(g.dtype == jnp.float32
               for g in jax.tree_util.tree_leaves(grads))


def test_the_reference_in_blocks_is_the_reference():
    batch = batch_of([[20, 50]], 64)
    params = params_of(TOY)
    whole = ref.loss_and_gradient(params, batch, sizes_of(TOY))
    blocks = ref.loss_and_gradient(
        params, batch, sizes_of(TOY, query_block=16))
    assert abs(whole[0] - blocks[0]) < 1e-6 * whole[0]
    assert_trees_close(blocks[1], whole[1], rtol=2e-6)


def test_the_reference_shares_no_code_with_the_models():
    import ast

    with open(ref.__file__) as f:
        tree = ast.parse(f.read())
    imported = {(node.module or "") if isinstance(node, ast.ImportFrom)
                else alias.name
                for node in ast.walk(tree)
                if isinstance(node, (ast.Import, ast.ImportFrom))
                for alias in node.names}
    assert imported == {"__future__", "dataclasses", "typing", "jax",
                        "jax.numpy", "numpy"}


# -- the loop against an unrolled stack --------------------------------------

def unrolled(cfg, copies, params, ids, segment):
    """``h_t`` for every pass from a stack of ``passes x L`` layers that
    are run once each: ``copies`` holds the weights of every use,
    ``[passes * L, ...]`` a leaf."""
    positions = jnp.arange(ids.shape[1])
    h = jnp.take(params["embed"], ids, axis=0)
    out = []
    for use in range(cfg.total_ut_steps * cfg.num_layers):
        p = jax.tree_util.tree_map(lambda a: a[use], copies)
        h = looped.layer(cfg, p, h, segment, positions)
        if (use + 1) % cfg.num_layers == 0:
            h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
            out.append(h)
    return jnp.stack(out)


def test_the_loop_is_an_unrolled_stack_that_holds_copies_of_the_weights():
    batch = batch_of([[20, 50], [33]], 64)
    ids, segment = batch["x"], jnp.asarray(batch["segment"])
    params = params_of(TOY)
    copies = jax.tree_util.tree_map(
        lambda a: jnp.tile(a, (TOY.total_ut_steps,) + (1,) * (a.ndim - 1)),
        params["layers"])
    target = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 64, 32))
    with jax.default_matmul_precision("highest"):
        in_a_loop, shared = jax.jit(jax.value_and_grad(
            lambda layers: jnp.sum(target * looped.hidden_states_by_pass(
                {**params, "layers": layers}, ids, TOY, segment)),
        ))(params["layers"])
        one_by_one, a_copy = jax.jit(jax.value_and_grad(
            lambda copies: jnp.sum(target * unrolled(
                TOY, copies, params, ids, segment))))(copies)
        assert distance(
            looped.hidden_states_by_pass(params, ids, TOY, segment),
            unrolled(TOY, copies, params, ids, segment)) < 1e-6
    assert abs(float(in_a_loop) - float(one_by_one)) < 1e-5 * abs(
        float(one_by_one))
    for name, g in shared.items():
        uses = np.asarray(a_copy[name]).reshape(
            TOY.total_ut_steps, TOY.num_layers, *g.shape[1:])
        # every use adds its part: none is a rounding of the sum
        assert all(np.linalg.norm(use) > 0.05 * np.linalg.norm(
            uses.sum(axis=0)) for use in uses), name
        assert distance(g, uses.sum(axis=0)) < RTOL, name


def test_one_pass_is_a_plain_decoder_under_the_next_token_loss():
    cfg = dataclasses.replace(TOY, total_ut_steps=1)
    batch = batch_of([[20, 50], [33]], 64)
    params = params_of(cfg)
    with jax.default_matmul_precision("highest"):
        loss, stats = looped.expected_exit_loss(params, batch, cfg)
        hidden = looped.hidden_states_by_pass(
            params, batch["x"], cfg, batch["segment"])
        assert hidden.shape == (1, 2, 64, 32)
        logp = jax.nn.log_softmax(hidden[0] @ params["head"])
    ce = -np.take_along_axis(
        np.asarray(logp), np.roll(batch["x"], -1, axis=1)[..., None],
        axis=-1)[..., 0]
    plain = np.sum(batch["w"] * ce) / np.sum(batch["w"])
    assert abs(float(loss) - plain) < 1e-6 * plain
    assert np.asarray(stats["loop_exit_mass"]) == pytest.approx([1.0])
    p, log_p = looped.exit_distribution(jnp.asarray([[0.3, -2.0, 11.0]]))
    assert np.array_equal(np.asarray(p), np.ones((1, 3)))
    assert np.array_equal(np.asarray(log_p), np.zeros((1, 3)))
    # its gate takes no part
    grads = jax.grad(
        lambda p: looped.expected_exit_loss(p, batch, cfg)[0])(params)
    assert float(jnp.abs(grads["gate_w"]).max()) == 0.0


# -- the exit distribution ---------------------------------------------------

def test_the_exit_distribution_is_the_closed_form_and_sums_to_one():
    logits = jax.random.normal(jax.random.PRNGKey(2), (4, 3, 50)) * 3.0
    p, log_p = looped.exit_distribution(logits)
    gate = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    stay = np.cumprod(1.0 - gate, axis=0)
    closed = np.stack([gate[0], gate[1] * stay[0], gate[2] * stay[1],
                       stay[2]])
    assert np.asarray(p) == pytest.approx(closed, rel=1e-5, abs=1e-9)
    assert np.asarray(jnp.sum(p, axis=0)) == pytest.approx(1.0, abs=1e-6)
    assert np.asarray(log_p) == pytest.approx(np.log(closed), rel=1e-5,
                                              abs=1e-6)
    # the reference multiplies 1 - lambda out in f32: 1e-7 of room
    assert np.asarray(p) == pytest.approx(np.asarray(
        ref.exit_distribution(jax.nn.sigmoid(logits))), rel=1e-5, abs=2e-7)
    # gates of zero: a half, a quarter, and the last two share the rest
    p, _ = looped.exit_distribution(jnp.zeros((4, 1)))
    assert np.asarray(p)[:, 0] == pytest.approx([0.5, 0.25, 0.125, 0.125])
    # gates so sure that a product underflows: no NaN, here or in the
    # entropy's gradient
    far = jnp.asarray([[200.0], [-200.0], [200.0], [0.0]])
    p, log_p = looped.exit_distribution(far)
    assert np.all(np.isfinite(np.asarray(log_p)))
    assert np.all(np.isfinite(np.asarray(jax.grad(lambda z: -jnp.sum(
        jnp.prod(jnp.stack(looped.exit_distribution(z)), axis=0)))(far))))


def test_the_entropys_gradient_pushes_the_exits_towards_uniform():
    def entropy(logits):
        p, log_p = looped.exit_distribution(logits)
        return -jnp.sum(p * log_p)

    # lambda = 1/4, 1/3, 1/2 is the uniform distribution: nothing to gain
    uniform = jnp.log(jnp.asarray([1 / 3, 1 / 2, 1.0, 7.0]))[:, None]
    assert float(entropy(uniform)) == pytest.approx(math.log(4), rel=1e-6)
    assert np.asarray(jax.grad(entropy)(uniform)) == pytest.approx(
        0.0, abs=1e-6)
    logits = jnp.zeros((4, 1))
    for _ in range(3):
        before = float(entropy(logits))
        step = jax.grad(entropy)(logits)
        assert float(step[-1, 0]) == 0.0      # the last gate takes no part
        logits = logits + 0.5 * step
        assert before < float(entropy(logits)) <= math.log(4)
    # and in the objective the entropy enters with -beta
    batch = batch_of([[20]], 64)
    params = params_of(TOY)
    more = dataclasses.replace(TOY, entropy_weight=0.3)
    with jax.default_matmul_precision("highest"):
        gates = jax.vmap(lambda h: looped.gate_logits(params, h))(
            looped.hidden_states_by_pass(
                params, batch["x"], TOY, batch["segment"]))
        p, log_p = looped.exit_distribution(gates)
        mean_entropy = float(jnp.sum(batch["w"] * -jnp.sum(
            p * log_p, axis=0)) / np.sum(batch["w"]))
        assert float(looped.expected_exit_loss(params, batch, TOY)[0]
                     - looped.expected_exit_loss(params, batch, more)[0]
                     ) == pytest.approx(0.2 * mean_entropy, rel=1e-4)


# -- documents ---------------------------------------------------------------

def test_another_documents_tokens_leave_every_exit_of_the_others_bit_equal():
    batch = batch_of([[20, 50]], 64)
    params = params_of(TOY)
    other = {**batch, "x": batch["x"].copy()}
    other["x"][:, 20:50] = (other["x"][:, 20:50] + 1) % 96
    a, b = exits_of(TOY, params, batch), exits_of(TOY, params, other)
    assert a.shape == (4, 1, 64)
    # position 19's label is the changed document's first token: weight 0
    assert np.array_equal(a[:, :, :19], b[:, :, :19])
    assert np.array_equal(a[:, :, 50:], b[:, :, 50:])
    assert distance(a[:, :, 20:49], b[:, :, 20:49]) > 1e-2


def test_a_document_moved_inside_its_row_keeps_its_exits():
    """RoPE's scores depend on ``q_pos - k_pos`` alone: positions
    counted along the row and positions restarted at each document give
    the same scores inside a document."""
    rng = np.random.default_rng(4)
    first, second = rng.integers(0, 96, 20), rng.integers(0, 96, 44)
    params = params_of(TOY)

    def row(*documents):
        x = np.concatenate(documents)[None].astype(np.int32)
        segment = np.concatenate([
            np.full(len(d), i, np.int32) for i, d in enumerate(documents)])
        return {"x": x, "segment": segment[None]}

    ahead = exits_of(TOY, params, row(first, second))
    behind = exits_of(TOY, params, row(second, first))
    # all but each document's last position, whose label is the neighbour's
    assert distance(ahead[:, :, :19], behind[:, :, 44:63]) < RTOL
    assert distance(ahead[:, :, 20:63], behind[:, :, :43]) < RTOL
    # and it is the positions that do it: a document alone in its row
    alone = exits_of(TOY, params, row(first))
    assert distance(alone[:, :, :19], behind[:, :, 44:63]) < RTOL


# -- operations --------------------------------------------------------------

COUNTED = {"sequence_length": 128, "hidden_size": 128, "head_dim": 32,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "intermediate_size": 256, "vocab_size": 512,
           "num_hidden_layers": 2, "total_ut_steps": 4}


def test_the_count_takes_a_layer_once_a_use_and_the_head_once_an_exit():
    macs = flops.forward_macs_per_row(COUNTED, pairs_per_row=1000.0)
    once = flops.forward_macs_per_row(
        {**COUNTED, "total_ut_steps": 1}, pairs_per_row=1000.0)
    assert macs == {part: 4 * count for part, count in once.items()}
    # by hand, one pass: q, k, v, o 128 x 128 each; gate, up, down
    assert once["projections"] == 2 * 128 * 4 * 128 * 128
    assert once["mlp"] == 2 * 128 * 3 * 128 * 256
    assert once["attention"] == 2 * 4 * 2 * 32 * 1000
    assert once["head"] == 128 * 128 * 512 and once["gate"] == 128 * 128
    assert flops.train_flops_per_sample(COUNTED, 1000.0) == round(
        6 * sum(macs.values()) / 128)
    assert flops.train_flops_per_step(COUNTED, 1000.0, 3, flops.EXITS) == (
        6 * 3 * macs["head"])
    assert flops.train_flops_per_step(COUNTED, 1000.0, 1, flops.STACK) == (
        6 * (macs["projections"] + macs["attention"] + macs["mlp"]))


def test_the_count_is_what_the_compiler_counts_in_the_unchecked_forward():
    """``cost_analysis()`` of the reference's forward pass, which has no
    loop the compiler could count once and recomputes nothing, at widths
    where the products are most of it.  Its dense mask scores every
    pair, so the count is given ``T x T`` pairs a head.  The compiler
    also counts norms, softmax, SiLU and RoPE, which the count leaves
    out: read 7.6 % over; the band is 0 to 12 %."""
    cfg = looped.LoopedConfig(
        vocab_size=512, hidden_size=128, num_layers=2, mlp_width=256,
        num_heads=4, num_kv_heads=4, head_dim=32, rope_theta=1e6,
        rms_norm_eps=1e-6, total_ut_steps=4, entropy_weight=0.1)
    params = jax.eval_shape(
        lambda key: looped.init_params(key, cfg), jax.random.PRNGKey(0))
    row = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    batch = {"x": row, "segment": row,
             "w": jax.ShapeDtypeStruct((1, 128), jnp.float32)}
    counted = jax.jit(
        lambda p, b: ref.loss(p, b, sizes_of(cfg))).lower(
            params, batch).compile().cost_analysis()["flops"]
    required = 2 * sum(flops.forward_macs_per_row(
        COUNTED, pairs_per_row=128 * 128).values())
    assert 1.0 <= counted / required < 1.12, counted / required


# -- the configuration -------------------------------------------------------

def test_the_published_widths_give_the_configurations_count():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b-6of48.json")) as f:
        config = json.load(f)
    from benchmark.builders import looped_lm

    cfg = looped_lm.model_config(config)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_width, cfg.vocab_size, cfg.total_ut_steps,
            cfg.rope_theta, cfg.num_layers) == (
                2048, 16, 16, 128, 5632, 49152, 4, 1e6, 6)
    shapes = jax.eval_shape(
        lambda key: looped.init_params(key, cfg), jax.random.PRNGKey(0))
    count = lambda tree: sum(
        x.size for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layers"]) == 6 * 51_388_416
    assert count(shapes) == config["parameters"] == 509_661_185
    whole = dataclasses.replace(cfg, num_layers=48)
    assert count(jax.eval_shape(
        lambda key: looped.init_params(key, whole),
        jax.random.PRNGKey(0))) == 2_667_974_657     # "2.6B"


def test_the_start_gives_the_expected_first_loss():
    batch = batch_of([[20, 50], [33]], 64)
    params = looped.init_params(jax.random.PRNGKey(3), TOY)
    assert float(jnp.abs(params["gate_b"]).max()) == 0.0
    assert np.asarray(params["layers"]["norm4"]).min() == 1.0
    loss, stats = looped.expected_exit_loss(params, batch, TOY)
    spread = -sum(q * math.log(q) for q in (0.5, 0.25, 0.125, 0.125))
    assert float(loss) == pytest.approx(
        math.log(96) - 0.1 * spread, abs=0.05)
    assert np.asarray(stats["loop_exit_mass"]) == pytest.approx(
        [0.5, 0.25, 0.125, 0.125], abs=0.03)
    assert float(jnp.sum(stats["loop_exit_mass"])) == pytest.approx(1.0)
    assert np.asarray(stats["loop_exit_loss"]) == pytest.approx(
        math.log(96), abs=0.05)


# -- counters ----------------------------------------------------------------

def test_the_layer_uses_are_counted_when_a_program_is_traced():
    uses = metrics.counter("hvtpu_loop_layer_uses_total")
    before = uses.value()
    batch = batch_of([[20]], 64)
    params = params_of(TOY)
    run = jax.jit(lambda x: looped.hidden_states_by_pass(params, x, TOY))
    run(batch["x"])
    run(batch["x"])                # traced once: counted once
    assert uses.value() - before == 2 * 4


def test_the_exits_are_noted_from_the_hosts_loop():
    batch = batch_of([[20, 50], [33]], 64)
    _, stats = looped.expected_exit_loss(params_of(TOY), batch, TOY)
    metrics.note_loop_exits(stats)
    for name, key in (("hvtpu_loop_exit_mass", "loop_exit_mass"),
                      ("hvtpu_loop_exit_loss", "loop_exit_loss")):
        assert [metrics.gauge(name).value(exit=str(t))
                for t in (1, 2, 3, 4)] == pytest.approx(
                    np.asarray(stats[key]).tolist())
    assert sum(metrics.gauge("hvtpu_loop_exit_mass").value(exit=str(t))
               for t in (1, 2, 3, 4)) == pytest.approx(1.0)


def test_the_cells_attention_metric_reads_the_models_attention_scope():
    """``loop_attention_ms_per_step`` (PR 35) is the device time under
    ``hvtpu:attention``, the scope the model's attention runs under, a
    part of ``loop_stack_ms_per_step``, reported in the looped cell
    alone; it needs a chip's trace, like the other scope metrics."""
    import types

    from benchmark.layer_metrics import (
        loop_attention_ms_per_step, loop_stack_ms_per_step)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = (m for m in json.load(f)["per_layer"]
                  if m["name"] == "loop_attention_ms_per_step")
    assert entry == {
        "name": "loop_attention_ms_per_step", "unit": "ms",
        "better": "lower", "source": "device_trace", "layer": "attention",
        "moves": "samples_per_s_per_chip",
        "workloads": ["ouro-2.6b-6of48-t8k-b1"]}
    text = """
  %fusion.1 = bf16[1,8192,2048]{2,1,0} fusion(%p.1), kind=kOutput, calls=%fc.1, metadata={op_name="jit(one_step)/jvp()/while/body/closed_call/hvtpu:loop.proj/dot_general"}
  %hvtpu_flash_attention_fwd.2 = (bf16[1,8192,16,128]{3,2,1,0}) custom-call(%p.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(one_step)/jvp()/while/body/closed_call/hvtpu:attention/pallas_call"}
  %fusion.3 = s32[1,136]{1,0} fusion(%p.3), kind=kLoop, calls=%fc.3, metadata={op_name="jit(one_step)/jvp()/while/body/closed_call/hvtpu:attention/cummax"}
  %fusion.4 = f32[8]{0} fusion(%p.4), kind=kLoop, calls=%fc.4, metadata={op_name="jit(one_step)/mul"}
"""
    steps = 4
    op_ms = {"fusion.1 fusion bf16[1,8192,2048]": 200.0,
             "hvtpu_flash_attention_fwd.2 custom-call "
             "(bf16[1,8192,16,128])": 130.0,
             "fusion.3 fusion s32[1,136]": 0.5, "fusion.4 fusion f32[8]": 7.0}
    device = types.SimpleNamespace(
        op_ns={name: ms * 1e6 * steps for name, ms in op_ms.items()},
        step_ns=[1.0] * steps)
    obs = types.SimpleNamespace(
        trace=types.SimpleNamespace(
            devices=[device], busy_s=sum(op_ms.values()) * steps / 1e3),
        compiled_text=text)
    assert loop_attention_ms_per_step.read(obs) == pytest.approx(130.5)
    assert loop_stack_ms_per_step.stack_ms(obs) == pytest.approx(330.5)
    assert loop_attention_ms_per_step.read(types.SimpleNamespace(
        trace=None, compiled_text=text)) is None      # no chip: no trace
    batch = batch_of([[20]], 64)
    params = params_of(TOY)
    lowered = jax.jit(
        lambda x: looped.hidden_states_by_pass(params, x, TOY)).lower(
            batch["x"]).as_text(debug_info=True)
    assert "hvtpu:attention" in lowered


def test_the_models_package_names_the_model_and_does_not_import_it():
    """The other cells' set-up is imports first."""
    import subprocess

    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, horovod_tpu.models as m; "
         "print('looped' in m.__all__, "
         "'horovod_tpu.models.looped' in sys.modules)"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT)
    assert out.stdout.strip() == "True False", out.stderr[-1000:]
