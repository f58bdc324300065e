"""The block-diffusion configuration, its traffic, its builder, its
operation count and its five per-layer metrics (PR 27).

``test_cells.py`` and ``test_rehearsal.py`` find the new entries by name
like every other; two of their cases cannot pass for them and only a
``benchmark`` PR may edit those files (PERF.md, open questions):
``test_a_configuration_is_a_file_of_sizes`` asserts ``reduced == []``,
and ``test_every_cell_walks_through_the_rehearsal`` holds a closed list
of the metrics that need a chip.  What they would have asserted is
asserted here."""

import ast
import gzip
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import cells, flops_block_diffusion_lm as flops, scopes, xplane
from benchmark.builders import block_diffusion_lm

CELL = "sdar-30b-a3b-1of8-t8k-b2"
BENCH = cells.load_benchmark()
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW_METRICS = ["attention_ms_per_step", "moe_ms_per_step",
               "moe_dispatch_share", "block_attention_roofline",
               "expert_matmul_roofline"]
# the catalog's row: the published config.json without the keys that say
# nothing about the model's shape
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def test_the_file_is_the_published_config_with_the_share_in_reduced(cell):
    config = cell.config
    entry = next(c for c in BENCH["configs"] if c["name"] == config["name"])
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "num_attention_heads",
        "num_key_value_heads", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["vocab_size"]) == (4, 16, 4, 1, 18992)
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    deployment = config["deployment"]
    assert deployment["chips_per_layer"] == 8
    assert deployment["first_expert"] == (
        deployment["chip"] * config["num_experts"])
    assert config["sample_unit"] == "token"
    assert config["assumed"] and config["rehearsal"]
    assert os.path.exists(os.path.join(
        cells.HERE, "builders", config["builder"] + ".py"))


def test_the_cell_is_two_packed_8k_sequences_on_one_chip(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1
    traffic, config = cell.traffic, cell.config
    assert traffic["batch_per_chip"] == 2
    assert traffic["sequence_length"] == config["sequence_length"] == 8192
    assert traffic["block_length"] == config["block_length"] == 4
    assert traffic["mask_schedule"] == "t~U[0.05,1] per block, weight 1/t"
    assert config["mask_t_min"] == 0.05
    assert traffic["feed"] == {"host_pool_batches": 2, "reshuffle": True,
                               "dtype": "bfloat16"}
    assert (traffic["steps_per_dispatch"], traffic["fence_lag"],
            traffic["compression"], traffic["trace_steps"]) == (
                1, 2, "none", 26)
    toy = cells.load_cell(CELL, rehearse=True)
    assert toy.traffic["sequence_length"] == toy.config["sequence_length"]
    assert {m["name"] for m in cell.end_to_end} == {
        "samples_per_s_per_chip", "setup_s"}
    assert set(NEW_METRICS) <= {m["name"] for m in cell.per_layer}
    for name in NEW_METRICS:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]


def test_the_parameter_count_is_the_shapes(cell):
    import jax

    config = cell.config
    d, f, hd = (config["hidden_size"], config["moe_intermediate_size"],
                config["head_dim"])
    layer = (config["num_experts"] * 3 * d * f
             + d * config["published"]["num_experts"]
             + 2 * d * config["num_attention_heads"] * hd
             + 2 * d * config["num_key_value_heads"] * hd
             + 2 * hd + 2 * d)
    assert layer == 78_385_408
    by_hand = (config["num_hidden_layers"] * layer
               + 2 * config["vocab_size"] * d + d)
    assert by_hand == config["parameters"] == 391_334_912
    workload = block_diffusion_lm.build(config)
    params, state = jax.eval_shape(
        lambda key: workload.init(key, None), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == by_hand
    assert state["moe_rows_per_expert"].shape == (4, 16)
    assert workload.samples_per_row == 8192
    assert workload.sample_unit == "token"
    assert workload.expected_first_loss == pytest.approx(9.85, abs=0.005)
    assert workload.train_flops_per_sample == config[
        "train_flops_per_sample"]


def test_the_builder_refuses_what_the_model_does_not_build(cell):
    for key, other in (("hidden_act", "gelu"), ("tie_word_embeddings", True),
                       ("mlp_only_layers", [0]), ("decoder_sparse_step", 2)):
        with pytest.raises(ValueError, match="models.block_diffusion"):
            block_diffusion_lm.build({**cell.config, key: other})


def test_the_pool_is_the_block_diffusion_batch(cell):
    config = {**cell.config, "sequence_length": 4096}
    pool = block_diffusion_lm.make_pool(
        config, np.random.default_rng(7), 8, "bfloat16")
    again = block_diffusion_lm.make_pool(
        config, np.random.default_rng(7), 8, "bfloat16")
    assert all(np.array_equal(pool[k], again[k]) for k in pool)
    x, mask, w = pool["x"], pool["mask"], pool["w"]
    assert (x.dtype, mask.dtype, str(w.dtype)) == (
        np.int32, np.int8, "bfloat16")
    assert x.shape == mask.shape == w.shape == (8, 4096)
    # ids from the slice, never [MASK], its last id
    assert x.min() >= 0 and x.max() <= config["vocab_size"] - 2
    w = w.astype(np.float32)
    assert ((w > 0) == (mask != 0)).all()
    # one t a block of four: the weights of a block's masked tokens agree
    blocks = w.reshape(8, -1, 4)
    top = blocks.max(axis=-1, keepdims=True)
    assert ((blocks == 0) | (blocks == top)).all()
    assert 1.0 <= w[w > 0].min() and w.max() <= 20.0   # 1/t, t in [0.05, 1]
    # P(masked) = E[t] = 0.525, and E[mask / t] = 1 a position
    assert mask.mean() == pytest.approx(0.525, abs=0.01)
    assert w.mean() == pytest.approx(1.0, abs=0.03)


TOY = {"sequence_length": 8, "block_length": 4, "hidden_size": 6,
       "head_dim": 2, "num_hidden_layers": 3, "num_attention_heads": 4,
       "num_key_value_heads": 2, "num_experts": 2, "num_experts_per_tok": 2,
       "published": {"num_experts": 8}, "moe_intermediate_size": 5,
       "vocab_size": 11}


def test_the_operation_count_by_hand_at_toy_size():
    from benchmark.reference import sdar_block_diffusion as ref

    # two blocks of four: a noised query sees 4 noised keys and the
    # clean keys of earlier blocks (0, then 4); a clean one 4, then 8
    assert flops.visible_pairs(8, 4) == 4 * 4 + 4 * 8 + 4 * 4 + 4 * 8 == 96
    assert flops.visible_pairs(8, 4) == 8 * 8 + 8 * 4
    for seq_len, block in [(8, 4), (12, 1), (12, 12), (10, 4)]:
        assert flops.visible_pairs(seq_len, block) == int(
            np.asarray(ref.dense_mask(seq_len, block)).sum())
    macs = flops.forward_macs_per_sequence(TOY)
    # 16 positions: q and o are 6x8, k and v 6x4; 3 layers
    assert macs["projections"] == 3 * 16 * (2 * 6 * 8 + 2 * 6 * 4)
    # 4 heads, a score and a weighted value of 2 a pair
    assert macs["attention"] == 3 * 4 * 96 * 2 * 2
    assert macs["router"] == 3 * 16 * 6 * 8
    # 16 positions x 2 choices x 2/8 held = 8 rows, three 6x5 products
    assert macs["experts"] == 3 * 8 * 3 * 6 * 5
    assert macs["head"] == 8 * 6 * 11                 # the noised half
    assert flops.train_flops_per_sample(TOY) == 6 * sum(macs.values()) // 8
    assert flops.attention_train_flops_per_step(TOY, 2) == (
        6 * macs["attention"] * 2)
    assert flops.expert_train_flops_per_step(TOY, 2) == (
        6 * macs["experts"] * 2)


def test_the_cells_count(cell):
    per_token = {k: 6 * v / 8192 / 1e6 for k, v in
                 flops.forward_macs_per_sequence(cell.config).items()}
    # MFLOP a trained token, forward and backward, all four layers
    assert per_token["projections"] == pytest.approx(4 * 3 * 10.49, rel=1e-3)
    assert per_token["attention"] == pytest.approx(4 * 3 * 16.79, rel=1e-3)
    assert per_token["experts"] == pytest.approx(4 * 3 * 18.87, rel=1e-3)
    assert per_token["router"] == pytest.approx(4 * 3 * 1.049, rel=1e-3)
    assert per_token["head"] == pytest.approx(3 * 77.79, rel=1e-3)
    assert flops.train_flops_per_sample(cell.config) == 799_703_040


# -- the five readers ---------------------------------------------------------

TEXT = """
  %fusion.1 = bf16[2,4,512]{2,1,0} fusion(%p.1), kind=kOutput, calls=%fc.1, metadata={op_name="jit(one_step)/jvp(hvtpu:attention)/dot_general" stack_frame_id=3}
  %fusion.2 = f32[8]{0} fusion(%p.2), kind=kLoop, calls=%fc.2, metadata={op_name="jit(one_step)/transpose(jvp(hvtpu:attention))/while/body/mul"}
  ROOT %sort.3 = (s32[8]{0}) sort(%p.3), dimensions={0}, metadata={op_name="jit(one_step)/hvtpu:moe.dispatch/sort"}
  %convolution.4 = f32[8,8]{1,0} convolution(%a, %b), metadata={op_name="jit(one_step)/while/body/hvtpu:moe.experts/dot_general"}
  %fusion.5 = f32[8]{0} fusion(%p.5), kind=kLoop, calls=%fc.5, metadata={op_name="jit(one_step)/hvtpu:moe.route/hvtpu:moe.combine/add"}
  %fusion.6 = f32[8]{0} fusion(%p.6), kind=kLoop, calls=%fc.6, metadata={op_name="jit(one_step)/hvtpu:moe.route/top_k"}
  %fusion.7 = f32[8]{0} fusion(%p.7), kind=kLoop, calls=%fc.7, metadata={op_name="jit(one_step)/mul"}
  %copy.8 = f32[8]{0} copy(%p.8)
"""


def _observations(cell, op_ms, steps=4, text=TEXT):
    device = types.SimpleNamespace(
        op_ns={name: ms * 1e6 * steps for name, ms in op_ms.items()},
        step_ns=[1.0] * steps)
    trace = types.SimpleNamespace(
        devices=[device],
        busy_s=sum(op_ms.values()) * steps / 1e3)
    return types.SimpleNamespace(
        trace=trace, compiled_text=text, config=cell.config,
        traffic=cell.traffic, device_kind="TPU v5 lite")


def test_an_op_belongs_to_the_innermost_scope_of_its_op_name():
    assert scopes.scope_by_instruction(TEXT) == {
        "fusion.1": "hvtpu:attention", "fusion.2": "hvtpu:attention",
        "sort.3": "hvtpu:moe.dispatch", "convolution.4": "hvtpu:moe.experts",
        "fusion.5": "hvtpu:moe.combine", "fusion.6": "hvtpu:moe.route"}


def test_the_five_readers_by_hand(cell, capsys):
    obs = _observations(cell, {
        "fusion.1 fusion bf16[2,4,512]": 30.0, "fusion.2 fusion f32[8]": 10.0,
        "sort.3 sort (s32[8])": 4.0, "convolution.4 convolution f32[8,8]": 50.0,
        "fusion.5 fusion f32[8]": 5.0, "fusion.6 fusion f32[8]": 1.0,
        "fusion.7 fusion f32[8]": 7.0, "copy.8 copy f32[8]": 3.0})
    read = {name: cells.load_metric("per_layer", name).read(obs)
            for name in NEW_METRICS}
    assert read["attention_ms_per_step"] == pytest.approx(40.0)
    assert read["moe_ms_per_step"] == pytest.approx(60.0)
    assert read["moe_dispatch_share"] == pytest.approx(100 * 10 / 60)
    # required: 6 FLOPs a multiply-accumulate and pass, two sequences
    macs = flops.forward_macs_per_sequence(cell.config)
    assert read["block_attention_roofline"] == pytest.approx(
        100 * (6 * 2 * macs["attention"] / 197e12) / 40e-3)
    assert read["expert_matmul_roofline"] == pytest.approx(
        100 * (6 * 2 * macs["experts"] / 197e12) / 50e-3)
    # scoped and unscoped add up to the time an op ran
    line = capsys.readouterr().out
    assert "unscoped 10.000" in line and "(+0.00 %)" in line
    by_scope = scopes.ms_per_step(obs.trace, TEXT)
    assert by_scope[scopes.UNSCOPED] == pytest.approx(10.0)
    assert sum(by_scope.values()) == pytest.approx(110.0)


def test_the_readers_find_nothing_where_the_program_has_no_scopes(cell):
    """A parent commit's step, a CPU rehearsal: None, never a raise."""
    obs = _observations(cell, {"fusion.7 fusion f32[8]": 7.0},
                        text='%fusion.7 = f32[8]{0} fusion(%p), '
                             'metadata={op_name="jit(one_step)/mul"}')
    untraced = types.SimpleNamespace(
        trace=None, compiled_text=TEXT, config=cell.config,
        traffic=cell.traffic, device_kind="TPU v5 lite")
    for name in NEW_METRICS:
        reader = cells.load_metric("per_layer", name)
        assert reader.read(obs) is None
        assert reader.read(untraced) is None


def test_the_readers_on_a_recorded_extract(cell, capsys):
    """Five steps of the cell's own traced run on the v5e and the lines
    of its compiled step that name an op of the extract
    (``data/PROVENANCE-pr27.txt``)."""
    reduction = xplane.reduce(xplane.load_extract(
        os.path.join(DATA, CELL + ".5steps.json.gz")))
    with gzip.open(os.path.join(DATA, CELL + ".hlo-lines.txt.gz"),
                   "rt") as f:
        text = f.read()
    obs = types.SimpleNamespace(
        trace=reduction, compiled_text=text, config=cell.config,
        traffic=cell.traffic, device_kind="TPU v5 lite")
    read = {name: cells.load_metric("per_layer", name).read(obs)
            for name in NEW_METRICS}
    by_scope = scopes.ms_per_step(reduction, text)
    assert {"hvtpu:attention", "hvtpu:lm_head", "hvtpu:moe.route",
            "hvtpu:moe.dispatch", "hvtpu:moe.experts", "hvtpu:moe.combine",
            scopes.UNSCOPED} == set(by_scope)
    # innermost ops do not overlap: their times add up to the busy time
    steps = len(reduction.devices[0].step_ns)
    assert sum(by_scope.values()) == pytest.approx(
        1e3 * reduction.busy_s / steps, rel=0.02)
    assert read["attention_ms_per_step"] == pytest.approx(
        by_scope["hvtpu:attention"])
    assert read["moe_ms_per_step"] == pytest.approx(sum(
        v for k, v in by_scope.items() if k.startswith("hvtpu:moe.")))
    assert 0 < read["moe_dispatch_share"] < 100
    assert 0 < read["block_attention_roofline"] < 100
    assert 0 < read["expert_matmul_roofline"] < 100
    assert "scopes: device ms a step by scope" in capsys.readouterr().out


# -- the cell as a command ----------------------------------------------------

def _run(script, *args):
    return subprocess.run(
        [sys.executable, os.path.join(cells.HERE, script), *args],
        capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace, kind", [("1", "per_layer"),
                                         ("0", "end_to_end")])
def test_the_cell_walks_through_the_rehearsal(trace, kind):
    from benchmark.tests.test_rehearsal import NEED_A_CHIP

    proc = _run("run.py", "--workload", CELL, "--seed", "2147483700",
                "--seconds", "2", "--trace", trace, "--rehearse-on-cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    assert all(line.startswith("REHEARSAL ") for line in lines)
    assert lines[-1] == "REHEARSAL not a chip result"
    assert "0 compilation(s) in the window" in proc.stdout
    checks = next(line for line in lines if " checks: " in line)
    assert "False" not in checks, checks
    wanted = {m["name"] for m in BENCH[kind]
              if CELL in m.get("workloads", [CELL])}
    if kind == "per_layer":     # the new ones read the device trace too
        wanted -= NEED_A_CHIP | set(NEW_METRICS)
    read = next(line for line in lines if f"{kind} metrics read: " in line)
    found = ast.literal_eval(read.split("metrics read: ")[1].split(";")[0])
    assert set(found) == wanted


def test_the_comparison_walks_through_the_rehearsal():
    proc = _run("compare_sdar.py", "--workload", CELL, "--seed", "5",
                "--rehearse-on-cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    assert lines[-1] == "REHEARSAL not a chip result"
    assert all(line.startswith("REHEARSAL ") for line in lines)
    assert sum("gradient [" in line for line in lines) == 15
    assert any(" update: distance " in line for line in lines)


def test_off_a_tpu_nothing_is_compared():
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "compare_sdar.py"),
         "--workload", CELL], capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert "Nothing was compared" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
