"""The hybrid state-space configuration, its traffic, its builder, its
operation count and its three per-layer metrics (PR 32).

``test_cells.py`` finds the new entries by name like every other; its
``test_a_configuration_is_a_file_of_sizes`` asserts ``reduced == []``
and cannot pass for a configuration that is cut (PERF.md, open
questions: only a ``benchmark`` PR may edit it).  What it would have
asserted is asserted here."""

import ast
import gzip
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import cells, flops_hybrid_ssm_lm as flops, scopes, xplane
from benchmark.builders import hybrid_ssm_lm
from benchmark.tests.test_block_diffusion_cell import (
    _observations as observations_of, _run)

CELL = "granite-4.0-h-micro-10of40-t8k-b2"
BENCH = cells.load_benchmark()
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW_METRICS = ["ssm_ms_per_step", "ssm_scan_share", "ssm_scan_roofline"]
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# the catalog's row: the published config.json without the keys that say
# nothing about the model's shape
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def test_the_file_is_the_published_config_with_the_cut_in_reduced(cell):
    config = cell.config
    entry = next(c for c in BENCH["configs"] if c["name"] == config["name"])
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    # one whole period: five Mamba layers, attention, four Mamba layers
    assert config["layer_types"] == PERIOD == PUBLISHED["layer_types"][:10]
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 10
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    deployment = config["deployment"]
    assert deployment["vocabulary_shards"] == 8
    assert deployment["first_vocabulary_row"] == (
        deployment["vocabulary_shard"] * config["vocab_size"]) == 37632
    assert deployment["pipeline_stages"] * 10 == 40
    assert config["sample_unit"] == "token"
    assert config["assumed"] and config["rehearsal"]
    assert config["optimizer"]["name"] == "sgd"
    assert os.path.exists(os.path.join(
        cells.HERE, "builders", config["builder"] + ".py"))


def test_the_cell_is_two_packed_rows_of_8k_on_one_chip(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1
    traffic, config = cell.traffic, cell.config
    assert traffic["batch_per_chip"] == 2
    assert traffic["sequence_length"] == config["sequence_length"] == 8192
    assert traffic["document_length"] == config["document_length"] == {
        "law": "lognormal", "median": 1024, "sigma": 1.0, "min": 16,
        "max": 8192}
    assert traffic["feed"] == {"host_pool_batches": 2, "reshuffle": True,
                               "dtype": "bfloat16"}
    assert (traffic["steps_per_dispatch"], traffic["fence_lag"],
            traffic["compression"], traffic["trace_steps"]) == (
                1, 2, "none", 12)
    toy = cells.load_cell(CELL, rehearse=True)
    assert toy.traffic["sequence_length"] == toy.config["sequence_length"]
    assert toy.traffic["batch_per_chip"] == 1
    assert {m["name"] for m in cell.end_to_end} == {
        "samples_per_s_per_chip", "setup_s"}
    assert set(NEW_METRICS) <= {m["name"] for m in cell.per_layer}
    for name in NEW_METRICS:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
    # the metrics of the other transformer cell keep their lists
    assert not {"attention_ms_per_step", "moe_ms_per_step"} & {
        m["name"] for m in cell.per_layer}


def test_the_parameter_count_is_the_shapes(cell):
    import jax

    config = cell.config
    d, f = config["hidden_size"], config["shared_intermediate_size"]
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    conv = inner + 2 * config["mamba_d_state"]
    heads = config["mamba_n_heads"]
    mixer = (d * (inner + conv + heads) + conv * config["mamba_d_conv"]
             + conv + 3 * heads + inner + inner * d)
    assert mixer == 25_847_232
    mlp_and_norms = d * 2 * f + f * d + 2 * d
    assert mixer + mlp_and_norms == 76_182_976
    hd = d // config["num_attention_heads"]
    attention = 2 * d * d + 2 * d * config["num_key_value_heads"] * hd
    assert attention + mlp_and_norms == 60_821_504
    by_hand = (9 * (mixer + mlp_and_norms) + attention + mlp_and_norms
               + d + config["vocab_size"] * d)
    assert by_hand == config["parameters"] == 772_160_448
    workload = hybrid_ssm_lm.build(config)
    params, state = jax.eval_shape(
        lambda key: workload.init(key, None), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == by_hand
    assert state == {}
    assert workload.samples_per_row == 8192
    assert workload.sample_unit == "token"
    assert workload.expected_first_loss == pytest.approx(9.437, abs=0.001)


def test_the_builder_refuses_what_the_model_does_not_build(cell):
    for key, other in (("hidden_act", "gelu"), ("tie_word_embeddings", False),
                       ("position_embedding_type", "rope"),
                       ("num_local_experts", 8), ("mamba_n_groups", 8),
                       ("mamba_proj_bias", True)):
        with pytest.raises(ValueError, match="models.hybrid_ssm builds"):
            hybrid_ssm_lm.build({**cell.config, key: other})
    with pytest.raises(ValueError, match="a layer type a layer"):
        hybrid_ssm_lm.build({**cell.config, "num_hidden_layers": 9})
    with pytest.raises(ValueError, match="mamba_expand"):
        hybrid_ssm_lm.build({**cell.config, "mamba_n_heads": 32})


def test_the_pool_is_packed_documents(cell):
    config = cell.config
    pool = hybrid_ssm_lm.make_pool(
        config, np.random.default_rng(7), 64, "bfloat16")
    again = hybrid_ssm_lm.make_pool(
        config, np.random.default_rng(7), 64, "bfloat16")
    assert all(np.array_equal(pool[k], again[k]) for k in pool)
    x, segment, w = pool["x"], pool["segment"], pool["w"]
    assert (x.dtype, segment.dtype, str(w.dtype)) == (
        np.int32, np.int32, "bfloat16")
    assert x.shape == segment.shape == w.shape == (64, 8192)
    assert x.min() >= 0 and x.max() <= config["vocab_size"] - 1
    # documents are contiguous, numbered from 0, and fill the row
    steps = np.diff(segment, axis=1)
    assert (segment[:, 0] == 0).all() and set(np.unique(steps)) == {0, 1}
    w = w.astype(np.float32)
    assert (w[:, -1] == 0).all()
    assert np.array_equal(w[:, :-1] == 1, steps == 0)
    lengths = np.concatenate([
        np.diff(np.flatnonzero(np.diff(row, prepend=-1, append=-1)))
        for row in segment])
    whole = np.concatenate([
        np.diff(np.flatnonzero(np.diff(row, prepend=-1)))
        for row in segment])            # all but each row's last, cut one
    assert whole.min() >= 16 and lengths.max() <= 8192
    assert np.median(whole) == pytest.approx(1024, rel=0.25)
    assert 3 < len(lengths) / 64 < 9


TOY = {"sequence_length": 8, "hidden_size": 6, "num_attention_heads": 3,
       "num_key_value_heads": 1, "mamba_n_heads": 4, "mamba_d_head": 3,
       "mamba_d_state": 5, "mamba_n_groups": 1, "mamba_chunk_size": 4,
       "shared_intermediate_size": 7, "vocab_size": 11,
       "layer_types": ["mamba", "attention", "mamba"]}


def test_the_operation_count_by_hand_at_toy_size():
    from benchmark.reference import granite_hybrid as ref

    segment = np.array([[0, 0, 0, 1, 1, 1, 1, 1], [0] * 8])
    # documents of 3 and 5, and of 8: n (n + 1) / 2 each
    assert flops.visible_pairs(segment) == 6 + 15 + 36
    for row in segment:
        assert flops.visible_pairs(row[None]) == int(
            np.asarray(ref.dense_mask(row)).sum())
    # a token of one Mamba layer: C B^T 4x5, the masked product 4x12,
    # the chunk's state and C H 5x12 each
    assert flops.scan_macs_per_token(TOY) == 4 * 5 + 4 * 12 + 2 * 5 * 12
    macs = flops.forward_macs_per_row(TOY, 57 / 2)
    # in: 6 -> z 12 + xBC 22 + dt 4; out: 12 -> 6; two Mamba layers
    assert macs["ssm_projections"] == 2 * 8 * (6 * 38 + 12 * 6)
    assert macs["ssm_scan"] == 2 * 8 * 188
    # q and o 6x6, k and v 6x2 (one key/value head of 2)
    assert macs["attention_projections"] == 8 * (2 * 36 + 2 * 12)
    # 3 heads, a score and a weighted value of 2 a pair
    assert macs["attention"] == 3 * 2 * 2 * 57 / 2
    assert macs["mlp"] == 3 * 8 * 3 * 6 * 7
    assert macs["head"] == 8 * 6 * 11
    assert flops.train_flops_per_sample(TOY, 57 / 2) == round(
        6 * sum(macs.values()) / 8)
    assert flops.scan_train_flops_per_step(TOY, 16) == 6 * 188 * 16 * 2
    # bf16 x 12, B and C 5 each, f32 delta a head; y 12
    operands, result = 2 * 22 + 4 * 4, 2 * 12
    assert flops.scan_train_bytes_per_step(TOY, 16) == (
        (operands + result) + (2 * operands + result)) * 16 * 2


def test_the_cells_count(cell):
    config = cell.config
    pairs = hybrid_ssm_lm.expected_pairs_per_row(config)
    assert pairs == hybrid_ssm_lm.expected_pairs_per_row(config)
    # between every document at the median and one document a row
    assert 8 * 1024 * 1025 / 2 < pairs < 8192 * 8193 / 2
    per_token = {k: 2 * v / 8192 / 1e6 for k, v in
                 flops.forward_macs_per_row(config, pairs).items()}
    # MFLOP a token forward; a Mamba layer's products 152.3, its scan 4.26
    assert per_token["ssm_scan"] / 9 == pytest.approx(4.26, rel=1e-3)
    assert (per_token["ssm_projections"] / 9 + per_token["mlp"] / 10
            ) == pytest.approx(152.3, rel=1e-3)
    assert per_token["attention_projections"] == pytest.approx(20.97, rel=1e-3)
    assert per_token["head"] == pytest.approx(51.38, rel=1e-3)
    assert sum(per_token.values()) == pytest.approx(1590, rel=5e-3)
    workload = hybrid_ssm_lm.build(config)
    assert workload.train_flops_per_sample == flops.train_flops_per_sample(
        config, pairs)
    # the recurrence a step requires, as time at the chip's peaks
    assert flops.scan_train_flops_per_step(config, 16384) / 197e12 == (
        pytest.approx(9.57e-3, rel=1e-3))
    assert flops.scan_train_bytes_per_step(config, 16384) / 819e9 == (
        pytest.approx(7.79e-3, rel=1e-3))


# -- the three readers --------------------------------------------------------

TEXT = """
  %fusion.1 = bf16[2,8192,8448]{2,1,0} fusion(%p.1), kind=kOutput, calls=%fc.1, metadata={op_name="jit(one_step)/jvp()/while/body/closed_call/hvtpu:ssm.proj/dot_general"}
  %fusion.2 = f32[2,8192,4352]{2,1,0} fusion(%p.2), kind=kLoop, calls=%fc.2, metadata={op_name="jit(one_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/hvtpu:ssm.conv/mul"}
  %fusion.3 = f32[2,64,256,256]{3,2,1,0} fusion(%p.3), kind=kLoop, calls=%fc.3, metadata={op_name="jit(one_step)/jvp()/while/body/closed_call/hvtpu:ssm.scan/while/body/checkpoint/exp"}
  %convolution.4 = f32[2,256,64,64]{3,2,1,0} convolution(%a, %b), metadata={op_name="jit(one_step)/transpose(jvp())/while/body/closed_call/hvtpu:ssm.scan/while/body/dot_general"}
  %fusion.5 = bf16[2,8192,4096]{2,1,0} fusion(%p.5), kind=kLoop, calls=%fc.5, metadata={op_name="jit(one_step)/jvp()/while/body/closed_call/hvtpu:ssm.gate/mul"}
  %fusion.6 = bf16[2,8192,2048]{2,1,0} fusion(%p.6), kind=kOutput, calls=%fc.6, metadata={op_name="jit(one_step)/jvp()/while/body/closed_call/hvtpu:mlp/dot_general"}
  %fusion.7 = f32[8]{0} fusion(%p.7), kind=kLoop, calls=%fc.7, metadata={op_name="jit(one_step)/mul"}
  %copy.8 = f32[8]{0} copy(%p.8)
"""


def _observations(cell, op_ms, text=TEXT):
    return observations_of(cell, op_ms, text=text)


def test_the_three_readers_by_hand(cell, capsys):
    obs = _observations(cell, {
        "fusion.1 fusion bf16[2,8192,8448]": 60.0,
        "fusion.2 fusion f32[2,8192,4352]": 10.0,
        "fusion.3 fusion f32[2,64,256,256]": 70.0,
        "convolution.4 convolution f32[2,256,64,64]": 30.0,
        "fusion.5 fusion bf16[2,8192,4096]": 30.0,
        "fusion.6 fusion bf16[2,8192,2048]": 90.0,
        "fusion.7 fusion f32[8]": 7.0, "copy.8 copy f32[8]": 3.0})
    read = {name: cells.load_metric("per_layer", name).read(obs)
            for name in NEW_METRICS}
    assert read["ssm_ms_per_step"] == pytest.approx(200.0)   # not the MLP
    assert read["ssm_scan_share"] == pytest.approx(100 * 140 / 200)
    # compute bound: 9.57 ms of FLOPs against 7.79 ms of bytes
    assert read["ssm_scan_roofline"] == pytest.approx(
        100 * 1e3 * (flops.scan_train_flops_per_step(cell.config, 16384)
                     / 197e12) / 100.0)
    assert read["ssm_scan_roofline"] == pytest.approx(9.566, rel=1e-3)
    line = capsys.readouterr().out
    assert "unscoped 10.000" in line and "(+0.00 %)" in line
    assert "hvtpu:mlp 90.000" in line


def test_the_readers_find_nothing_where_the_program_has_no_scopes(cell):
    """A parent commit's step, a CPU rehearsal: None, never a raise."""
    obs = _observations(cell, {"fusion.7 fusion f32[8]": 7.0},
                        text='%fusion.7 = f32[8]{0} fusion(%p), '
                             'metadata={op_name="jit(one_step)/mul"}')
    untraced = types.SimpleNamespace(
        trace=None, compiled_text=TEXT, config=cell.config,
        traffic=cell.traffic, device_kind="TPU v5 lite")
    other_scopes = _observations(
        cell, {"fusion.6 fusion bf16[2,8192,2048]": 7.0})
    for name in NEW_METRICS:
        reader = cells.load_metric("per_layer", name)
        assert reader.read(obs) is None
        assert reader.read(untraced) is None
        assert reader.read(other_scopes) is None


def test_the_readers_on_a_recorded_extract(cell, capsys):
    """Five steps of the cell's own traced run on the v5e and the lines of
    its compiled step that name an op of the extract
    (``data/PROVENANCE-pr32.txt``)."""
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
    assert {"hvtpu:ssm.proj", "hvtpu:ssm.conv", "hvtpu:ssm.scan",
            "hvtpu:ssm.gate", "hvtpu:mlp", "hvtpu:attention",
            "hvtpu:lm_head", scopes.UNSCOPED} == set(by_scope)
    steps = len(reduction.devices[0].step_ns)
    assert sum(by_scope.values()) == pytest.approx(
        1e3 * reduction.busy_s / steps, rel=0.02)
    assert read["ssm_ms_per_step"] == pytest.approx(sum(
        v for k, v in by_scope.items() if k.startswith("hvtpu:ssm.")))
    assert 0 < read["ssm_scan_share"] < 100
    assert 0 < read["ssm_scan_roofline"] < 100
    assert "scopes: device ms a step by scope" in capsys.readouterr().out


# -- the cell as a command ----------------------------------------------------

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
    proc = _run("compare_granite.py", "--workload", CELL, "--seed", "5",
                "--rehearse-on-cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    assert lines[-1] == "REHEARSAL not a chip result"
    assert all(line.startswith("REHEARSAL ") for line in lines)
    # two Mamba groups of 12 leaves, an attention group of 8, two more
    assert sum("gradient [" in line for line in lines) == 34
    assert any(" update: distance " in line for line in lines)
    assert any("passed by nothing; in a bfloat16 store by ['update"
               in line for line in lines)


def test_off_a_tpu_nothing_is_compared():
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "compare_granite.py"),
         "--workload", CELL], capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert "Nothing was compared" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_the_configurations_file_parses_and_names_its_cut():
    with open(os.path.join(cells.HERE, "configs",
                           "granite-4.0-h-micro-10of40.json")) as f:
        config = json.load(f)
    assert config["time_step_limit"] == [0.0, "inf"]
    assert len(config["published"]["layer_types"]) == 40
    assert config["rehearsal"]["layer_types"] == [
        "mamba", "attention", "mamba"]
