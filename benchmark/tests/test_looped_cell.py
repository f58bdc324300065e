"""The looped-decoder configuration, its traffic, its builder, its
operation count and its four per-layer metrics (PR 34).

``test_cells.py`` finds the new entries by name like every other; its
``test_a_configuration_is_a_file_of_sizes`` asserts ``reduced == []``
and cannot pass for a configuration that is cut (PERF.md, open
questions: only a ``benchmark`` PR may edit it).  What it would have
asserted is asserted here."""

import ast
import gzip
import json
import math
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import cells, flops_looped_lm as flops, scopes, xplane
from benchmark.builders import hybrid_ssm_lm as packed, looped_lm
from benchmark.tests.test_block_diffusion_cell import (
    _observations as observations_of, _run)

CELL = "ouro-2.6b-6of48-t8k-b1"
BENCH = cells.load_benchmark()
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW_METRICS = ["loop_stack_ms_per_step", "loop_exit_ms_per_step",
               "loop_stack_roofline", "loop_exit_roofline"]
# the catalog's row: the published config.json without the keys that say
# nothing about the model's shape
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def test_the_file_is_the_published_config_with_the_cut_in_reduced(cell):
    config = cell.config
    entry = next(c for c in BENCH["configs"] if c["name"] == config["name"])
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "layer_types"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert config["layer_types"] == PUBLISHED["layer_types"][:6]
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 6
    deployment = config["deployment"]
    assert deployment["pipeline_stages"] * deployment["layers_a_stage"] == 48
    assert deployment["pipeline_stage"] == 0
    assert config["exit_entropy_weight"] == 0.1
    assert config["sample_unit"] == "token"
    assert len(config["assumed"]) >= 9 and config["rehearsal"]
    assert config["optimizer"]["name"] == "sgd"
    toy = config["rehearsal"]
    assert (toy["hidden_size"], toy["num_attention_heads"], toy["head_dim"],
            toy["num_hidden_layers"], toy["vocab_size"],
            toy["sequence_length"]) == (64, 4, 16, 2, 96, 64)
    assert os.path.exists(os.path.join(
        cells.HERE, "builders", config["builder"] + ".py"))


def test_the_cell_is_one_packed_row_of_8k_on_one_chip(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "t8k-b1-packed"
    traffic, config = cell.traffic, cell.config
    assert traffic["batch_per_chip"] == 1
    assert traffic["sequence_length"] == config["sequence_length"] == 8192
    assert traffic["document_length"] == config["document_length"] == {
        "law": "lognormal", "median": 1024, "sigma": 1.0, "min": 16,
        "max": 8192}
    # the law of the hybrid cell's traffic
    assert traffic["document_length"] == cells.load_cell(
        "granite-4.0-h-micro-10of40-t8k-b2").traffic["document_length"]
    assert traffic["feed"] == {"host_pool_batches": 16, "reshuffle": True,
                               "dtype": "bfloat16"}
    assert (traffic["steps_per_dispatch"], traffic["fence_lag"],
            traffic["compression"], traffic["trace_steps"]) == (
                1, 2, "none", 12)
    toy = cells.load_cell(CELL, rehearse=True)
    assert toy.traffic["sequence_length"] == toy.config["sequence_length"]
    assert {m["name"] for m in cell.end_to_end} == {
        "samples_per_s_per_chip", "setup_s"}
    assert set(NEW_METRICS) <= {m["name"] for m in cell.per_layer}
    for name in NEW_METRICS:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "samples_per_s_per_chip"
        reader = cells.load_metric("per_layer", name)
        assert (reader.LAYER, reader.UNIT) == (entry["layer"], entry["unit"])
    # the metrics of the two other transformer cells keep their lists
    assert not {"attention_ms_per_step", "document_attention_ms_per_step",
                "moe_ms_per_step", "ssm_ms_per_step"} & {
        m["name"] for m in cell.per_layer}
    assert len(BENCH["workloads"]) == 6 and sum(
        w["chips"] == 4 for w in BENCH["workloads"]) == 1


def test_the_parameter_count_is_the_shapes(cell):
    import jax

    config = cell.config
    d, f = config["hidden_size"], config["intermediate_size"]
    layer = 4 * d * d + 3 * d * f + 4 * d
    assert layer == 51_388_416
    by_hand = 6 * layer + 2 * config["vocab_size"] * d + d + (d + 1)
    assert by_hand == config["parameters"] == 509_661_185
    workload = looped_lm.build(config)
    params, state = jax.eval_shape(
        lambda key: workload.init(key, None), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == by_hand
    assert {k: v.shape for k, v in state.items()} == {
        "loop_exit_mass": (4,), "loop_exit_loss": (4,)}
    assert workload.samples_per_row == 8192
    assert workload.sample_unit == "token"
    assert workload.expected_first_loss == pytest.approx(
        10.803 - 0.121, abs=0.001)


def test_the_builder_refuses_what_the_model_does_not_build(cell):
    for key, other in (("hidden_act", "gelu"), ("tie_word_embeddings", True),
                       ("use_sliding_window", True),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match="models.looped builds"):
            looped_lm.build({**cell.config, key: other})
    with pytest.raises(ValueError, match="full attention in every layer"):
        looped_lm.build({**cell.config, "num_hidden_layers": 5})


def test_the_pool_is_the_hybrid_cells_packing_over_the_whole_vocabulary(cell):
    config = cell.config
    pool = looped_lm.build(config).make_pool(
        np.random.default_rng(7), 16, "bfloat16")
    assert all(np.array_equal(v, w) for v, w in zip(
        pool.values(), packed.make_pool(
            config, np.random.default_rng(7), 16, "bfloat16").values()))
    x, segment, w = pool["x"], pool["segment"], pool["w"]
    assert x.shape == segment.shape == w.shape == (16, 8192)
    assert x.min() >= 0 and 49152 - 64 < x.max() <= 49151
    steps = np.diff(segment, axis=1)
    assert (segment[:, 0] == 0).all() and set(np.unique(steps)) == {0, 1}
    assert np.array_equal(w.astype(np.float32)[:, :-1] == 1, steps == 0)


def test_the_cells_count(cell):
    config = cell.config
    pairs = packed.expected_pairs_per_row(config)
    per_token = {k: 2 * v / 8192 / 1e6 for k, v in
                 flops.forward_macs_per_row(config, pairs).items()}
    # MFLOP a token forward: a layer use's products 102.8, its visible
    # pairs 10.8; a head 201.3
    assert (per_token["projections"] + per_token["mlp"]) / 24 == (
        pytest.approx(102.8, rel=1e-3))
    assert per_token["attention"] / 24 == pytest.approx(10.8, rel=0.02)
    assert per_token["head"] / 4 == pytest.approx(201.3, rel=1e-3)
    assert sum(per_token.values()) == pytest.approx(3530, rel=2e-3)
    workload = looped_lm.build(config)
    assert workload.train_flops_per_sample == flops.train_flops_per_sample(
        config, pairs)
    # 6 N D would count a layer once: a quarter of the stack's work
    n_d = 6 * config["parameters"]
    assert workload.train_flops_per_sample / n_d == pytest.approx(3.46, 0.01)
    # a step, as time at the chip's peak
    assert workload.train_flops_per_sample * 8192 / 197e12 == (
        pytest.approx(0.440, rel=5e-3))
    stack, exits = (flops.train_flops_per_step(config, pairs, 1, parts)
                    for parts in (flops.STACK, flops.EXITS))
    assert exits / (stack + exits) == pytest.approx(0.228, abs=0.002)


# -- the four readers ---------------------------------------------------------

TEXT = """
  %fusion.1 = bf16[1,8192,2048]{2,1,0} fusion(%p.1), kind=kOutput, calls=%fc.1, metadata={op_name="jit(one_step)/jvp()/while/body/while/body/closed_call/hvtpu:loop.proj/dot_general"}
  %fusion.2 = bf16[1,8192,11264]{2,1,0} fusion(%p.2), kind=kOutput, calls=%fc.2, metadata={op_name="jit(one_step)/transpose(jvp())/while/body/while/body/closed_call/checkpoint/rematted_computation/hvtpu:loop.mlp/dot_general"}
  %hvtpu_flash_attention_fwd.3 = (bf16[1,8192,16,128]{3,2,1,0}) custom-call(%p.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(one_step)/jvp()/while/body/while/body/closed_call/hvtpu:attention/pallas_call"}
  %fusion.4 = f32[1,8192]{1,0} fusion(%p.4), kind=kLoop, calls=%fc.4, metadata={op_name="jit(one_step)/jvp()/while/body/checkpoint/hvtpu:loop.exit/reduce_sum"}
  %fusion.5 = f32[8192,49152]{1,0} fusion(%p.5), kind=kOutput, calls=%fc.5, metadata={op_name="jit(one_step)/transpose(jvp())/while/body/checkpoint/rematted_computation/hvtpu:lm_head/dot_general"}
  %fusion.6 = f32[49152,2048]{1,0} fusion(%p.6), kind=kLoop, calls=%fc.6, metadata={op_name="jit(one_step)/transpose(jvp())/hvtpu:lm_head/scatter-add"}
  %fusion.7 = f32[8]{0} fusion(%p.7), kind=kLoop, calls=%fc.7, metadata={op_name="jit(one_step)/mul"}
  %copy.8 = f32[8]{0} copy(%p.8)
"""


def _observations(cell, op_ms, text=TEXT):
    return observations_of(cell, op_ms, text=text)


def test_the_four_readers_by_hand(cell, capsys):
    obs = _observations(cell, {
        "fusion.1 fusion bf16[1,8192,2048]": 200.0,
        "fusion.2 fusion bf16[1,8192,11264]": 300.0,
        "hvtpu_flash_attention_fwd.3 custom-call (bf16[1,8192,16,128])": 100.0,
        "fusion.4 fusion f32[1,8192]": 10.0,
        "fusion.5 fusion f32[8192,49152]": 180.0,
        "fusion.6 fusion f32[49152,2048]": 20.0,
        "fusion.7 fusion f32[8]": 7.0, "copy.8 copy f32[8]": 3.0})
    read = {name: cells.load_metric("per_layer", name).read(obs)
            for name in NEW_METRICS}
    assert read["loop_stack_ms_per_step"] == pytest.approx(600.0)
    assert read["loop_exit_ms_per_step"] == pytest.approx(210.0)
    pairs = packed.expected_pairs_per_row(cell.config)
    stack, exits = (flops.train_flops_per_step(cell.config, pairs, 1, parts)
                    for parts in (flops.STACK, flops.EXITS))
    assert read["loop_stack_roofline"] == pytest.approx(
        100 * (1e3 * stack / 197e12) / 600.0)
    assert read["loop_stack_roofline"] == pytest.approx(56.6, abs=0.3)
    # the heads over the time under hvtpu:lm_head, not the gate's
    assert read["loop_exit_roofline"] == pytest.approx(
        100 * (1e3 * exits / 197e12) / 200.0)
    assert read["loop_exit_roofline"] == pytest.approx(50.2, abs=0.1)
    line = capsys.readouterr().out
    assert "unscoped 10.000" in line and "(+0.00 %)" in line
    assert "hvtpu:loop.mlp 300.000" in line


def test_the_readers_find_nothing_where_the_program_has_no_such_scopes(cell):
    """A parent commit's step, a CPU rehearsal, another cell's program
    (which has ``hvtpu:attention`` and ``hvtpu:lm_head`` of its own):
    None, never a raise."""
    nameless = _observations(
        cell, {"fusion.7 fusion f32[8]": 7.0},
        text='%fusion.7 = f32[8]{0} fusion(%p), '
             'metadata={op_name="jit(one_step)/mul"}')
    untraced = types.SimpleNamespace(
        trace=None, compiled_text=TEXT, config=cell.config,
        traffic=cell.traffic, device_kind="TPU v5 lite")
    another = _observations(
        cell, {"hvtpu_flash_attention_fwd.3 custom-call "
               "(bf16[1,8192,16,128])": 7.0,
               "fusion.5 fusion f32[8192,49152]": 9.0},
        text="\n".join(line for line in TEXT.splitlines()
                       if "hvtpu:loop." not in line))
    for name in NEW_METRICS:
        reader = cells.load_metric("per_layer", name)
        assert reader.read(nameless) is None
        assert reader.read(untraced) is None
        assert reader.read(another) is None


def test_the_readers_on_a_recorded_extract(cell, capsys):
    """The twelve traced steps of the cell's own run on the v5e and the
    lines of its compiled step that name an op of the extract
    (``data/PROVENANCE-pr34.txt``): the four readers, and the reduction
    under them, give the numbers that run printed.  (Twelve and not
    five: a step's time goes with its row's documents, so five steps
    are not the run.)"""
    reduction = xplane.reduce(xplane.load_extract(
        os.path.join(DATA, CELL + ".12steps.json.gz")))
    with gzip.open(os.path.join(DATA, CELL + ".hlo-lines.txt.gz"),
                   "rt") as f:
        text = f.read()
    with open(os.path.join(DATA, CELL + ".printed.json")) as f:
        printed = {k: v["value"] for k, v in json.load(f).items()}
    obs = types.SimpleNamespace(
        trace=reduction, compiled_text=text, config=cell.config,
        traffic=cell.traffic, device_kind="TPU v5 lite")
    read = {name: cells.load_metric("per_layer", name).read(obs)
            for name in NEW_METRICS}
    by_scope = scopes.ms_per_step(reduction, text)
    assert {"hvtpu:loop.proj", "hvtpu:loop.mlp", "hvtpu:loop.exit",
            "hvtpu:attention", "hvtpu:lm_head", scopes.UNSCOPED} == set(
                by_scope)
    steps = len(reduction.devices[0].step_ns)
    assert sum(by_scope.values()) == pytest.approx(
        1e3 * reduction.busy_s / steps, rel=1e-6)
    assert reduction.device_step_ms == pytest.approx(
        printed["device_step_ms"], rel=1e-9)
    for name in NEW_METRICS:
        assert read[name] == pytest.approx(printed[name], rel=1e-9)
    assert read["loop_stack_ms_per_step"] == pytest.approx(
        by_scope["hvtpu:loop.proj"] + by_scope["hvtpu:loop.mlp"]
        + by_scope["hvtpu:attention"])
    assert 0 < read["loop_stack_roofline"] < 100
    assert 0 < read["loop_exit_roofline"] < 100
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
    first = float(next(line for line in lines if "warm-up steps" in line)
                  .split("losses [")[1].split(",")[0])
    assert first == pytest.approx(math.log(96) - 0.121, abs=0.1)
    wanted = {m["name"] for m in BENCH[kind]
              if CELL in m.get("workloads", [CELL])}
    if kind == "per_layer":     # the new ones read the device trace too
        wanted -= NEED_A_CHIP | set(NEW_METRICS)
    read = next(line for line in lines if f"{kind} metrics read: " in line)
    found = ast.literal_eval(read.split("metrics read: ")[1].split(";")[0])
    assert set(found) == wanted


def test_the_comparison_walks_through_the_rehearsal():
    proc = _run("compare_ouro.py", "--workload", CELL, "--seed", "5",
                "--rehearse-on-cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    assert lines[-1] == "REHEARSAL not a chip result"
    assert all(line.startswith("REHEARSAL ") for line in lines)
    # ten stacked leaves of the layers and five more
    assert sum("as it is: gradient [" in line for line in lines) == 15
    assert any("as it is: update: distance " in line
               and "passed by nothing" in line for line in lines)


def test_off_a_tpu_nothing_is_compared():
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "compare_ouro.py"),
         "--workload", CELL], capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert "Nothing was compared" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
