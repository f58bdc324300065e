"""BENCHMARK.json against its contract, and every cell, configuration,
traffic mix and metric found by name from a file of its own."""

import json
import os
import re

import pytest

from benchmark import cells

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_the_file_has_the_contracts_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert BENCH["paths"] == ["benchmark"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert os.path.getsize(
        os.path.join(cells.ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in BENCH[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(len(x["why"]) <= 200
               for key in ("configs", "workloads") for x in BENCH[key])


def test_a_full_check_fits_the_drivers_limit_with_24_cells():
    runs = 2 + 14 * 24
    seconds = (runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200)
    assert seconds <= 43200


def test_cells_and_chips():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_metrics():
    end_to_end = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in end_to_end and end_to_end["setup_s"]["bound"] == 0.1
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
    for m in BENCH["per_layer"]:
        assert m["source"] in SOURCES and "bound" not in m
        assert m["moves"] in end_to_end
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_a_configuration_is_a_file_of_sizes(config):
    assert config["file"].startswith("benchmark/configs/")
    with open(os.path.join(cells.ROOT, config["file"])) as f:
        sizes = json.load(f)
    assert sizes["name"] == config["name"]
    assert sizes["source"] == config["source"]
    assert sizes["reduced"] == config["reduced"] == []
    assert sizes["sample_unit"] and sizes["assumed"] and sizes["rehearsal"]
    assert os.path.exists(os.path.join(
        cells.HERE, "builders", sizes["builder"] + ".py"))


@pytest.mark.parametrize("name", WORKLOADS)
def test_a_cell_is_found_by_name(name):
    cell = cells.load_cell(name)
    assert cell.traffic["name"] + ".json" in os.listdir(
        os.path.join(cells.HERE, "traffic"))
    assert cell.traffic["fence_lag"] == 2
    assert cell.traffic["steps_per_dispatch"] == 1
    assert cell.traffic["feed"]["host_pool_batches"] == 2
    assert cell.traffic["feed"]["dtype"] == "bfloat16"
    # every cell reports set-up, another end-to-end metric, a per-layer one
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    toy = cells.load_cell(name, rehearse=True)
    assert toy.traffic["batch_per_chip"] < cell.traffic["batch_per_chip"]
    assert toy.chips == cell.chips


def test_the_builder_refuses_widths_the_model_does_not_build():
    with open(os.path.join(cells.HERE, "configs", "vgg16.json")) as f:
        config = json.load(f)
    builder = cells.load_builder(config)
    builder.build(config)
    for key, other in (("conv_widths", [64, 128, 256, 512, 1024]),
                       ("dense_width", 2048)):
        with pytest.raises(ValueError, match="models.VGG builds"):
            builder.build({**config, key: other})


def test_an_unknown_cell_is_refused():
    with pytest.raises(SystemExit, match="no workload"):
        cells.load_cell("resnet50-b1-dp1")


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_a_metric_is_a_reader_of_its_own(kind):
    directory = os.path.join(cells.HERE, cells.METRIC_DIRS[kind])
    files = {f[:-3] for f in os.listdir(directory)
             if f.endswith(".py") and f != "__init__.py"}
    assert files == {m["name"] for m in BENCH[kind]}
    for entry in BENCH[kind]:
        reader = cells.load_metric(kind, entry["name"])
        assert callable(reader.read)
        assert reader.UNIT == entry["unit"]
        if kind == "per_layer":
            assert reader.LAYER == entry["layer"]
            assert reader.MOVES == entry["moves"]
