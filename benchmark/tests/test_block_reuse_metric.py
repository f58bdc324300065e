"""``input_block_reuse_share``: what it makes of a made-up pair of
totals, that it reads nothing and does not raise from a program without
the counter (the parent commit), what a real source's fetches come to,
and that a cell's rehearsal prints it."""

import ast
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells

NAME = "input_block_reuse_share"
RUN = os.path.join(cells.HERE, "run.py")


@pytest.fixture
def reader():
    return cells.load_metric("per_layer", NAME)


@pytest.fixture
def program_counters(monkeypatch):
    """The program's snapshot, replaced by a dict the test fills."""
    from horovod_tpu.obs import metrics

    families = {}
    monkeypatch.setattr(metrics, "snapshot", lambda: families)
    return families


def test_it_is_declared_as_its_file_says(reader):
    entry = next(m for m in cells.load_benchmark()["per_layer"]
                 if m["name"] == NAME)
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        reader.LAYER, reader.UNIT, reader.MOVES)
    assert (entry["source"], entry["better"]) == (
        "program_counter", "higher")


@pytest.mark.parametrize("values, want", [
    ({'{block="reused"}': 285.0, '{block="fresh"}': 15.0}, 95.0),
    ({'{block="reused"}': 7.0}, 100.0),
    ({'{block="fresh"}': 3.0}, 0.0),
    ({}, 0.0),      # the counter is there and no leaf was large enough
])
def test_the_share_of_a_made_up_pair_of_totals(
        reader, program_counters, values, want):
    program_counters[reader.COUNTER] = {"type": "counter", "values": values}
    assert reader.read(None) == pytest.approx(want)


def test_a_program_without_the_counter_reads_nothing(
        reader, program_counters, monkeypatch):
    program_counters["hvtpu_data_fetch_seconds"] = {"values": {"": 1.0}}
    assert reader.read(None) is None
    # nor without the program's metrics at all
    monkeypatch.setitem(sys.modules, "horovod_tpu.obs.metrics", None)
    assert reader.read(None) is None


def test_a_real_source_whose_batches_are_dropped(reader):
    from horovod_tpu.data import ArraySource
    from horovod_tpu.obs import metrics

    def totals():
        family = metrics.REGISTRY.counter(reader.COUNTER)
        return family.value(block="reused"), family.value(block="fresh")

    rows = np.zeros((32, 1 << 16), dtype=np.uint8)
    source = ArraySource(rows)
    reused0, fresh0 = totals()
    for k in range(20):
        source.fetch(np.arange(k % 8, k % 8 + 16))
    reused, fresh = totals()
    assert (reused - reused0, fresh - fresh0) == (19, 1)
    assert 0.0 < reader.read(None) <= 100.0


def test_a_cells_rehearsal_prints_it():
    cell = cells.load_benchmark()["workloads"][1]
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell["name"], "--seed", "9",
         "--seconds", "1", "--trace", "1", "--rehearse-on-cpu"],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    read = next(line for line in proc.stdout.splitlines()
                if "per_layer metrics read: " in line)
    found = ast.literal_eval(read.split("metrics read: ")[1].split(";")[0])
    assert NAME in found
