"""``run.py`` as a command: every cell walked at toy size on virtual CPU
devices, the refusal to measure off a TPU, and a further cell added with
one traffic file and one entry and no change to any file that is there.
Each case is a child process, as the driver would start it."""

import ast
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import cells

BENCH = cells.load_benchmark()
RUN = os.path.join(cells.HERE, "run.py")
# metrics a CPU has nothing to read for: device trace, peaks, allocator
NEED_A_CHIP = {"device_step_ms", "model_flops_utilization",
               "train_step_roofline", "collective_ms_per_step",
               "exposed_collective_ms_per_step", "device_idle_share",
               "peak_hbm_gib"}


def run(*args, script=RUN, env=None):
    return subprocess.run(
        [sys.executable, script, *args], capture_output=True, text=True,
        timeout=900, env={**os.environ, **(env or {})})


def check_rehearsal(proc, cell, kind):
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    assert lines and all(line.startswith("REHEARSAL ") for line in lines)
    assert lines[-1] == "REHEARSAL not a chip result"
    for line in lines:                       # never a result line
        assert not line.lstrip().startswith("{")
    text = proc.stdout
    assert f"cell {cell['name']} ({cell['chips']} chip(s))" in text
    assert f"count={cell['chips']}" in text
    assert "0 compilation(s) in the window" in text
    checks = next(line for line in lines if " checks: " in line)
    assert "False" not in checks, checks
    wanted = {m["name"] for m in BENCH[kind]
              if cell["name"] in m.get("workloads", [cell["name"]])}
    if kind == "per_layer":
        wanted -= NEED_A_CHIP
    read = next(line for line in lines if f"{kind} metrics read: " in line)
    found = ast.literal_eval(read.split("metrics read: ")[1].split(";")[0])
    assert set(found) == wanted


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_walks_through_the_rehearsal(cell):
    proc = run("--workload", cell["name"], "--seed", "5", "--seconds", "2",
               "--trace", "1", "--rehearse-on-cpu")
    check_rehearsal(proc, cell, "per_layer")
    if cell["chips"] > 1:
        assert "'replicas_bit_equal': True" in proc.stdout
        assert "'one_batch_shard_per_chip': True" in proc.stdout


def test_the_end_to_end_metrics_are_read_too():
    cell = BENCH["workloads"][0]
    proc = run("--workload", cell["name"], "--seed", "6", "--seconds", "2",
               "--trace", "0", "--rehearse-on-cpu")
    check_rehearsal(proc, cell, "end_to_end")


def test_off_a_tpu_nothing_is_measured():
    cell = BENCH["workloads"][0]
    proc = run("--workload", cell["name"], "--seed", "1", "--seconds", "1",
               "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert "Nothing was measured" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_without_the_program_nothing_is_measured(tmp_path):
    shutil.copytree(cells.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path)
    proc = run("--workload", BENCH["workloads"][0]["name"], "--seconds", "1",
               script=str(tmp_path / "benchmark" / "run.py"),
               env={"PYTHONPATH": "", "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "not in this checkout" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_a_further_cell_is_one_traffic_file_and_one_entry(tmp_path):
    """A copy of the benchmark, to which only files and entries are
    added: a mix of 8 samples a chip, and a cell of it on four chips."""
    shutil.copytree(cells.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(tmp_path / "benchmark" / "traffic" / "b64-hostfeed.json") as f:
        mix = json.load(f)
    mix.update(name="b8-hostfeed", batch_per_chip=8)
    with open(tmp_path / "benchmark" / "traffic" / "b8-hostfeed.json",
              "w") as f:
        json.dump(mix, f)
    bench = json.loads(json.dumps(BENCH))
    cell = {"name": "resnet50-b8-dp4", "config": "resnet50",
            "traffic": "b8-hostfeed", "chips": 4, "why": "a test's"}
    assert cell["name"] not in {w["name"] for w in bench["workloads"]}
    bench["workloads"].append(cell)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    proc = run("--workload", "resnet50-b8-dp4", "--seed", "7", "--seconds",
               "1", "--trace", "0", "--rehearse-on-cpu",
               script=str(tmp_path / "benchmark" / "run.py"),
               env={"PYTHONPATH": cells.ROOT})  # the program, not the copy
    check_rehearsal(proc, cell, "end_to_end")
    assert "batch 4/chip x 4 chip(s)" in proc.stdout
