"""The driver's benchmark, rehearsed: ``benchmark/run.py`` is what decides
a PR on the chip, and nothing under ``tests/`` started it.  Every cell
of ``BENCHMARK.json`` is walked here at toy size on virtual CPU devices,
untraced (the driver's timed pairs) and traced (the per-layer metrics),
each as the child process the driver would start; and every metric, span
and scope name the benchmark's own files spell out is held to what the
program registers or emits, by reading both sides.  A rehearsal is never
a result: no time it prints is a device number."""

import ast
import importlib
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)            # the readers import ``benchmark``
RUN = os.path.join(ROOT, "benchmark", "run.py")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
# a per-layer metric -> its entry; the cells it is reported in
PER_LAYER = {m["name"]: m for m in BENCHMARK["per_layer"]}


def parsed(directory, skip=()):
    """(path from the checkout's root, syntax tree) of every Python file
    under a directory of the checkout."""
    for here, dirs, files in os.walk(os.path.join(ROOT, directory)):
        dirs[:] = [d for d in dirs if d not in skip]
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(here, name)
                with open(path) as f:
                    yield os.path.relpath(path, ROOT), ast.parse(f.read())


def names_the_benchmark_reads():
    """name -> the files under ``benchmark/`` (its own tests left out)
    that spell it in a string, code or docstring."""
    found = {}
    for path, tree in parsed("benchmark", skip=("tests",)):
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                for name in re.findall(r"hvtpu_[a-z_]+|hvtpu:[a-z_.]+",
                                       node.value):
                    found.setdefault(name, set()).add(path)
    return found


READ = names_the_benchmark_reads()


@pytest.fixture(scope="module")
def emitted():
    """The spans and scopes the program can emit, read from its source:
    the literal of every ``jax.named_scope(...)`` under ``horovod_tpu/``,
    and ``"hvtpu:" +`` the literal of every ``tracing.span(...)`` in
    ``data/loader.py``."""
    names = set()
    for path, tree in parsed("horovod_tpu"):
        in_loader = path == os.path.join("horovod_tpu", "data", "loader.py")
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            if node.func.attr == "named_scope":
                names.add(node.args[0].value)
            elif in_loader and node.func.attr == "span":
                names.add("hvtpu:" + node.args[0].value)
    return names


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    """One persistent cache for the module's children, outside the
    checkout: a cell's traced case loads what its untraced case
    compiled."""
    return str(tmp_path_factory.mktemp("rehearsal_jax_cache"))


def run(*args, cache):
    return subprocess.run(
        [sys.executable, RUN, *args], capture_output=True, text=True,
        timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": cache})


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_walks_through_the_rehearsal(cell, trace, compile_cache):
    proc = run("--workload", cell, "--seed", "5", "--seconds", "2",
               "--trace", trace, "--rehearse-on-cpu", cache=compile_cache)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    assert lines and all(line.startswith("REHEARSAL ") for line in lines)
    assert lines[-1] == "REHEARSAL not a chip result"
    assert not any(line.lstrip().startswith("{") for line in lines)
    checks = next(line for line in lines if " checks: " in line)
    assert "False" not in checks, checks
    assert "0 compilation(s) in the window" in proc.stdout
    if trace == "1":
        # every per-layer metric the cell reports was asked for: read, or
        # named as one the CPU has nothing to read for (a device trace,
        # peaks, the allocator: ``loop_attention_ms_per_step`` and the
        # other scope metrics need a chip)
        read = next(line for line in lines if "per_layer metrics read: " in line)
        found, absent = (set(ast.literal_eval(names))
                         for names in re.findall(r"\[.*?\]", read))
        assert found | absent == {
            name for name, entry in PER_LAYER.items()
            if cell in entry.get("workloads", CELLS)}
        assert not found & {name for name, entry in PER_LAYER.items()
                            if entry["source"] == "device_trace"}


def test_off_a_tpu_nothing_is_measured(compile_cache):
    proc = run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--trace", "0", cache=compile_cache)
    assert proc.returncode != 0
    assert "Nothing was measured" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_a_per_layer_metric_has_the_reader_its_entry_names(name):
    """``benchmark/layer_metrics/<name>.py`` is found by the entry's
    name and says of itself what the entry says: its layer, its unit and
    the end-to-end metric it moves."""
    reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
    entry = PER_LAYER[name]
    assert callable(reader.read)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert set(entry.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize(
    "name", sorted(n for n in READ if n.startswith("hvtpu_")))
def test_a_metric_the_benchmark_reads_is_registered(name):
    import horovod_tpu  # noqa: F401
    import horovod_tpu.data.loader  # noqa: F401
    import horovod_tpu.data.sources  # noqa: F401
    from horovod_tpu.obs import metrics

    assert name in metrics.snapshot(), (
        f"no metric {name} is registered; read by {sorted(READ[name])}")


@pytest.mark.parametrize(
    "name", sorted(n for n in READ if n.startswith("hvtpu:")))
def test_a_span_or_scope_the_benchmark_reads_is_emitted(name, emitted):
    found = (any(e.startswith(name) for e in emitted) if name.endswith(".")
             else name in emitted)
    assert found, (
        f"no jax.named_scope or loader span under horovod_tpu/ is called "
        f"{name}; read by {sorted(READ[name])}")
