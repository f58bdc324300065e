"""End-to-end CLI launches: ``hvtpurun -np N python examples/...`` as a
real subprocess invocation — the reference's `horovodrun -np 2 python
train.py` acceptance path."""

import os
import subprocess
import sys

import pytest

import horovod_tpu

pytestmark = pytest.mark.multiprocess

_REPO = os.path.dirname(os.path.dirname(horovod_tpu.__file__))


# jaxlib's gloo CPU transport occasionally drops a connection under
# parallel localhost load (a rank SIGSEGVs; peers report "Connection
# closed by peer").  That race lives below this framework — retry the
# whole launch (core/retry.py's named gloo-teardown policy) so the
# acceptance assertions still gate every example, but an infra crash
# alone doesn't flake CI.
from horovod_tpu.core import retry as core_retry


def _gloo_race(res):
    return (res.returncode != 0
            and core_retry.is_gloo_infra_error(res.stdout + res.stderr))


def _hvtpurun(args, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return core_retry.call(
        core_retry.gloo_teardown_policy(max_attempts=3,
                                        retry_result=_gloo_race),
        lambda: subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner"] + args,
            capture_output=True, text=True, timeout=timeout, env=env,
            cwd=_REPO,
        ))


def test_cli_jax_mnist_2proc():
    res = _hvtpurun([
        "-np", "2", "--cpu-devices", "1", "--",
        sys.executable, os.path.join(_REPO, "examples", "train_mnist.py"),
        "--epochs", "1", "--train-size", "256", "--batch-size", "64",
    ])
    assert res.returncode == 0, res.stderr[-2000:]
    assert "ranks consistent (2 ranks" in res.stdout


def test_cli_torch_mnist_2proc():
    res = _hvtpurun([
        "-np", "2", "--cpu-devices", "1", "--",
        sys.executable, os.path.join(_REPO, "examples", "pytorch_mnist.py"),
        "--epochs", "1", "--train-size", "256", "--batch-size", "64",
    ])
    assert res.returncode == 0, res.stderr[-2000:]
    assert "ranks consistent (2 ranks)" in res.stdout


@pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
def test_cli_tf_keras_mnist_2proc():
    res = _hvtpurun([
        "-np", "2", "--cpu-devices", "1", "--",
        sys.executable,
        os.path.join(_REPO, "examples", "tensorflow2_keras_mnist.py"),
        "--epochs", "1", "--train-size", "256", "--batch-size", "64",
    ], timeout=420)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "ranks consistent (2 ranks)" in res.stdout


@pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
def test_cli_torch_adasum_2proc():
    res = _hvtpurun([
        "-np", "2", "--cpu-devices", "1", "--",
        sys.executable,
        os.path.join(_REPO, "examples", "pytorch_mnist_adasum.py"),
        "--epochs", "1", "--train-size", "256", "--batch-size", "64",
    ])
    assert res.returncode == 0, res.stderr[-2000:]
    assert "ranks consistent (2 ranks)" in res.stdout


@pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
def test_cli_tf2_custom_loop_2proc():
    res = _hvtpurun([
        "-np", "2", "--cpu-devices", "1", "--",
        sys.executable,
        os.path.join(_REPO, "examples", "tensorflow2_mnist.py"),
        "--steps", "8",
    ], timeout=420)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "ranks consistent (2 ranks)" in res.stdout


def _static_discovery(tmp_path, slots=2):
    from conftest import make_discovery_script

    _hosts, script = make_discovery_script(tmp_path,
                                           f"localhost:{slots}")
    return script


@pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
def test_cli_torch_elastic_example(tmp_path):
    res = _hvtpurun([
        "--host-discovery-script", _static_discovery(tmp_path),
        "--min-np", "2", "--cpu-devices", "1", "--",
        sys.executable,
        os.path.join(_REPO, "examples", "pytorch_mnist_elastic.py"),
    ], timeout=420)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "ranks consistent (2 ranks)" in res.stdout


@pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
def test_cli_keras_elastic_example(tmp_path):
    res = _hvtpurun([
        "--host-discovery-script", _static_discovery(tmp_path),
        "--min-np", "2", "--cpu-devices", "1", "--",
        sys.executable,
        os.path.join(_REPO, "examples",
                     "tensorflow2_keras_mnist_elastic.py"),
    ], timeout=420)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "ranks consistent (2 ranks)" in res.stdout


def test_cli_failure_exit_code():
    res = _hvtpurun([
        "-np", "2", "--cpu-devices", "1", "--",
        sys.executable, "-c", "import sys, os; "
        "sys.exit(3 if os.environ['HVTPU_RANK'] == '1' else 0)",
    ])
    assert res.returncode == 3
    assert "rank 1 exited with code 3" in res.stderr


def test_hybrid_transformer_example():
    """The post-parity parallel-layer example must run (single process,
    8 virtual CPU devices, dp x pp x tp + sp + ep)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "examples", "transformer_hybrid.py"),
         "--steps", "4", "--d-model", "32", "--layers", "2"],
        capture_output=True, text=True, timeout=420, env=env, cwd=_REPO,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "hybrid-parallel training OK" in res.stdout
