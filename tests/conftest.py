"""Test harness: 8 virtual CPU devices, one process.

This replicates the reference's localhost-as-cluster pattern
(SURVEY.md §4: all "multi-node" CI is N processes on loopback): here the
world is N=8 XLA CPU devices in one process, and test bodies are SPMD
(rank-oblivious shard_map bodies), the analog of tests running under
``horovodrun -np 8``.

The platform and device count go through ``jax.config`` before the
first backend touch, so the harness does not depend on the caller's
``JAX_PLATFORMS`` / ``XLA_FLAGS``.
"""

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _ensure_cpu_devices():
    assert jax.device_count() == 8, (
        "test harness expected 8 virtual CPU devices, got "
        f"{jax.device_count()}"
    )
    yield


@pytest.fixture()
def hvt(tmp_path, monkeypatch):
    """Fresh-initialized horovod_tpu for a test, shut down afterwards.

    The flight recorder is pointed at a tmp dir so a test that trips a
    fatal path (stall abort, audit abort) dumps its postmortem there
    instead of littering the repo root."""
    import horovod_tpu as hvt_mod

    monkeypatch.setenv("HVTPU_FLIGHT_DIR", str(tmp_path))
    hvt_mod.init()
    yield hvt_mod
    hvt_mod.shutdown()


@pytest.fixture(scope="session")
def world_axis():
    return "world"


def make_discovery_script(tmp_path, spec: str):
    """Shared elastic-driver discovery fixture: a script printing the
    (rewritable) hosts file — used by the elastic integration tests
    (which mutate the file mid-run) and the CLI example smokes."""
    hosts_file = tmp_path / "hosts.txt"
    hosts_file.write_text(spec + "\n")
    script = tmp_path / "discover.sh"
    script.write_text(f'#!/bin/sh\ncat "{hosts_file}"\n')
    script.chmod(0o755)
    return hosts_file, str(script)
