"""What can be held to chip_smoke.py's contract without a chip: it
refuses the CPU, the compile cache is placed from outside, an unknown
device has no peak, and the launcher hands each local rank its own chip
(or refuses before spawning)."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_ROOT, "chip_smoke.py")


def _run_smoke(**env_overrides):
    env = {k: v for k, v in os.environ.items()
           if k not in ("HVTPU_PALLAS_INTERPRET", "HVTPU_CPU_DEVICES")}
    env.update(env_overrides)
    return subprocess.run([sys.executable, _SMOKE], env=env,
                          capture_output=True, text=True, timeout=300)


class TestChipSmokeContract:
    def test_refuses_the_cpu_and_names_it(self):
        r = _run_smoke(JAX_PLATFORMS="cpu")
        assert r.returncode == 2, r.stderr[-2000:]
        assert "platform='cpu'" in r.stderr
        assert "JAX_PLATFORMS" in r.stderr
        # no result line, whatever else it reported
        assert '"ok"' not in r.stdout

    def test_interpreter_in_the_environment_is_a_failure(self):
        r = _run_smoke(JAX_PLATFORMS="cpu", HVTPU_PALLAS_INTERPRET="1")
        assert r.returncode == 1
        assert "HVTPU_PALLAS_INTERPRET" in r.stderr
        assert r.stdout == ""


# compile_cache.py imports nothing of the package, so a child can load
# it by path without paying for jax
_HELPER = os.path.join(_ROOT, "horovod_tpu", "core", "compile_cache.py")
_PROBE = (
    "import importlib.util, sys\n"
    "spec = importlib.util.spec_from_file_location('cc', sys.argv[1])\n"
    "m = importlib.util.module_from_spec(spec)\n"
    "spec.loader.exec_module(m)\n"
    "print(m.compile_cache_dir())\n")


def _cache_dir_in_child(cwd, **env_overrides):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, "-c", _PROBE, _HELPER], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=60, check=True
    ).stdout.strip()


class TestCompileCache:
    def test_environment_directory_is_returned_untouched(self, tmp_path):
        want = str(tmp_path / "elsewhere")
        got = _cache_dir_in_child(
            str(tmp_path), JAX_COMPILATION_CACHE_DIR=want)
        assert got == want
        assert not os.path.exists(want)  # JAX's to create, not ours

    def test_default_is_the_same_checkout_path_in_two_processes(
            self, tmp_path):
        a = _cache_dir_in_child(str(tmp_path))
        b = _cache_dir_in_child(_ROOT)
        assert a == b == os.path.join(_ROOT, ".jax_cache")

    def test_enable_sets_jax_only_when_the_variable_is_unset(
            self, monkeypatch, tmp_path):
        import jax

        import horovod_tpu as hvt

        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda key, value: updates.append((key, value)))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert hvt.enable_compile_cache() == str(tmp_path)
        assert updates == []  # JAX reads the variable by itself
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = hvt.enable_compile_cache()
        assert path == os.path.join(_ROOT, ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", path)]

    def test_launcher_hands_workers_the_same_directory(self, monkeypatch):
        from horovod_tpu.runner import launch
        from horovod_tpu.runner.hosts import SlotInfo

        slot = SlotInfo(hostname="localhost", rank=0, size=1, local_rank=0,
                        local_size=1, cross_rank=0, cross_size=1)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        env = launch.build_worker_env({}, slot, "127.0.0.1", 1)
        assert env["JAX_COMPILATION_CACHE_DIR"] == os.path.join(
            _ROOT, ".jax_cache")
        env = launch.build_worker_env(
            {"JAX_COMPILATION_CACHE_DIR": "/outside"}, slot, "127.0.0.1", 1)
        assert env["JAX_COMPILATION_CACHE_DIR"] == "/outside"


class TestPeakTable:
    def test_unknown_device_kind_raises(self, monkeypatch):
        from horovod_tpu.obs import stepprof

        monkeypatch.delenv("HVTPU_STEPPROF_PEAK_TFLOPS", raising=False)
        assert stepprof.peak_flops("TPU v5 lite") == 197e12
        with pytest.raises(LookupError, match="no such chip"):
            stepprof.peak_flops("no such chip")
        # the default reads this process's device: the CPU has no peak
        with pytest.raises(LookupError, match="cpu"):
            stepprof.peak_flops()

    def test_explicit_override_still_wins(self, monkeypatch):
        from horovod_tpu.obs import stepprof

        monkeypatch.setenv("HVTPU_STEPPROF_PEAK_TFLOPS", "100")
        assert stepprof.peak_flops("no such chip") == 100e12


_CHIP_KEYS = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
              "TPU_PROCESS_BOUNDS", "TPU_PROCESS_ADDRESSES",
              "TPU_PROCESS_PORT", "CLOUD_TPU_TASK_ID")


class TestChipAssignment:
    def _envs(self, argv, np=4, base_env=None):
        from horovod_tpu.runner import launch
        from horovod_tpu.runner.hosts import (get_host_assignments,
                                              parse_host_spec)

        args = launch.parse_args(argv + ["--", "python", "x.py"])
        slots = get_host_assignments(
            parse_host_spec(f"localhost:{np}"), np)
        return [launch.build_worker_env(
            dict(base_env or {}), s, "127.0.0.1", 1234, args)
            for s in slots]

    def test_four_local_ranks_get_four_distinct_chips(self):
        envs = self._envs(["-np", "4"])
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == list("0123")
        assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == list("0123")
        assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
        for e in envs:
            assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert e["TPU_PROCESS_BOUNDS"] == "2,2,1"
            addrs = e["TPU_PROCESS_ADDRESSES"].split(",")
            assert addrs == envs[0]["TPU_PROCESS_ADDRESSES"].split(",")
            assert f"localhost:{e['TPU_PROCESS_PORT']}" == addrs[
                int(e["CLOUD_TPU_TASK_ID"])]

    def test_cpu_devices_gives_none(self):
        for envs in (
                self._envs(["-np", "4", "--cpu-devices", "1"]),
                self._envs(["-np", "4"],
                           base_env={"HVTPU_CPU_DEVICES": "2"})):
            for e in envs:
                assert not set(_CHIP_KEYS) & set(e), e

    def test_one_rank_per_host_keeps_all_its_chips(self):
        (env,) = self._envs(["-np", "1"], np=1)
        assert not set(_CHIP_KEYS) & set(env)

    def test_refuses_before_any_spawn(self, monkeypatch, capsys):
        from horovod_tpu.runner import launch, safe_shell_exec
        from horovod_tpu.runner.hosts import (get_host_assignments,
                                              parse_host_spec)

        def no_spawn(*a, **kw):
            raise AssertionError("a worker was spawned")

        monkeypatch.setattr(safe_shell_exec, "WorkerProcess", no_spawn)
        monkeypatch.setattr(launch, "local_tpu_chips", lambda: 4)
        for np in (2, 3, 8):
            slots = get_host_assignments(
                parse_host_spec(f"localhost:{np}"), np)
            rc = launch.launch_workers(
                ["true"], slots, "127.0.0.1", 1, base_env={})
            assert rc == 2
            assert (f"{np} ranks on a host with 4 TPU chip(s)"
                    in capsys.readouterr().err)
        # four ranks on an eight-chip host is a partial layout too
        slots = get_host_assignments(parse_host_spec("localhost:4"), 4)
        assert "host with 8 TPU" in launch.check_chip_assignment(
            slots, cpu_mode=False, chips=8)
        assert launch.check_chip_assignment(
            slots, cpu_mode=False, chips=4) is None
        # a CPU run of the same shape is nobody's business
        slots = get_host_assignments(parse_host_spec("localhost:3"), 3)
        assert launch.check_chip_assignment(slots, cpu_mode=True) is None
        # and neither is a host without chips
        assert launch.check_chip_assignment(
            slots, cpu_mode=False, chips=0) is None
