"""RayExecutor's actor path, driven by a MOCKED ray
module — the same pattern the reference uses to unit-test its launcher
with mocked ssh (SURVEY §4.3).  Asserts actors are created with the
requested resources, each rank's env carries the launcher-equivalent
topology, results come back rank-ordered, and shutdown kills actors.
"""

import sys
import types

import pytest


class _FakeRef:
    """Stands in for a Ray ObjectRef."""

    def __init__(self, value):
        self.value = value


class _FakeMethod:
    def __init__(self, bound):
        self._bound = bound

    def remote(self, *args, **kwargs):
        return _FakeRef(self._bound(*args, **kwargs))


class _FakeActorHandle:
    def __init__(self, instance):
        self._instance = instance
        self.killed = False

    def __getattr__(self, name):
        return _FakeMethod(getattr(self._instance, name))


def _make_fake_ray(node_ips):
    """A minimal in-process ray: remote() records resource opts and
    wraps the class so .remote() constructs instances synchronously;
    node_info is overridden to walk the scripted node ip list."""
    ray = types.ModuleType("ray")
    state = {
        "remote_opts": [], "actors": [], "killed": [],
        "ips": list(node_ips), "next_ip": 0, "next_port": 29600,
    }
    ray._state = state

    def is_initialized():
        return True

    def remote(**opts):
        state["remote_opts"].append(opts)

        class _Factory:
            def __init__(self, cls):
                self._cls = cls

            def remote(self):
                inst = self._cls()
                ip = state["ips"][state["next_ip"] % len(state["ips"])]
                state["next_ip"] += 1
                state["next_port"] += 1
                port = state["next_port"]

                def node_info():
                    return ip, port

                inst.node_info = node_info
                h = _FakeActorHandle(inst)
                state["actors"].append(h)
                return h

        return _Factory

    def get(refs):
        if isinstance(refs, list):
            return [r.value for r in refs]
        return refs.value

    def kill(handle):
        handle.killed = True
        state["killed"].append(handle)

    ray.is_initialized = is_initialized
    ray.remote = remote
    ray.get = get
    ray.kill = kill
    return ray


@pytest.fixture
def fake_ray(monkeypatch):
    ray = _make_fake_ray(["10.0.0.1", "10.0.0.1", "10.0.0.2", "10.0.0.2"])
    monkeypatch.setitem(sys.modules, "ray", ray)
    import horovod_tpu.ray as ray_mod

    class _IsolatedWorker(ray_mod._ActorWorker):
        """The fake actors run IN-PROCESS: setup must not leak
        HVTPU_* into the test process's os.environ."""

        def setup(self, env):
            self.env = dict(env)
            return True

    monkeypatch.setattr(ray_mod, "_ActorWorker", _IsolatedWorker)
    return ray


class TestRayActorPath:
    def test_actors_created_with_resources(self, fake_ray):
        import horovod_tpu.ray as ray_mod

        ex = ray_mod.RayExecutor(num_workers=4, cpus_per_worker=3)
        ex.start()
        st = fake_ray._state
        # resource request reached ray.remote; one actor per rank
        assert st["remote_opts"] == [{"num_cpus": 3}]
        assert len(st["actors"]) == 4
        ex.shutdown()

    def test_env_assignment_and_rank_order(self, fake_ray):
        import horovod_tpu.ray as ray_mod

        recorded = []

        class RecordingWorker(ray_mod._ActorWorker):
            def setup(self, env):
                recorded.append(dict(env))
                self.env = dict(env)
                return True

            def execute(self, fn, args=(), kwargs=None):
                return (int(self.env["HVTPU_RANK"]),
                        fn(*args, **(kwargs or {})))

        orig = ray_mod._ActorWorker
        ray_mod._ActorWorker = RecordingWorker
        try:
            ex = ray_mod.RayExecutor(num_workers=4)
            ex.start()
            results = ex.run(lambda a: a * 2, args=(21,))
        finally:
            ray_mod._ActorWorker = orig
        assert [int(e["HVTPU_RANK"]) for e in recorded] == [0, 1, 2, 3]
        assert all(e["HVTPU_SIZE"] == "4" for e in recorded)
        # two ranks per fake node: local/cross topology per host
        assert [e["HVTPU_LOCAL_RANK"] for e in recorded] == \
            ["0", "1", "0", "1"]
        assert all(e["HVTPU_LOCAL_SIZE"] == "2" for e in recorded)
        assert [e["HVTPU_CROSS_RANK"] for e in recorded] == \
            ["0", "0", "1", "1"]
        assert all(e["HVTPU_CROSS_SIZE"] == "2" for e in recorded)
        assert all(e["HVTPU_UNIFORM_LOCAL_SIZE"] == "2" for e in recorded)
        # every rank points at rank 0's node for coordination
        addr0 = recorded[0]["HVTPU_COORDINATOR_ADDR"]
        port0 = recorded[0]["HVTPU_COORDINATOR_PORT"]
        assert addr0 == "10.0.0.1"
        assert all(e["HVTPU_COORDINATOR_ADDR"] == addr0 for e in recorded)
        assert all(e["HVTPU_COORDINATOR_PORT"] == port0 for e in recorded)
        # results come back rank-ordered
        assert results == [(0, 42), (1, 42), (2, 42), (3, 42)]

    def test_run_remote_returns_refs_execute_resolves(self, fake_ray):
        import horovod_tpu.ray as ray_mod

        ex = ray_mod.RayExecutor(num_workers=2)
        ex.start()
        refs = ex.run_remote(lambda: "x")
        assert all(isinstance(r, _FakeRef) for r in refs)
        assert ex.execute(refs) == ["x", "x"]
        ex.shutdown()

    def test_shutdown_kills_actors(self, fake_ray):
        import horovod_tpu.ray as ray_mod

        ex = ray_mod.RayExecutor(num_workers=3)
        ex.start()
        ex.shutdown()
        st = fake_ray._state
        assert len(st["killed"]) == 3
        assert ex._actors is None

    def test_env_vars_forwarded(self, fake_ray):
        import horovod_tpu.ray as ray_mod

        recorded = []

        class RecordingWorker(ray_mod._ActorWorker):
            def setup(self, env):
                recorded.append(dict(env))
                return True

        orig = ray_mod._ActorWorker
        ray_mod._ActorWorker = RecordingWorker
        try:
            ex = ray_mod.RayExecutor(
                num_workers=2, env_vars={"MY_FLAG": "7"})
            ex.start()
        finally:
            ray_mod._ActorWorker = orig
        assert all(e["MY_FLAG"] == "7" for e in recorded)

    def test_gpu_request_forwarded(self, fake_ray):
        import horovod_tpu.ray as ray_mod

        ex = ray_mod.RayExecutor(num_workers=1, use_gpu=True,
                                 gpus_per_worker=2)
        ex.start()
        assert fake_ray._state["remote_opts"][-1] == {
            "num_cpus": 1, "num_gpus": 2}
        ex.shutdown()


class TestLocalFallback:
    def test_no_ray_module_falls_back(self, monkeypatch):
        """ray not importable: start() arms the local path and run()
        still goes through the launcher machinery."""
        monkeypatch.setitem(sys.modules, "ray", None)
        import horovod_tpu.ray as ray_mod

        assert ray_mod._probe_ray() is None
        ex = ray_mod.RayExecutor(num_workers=2)
        ex.start()
        assert ex._actors is None

    def test_uninitialized_ray_falls_back(self, monkeypatch):
        ray = types.ModuleType("ray")
        ray.is_initialized = lambda: False
        monkeypatch.setitem(sys.modules, "ray", ray)
        import horovod_tpu.ray as ray_mod

        assert ray_mod._probe_ray() is None
