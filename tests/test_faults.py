"""core/faults.py: the deterministic fault-injection harness.

Unit tests drive the registry directly (grammar, selectors, seeded
probability, persistence); the acceptance smokes launch REAL 2-process
elastic jobs under ``HVTPU_FAULT_SPEC`` and assert (a) an injected
rank-kill at step 3 recovers within the restart budget, and (b) the
same failure under ``--max-restarts=0`` fails fast with the
restart-budget diagnostic.  The heavier matrix is marked ``chaos`` and
stays out of tier-1.
"""

import os
import subprocess
import sys
import time

import pytest

import horovod_tpu
from horovod_tpu.core import faults

pytestmark = []

_REPO = os.path.dirname(os.path.dirname(horovod_tpu.__file__))
_SCRIPT = os.path.join(_REPO, "tests", "elastic_train_script.py")


@pytest.fixture(autouse=True)
def _clean_registry():
    yield
    faults.uninstall()


class TestParse:
    def test_full_grammar(self):
        cs = faults.parse_spec(
            "worker.step:kill@rank=1,count=3; "
            "kv.put:error@prob=0.25,times=2; "
            "heartbeat:drop@rank=0|2; "
            "collective.pre:delay(250)@pset=1")
        assert [c.site for c in cs] == [
            "worker.step", "kv.put", "heartbeat", "collective.pre"]
        assert cs[0].action == "kill" and cs[0].times == 1  # kill: 1-shot
        assert cs[0].ranks == frozenset({1}) and cs[0].count == 3
        assert cs[1].prob == 0.25 and cs[1].times == 2
        assert cs[2].ranks == frozenset({0, 2}) and cs[2].times == 0
        assert cs[3].action == "delay" and cs[3].delay_ms == 250.0
        assert cs[3].pset == 1

    def test_corrupt_grammar(self):
        cs = faults.parse_spec(
            "collective.pre:corrupt@rank=1; "
            "collective.post:corrupt(bitflip)@count=2; "
            "collective.post:corrupt(nan)")
        assert [c.action for c in cs] == ["corrupt"] * 3
        assert cs[0].corrupt_mode == "nan"  # default
        assert cs[1].corrupt_mode == "bitflip" and cs[1].count == 2
        assert cs[2].corrupt_mode == "nan"
        assert cs[1].site == "collective.post"

    def test_storage_grammar(self):
        cs = faults.parse_spec(
            "ckpt.write:torn@rank=1,count=3; "
            "ckpt.write:bitflip@count=5,times=1; "
            "ckpt.fsync:drop; "
            "ckpt.rename:kill@rank=0,count=2")
        assert [c.site for c in cs] == [
            "ckpt.write", "ckpt.write", "ckpt.fsync", "ckpt.rename"]
        assert cs[0].action == "torn" and cs[0].times == 0  # unlimited
        assert cs[1].action == "bitflip" and cs[1].times == 1
        assert cs[3].action == "kill" and cs[3].times == 1

    @pytest.mark.parametrize("bad", [
        "kv.put:torn",              # torn only means something on bytes
        "worker.step:bitflip",
        "collective.pre:torn@rank=1",
    ])
    def test_storage_damage_limited_to_storage_sites(self, bad):
        with pytest.raises(faults.FaultSpecError):
            faults.parse_spec(bad)

    def test_partition_grammar(self):
        cs = faults.parse_spec(
            "kv.put:partition(3000)@rank=3; "
            "heartbeat:partition(250.5)@count=4,times=2; "
            "kv.get:partition(10)")
        assert [c.action for c in cs] == ["partition"] * 3
        assert cs[0].partition_ms == 3000.0
        assert cs[0].times == 1   # partition: 1-shot by default
        assert cs[1].partition_ms == 250.5
        assert cs[1].count == 4 and cs[1].times == 2
        assert cs[2].site == "kv.get" and cs[2].partition_ms == 10.0

    @pytest.mark.parametrize("bad", [
        "worker.step:partition(3000)",     # not a coordination site
        "collective.pre:partition(100)",
        "ckpt.write:partition(100)",
        "kv.put:partition()",              # missing window
        "kv.put:partition(abc)",
        "kv.put:partition(-5)",
    ])
    def test_partition_limited_to_coordination_sites(self, bad):
        with pytest.raises(faults.FaultSpecError):
            faults.parse_spec(bad)

    def test_wire_grammar(self):
        cs = faults.parse_spec(
            "wire.send:drop@rank=1,count=2,times=2; "
            "wire.recv:slow(250)@prob=0.5; "
            "collective.exec:flap(1500)")
        assert [c.site for c in cs] == [
            "wire.send", "wire.recv", "collective.exec"]
        assert cs[0].action == "drop" and cs[0].times == 2
        assert cs[0].ranks == frozenset({1}) and cs[0].count == 2
        assert cs[1].action == "slow" and cs[1].delay_ms == 250.0
        assert cs[1].prob == 0.5
        assert cs[2].action == "flap" and cs[2].flap_ms == 1500.0
        assert cs[2].times == 1  # flap: 1-shot by default

    @pytest.mark.parametrize("bad,msg", [
        ("wire.send:torn", "no durable bytes to tear"),
        ("wire.recv:bitflip", "no durable bytes to tear"),
        ("collective.exec:torn@rank=1", "no durable bytes to tear"),
        ("wire.send:corrupt", "no tensor to poison"),
        ("wire.recv:corrupt(bitflip)", "no tensor to poison"),
        ("wire.send:partition(100)", "coordination sites"),
    ])
    def test_wire_sites_reject_foreign_damage(self, bad, msg):
        """The wire sites carry no durable bytes and no tensor: the
        parser must name WHY the action is wrong and what to use."""
        with pytest.raises(faults.FaultSpecError, match=msg):
            faults.parse_spec(bad)

    @pytest.mark.parametrize("bad", [
        "kv.put:slow(100)",
        "worker.step:flap(500)",
        "ckpt.write:slow(10)",
        "heartbeat:flap(100)",
        "collective.pre:slow(50)",
    ])
    def test_slow_flap_limited_to_wire_sites(self, bad):
        with pytest.raises(faults.FaultSpecError,
                           match="only applies at wire sites"):
            faults.parse_spec(bad)

    @pytest.mark.parametrize("bad", [
        "wire.send:slow()",
        "wire.send:slow(abc)",
        "wire.recv:flap()",
        "wire.recv:flap(-5)",
    ])
    def test_malformed_wire_windows_fail_loudly(self, bad):
        with pytest.raises(faults.FaultSpecError):
            faults.parse_spec(bad)

    def test_empty_spec_yields_nothing(self):
        assert faults.parse_spec("") == []
        assert faults.parse_spec(" ; ; ") == []

    @pytest.mark.parametrize("bad", [
        "nosuchsite:drop",
        "kv.put:explode",
        "kv.put",
        "kv.put:drop@rank",
        "kv.put:drop@color=red",
        "kv.put:drop@prob=1.5",
        "kv.put:drop@count=0",
        "worker.step:delay(x)",
        "collective.pre:corrupt(weird)",
    ])
    def test_malformed_specs_fail_loudly(self, bad):
        with pytest.raises(faults.FaultSpecError):
            faults.parse_spec(bad)


class TestRegistry:
    def test_inactive_module_is_noop(self):
        assert faults.ACTIVE is False
        assert faults.inject("kv.put") is False

    def test_install_empty_uninstalls(self):
        faults.install("kv.put:drop")
        assert faults.ACTIVE is True
        faults.install("")
        assert faults.ACTIVE is False

    def test_rank_selector(self):
        faults.install("kv.put:drop@rank=1", rank=0)
        assert faults.inject("kv.put") is False  # rank 0: no match
        faults.install("kv.put:drop@rank=1", rank=1)
        assert faults.inject("kv.put") is True

    def test_count_fires_from_nth_invocation(self):
        faults.install("kv.put:drop@count=3", rank=0)
        assert [faults.inject("kv.put") for _ in range(5)] == [
            False, False, True, True, True]

    def test_times_caps_firings(self):
        faults.install("kv.put:drop@times=2", rank=0)
        assert [faults.inject("kv.put") for _ in range(4)] == [
            True, True, False, False]

    def test_pset_selector(self):
        faults.install("collective.pre:drop@pset=7", rank=0)
        assert faults.inject("collective.pre", pset=3) is False
        assert faults.inject("collective.pre") is False  # no pset info
        assert faults.inject("collective.pre", pset=7) is True

    def test_error_action_raises_retryable_marker(self):
        faults.install("kv.get:error", rank=0)
        with pytest.raises(faults.InjectedFault, match="UNAVAILABLE"):
            faults.inject("kv.get")

    def test_delay_action_sleeps(self):
        faults.install("worker.step:delay(80)", rank=0)
        t0 = time.monotonic()
        assert faults.inject("worker.step") is False
        assert time.monotonic() - t0 >= 0.07

    def test_prob_is_seeded_and_reproducible(self):
        def draws(seed, rank, n=64):
            faults.install("kv.put:drop@prob=0.5", rank=rank, seed=seed)
            return [faults.inject("kv.put") for _ in range(n)]

        a = draws(seed=7, rank=0)
        b = draws(seed=7, rank=0)
        c = draws(seed=8, rank=0)
        d = draws(seed=7, rank=1)
        assert a == b                      # same seed+rank: identical
        assert a != c or a != d            # different stream somewhere
        assert 5 < sum(a) < 59             # actually probabilistic

    def test_persistence_across_incarnations(self, tmp_path):
        spec = "worker.step:kill@count=3"
        # incarnation 1 "fires" (we can't os._exit in-test; simulate by
        # writing the marker the way the registry does)
        reg = faults.FaultRegistry(
            faults.parse_spec(spec), rank=1, state_dir=str(tmp_path))
        clause = reg._by_site["worker.step"][0]
        clause._fired = 1
        reg._persist_fired(clause)
        # incarnation 2 loads the spent budget: never fires again
        faults.install(spec, rank=1, state_dir=str(tmp_path))
        assert all(not faults.inject("worker.step") for _ in range(10))

    def test_unlimited_clause_ignores_state_dir(self, tmp_path):
        faults.install("kv.put:drop", rank=0, state_dir=str(tmp_path))
        assert faults.inject("kv.put") is True
        assert not (tmp_path / "faults_fired").exists()


class TestPartitionWindow:
    """A fired ``partition(MS)`` clause opens a WINDOW: unlike ``drop``
    (one lost operation), every coordination site — kv.get, kv.put,
    heartbeat — is silenced as a unit until the window expires, which
    is what a real network partition looks like to one rank."""

    @pytest.fixture()
    def tick(self):
        from horovod_tpu.core import clock as core_clock

        class _TickClock(core_clock.Clock):
            def __init__(self):
                self.t = 100.0

            def monotonic(self):
                return self.t

            def wall(self):
                return self.t

            def sleep(self, seconds):
                self.t += max(0.0, seconds)

            def call_later(self, seconds, fn):
                fn()

        fake = _TickClock()
        core_clock.install(fake)
        yield fake
        core_clock.install(None)

    def test_window_silences_all_coordination_sites(self, tick):
        faults.install("kv.put:partition(3000)", rank=0)
        assert faults.partition_remaining() == 0.0
        assert faults.inject("kv.get") is False  # window not yet open
        assert faults.inject("kv.put") is True   # trigger: opens window
        # every coordination site now drops, not just the trigger site
        assert faults.inject("kv.get") is True
        assert faults.inject("heartbeat") is True
        assert faults.inject("kv.put") is True
        assert 0.0 < faults.partition_remaining() <= 3.0

    def test_window_expires_on_clock(self, tick):
        faults.install("heartbeat:partition(500)", rank=0)
        assert faults.inject("heartbeat") is True
        tick.t += 0.4
        assert faults.inject("kv.put") is True   # still inside window
        tick.t += 0.2                            # past 500ms total
        assert faults.partition_remaining() == 0.0
        assert faults.inject("kv.put") is False
        assert faults.inject("heartbeat") is False  # times=1: spent

    def test_window_spares_non_coordination_sites(self, tick):
        faults.install("kv.put:partition(3000)", rank=0)
        assert faults.inject("kv.put") is True
        # compute/storage planes keep flowing during the partition
        assert faults.inject("worker.step") is False
        assert faults.inject("collective.pre") is False
        assert faults.inject_storage("ckpt.write") is None

    def test_count_delays_window_open(self, tick):
        faults.install("kv.get:partition(1000)@count=3", rank=0)
        assert faults.inject("kv.get") is False
        assert faults.inject("kv.get") is False
        assert faults.partition_remaining() == 0.0
        assert faults.inject("kv.get") is True   # 3rd hit opens it
        assert faults.inject("heartbeat") is True

    def test_rank_selector_scopes_window(self, tick):
        faults.install("kv.put:partition(1000)@rank=1", rank=0)
        assert faults.inject("kv.put") is False
        assert faults.partition_remaining() == 0.0


class TestFlapWindow:
    """A fired ``flap(MS)`` clause takes the WHOLE wire link down for a
    window: every wire-site operation on this rank drops until it
    expires — the link-level analog of ``partition(MS)``."""

    @pytest.fixture()
    def tick(self):
        from horovod_tpu.core import clock as core_clock

        class _TickClock(core_clock.Clock):
            def __init__(self):
                self.t = 100.0

            def monotonic(self):
                return self.t

            def wall(self):
                return self.t

            def sleep(self, seconds):
                self.t += max(0.0, seconds)

            def call_later(self, seconds, fn):
                fn()

        fake = _TickClock()
        core_clock.install(fake)
        yield fake
        core_clock.install(None)

    def test_window_drops_every_wire_site(self, tick):
        faults.install("wire.send:flap(1500)", rank=0)
        assert faults.flap_remaining() == 0.0
        assert faults.inject("wire.recv") is False  # window not open
        assert faults.inject("wire.send") is True   # trigger: opens it
        assert faults.inject("wire.recv") is True
        assert faults.inject("collective.exec") is True
        assert 0.0 < faults.flap_remaining() <= 1.5

    def test_window_spares_other_planes(self, tick):
        faults.install("wire.send:flap(1500)", rank=0)
        assert faults.inject("wire.send") is True
        # coordination/compute/storage keep flowing: the LINK is down,
        # not the rank
        assert faults.inject("kv.put") is False
        assert faults.inject("heartbeat") is False
        assert faults.inject("worker.step") is False
        assert faults.inject_storage("ckpt.write") is None

    def test_window_expires_on_clock(self, tick):
        faults.install("collective.exec:flap(500)", rank=0)
        assert faults.inject("collective.exec") is True
        tick.t += 0.4
        assert faults.inject("wire.send") is True   # inside the window
        tick.t += 0.2                               # past 500ms total
        assert faults.flap_remaining() == 0.0
        assert faults.inject("wire.send") is False
        assert faults.inject("collective.exec") is False  # times=1 spent

    def test_slow_adds_latency_without_dropping(self, tick):
        faults.install("wire.recv:slow(80)", rank=0)
        t0 = tick.t
        assert faults.inject("wire.recv") is False  # delivered, late
        assert tick.t - t0 >= 0.079

    def test_rank_selector_scopes_window(self, tick):
        faults.install("wire.send:flap(1000)@rank=1", rank=0)
        assert faults.inject("wire.send") is False
        assert faults.flap_remaining() == 0.0


def test_inactive_guard_is_zero_overhead():
    """Acceptance: with an empty fault spec the hot-path hook is one
    module-attribute read — bound it at far under a microsecond per op
    so a job without faults pays nothing for the hooks."""
    import timeit

    assert faults.ACTIVE is False
    n = 100_000
    t = timeit.timeit(
        lambda: faults.ACTIVE and faults.inject("collective.pre"),
        number=n)
    assert t / n < 5e-6, f"{t / n * 1e9:.0f} ns/op"


class TestInjectionSites:
    """The sites are actually threaded through the framework."""

    def test_collective_pre_site(self, hvt):
        import jax.numpy as jnp

        faults.install("collective.pre:error@count=2", rank=0)
        hvt.allreduce(jnp.ones(2))  # op 1: below count
        with pytest.raises(faults.InjectedFault):
            hvt.allreduce(jnp.ones(2))

    def test_collective_pre_corrupt_poisons_input(self, hvt):
        import jax.numpy as jnp
        import numpy as np

        faults.install("collective.pre:corrupt", rank=0)
        out = hvt.allreduce(jnp.ones(4))
        assert not np.isfinite(np.asarray(out)).all()

    def test_collective_post_corrupt_poisons_result(self, hvt):
        import jax.numpy as jnp
        import numpy as np

        faults.install("collective.post:corrupt", rank=0)
        out = hvt.allreduce(jnp.ones(4))
        assert not np.isfinite(np.asarray(out)).all()
        faults.uninstall()
        clean = hvt.allreduce(jnp.ones(4))
        assert np.isfinite(np.asarray(clean)).all()

    def test_corrupt_clause_never_fires_at_non_tensor_sites(self):
        """A corrupt clause at a KV site has nothing to poison; plain
        inject() must neither fire nor consume its budget."""
        faults.install("kv.put:corrupt@times=1", rank=0)
        assert faults.inject("kv.put") is False
        assert faults.inject("kv.put") is False

    def test_storage_clause_never_fires_at_plain_inject(self):
        """A torn clause outside inject_storage has no byte stream to
        damage; plain inject() must neither fire nor spend budget
        (same argument as corrupt at non-tensor sites)."""
        faults.install("ckpt.write:torn@times=1", rank=0)
        assert faults.inject("ckpt.write") is False
        assert faults.inject_storage("ckpt.write") == "torn"

    def test_inject_storage_damage_modes(self):
        faults.install(
            "ckpt.write:bitflip@times=1; ckpt.fsync:drop@times=1",
            rank=0)
        assert faults.inject_storage("ckpt.write") == "bitflip"
        assert faults.inject_storage("ckpt.write") is None  # spent
        assert faults.inject_storage("ckpt.fsync") == "drop"

    def test_inject_storage_error_raises(self):
        faults.install("ckpt.write:error@times=1", rank=0)
        with pytest.raises(faults.InjectedFault):
            faults.inject_storage("ckpt.write")

    def test_bitflip_corrupts_non_float_dtypes(self):
        import jax.numpy as jnp
        import numpy as np

        faults.install("collective.post:corrupt(bitflip)", rank=0)
        out = faults.inject_tensor(
            "collective.post", jnp.zeros((3,), jnp.int32))
        assert int(np.asarray(out)[0]) != 0

    def test_worker_step_site_fires_at_commit(self):
        import horovod_tpu.elastic as elastic

        state = elastic.ObjectState(epoch=0)
        faults.install("worker.step:error@count=2", rank=0)
        state.commit()
        with pytest.raises(faults.InjectedFault):
            state.commit()

    def test_heartbeat_site_drops_beats(self):
        from test_stall import FakeKV

        from horovod_tpu.comm.stall import AmortizedStallInspector

        faults.install("heartbeat:drop", rank=0)
        insp = AmortizedStallInspector(
            FakeKV(), rank=0, warn_s=10, abort_s=0, heartbeat_s=0.05)
        try:
            time.sleep(0.3)
            assert insp._kv.d == {}  # every beat suppressed
            faults.uninstall()
            deadline = time.monotonic() + 2.0
            while not insp._kv.d and time.monotonic() < deadline:
                time.sleep(0.02)
            assert insp._kv.d  # beats resume once the fault clears
        finally:
            insp.stop()


# ---------------------------------------------------------------------------
# acceptance: real 2-process elastic runs under an injected rank-kill
# ---------------------------------------------------------------------------


def _launch_elastic(tmp_path, extra_args=(), epochs=5, timeout=240):
    from conftest import make_discovery_script

    _hosts, disc = make_discovery_script(tmp_path, "localhost:2")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELASTIC_EPOCHS"] = str(epochs)
    env["EPOCH_SLEEP"] = "0.2"
    env["HVTPU_ELASTIC_DISCOVERY_INTERVAL"] = "0.2"
    cmd = [
        sys.executable, "-m", "horovod_tpu.runner",
        "--host-discovery-script", disc,
        "--min-np", "2", "--cpu-devices", "1", "--verbose",
        "--fault-spec", "worker.step:kill@rank=1,count=3",
        *extra_args,
        "--", sys.executable, _SCRIPT,
    ]
    res = subprocess.run(cmd, env=env, cwd=_REPO, timeout=timeout,
                         capture_output=True, text=True)
    return res, res.stdout + res.stderr


@pytest.mark.multiprocess
def test_injected_rank_kill_recovers_within_budget(tmp_path):
    """Tier-1 chaos smoke (ISSUE-2 acceptance): rank 1 is killed by the
    harness at its 3rd step; the elastic driver must relaunch within
    the restart budget and the job must reach the target epoch."""
    res, out = _launch_elastic(tmp_path, extra_args=("--max-restarts",
                                                     "3"))
    assert res.returncode == 0, out[-3000:]
    assert "fault injection: killing rank 1" in out, out[-3000:]
    assert "DONE size=2 epoch=5" in out, out[-3000:]
    # exactly one relaunch: the kill clause is one-shot (persisted
    # across incarnations through the driver's state dir)
    assert out.count("launching 2 workers") == 2, out[-3000:]


@pytest.mark.multiprocess
@pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
def test_injected_kill_with_zero_budget_fails_fast(tmp_path):
    """The same injected death with --max-restarts=0 must NOT relaunch:
    the driver exits non-zero with the restart-budget diagnostic."""
    res, out = _launch_elastic(tmp_path, extra_args=("--max-restarts",
                                                     "0"))
    assert res.returncode != 0, out[-3000:]
    assert "restart budget exhausted" in out, out[-3000:]
    assert "DONE" not in out, out[-3000:]
    assert out.count("launching 2 workers") == 1, out[-3000:]


@pytest.mark.multiprocess
def test_coordinator_rank_kill_replays_journal(tmp_path):
    """ISSUE-17 acceptance: rank 0 — the rank on the coordinator host
    — is killed mid-run.  The startup restore quorum's votes rode the
    durable key journal (core/journal.py via the fenced quorum KV), so
    the relaunched incarnation must REPLAY them into its fresh
    coordination KV and still finish with exactly-once accounting."""
    from conftest import make_discovery_script

    _hosts, disc = make_discovery_script(tmp_path, "localhost:2")
    state_dir = tmp_path / "state"
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELASTIC_EPOCHS"] = "5"
    env["EPOCH_SLEEP"] = "0.2"
    env["HVTPU_ELASTIC_DISCOVERY_INTERVAL"] = "0.2"
    env["HVTPU_ELASTIC_STATE_DIR"] = str(state_dir)
    env["HVTPU_LOG_LEVEL"] = "info"  # surfaces the replay line
    cmd = [
        sys.executable, "-m", "horovod_tpu.runner",
        "--host-discovery-script", disc,
        "--min-np", "2", "--cpu-devices", "1", "--verbose",
        "--max-restarts", "3",
        "--fault-spec", "worker.step:kill@rank=0,count=3",
        "--", sys.executable, _SCRIPT,
    ]
    res = subprocess.run(cmd, env=env, cwd=_REPO, timeout=240,
                         capture_output=True, text=True)
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-4000:]
    assert "fault injection: killing rank 0" in out, out[-4000:]
    assert "DONE size=2 epoch=5" in out, out[-4000:]
    assert out.count("launching 2 workers") == 2, out[-4000:]
    # gen 0's restore-quorum votes rode the journal; the relaunch
    # replayed them into the fresh coordinator
    assert "kv journal: rank 0 replayed" in out, out[-4000:]
    journal = state_dir / "kvjournal" / "rank0.jsonl"
    assert journal.exists() and journal.read_text().strip(), (
        "quorum votes never reached the durable key journal")


@pytest.mark.multiprocess
def test_partition_lease_expiry_self_fences_no_strike(tmp_path):
    """ISSUE-17 acceptance: a partition(MS) window starves rank 1's KV
    lease mid-run; the rank must SELF-FENCE (exit FENCE_EXIT_CODE)
    rather than zombie on, and the driver must relaunch WITHOUT
    charging its host a blacklist strike."""
    from conftest import make_discovery_script

    _hosts, disc = make_discovery_script(tmp_path, "localhost:2")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELASTIC_EPOCHS"] = "8"
    env["EPOCH_SLEEP"] = "0.5"  # long enough for the lease to starve
    env["HVTPU_ELASTIC_DISCOVERY_INTERVAL"] = "0.2"
    env["HVTPU_KV_LEASE_S"] = "1"
    cmd = [
        sys.executable, "-m", "horovod_tpu.runner",
        "--host-discovery-script", disc,
        "--min-np", "2", "--cpu-devices", "1", "--verbose",
        "--max-restarts", "3",
        "--fault-spec", "kv.put:partition(8000)@rank=1,count=2",
        "--", sys.executable, _SCRIPT,
    ]
    res = subprocess.run(cmd, env=env, cwd=_REPO, timeout=240,
                         capture_output=True, text=True)
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-4000:]
    assert "self-fenced (exit 89)" in out, out[-4000:]
    assert "without a blacklist strike" in out, out[-4000:]
    assert "blacklisting host" not in out, out[-4000:]
    assert "DONE size=2 epoch=8" in out, out[-4000:]
    assert out.count("launching 2 workers") == 2, out[-4000:]


@pytest.mark.multiprocess
@pytest.mark.chaos
@pytest.mark.slow  # tier-1 keeps the two smokes above; -m chaos runs this
def test_chaos_kv_error_burst_job_survives(tmp_path):
    """Chaos matrix (opt-in): a burst of injected coordination-KV
    failures must be absorbed by the retry layer — the job completes
    with no restart at all."""
    from conftest import make_discovery_script

    _hosts, disc = make_discovery_script(tmp_path, "localhost:2")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELASTIC_EPOCHS"] = "4"
    env["EPOCH_SLEEP"] = "0.2"
    env["HVTPU_ELASTIC_DISCOVERY_INTERVAL"] = "0.2"
    cmd = [
        sys.executable, "-m", "horovod_tpu.runner",
        "--host-discovery-script", disc,
        "--min-np", "2", "--cpu-devices", "1", "--verbose",
        "--fault-spec", "kv.put:error@prob=0.05,times=6",
        "--", sys.executable, _SCRIPT,
    ]
    res = subprocess.run(cmd, env=env, cwd=_REPO, timeout=240,
                         capture_output=True, text=True)
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-3000:]
    assert "DONE size=2 epoch=4" in out, out[-3000:]


# ---------------------------------------------------------------------------
# acceptance (PR 15): kill mid-commit at each storage site — every rank
# recovers to the last FULLY-durable commit, never a torn/corrupt one
# ---------------------------------------------------------------------------


def _launch_storage_chaos(tmp_path, fault_spec, epochs=5, timeout=240):
    """2-proc elastic run with the durable commit protocol under the
    given storage fault spec.  Epoch N's snapshot is commit N, and each
    commit is exactly two ckpt.write/fsync/rename invocations (payload,
    then manifest), so count=3 targets commit 2's payload op."""
    from conftest import make_discovery_script

    _hosts, disc = make_discovery_script(tmp_path, "localhost:2")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELASTIC_EPOCHS"] = str(epochs)
    env["EPOCH_SLEEP"] = "0.2"
    env["HVTPU_ELASTIC_DISCOVERY_INTERVAL"] = "0.2"
    cmd = [
        sys.executable, "-m", "horovod_tpu.runner",
        "--host-discovery-script", disc,
        "--min-np", "2", "--cpu-devices", "1", "--verbose",
        "--max-restarts", "3",
        "--fault-spec", fault_spec,
        "--", sys.executable, _SCRIPT,
    ]
    res = subprocess.run(cmd, env=env, cwd=_REPO, timeout=timeout,
                         capture_output=True, text=True)
    return res, res.stdout + res.stderr


def _assert_rolled_back_to_last_durable(res, out):
    assert res.returncode == 0, out[-4000:]
    assert "fault injection: killing rank 0" in out, out[-4000:]
    # rank 0 (the ObjectState writer rank) died mid-commit of epoch
    # 2's snapshot, so the last fully-durable commit is epoch 1.  The
    # restore quorum must land every rank there — never on the torn
    # attempt — which replays epoch 1: the epoch-1 line prints twice.
    assert out.count("EPOCH epoch=1 ") == 2, out[-4000:]
    assert "DONE size=2 epoch=5" in out, out[-4000:]
    assert out.count("launching 2 workers") == 2, out[-4000:]


@pytest.mark.multiprocess
def test_kill_mid_commit_at_ckpt_write_recovers(tmp_path):
    """Tier-1 storage-chaos smoke: rank 0 dies inside the payload
    write of commit 2 (ckpt.write invocation 3).  The torn attempt has
    no manifest, so it never existed as far as restore is concerned."""
    res, out = _launch_storage_chaos(
        tmp_path, "ckpt.write:kill@rank=0,count=3")
    _assert_rolled_back_to_last_durable(res, out)


@pytest.mark.multiprocess
@pytest.mark.chaos
@pytest.mark.slow  # tier-1 keeps the ckpt.write smoke; -m chaos runs all 3
def test_kill_mid_commit_at_ckpt_fsync_recovers(tmp_path):
    res, out = _launch_storage_chaos(
        tmp_path, "ckpt.fsync:kill@rank=0,count=3")
    _assert_rolled_back_to_last_durable(res, out)


@pytest.mark.multiprocess
@pytest.mark.chaos
@pytest.mark.slow  # tier-1 keeps the ckpt.write smoke; -m chaos runs all 3
def test_kill_mid_commit_at_ckpt_rename_recovers(tmp_path):
    res, out = _launch_storage_chaos(
        tmp_path, "ckpt.rename:kill@rank=0,count=3")
    _assert_rolled_back_to_last_durable(res, out)


@pytest.mark.multiprocess
def test_bitflip_snapshot_rejected_with_fallback(tmp_path):
    """Acceptance: a bitflip-corrupted snapshot (commit 2's payload)
    parses as committed but fails sha256 verification at restore; the
    restore falls back to the previous retained snapshot and the
    quorum lands every rank on epoch 1."""
    res, out = _launch_storage_chaos(
        tmp_path,
        "ckpt.write:bitflip@rank=0,count=3,times=1; "
        "worker.step:kill@rank=0,count=3")
    assert res.returncode == 0, out[-4000:]
    assert "bitflip storage damage" in out, out[-4000:]
    # the corrupt commit 2 must be SKIPPED: both ranks replay epoch 1
    assert out.count("EPOCH epoch=1 ") == 2, out[-4000:]
    assert "DONE size=2 epoch=5" in out, out[-4000:]
