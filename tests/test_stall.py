"""Sync-path stall watchdog (comm/stall.py).

Parity: ``horovod/common/stall_inspector.cc`` — the reference warns
after STALL_CHECK_TIME naming the tensors and missing ranks, and shuts
down after STALL_SHUTDOWN_TIME.  Unit tests drive the inspector over a
fake KV client; the integration tests launch 2 REAL processes where
one rank skips (or diverges from) a collective — the exact deadlock
SURVEY §5.2 calls this subsystem essential for — and assert the other
rank aborts with a named diagnosis instead of hanging forever.
"""

import logging
import os
import threading
import time

import pytest

import horovod_tpu
from horovod_tpu.core.exceptions import HorovodInternalError
from horovod_tpu.comm.stall import (
    AmortizedStallInspector,
    SyncStallInspector,
)
from horovod_tpu.runner import run

_REPO_ROOT = os.path.dirname(os.path.dirname(horovod_tpu.__file__))
_ENV = {"PYTHONPATH": _REPO_ROOT + os.pathsep
        + os.environ.get("PYTHONPATH", "")}


class FakeKV:
    """Dict-backed stand-in for the coordination-service client,
    including the directory get the fast path uses."""

    def __init__(self):
        self.d = {}
        self.lock = threading.Lock()

    def key_value_set(self, k, v):
        with self.lock:
            self.d[k] = v

    def key_value_try_get(self, k):
        with self.lock:
            if k not in self.d:
                raise KeyError(k)
            return self.d[k]

    def key_value_dir_get(self, prefix):
        with self.lock:
            return [(k, v) for k, v in self.d.items()
                    if k.startswith(prefix)]

    def key_value_delete(self, k):
        with self.lock:
            self.d.pop(k, None)


class FakeKVNoDir(FakeKV):
    """An older client without dir-get: exercises the per-rank
    try_get fallback branch."""

    key_value_dir_get = None


@pytest.fixture(params=[FakeKV, FakeKVNoDir],
                ids=["dir-get", "try-get-fallback"])
def kv(request):
    """Both client shapes: the one-RPC dir-get fast path and the
    per-rank try_get fallback must behave identically."""
    return request.param()


class TestInspectorUnit:
    def test_completes_when_all_marks_present(self, kv):
        # peer (rank 1) already posted its mark for seq 0
        kv.key_value_set("hvtstall/1/0/0/1", "allreduce:x")
        insp = SyncStallInspector(kv, rank=0, warn_s=60, abort_s=0,
                                  generation=1)
        insp.rendezvous(0, [0, 1], "allreduce:x")  # returns, no raise
        assert "hvtstall/1/0/0/0" in kv.d  # own mark posted

    def test_abort_names_missing_ranks(self, kv):
        insp = SyncStallInspector(kv, rank=0, warn_s=0.05, abort_s=0.2,
                                  generation=1)
        t0 = time.monotonic()
        with pytest.raises(HorovodInternalError) as ei:
            insp.rendezvous(0, [0, 1, 2], "allreduce:y")
        assert time.monotonic() - t0 < 5.0  # bounded, not a hang
        msg = str(ei.value)
        assert "allreduce:y" in msg
        assert "[1, 2]" in msg  # the missing ranks, by name

    def test_descriptor_mismatch_raises_immediately(self, kv):
        kv.key_value_set("hvtstall/1/0/0/1", "broadcast:z")
        insp = SyncStallInspector(kv, rank=0, warn_s=60, abort_s=0,
                                  generation=1)
        t0 = time.monotonic()
        with pytest.raises(HorovodInternalError, match="diverged"):
            insp.rendezvous(0, [0, 1], "allreduce:z")
        assert time.monotonic() - t0 < 1.0  # no deadline needed

    def test_warn_then_recover(self, kv, caplog):
        insp = SyncStallInspector(kv, rank=0, warn_s=0.05, abort_s=0,
                                  generation=1)

        def late_peer():
            time.sleep(0.3)
            kv.key_value_set("hvtstall/1/0/0/1", "op")

        t = threading.Thread(target=late_peer)
        t.start()
        with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
            insp.rendezvous(0, [0, 1], "op")
        t.join()
        stalls = [r for r in caplog.records
                  if "stalled collective" in r.getMessage()]
        assert stalls and "[1]" in stalls[0].getMessage()

    def test_rolling_cleanup_keeps_kv_bounded(self, kv):
        insp = SyncStallInspector(kv, rank=0, warn_s=60, abort_s=0,
                                  generation=1)
        for seq in range(3):
            kv.key_value_set(f"hvtstall/1/0/{seq}/1", "op")
            insp.rendezvous(0, [0, 1], "op")
        own = [k for k in kv.d if k.endswith("/0")]
        # only the newest own mark survives (seq 2)
        assert own == ["hvtstall/1/0/2/0"]

    def test_generation_namespacing_ignores_stale_marks(self, kv):
        # a PREVIOUS session's mark with a different descriptor must
        # not trip the mismatch check after re-init
        kv.key_value_set("hvtstall/1/0/0/1", "old-op")
        kv.key_value_set("hvtstall/2/0/0/1", "new-op")
        insp = SyncStallInspector(kv, rank=0, warn_s=60, abort_s=0,
                                  generation=2)
        insp.rendezvous(0, [0, 1], "new-op")


class _NeverReady:
    """Stands in for a jax.Array whose collective never completes."""

    def is_ready(self):
        return False


class _Ready:
    def is_ready(self):
        return True


class TestAmortizedInspectorUnit:
    """The default mode: local bookkeeping + background heartbeat.
    Per-op cost must be RPC-free; detection happens within a beat."""

    def _make(self, kv, rank, warn_s=0.05, abort_s=0.0, hb=0.03):
        return AmortizedStallInspector(
            kv, rank, warn_s=warn_s, abort_s=abort_s,
            heartbeat_s=hb, generation=1)

    def test_healthy_path_stays_clean(self):
        kv = FakeKV()
        a, b = self._make(kv, 0), self._make(kv, 1)
        try:
            for i in range(5):
                a.pre_op(0, [0, 1], f"allreduce:t{i}")
                a.wait_ready(0, _Ready())
                b.pre_op(0, [0, 1], f"allreduce:t{i}")
                b.wait_ready(0, _Ready())
            time.sleep(0.2)  # several beats
            assert a.failure is None and b.failure is None
        finally:
            a.stop(); b.stop()

    def test_pre_op_is_rpc_free(self):
        """The hot path must not touch the KV: 10k ops through a KV
        whose set/get explode must neither fail nor take RPC time."""

        class ExplodingKV(FakeKV):
            def key_value_set(self, k, v):
                raise AssertionError("hot path hit the KV")

            key_value_dir_get = property(
                lambda self: (_ for _ in ()).throw(AssertionError))

        insp = AmortizedStallInspector(
            ExplodingKV(), 0, warn_s=60, abort_s=0,
            heartbeat_s=30.0, generation=1)  # beat never fires
        try:
            t0 = time.monotonic()
            for i in range(10_000):
                insp.pre_op(0, [0, 1], "allreduce:x")
                insp.wait_ready(0, _Ready())
            dt = time.monotonic() - t0
            # ~1 µs/op bookkeeping; 50 ms budget leaves 100x headroom
            assert dt < 0.5, f"hot path too slow: {dt:.3f}s / 10k ops"
        finally:
            insp.stop()

    def test_mismatch_diagnosed_within_a_beat(self):
        kv = FakeKV()
        a, b = self._make(kv, 0), self._make(kv, 1)
        try:
            a.pre_op(0, [0, 1], "allreduce:grad:(2,):float32")
            b.pre_op(0, [0, 1], "broadcast:weights:(2,):float32")
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline and not (
                    a.failure and b.failure):
                time.sleep(0.02)
            for insp, mine, theirs in (
                    (a, "allreduce:grad", "broadcast:weights"),
                    (b, "broadcast:weights", "allreduce:grad")):
                msg = insp.failure or ""
                assert "diverged" in msg
                # BOTH tensor names appear in the diagnosis
                assert mine in msg and theirs in msg
        finally:
            a.stop(); b.stop()

    def test_stall_abort_names_missing_ranks(self):
        kv = FakeKV()
        a = self._make(kv, 0, warn_s=0.05, abort_s=0.25)
        b = self._make(kv, 1, warn_s=0.05, abort_s=0.25)  # posts beats,
        try:                                              # runs no ops
            a.pre_op(0, [0, 1], "allreduce:loss:(4,):float32")
            with pytest.raises(HorovodInternalError) as ei:
                a.wait_ready(0, _NeverReady())
            msg = str(ei.value)
            assert "stalled collective" in msg
            assert "allreduce:loss" in msg
            assert "[1]" in msg  # the absent rank, by name
        finally:
            a.stop(); b.stop()

    def test_wait_ready_raises_after_peer_failure(self):
        """A rank blocked in a healthy-looking wait must still abort
        when a PEER latches a failure (shutdown-on-stall semantics)."""
        kv = FakeKV()
        a = self._make(kv, 0, hb=0.03)
        b = self._make(kv, 1, hb=0.03)
        try:
            with a._lock:
                a.failure = "synthetic failure on rank 0"
            with pytest.raises(HorovodInternalError, match="rank 0"):
                deadline = time.monotonic() + 2.0
                while time.monotonic() < deadline:
                    b.pre_op(0, [0, 1], "allreduce:x")
                    b.wait_ready(0, _Ready())
                    time.sleep(0.02)
                pytest.fail("peer failure never propagated")
        finally:
            a.stop(); b.stop()

    def test_dead_peer_mid_collective_detected_via_staleness(self):
        """A peer that posts a caught-up heartbeat and THEN dies (mid
        wire-exchange) must still be diagnosed: its beat number stops
        advancing, so staleness marks it absent even though its last
        snapshot showed seq parity."""
        kv = FakeKV()
        a = AmortizedStallInspector(
            kv, 0, warn_s=0.1, abort_s=0.6, heartbeat_s=0.03,
            generation=1, stale_s=0.2)
        b = AmortizedStallInspector(
            kv, 1, warn_s=0.1, abort_s=0.6, heartbeat_s=0.03,
            generation=1, stale_s=0.2)
        try:
            # both ranks dispatch the same op (seq parity)...
            a.pre_op(0, [0, 1], "allreduce:w:(8,):float32")
            b.pre_op(0, [0, 1], "allreduce:w:(8,):float32")
            time.sleep(0.1)  # both post caught-up beats
            # ...then rank 1 dies mid-collective: beats stop, but its
            # last posted snapshot stays in the KV forever
            b._stopped.set()
            with pytest.raises(HorovodInternalError) as ei:
                a.wait_ready(0, _NeverReady())
            msg = str(ei.value)
            assert "stalled collective" in msg and "[1]" in msg
        finally:
            a.stop(); b.stop()

    def test_rearm_names_outer_op_and_keeps_its_clock(self):
        """After a nested negotiation clears the in-flight marker, the
        outer wait re-arms under the OUTER op's descriptor and its
        original start time — not the nested op's."""
        kv = FakeKV()
        insp = AmortizedStallInspector(
            kv, 0, warn_s=60, abort_s=0, heartbeat_s=30.0, generation=1)
        try:
            outer = insp.pre_op(0, [0, 1], "alltoall:x:(4,):float32")
            t_outer = insp._tracks["0"].t0
            time.sleep(0.02)
            insp.pre_op(0, [0, 1], "allgather:splits:(2,):int32")
            insp.wait_ready(0, _Ready())  # nested finish clears marker
            assert insp._tracks["0"].inflight is None

            # outer finish: briefly pending, then ready
            class _ReadyAfter:
                n = 0

                def is_ready(self):
                    self.n += 1
                    if self.n == 1:
                        tr = insp._tracks["0"]
                        assert tr.inflight == "alltoall:x:(4,):float32"
                        assert tr.t0 == t_outer
                    return self.n > 1

            insp.wait_ready(0, _ReadyAfter(), outer)
            assert insp._tracks["0"].inflight is None
        finally:
            insp.stop()

    def test_slow_collective_everyone_present_no_warn(self, caplog):
        """Both ranks dispatched the op (seq caught up): a long wait is
        a slow collective, not a stall — no warning."""
        kv = FakeKV()
        a = self._make(kv, 0, warn_s=0.05, abort_s=0.0)
        b = self._make(kv, 1, warn_s=0.05, abort_s=0.0)
        try:
            a.pre_op(0, [0, 1], "allreduce:big")
            b.pre_op(0, [0, 1], "allreduce:big")
            with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
                time.sleep(0.3)
            assert a.failure is None and b.failure is None
            assert not [r for r in caplog.records
                        if "stalled" in r.getMessage()]
        finally:
            a.stop(); b.stop()


pytestmark_integration = pytest.mark.multiprocess


@pytest.mark.multiprocess
def test_skipped_collective_aborts_cleanly_2proc():
    """Rank 1 skips a collective rank 0 enters: rank 0 must diagnose
    and abort within the stall shutdown deadline — not hang."""

    def body():
        import time as _t

        import jax.numpy as jnp

        import horovod_tpu as hvt
        from horovod_tpu.core.exceptions import HorovodInternalError

        hvt.init()
        r = hvt.rank()
        # one successful collective first: the watchdog must not
        # perturb the healthy path
        ok = float(hvt.allreduce(jnp.ones(()), op=hvt.Sum))
        assert ok == 2.0
        if r == 0:
            t0 = _t.monotonic()
            try:
                hvt.allreduce(jnp.ones((4,)), op=hvt.Sum)
            except HorovodInternalError as e:
                waited = _t.monotonic() - t0
                return ("aborted", waited, str(e))
            return ("hung-or-succeeded", None, None)
        _t.sleep(8)  # never calls the collective
        return ("skipped", None, None)

    results = run(
        body, np=2, cpu_devices=1, env={
            **_ENV,
            "HVTPU_STALL_CHECK_TIME_SECONDS": "1",
            "HVTPU_STALL_SHUTDOWN_TIME_SECONDS": "3",
        }, start_timeout=300.0, timeout=600.0)
    by_rank = dict(zip(("r0", "r1"), results))
    status, waited, msg = results[0]
    assert status == "aborted", by_rank
    assert waited < 8.0
    assert "stalled collective" in msg and "allreduce" in msg
    assert "[1]" in msg  # names the absent rank
    assert results[1][0] == "skipped"


@pytest.mark.multiprocess
def test_diverged_collectives_diagnosed_2proc():
    """Ranks entering DIFFERENT collectives at the same point must get
    the mismatch diagnosis within one heartbeat (amortized mode: the
    doomed op may dispatch — even complete — but the very next
    heartbeat latches the divergence and the job aborts with both op
    names instead of silently desyncing)."""

    def body():
        import time as _t

        import jax.numpy as jnp

        import horovod_tpu as hvt
        from horovod_tpu.core.exceptions import HorovodInternalError

        hvt.init()
        r = hvt.rank()
        try:
            # the divergence: same step, different collectives
            if r == 0:
                hvt.allreduce(jnp.ones((2,)), op=hvt.Sum, name="grads")
            else:
                hvt.broadcast(jnp.ones((2,)), root_rank=0, name="weights")
            # a real training loop keeps stepping — the watchdog must
            # kill it within ~a heartbeat, not let it run corrupted
            deadline = _t.monotonic() + 8.0
            while _t.monotonic() < deadline:
                hvt.allreduce(jnp.ones(()), op=hvt.Sum)
                _t.sleep(0.1)
        except HorovodInternalError as e:
            return ("mismatch", str(e))
        return ("no-error", None)

    results = run(
        body, np=2, cpu_devices=1, env={
            **_ENV,
            "HVTPU_STALL_CHECK_TIME_SECONDS": "1",
            "HVTPU_STALL_SHUTDOWN_TIME_SECONDS": "10",
            "HVTPU_STALL_HEARTBEAT_SECONDS": "0.2",
        }, start_timeout=300.0, timeout=600.0)
    assert any(s == "mismatch" for s, _ in results), results
    for s, msg in results:
        if s == "mismatch":
            assert "diverged" in msg
            # the diagnosis names the diverged ops by tensor name
            assert "grads" in msg and "weights" in msg, msg


@pytest.mark.multiprocess
def test_diverged_strict_mode_immediate_2proc():
    """HVTPU_STALL_CHECK_MODE=strict restores the pre-dispatch
    rendezvous: a mismatched collective is diagnosed BEFORE anything
    dispatches, on the first offending op."""

    def body():
        import jax.numpy as jnp

        import horovod_tpu as hvt
        from horovod_tpu.core.exceptions import HorovodInternalError

        hvt.init()
        r = hvt.rank()
        try:
            if r == 0:
                hvt.allreduce(jnp.ones((2,)), op=hvt.Sum)
            else:
                hvt.broadcast(jnp.ones((2,)), root_rank=0)
        except HorovodInternalError as e:
            return ("mismatch", str(e))
        return ("no-error", None)

    results = run(
        body, np=2, cpu_devices=1, env={
            **_ENV,
            "HVTPU_STALL_CHECK_MODE": "strict",
            "HVTPU_STALL_CHECK_TIME_SECONDS": "1",
            "HVTPU_STALL_SHUTDOWN_TIME_SECONDS": "10",
        }, start_timeout=300.0, timeout=600.0)
    # at least the slower-arriving rank sees the peer's conflicting
    # mark; with both marks posted, typically both do
    assert any(s == "mismatch" for s, _ in results), results
    for s, msg in results:
        if s == "mismatch":
            assert "diverged" in msg


class TestStallGuardUnit:
    def test_passthrough_before_init_and_at_world_1(self):
        import jax.numpy as jnp

        from horovod_tpu.comm.stall import stall_guard

        calls = []

        @stall_guard(name="t")
        def step(x):
            calls.append(1)
            return x + 1

        # single-process hvt: guard must be a plain passthrough
        horovod_tpu.init()
        try:
            out = step(jnp.zeros(()))
            assert float(out) == 1.0 and calls == [1]
        finally:
            horovod_tpu.shutdown()

    def test_guard_marks_and_diverged_names(self):
        """Two guards with different names on the same channel set:
        the heartbeat diagnoses ranks running different step fns."""
        kv = FakeKV()
        a = AmortizedStallInspector(kv, 0, warn_s=60, abort_s=0,
                                    heartbeat_s=0.03, generation=1)
        b = AmortizedStallInspector(kv, 1, warn_s=60, abort_s=0,
                                    heartbeat_s=0.03, generation=1)
        try:
            a.pre_op("jit.0", [0, 1], "jit_step:train")
            b.pre_op("jit.0", [0, 1], "jit_step:evaluate")
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline and not a.failure:
                time.sleep(0.02)
            assert a.failure and "jit_step:train" in a.failure
            assert "jit_step:evaluate" in a.failure
        finally:
            a.stop(); b.stop()

    def test_stopped_ranks_tombstone_propagates_failure(self):
        """An aborting rank usually stops BEFORE its next scheduled
        beat: its goodbye tombstone must carry the latched diagnosis,
        or the peers never learn it — they'd hang in their next
        collective and die on the torn-down transport instead."""
        kv = FakeKV()
        a = AmortizedStallInspector(kv, 0, warn_s=60, abort_s=0,
                                    heartbeat_s=5.0, generation=1)
        b = AmortizedStallInspector(kv, 1, warn_s=60, abort_s=0,
                                    heartbeat_s=0.03, generation=1)
        try:
            # rank 0 latches a divergence and stops immediately — its
            # 5s heartbeat never gets to post the failure in a beat
            with a._lock:
                a.failure = ("collective mismatch at process set 0 op "
                             "#3: ... diverged ...")
            a.stop()
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline and not b.failure:
                time.sleep(0.02)
            assert b.failure and "rank 0 aborted" in b.failure
            assert "diverged" in b.failure
        finally:
            a.stop(); b.stop()

    def test_clean_exit_not_blamed(self):
        """A rank whose inspector stopped CLEANLY (goodbye tombstone)
        is never blamed for a stall, even with a marker still armed."""
        kv = FakeKV()
        a = AmortizedStallInspector(
            kv, 0, warn_s=0.05, abort_s=0.3, heartbeat_s=0.03,
            generation=1, stale_s=0.15)
        b = AmortizedStallInspector(
            kv, 1, warn_s=0.05, abort_s=0.3, heartbeat_s=0.03,
            generation=1, stale_s=0.15)
        try:
            # both step once (block=False style: marker stays armed)
            a.pre_op("jit.0", [0, 1], "jit_step:s")
            b.pre_op("jit.0", [0, 1], "jit_step:s")
            time.sleep(0.1)
            b.stop()  # clean exit posts the tombstone
            time.sleep(0.5)  # well past warn+abort+stale deadlines
            assert a.failure is None, a.failure
        finally:
            a.stop(); b.stop()


@pytest.mark.multiprocess
def test_stall_guard_jit_plane_2proc():
    """A pod-shape jitted training loop where one
    process stops dispatching.  The guarded survivor must abort with a
    named diagnosis instead of hanging inside the XLA collective."""

    def body():
        import time as _t

        import jax
        import jax.numpy as jnp

        import horovod_tpu as hvt
        from horovod_tpu.core.exceptions import HorovodInternalError

        hvt.init()
        r = hvt.rank()
        mesh = hvt.world_mesh()
        from functools import partial

        from jax.sharding import NamedSharding, PartitionSpec as P

        # a REAL cross-process collective inside the step:
        def make_step():
            @hvt.stall_guard(name="train")
            @jax.jit
            @partial(jax.shard_map, mesh=mesh, in_specs=P("world"),
                     out_specs=P(), check_vma=False)
            def train(x):
                return jax.lax.psum(x.sum(), "world")

            return train

        train = make_step()
        xs = jax.device_put(
            jnp.ones((2,)),
            NamedSharding(mesh, P("world")))
        t0 = _t.monotonic()
        try:
            for i in range(100):
                if r == 1 and i == 3:
                    _t.sleep(10)  # stops stepping mid-loop
                    return ("stopped", None)
                float(train(xs))
        except HorovodInternalError as e:
            return ("aborted", str(e))
        return ("finished", None)

    results = run(
        body, np=2, cpu_devices=1, env={
            **_ENV,
            "HVTPU_STALL_CHECK_TIME_SECONDS": "1",
            "HVTPU_STALL_SHUTDOWN_TIME_SECONDS": "3",
            "HVTPU_STALL_HEARTBEAT_SECONDS": "0.2",
        }, start_timeout=300.0, timeout=600.0)
    status0, msg0 = results[0]
    assert status0 == "aborted", results
    assert "jit_step:train" in msg0 and "[1]" in msg0
    assert results[1][0] == "stopped"


@pytest.mark.multiprocess
def test_stall_guard_strict_mode_2proc():
    """stall_guard under HVTPU_STALL_CHECK_MODE=strict: each step is a
    pre-dispatch rendezvous — a rank that stops stepping aborts the
    survivor at the step boundary BEFORE it dispatches the doomed
    step."""

    def body():
        import time as _t
        from functools import partial

        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        import horovod_tpu as hvt
        from horovod_tpu.core.exceptions import HorovodInternalError

        hvt.init()
        r = hvt.rank()
        mesh = hvt.world_mesh()

        @hvt.stall_guard(name="strict_train")
        @jax.jit
        @partial(jax.shard_map, mesh=mesh, in_specs=P("world"),
                 out_specs=P(), check_vma=False)
        def train(x):
            return jax.lax.psum(x.sum(), "world")

        xs = jax.device_put(
            jnp.ones((2,)), NamedSharding(mesh, P("world")))
        try:
            for i in range(50):
                if r == 1 and i == 2:
                    _t.sleep(8)
                    return ("stopped", None)
                float(train(xs))
        except HorovodInternalError as e:
            return ("aborted", str(e))
        return ("finished", None)

    results = run(
        body, np=2, cpu_devices=1, env={
            **_ENV,
            "HVTPU_STALL_CHECK_MODE": "strict",
            "HVTPU_STALL_CHECK_TIME_SECONDS": "1",
            "HVTPU_STALL_SHUTDOWN_TIME_SECONDS": "3",
        }, start_timeout=300.0, timeout=600.0)
    status0, msg0 = results[0]
    assert status0 == "aborted", results
    # strict mode: the abort happens at the rendezvous, pre-dispatch,
    # with the step and absent rank named
    assert "jit_step:strict_train" in msg0 and "[1]" in msg0
    assert results[1][0] == "stopped"


@pytest.mark.multiprocess
def test_watchdog_transparent_on_healthy_path_2proc():
    """With stall checking at defaults, the full sync op matrix still
    produces correct results (the rendezvous must be semantically
    invisible)."""

    def body():
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt

        hvt.init()
        r = hvt.rank()
        a = np.asarray(hvt.allreduce(jnp.full((3,), float(r + 1)),
                                     op=hvt.Sum))
        g = np.asarray(hvt.allgather(jnp.full((r + 1, 2), float(r))))
        b = np.asarray(hvt.broadcast(jnp.full((2,), float(r * 7)),
                                     root_rank=1))
        rs = np.asarray(hvt.reducescatter(jnp.ones((4, 2)), op=hvt.Sum))
        hvt.barrier()
        return (a.tolist(), g.shape[0], b.tolist(), rs.tolist())

    results = run(body, np=2, cpu_devices=1, env=_ENV,
                  start_timeout=300.0)
    for a, g0, b, rs in results:
        assert a == [3.0, 3.0, 3.0]
        assert g0 == 3
        assert b == [7.0, 7.0]
        assert rs == [[2.0, 2.0], [2.0, 2.0]]


class TestPoisonLatch:
    """The poison latch across re-init generations (ISSUE-2 satellite):
    ``poison_exit_status`` must clear (0) ONLY once ``init_generation``
    advances past the poisoning generation, and an elastic job's
    terminal stall abort must feed the driver's recovery loop
    (``RESET_EXIT_CODE``) instead of reading as a crash."""

    @pytest.fixture()
    def latched(self, monkeypatch):
        from horovod_tpu.comm import stall
        from horovod_tpu.core import state as core_state

        st = core_state.global_state()
        insp = AmortizedStallInspector(
            FakeKV(), rank=0, warn_s=10, abort_s=0, heartbeat_s=60,
            generation=st.init_generation)
        monkeypatch.setattr(st, "sync_stall", insp)
        monkeypatch.delenv("HVTPU_ELASTIC", raising=False)
        stall._latch_poison(insp)
        yield stall, st, insp
        insp.stop()
        stall._reset_poison()

    def test_latch_requires_installed_inspector(self):
        from horovod_tpu.comm import stall

        stray = AmortizedStallInspector(
            FakeKV(), rank=0, warn_s=10, abort_s=0, heartbeat_s=60)
        try:
            stall._latch_poison(stray)  # NOT the installed inspector
            assert not stall.poisoned()
        finally:
            stray.stop()
            stall._reset_poison()

    def test_same_generation_is_terminal(self, latched):
        stall, st, insp = latched
        assert stall.poisoned()
        assert stall.poison_exit_status() == 1

    def test_clears_only_past_poisoning_generation(self, latched,
                                                   monkeypatch):
        stall, st, insp = latched
        # re-init into the SAME generation: still terminal
        assert stall.poison_exit_status() == 1
        # generation advances PAST the poisoning one (elastic in-process
        # resync completed): the wedged execution belongs to a dead
        # session — exit clean
        monkeypatch.setattr(st, "init_generation",
                            insp.gen + 1)
        assert stall.poison_exit_status() == 0

    def test_elastic_terminal_stall_requests_reset(self, latched,
                                                   monkeypatch):
        stall, st, insp = latched
        from horovod_tpu.elastic.worker import RESET_EXIT_CODE

        monkeypatch.setenv("HVTPU_ELASTIC", "1")
        assert stall.poison_exit_status() == RESET_EXIT_CODE
        # ...but a completed recovery still wins: clean exit
        monkeypatch.setattr(st, "init_generation", insp.gen + 1)
        assert stall.poison_exit_status() == 0


class TestInflightLeakRegression:
    """PR-20 satellite: an exception inside ``dispatch``/``wait_ready``
    must CLEAR the in-flight marker.  The leak left ``_SetTrack.
    inflight`` armed with the dead op's start time, so the marker aged
    across later healthy ops and the heartbeat eventually diagnosed a
    false stall abort on a perfectly live job."""

    def _make(self, kv, rank, warn_s=0.05, abort_s=0.0, hb=0.03):
        return AmortizedStallInspector(
            kv, rank, warn_s=warn_s, abort_s=abort_s,
            heartbeat_s=hb, generation=1)

    def test_dispatch_error_clears_inflight(self):
        insp = self._make(FakeKV(), 0, warn_s=60, hb=30.0)
        try:
            insp.pre_op(0, [0, 1], "allreduce:x")

            def boom():
                raise ValueError("backend exploded")

            with pytest.raises(ValueError, match="exploded"):
                insp.dispatch(0, boom, ())
            assert insp._tracks["0"].inflight is None
        finally:
            insp.stop()

    def test_wait_ready_error_clears_inflight(self):
        insp = self._make(FakeKV(), 0, warn_s=60, hb=30.0)
        try:
            insp.pre_op(0, [0, 1], "allreduce:y")

            class _Explodes:
                def is_ready(self):
                    raise RuntimeError("torn result")

            with pytest.raises(RuntimeError, match="torn result"):
                insp.wait_ready(0, _Explodes())
            assert insp._tracks["0"].inflight is None
        finally:
            insp.stop()

    def test_failed_attempt_never_becomes_false_stall_abort(self):
        """The observable symptom: after a failed dispatch, an idle-but-
        healthy job must NOT age the stale marker into a stall abort
        naming the innocent peer."""
        kv = FakeKV()
        a = self._make(kv, 0, warn_s=0.05, abort_s=0.25)
        b = self._make(kv, 1, warn_s=0.05, abort_s=0.25)
        try:
            a.pre_op(0, [0, 1], "allreduce:z")

            def boom():
                raise ValueError("attempt died")

            with pytest.raises(ValueError):
                a.dispatch(0, boom, ())
            # well past warn + abort: the cleared marker means no op is
            # in flight, so nothing may latch
            time.sleep(0.6)
            assert a.failure is None, a.failure
        finally:
            a.stop(); b.stop()


class TestWireConsensusUnit:
    """comm/wirefault.py: the abort-and-retry agreement over a fake KV
    — every decision path, plus the no-torn-attempt property."""

    def _wc(self, kv, rank=0, deadline_s=5.0):
        from horovod_tpu.comm import wirefault

        return wirefault.WireConsensus(
            kv, rank, generation=1, hb_prefix="hvtstallhb/1/",
            deadline_s=deadline_s)

    def _hb(self, kv, rank, seq, inflight, beat=0, bye=False, fail=None):
        import json

        kv.key_value_set(
            f"hvtstallhb/1/{rank}/{beat}",
            json.dumps({"bye": bye, "fail": fail,
                        "sets": {"0": {"seq": seq,
                                       "inflight": inflight}}}))

    def test_all_voted_means_retry(self, kv):
        import json

        from horovod_tpu.comm import wirefault

        for r in (1, 2):
            kv.key_value_set(f"hvtwire/1/0/5/0/{r}",
                             json.dumps({"st": "mid", "d": "allreduce:x"}))
        wc = self._wc(kv)
        got = wc.vote_and_decide("0", 5, 0, [0, 1, 2], "allreduce:x",
                                 predispatch=False)
        assert got == wirefault.RETRY
        # own vote rode the KV for the peers' agreement
        assert "hvtwire/1/0/5/0/0" in kv.d

    def test_completed_peer_escalates(self):
        import json

        from horovod_tpu.comm import wirefault

        kv = FakeKV()
        kv.key_value_set("hvtwire/1/0/5/0/1",
                         json.dumps({"st": "pre", "d": "allreduce:x"}))
        # rank 2 never votes: its heartbeat shows it COMPLETED op 5
        # and moved on (seq advanced past) — a retry would deliver a
        # second, different attempt on rank 2
        self._hb(kv, 2, seq=7, inflight=None)
        wc = self._wc(kv)
        got = wc.vote_and_decide("0", 5, 0, [0, 1, 2], "allreduce:x",
                                 predispatch=True)
        assert got == wirefault.ESCALATE

    def test_exited_peer_escalates(self):
        import json

        from horovod_tpu.comm import wirefault

        kv = FakeKV()
        kv.key_value_set("hvtwire/1/0/5/0/1",
                         json.dumps({"st": "pre", "d": "allreduce:x"}))
        self._hb(kv, 2, seq=6, inflight="allreduce:x", bye=True)
        wc = self._wc(kv)
        got = wc.vote_and_decide("0", 5, 0, [0, 1, 2], "allreduce:x",
                                 predispatch=True)
        assert got == wirefault.ESCALATE

    def test_wedged_peers_late_join_retracts_vote(self):
        """Every voter failed PRE-dispatch and the non-voters are
        observably parked inside attempt 0: re-enter it (LATE_JOIN) —
        and the failure vote must flip to ``rejoin`` BEFORE re-entry,
        so a peer failing later can never read a completed vote set."""
        import json

        from horovod_tpu.comm import wirefault

        kv = FakeKV()
        kv.key_value_set("hvtwire/1/0/5/0/1",
                         json.dumps({"st": "pre", "d": "allreduce:x"}))
        self._hb(kv, 2, seq=6, inflight="allreduce:x")
        wc = self._wc(kv)
        got = wc.vote_and_decide("0", 5, 0, [0, 1, 2], "allreduce:x",
                                 predispatch=True)
        assert got == wirefault.LATE_JOIN
        assert json.loads(kv.d["hvtwire/1/0/5/0/0"])["st"] == "rejoin"

    def test_midflight_failure_never_late_joins(self):
        """A failure AFTER bytes hit the wire can only RETRY (all voted)
        or ESCALATE — here the wedged peer never votes, so the deadline
        escalates rather than tearing into the pending attempt."""
        from horovod_tpu.comm import wirefault

        kv = FakeKV()
        self._hb(kv, 2, seq=6, inflight="allreduce:x")
        wc = self._wc(kv, deadline_s=0.3)
        t0 = time.monotonic()
        got = wc.vote_and_decide("0", 5, 0, [0, 2], "allreduce:x",
                                 predispatch=False)
        assert got == wirefault.ESCALATE
        assert time.monotonic() - t0 < 5.0  # bounded by the deadline

    def test_deadline_escalates_on_silent_peer(self):
        from horovod_tpu.comm import wirefault

        kv = FakeKV()  # rank 1: no vote, no heartbeat — nothing to read
        wc = self._wc(kv, deadline_s=0.2)
        got = wc.vote_and_decide("0", 5, 0, [0, 1], "allreduce:x",
                                 predispatch=True)
        assert got == wirefault.ESCALATE

    def test_rejoin_vote_never_licenses_next_attempt(self):
        """The no-torn-result property: with a late-joiner back INSIDE
        attempt 0 (rejoin vote), a subsequently-failing peer must never
        decide RETRY — the late-joiner would wedge in attempt 0 while
        others tear off into attempt 1."""
        import json

        from horovod_tpu.comm import wirefault

        kv = FakeKV()
        kv.key_value_set("hvtwire/1/0/5/0/1",
                         json.dumps({"st": "rejoin", "d": "allreduce:x"}))
        wc = self._wc(kv, deadline_s=0.3)
        # pre-dispatch failure: join the pending attempt instead
        assert wc.vote_and_decide(
            "0", 5, 0, [0, 1], "allreduce:x",
            predispatch=True) == wirefault.LATE_JOIN
        # mid-flight failure: cannot join — escalate, never RETRY
        assert wc.vote_and_decide(
            "0", 5, 0, [0, 1], "allreduce:x",
            predispatch=False) == wirefault.ESCALATE

    def test_cleanup_deletes_only_own_votes(self):
        import json

        kv = FakeKV()
        kv.key_value_set("hvtwire/1/0/5/0/1", json.dumps({"st": "mid"}))
        wc = self._wc(kv)
        wc.vote_and_decide("0", 5, 0, [0, 1], "op", predispatch=False)
        wc.cleanup("0", 5, attempts=1)
        assert "hvtwire/1/0/5/0/0" not in kv.d
        assert "hvtwire/1/0/5/0/1" in kv.d  # the peer deletes its own

    def test_attempt_tag_namespaces_are_disjoint(self):
        from horovod_tpu.native.wire import attempt_tag, split_attempt

        assert attempt_tag("hvt/allreduce/x", 0) == "hvt/allreduce/x"
        tagged = attempt_tag("hvt/allreduce/x", 3)
        assert tagged != "hvt/allreduce/x"
        assert split_attempt(tagged) == ("hvt/allreduce/x", 3)
        assert split_attempt("hvt/allreduce/x") == ("hvt/allreduce/x", 0)
        # attempts never collide with each other or with attempt 0
        assert len({attempt_tag("k", a) for a in range(5)}) == 5


class TestWireRetryLoop:
    """The module-level ``dispatch`` retry loop end-to-end in-process:
    an injected ``wire.send`` drop, a real consensus round over the
    fake KV, and the reissued attempt delivering the result."""

    @pytest.fixture(autouse=True)
    def _clean_faults(self):
        from horovod_tpu.core import faults

        yield
        faults.uninstall()

    def _harness(self, kv, members=(0,)):
        from types import SimpleNamespace

        insp = AmortizedStallInspector(
            kv, 0, warn_s=60, abort_s=0, heartbeat_s=0.05, generation=1)
        st = SimpleNamespace(sync_stall=insp)
        ps = SimpleNamespace(size=2, process_set_id=0)
        insp.pre_op(0, list(members), "allreduce:r:(2,):float32")
        return insp, st, ps

    def test_consensus_retry_delivers_result(self, monkeypatch):
        from horovod_tpu.comm import stall as stall_mod
        from horovod_tpu.core import faults
        from horovod_tpu.obs import metrics as obs_metrics

        monkeypatch.setenv("HVTPU_WIRE_RETRIES", "2")
        monkeypatch.setenv("HVTPU_WIRE_RETRY_BACKOFF_S", "0.01")
        faults.install("wire.send:drop@times=1", rank=0)
        kv = FakeKV()
        insp, st, ps = self._harness(kv)
        before = obs_metrics.counter(
            "hvtpu_collective_retries_total").value()
        try:
            out = stall_mod.dispatch(st, ps, lambda: 42, (),
                                     desc="allreduce:r:(2,):float32")
            assert out == 42
            assert obs_metrics.counter(
                "hvtpu_collective_retries_total").value() == before + 1
            # delivered: the rank's own votes were cleaned up
            assert not [k for k in kv.d if k.startswith("hvtwire/")]
            # and the completion wait leaves no stale marker behind
            insp.wait_ready(0, out)
            assert insp._tracks["0"].inflight is None
        finally:
            insp.stop()

    def test_retries_disabled_is_failfast(self, monkeypatch):
        """Default budget (0): the injected wire fault surfaces as the
        pre-existing HorovodInternalError with zero consensus traffic
        — the opt-out path is byte-for-byte the old behavior."""
        from horovod_tpu.comm import stall as stall_mod
        from horovod_tpu.core import faults

        monkeypatch.delenv("HVTPU_WIRE_RETRIES", raising=False)
        faults.install("wire.send:drop@times=1", rank=0)
        kv = FakeKV()
        insp, st, ps = self._harness(kv)
        try:
            with pytest.raises(HorovodInternalError,
                               match="transport failure"):
                stall_mod.dispatch(st, ps, lambda: 42, ())
            assert not [k for k in kv.d if k.startswith("hvtwire/")]
        finally:
            insp.stop()

    def test_budget_exhaustion_escalates(self, monkeypatch):
        from horovod_tpu.comm import stall as stall_mod
        from horovod_tpu.core import faults

        monkeypatch.setenv("HVTPU_WIRE_RETRIES", "2")
        monkeypatch.setenv("HVTPU_WIRE_RETRY_BACKOFF_S", "0.01")
        faults.install("wire.send:drop", rank=0)  # unlimited drops
        insp, st, ps = self._harness(FakeKV())
        try:
            with pytest.raises(HorovodInternalError,
                               match="transport failure"):
                stall_mod.dispatch(st, ps, lambda: 42, ())
        finally:
            insp.stop()

    def test_non_transport_error_is_not_retried(self, monkeypatch):
        from horovod_tpu.comm import stall as stall_mod

        monkeypatch.setenv("HVTPU_WIRE_RETRIES", "3")

        def boom():
            raise ValueError("a real bug, not the wire")

        insp, st, ps = self._harness(FakeKV())
        try:
            with pytest.raises(ValueError, match="real bug"):
                stall_mod.dispatch(st, ps, boom, ())
        finally:
            insp.stop()


@pytest.mark.multiprocess
def test_wire_drop_retry_bitwise_identical_2proc():
    """PR-20 acceptance: rank 0's allreduce dies on an injected
    ``wire.send`` drop with retries armed.  The abort consensus sees
    rank 1 parked inside the pending attempt (late join), the reissued
    dispatch completes it, and the delivered tensor is BITWISE-equal to
    the clean run on both ranks — the job never restarts and never
    consumes bytes from the aborted attempt."""

    def body():
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt
        from horovod_tpu.core import faults
        from horovod_tpu.obs import metrics as obs_metrics

        hvt.init()
        r = hvt.rank()
        x = jnp.arange(8, dtype=jnp.float32) * (r + 1) + 0.125
        clean = np.asarray(hvt.allreduce(x, op=hvt.Sum, name="clean"))
        before = obs_metrics.counter(
            "hvtpu_collective_retries_total").value()
        # only rank 0's next send dies; rank 1 dispatches and wedges
        # inside the pending collective until the late join lands
        faults.install("wire.send:drop@rank=0,times=1", rank=r)
        faulted = np.asarray(hvt.allreduce(x, op=hvt.Sum, name="clean"))
        faults.uninstall()
        retries = obs_metrics.counter(
            "hvtpu_collective_retries_total").value() - before
        # the job is still healthy: one more collective completes
        ok = float(hvt.allreduce(jnp.ones(()), op=hvt.Sum))
        return (clean.tolist(), faulted.tolist(), retries, ok)

    results = run(
        body, np=2, cpu_devices=1, env={
            **_ENV,
            "HVTPU_WIRE_RETRIES": "2",
            "HVTPU_WIRE_CONSENSUS_S": "30",
            "HVTPU_STALL_HEARTBEAT_SECONDS": "0.2",
            "HVTPU_STALL_CHECK_TIME_SECONDS": "5",
            "HVTPU_STALL_SHUTDOWN_TIME_SECONDS": "60",
        }, start_timeout=300.0, timeout=600.0)
    for clean, faulted, retries, ok in results:
        assert faulted == clean, (faulted, clean)  # bitwise identical
        assert ok == 2.0
    # the faulted rank's reissue was consensus-approved and counted
    assert results[0][2] >= 1, results
