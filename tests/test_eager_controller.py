"""Eager mini-controller tests.

The reference exercises its controller via the async torch API under
horovodrun (SURVEY.md §4).  Here, multi-rank negotiation runs as N
controller instances over an in-memory KV store (the localhost-as-
cluster pattern at the thread level); the XLA data plane degenerates to
local math in a 1-process world, which is exactly what we want: these
tests pin the *coordination* semantics.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.comm.compression import Compression
from horovod_tpu.comm.reduce_ops import ReduceOp
from horovod_tpu.core.exceptions import HorovodInternalError
from horovod_tpu.eager.controller import EagerController, KVTransport
from horovod_tpu.native import wire


class FakeKV:
    """In-memory stand-in for the JAX coordination-service KV client."""

    def __init__(self):
        self._lock = threading.Condition()
        self._store = {}

    def key_value_set(self, key, value):
        with self._lock:
            self._store[key] = value
            self._lock.notify_all()

    def blocking_key_value_get(self, key, timeout_ms):
        deadline = time.monotonic() + timeout_ms / 1000.0
        with self._lock:
            while key not in self._store:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(f"KV key {key} not set")
                self._lock.wait(remaining)
            return self._store[key]

    def key_value_delete(self, key):
        with self._lock:
            self._store.pop(key, None)


def make_world(size, **kw):
    kv = FakeKV()
    ctrls = [
        EagerController(
            r, size,
            transport=KVTransport(r, size, client=kv, timeout_s=20.0),
            cycle_time_ms=0.5,
            **kw,
        )
        for r in range(size)
    ]
    for c in ctrls:
        c.start()
    return ctrls


def stop_world(ctrls):
    # announce shutdown everywhere FIRST so no controller lingers
    # waiting for the others' agreement (coordinated-shutdown parity)
    for c in ctrls:
        c.request_shutdown()
    for c in ctrls:
        c.stop()


# --------------------------------------------------------------------------
# single-process (LocalTransport) behavior through the public API
# --------------------------------------------------------------------------

class TestSingleProcess:
    def test_allreduce_async_roundtrip(self, hvt):
        h = hvt.allreduce_async(jnp.arange(6.0), average=False, name="t0")
        out = hvt.synchronize(h)
        np.testing.assert_allclose(np.asarray(out), np.arange(6.0))

    def test_poll_completes(self, hvt):
        h = hvt.allreduce_async(jnp.ones((4,)), average=True, name="t1")
        deadline = time.monotonic() + 10
        while not hvt.poll(h):
            assert time.monotonic() < deadline
            time.sleep(0.005)
        out = hvt.synchronize(h)
        np.testing.assert_allclose(np.asarray(out), 1.0)

    def test_out_of_order_many(self, hvt):
        handles = {
            name: hvt.allreduce_async(jnp.full((3,), float(i)), name=name)
            for i, name in enumerate(["z", "b", "q", "a"])
        }
        for i, name in enumerate(["z", "b", "q", "a"]):
            out = hvt.synchronize(handles[name])
            np.testing.assert_allclose(np.asarray(out), float(i))

    def test_all_op_kinds(self, hvt):
        ha = hvt.allgather_async(jnp.arange(4.0), name="ag")
        hb = hvt.broadcast_async(jnp.full((2,), 7.0), 0, name="bc")
        hr = hvt.reducescatter_async(jnp.arange(8.0), name="rs")
        np.testing.assert_allclose(np.asarray(hvt.synchronize(ha)),
                                   np.arange(4.0))
        np.testing.assert_allclose(np.asarray(hvt.synchronize(hb)), 7.0)
        hvt.synchronize(hr)

    def test_grouped_allreduce_async(self, hvt):
        tensors = [jnp.full((2,), 1.0), jnp.full((3,), 2.0)]
        handles = hvt.grouped_allreduce_async(
            tensors, names=["ga/x", "ga/y"], average=False
        )
        outs = [hvt.synchronize(h) for h in handles]
        np.testing.assert_allclose(np.asarray(outs[0]), 1.0)
        np.testing.assert_allclose(np.asarray(outs[1]), 2.0)

    def test_duplicate_pending_name_fails(self, hvt):
        # manual mode: no background cycle can drain the first enqueue
        # between the two calls (that made this racy before)
        ctrl = EagerController(0, 1, manual=True)
        try:
            f1 = ctrl.enqueue("allreduce", jnp.ones(2), name="dup")
            f2 = ctrl.enqueue("allreduce", jnp.ones(2), name="dup")
            with pytest.raises(HorovodInternalError, match="duplicate"):
                f2.result(timeout=5)
            ctrl.run_cycle_once()
            f1.result(timeout=5)
        finally:
            ctrl.stop()

    def test_join_single(self, hvt):
        assert hvt.join() == 0

    def test_compression_fused(self, hvt):
        hs = [
            hvt.allreduce_async(
                jnp.full((4,), 3.0), name=f"c/{i}",
                compression=Compression.fp16, average=False,
            )
            for i in range(3)
        ]
        for h in hs:
            out = hvt.synchronize(h)
            assert out.dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(out), 3.0)


# --------------------------------------------------------------------------
# multi-rank negotiation over the KV transport
#
# The N "ranks" are N controller instances in one process; the XLA data
# plane underneath each runs in this process's 1-rank world (so results
# are local values) — these tests pin negotiation, not the math.  The
# `hvt` fixture initializes that 1-rank world for the data plane.
# --------------------------------------------------------------------------

class TestMultiRankNegotiation:
    def test_out_of_order_enqueue_resolves(self, hvt):
        ctrls = make_world(2)
        try:
            # rank 0 enqueues a then b; rank 1 enqueues b then a — the
            # exact reordering scenario the controller exists for.
            fa0 = ctrls[0].enqueue("allreduce", jnp.ones(4), name="a")
            fb0 = ctrls[0].enqueue("allreduce", jnp.ones(4), name="b")
            fb1 = ctrls[1].enqueue("allreduce", jnp.ones(4), name="b")
            fa1 = ctrls[1].enqueue("allreduce", jnp.ones(4), name="a")
            for f in (fa0, fb0, fb1, fa1):
                f.result(timeout=20)
        finally:
            stop_world(ctrls)

    def test_partial_submission_waits(self, hvt):
        ctrls = make_world(2)
        try:
            f0 = ctrls[0].enqueue("allreduce", jnp.ones(2), name="only0")
            time.sleep(0.2)
            assert not f0.done()  # rank 1 never submitted
            f1 = ctrls[1].enqueue("allreduce", jnp.ones(2), name="only0")
            f0.result(timeout=20)
            f1.result(timeout=20)
        finally:
            stop_world(ctrls)

    def test_dynamic_join(self, hvt):
        ctrls = make_world(2)
        try:
            jf0 = ctrls[0].join()
            # join resolves only after EVERY rank joins; rank 1 is late.
            time.sleep(0.1)
            assert not jf0.done()
            jf1 = ctrls[1].join()
            assert jf0.result(timeout=20) == 1
            assert jf1.result(timeout=20) == 1
        finally:
            stop_world(ctrls)

    def test_join_unblocks_remaining_ranks(self, hvt):
        """After rank 1 joins, rank 0's
        subsequent collectives complete (rank 1 implicitly ready with a
        zero contribution) instead of stalling until abort."""
        ctrls = make_world(2)
        try:
            # both ranks run one normal batch
            f0 = ctrls[0].enqueue("allreduce", jnp.ones(4), name="b0")
            f1 = ctrls[1].enqueue("allreduce", jnp.ones(4), name="b0")
            f0.result(timeout=20), f1.result(timeout=20)
            # rank 1 exhausts its data and joins
            jf1 = ctrls[1].join()
            # rank 0 keeps training: 2 more steps, must NOT stall
            for step in range(2):
                f = ctrls[0].enqueue(
                    "allreduce", jnp.ones(4), name=f"late{step}"
                )
                f.result(timeout=20)
            assert not jf1.done()  # join still pending (rank 0 not joined)
            jf0 = ctrls[0].join()
            # rank 0 joined last -> join() returns 0 on every rank
            assert jf0.result(timeout=20) == 0
            assert jf1.result(timeout=20) == 0
        finally:
            stop_world(ctrls)

    def test_join_unblocks_allgather_and_broadcast(self, hvt):
        ctrls = make_world(2)
        try:
            ctrls[1].join()
            fg = ctrls[0].enqueue("allgather", jnp.ones((2, 3)), name="g")
            fb = ctrls[0].enqueue("broadcast", jnp.ones(3), name="bc",
                                  root_rank=0)
            fg.result(timeout=20)
            fb.result(timeout=20)
            ctrls[0].join().result(timeout=20)
        finally:
            stop_world(ctrls)

    def test_shutdown_error_reaches_only_enqueuers(self, hvt):
        """A 'rank N has shut down' error response is broadcast to all
        ranks; members that never enqueued the tensor must IGNORE it
        (not kill their cycle thread), and the enqueuer's future gets
        the error."""
        ctrls = make_world(3)
        try:
            f0 = ctrls[0].enqueue("allreduce", jnp.ones(2), name="dead")
            ctrls[2].request_shutdown()
            with pytest.raises(HorovodInternalError,
                               match="rank 2 has shut down"):
                f0.result(timeout=20)
            # ranks 1 and 2 saw the same error response without having
            # the payload; their cycle threads must still be healthy
            time.sleep(0.1)
            assert ctrls[1]._thread_error is None
            assert ctrls[2]._thread_error is None
        finally:
            stop_world(ctrls)

    def test_same_name_in_disjoint_process_sets(self):
        """The coordination table is scoped per process set: the same
        tensor name pending in two disjoint sets must not collide
        (parity: each ProcessSet owns its own controller/MessageTable).
        Driven at the protocol level: 4 ranks, sets {0,2} and {1,3},
        all four report tensor 'x' — the coordinator must emit TWO
        responses, one per set, each only when ITS members reported."""
        from horovod_tpu.native.fallback import PyController

        coord = PyController(0, 4, fusion_threshold=1 << 20)
        coord.register_process_set(1, [0, 2])
        coord.register_process_set(2, [1, 3])
        workers = []
        for r in range(4):
            c = PyController(r, 4, fusion_threshold=1 << 20)
            c.register_process_set(1, [0, 2])
            c.register_process_set(2, [1, 3])
            workers.append(c)
        # ranks 0 and 1 report 'x' for their respective sets
        workers[0].enqueue(1, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (2,),
                           process_set_id=1)
        workers[1].enqueue(1, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (5,),
                           process_set_id=2)
        coord.ingest(workers[0].drain_requests())
        coord.ingest(workers[1].drain_requests())
        rl = wire.parse_response_list(coord.compute_responses())
        assert rl.responses == []  # neither set complete yet
        # remaining members report
        workers[2].enqueue(1, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (2,),
                           process_set_id=1)
        workers[3].enqueue(1, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (5,),
                           process_set_id=2)
        coord.ingest(workers[2].drain_requests())
        coord.ingest(workers[3].drain_requests())
        rl = wire.parse_response_list(coord.compute_responses())
        assert len(rl.responses) == 2
        by_ps = {rs.process_set_id: rs for rs in rl.responses}
        assert by_ps[1].tensor_names == ["x"]
        assert by_ps[1].tensor_shapes == [(2,)]
        assert by_ps[2].tensor_names == ["x"]
        assert by_ps[2].tensor_shapes == [(5,)]

    def test_steady_state_bypass_observable_and_bit_identical(self, hvt):
        """Acceptance: a same-shape allreduce loop reports
        hvtpu_controller_bypass_cycles_total > 0, and the results of
        bypass cycles are bit-identical to full cycles (resync_every=0
        disables the fast path entirely)."""
        from horovod_tpu.obs import metrics as obs_metrics

        bypass_ctr = obs_metrics.counter(
            "hvtpu_controller_bypass_cycles_total")

        def run_loop(disable_bypass):
            ctrls = make_world(2)
            if disable_bypass:
                for c in ctrls:
                    c._ctrl.set_resync_every(0)
            outs = []
            try:
                for step in range(5):
                    futs = []
                    for c in ctrls:
                        for i in range(3):
                            futs.append(c.enqueue(
                                "allreduce",
                                jnp.full((8,), float(step * 3 + i)),
                                name=f"bp/{i}", op=ReduceOp.SUM,
                            ))
                    outs.extend(np.asarray(f.result(timeout=20))
                                for f in futs)
            finally:
                stop_world(ctrls)
            return np.stack(outs)

        base = bypass_ctr.value()
        with_bypass = run_loop(disable_bypass=False)
        assert bypass_ctr.value() > base
        mid = bypass_ctr.value()
        without = run_loop(disable_bypass=True)
        assert bypass_ctr.value() == mid  # fast path really was off
        np.testing.assert_array_equal(with_bypass, without)

    def test_predicted_fast_path_opt_in(self, hvt, monkeypatch):
        """HVTPU_EAGER_PREDICT=1 (experimental): a steady same-shape
        loop eventually executes predicted schedules without waiting
        for the coordinator round trip, with correct results."""
        import numpy as np

        from horovod_tpu.obs import metrics as obs_metrics

        monkeypatch.setenv("HVTPU_EAGER_PREDICT", "1")
        pred = obs_metrics.counter(
            "hvtpu_controller_predicted_cycles_total")
        base = pred.value()
        ctrls = make_world(2)
        try:
            for step in range(30):
                futs = [c.enqueue("allreduce",
                                  jnp.full((4,), float(step)),
                                  name=f"pr/{i}")
                        for c in ctrls for i in range(2)]
                for f in futs:
                    np.testing.assert_allclose(
                        np.asarray(f.result(timeout=20)), float(step))
                if pred.value() > base:
                    break
        finally:
            stop_world(ctrls)
        assert pred.value() > base

    @pytest.mark.chaos
    def test_kv_faults_during_bypass_cycles_recover(self, hvt):
        """Chaos: seeded error-injected KV writes during steady-state
        bypass cycles are retried by the transport (UNAVAILABLE is
        transient) and every future still resolves."""
        from horovod_tpu.core import faults as core_faults
        from horovod_tpu.obs import metrics as obs_metrics

        bypass_ctr = obs_metrics.counter(
            "hvtpu_controller_bypass_cycles_total")
        base = bypass_ctr.value()
        core_faults.install("kv.put:error@prob=0.2,times=12", rank=0,
                            seed=11)
        try:
            ctrls = make_world(2)
            try:
                for step in range(8):
                    futs = [c.enqueue("allreduce", jnp.ones(4),
                                      name=f"ch/{step % 2}")
                            for c in ctrls]
                    for f in futs:
                        f.result(timeout=30)
            finally:
                stop_world(ctrls)
        finally:
            core_faults.uninstall()
        assert bypass_ctr.value() > base  # faults hit the fast path

    def test_steady_state_cache_and_fusion(self, hvt):
        ctrls = make_world(2, fusion_threshold=1 << 20)
        try:
            for step in range(3):
                futs = []
                for c in ctrls:
                    for i in range(4):
                        futs.append(c.enqueue(
                            "allreduce", jnp.full((8,), float(step)),
                            name=f"g/{i}", op=ReduceOp.SUM,
                        ))
                for f in futs:
                    f.result(timeout=20)
            assert ctrls[0]._ctrl.cache_size == 4
        finally:
            stop_world(ctrls)

    @pytest.mark.slow  # tier-1 runtime diet: heaviest in the --durations audit; full matrix via -m slow
    def test_stall_abort_fails_futures(self, hvt):
        ctrls = make_world(2, stall_warn_s=0.0, stall_abort_s=0.3)
        try:
            f0 = ctrls[0].enqueue("allreduce", jnp.ones(2), name="never")
            with pytest.raises(HorovodInternalError):
                f0.result(timeout=30)
        finally:
            stop_world(ctrls)

    def test_shutdown_fails_pending(self, hvt):
        ctrls = make_world(2)
        f0 = ctrls[0].enqueue("allreduce", jnp.ones(2), name="pend")
        stop_world(ctrls)
        with pytest.raises(HorovodInternalError):
            f0.result(timeout=5)


# --------------------------------------------------------------------------
# default-on schedule prediction (atomic burst units make it sound)
# --------------------------------------------------------------------------

class TestPredictedSchedules:
    def _run_steady(self, ctrls, steps, start=0, names=2, width=2):
        for step in range(start, start + steps):
            futs = [c.enqueue("allreduce", jnp.full((4,), float(step)),
                              name=f"ps/{i}")
                    for c in ctrls for i in range(names)]
            for f in futs:
                np.testing.assert_allclose(
                    np.asarray(f.result(timeout=20)), float(step))

    def test_predicted_default_on_confirms_and_drains(self, hvt):
        """HVTPU_EAGER_PREDICT defaults to auto: a steady same-shape
        loop predicts schedules, the post-hoc confirm hashes drain the
        outstanding-prediction FIFO, and nothing mispredicts."""
        from horovod_tpu.obs import metrics as obs_metrics

        pred = obs_metrics.counter(
            "hvtpu_controller_predicted_cycles_total")
        misp = obs_metrics.counter("hvtpu_controller_mispredicts_total")
        base_p, base_m = pred.value(), misp.value()
        ctrls = make_world(2)
        try:
            self._run_steady(ctrls, steps=30)
            assert pred.value() > base_p
            assert misp.value() == base_m
            # quiesce waits for outstanding confirmations, then idles
            for c in ctrls:
                assert c.quiesce(timeout=10) is True
                assert not c._predicted
        finally:
            stop_world(ctrls)

    def test_predict_confirm_instants_traced(self, hvt, tmp_path):
        """PR 12: every drained prediction leaves a ``predict_confirm``
        instant (how=hash for suppressed bursts, how=byte-verify for
        streamed ones) naming its tensors, so hvtputrace can tell
        confirmed PREDICT spans from aborted ones; a clean steady run
        traces zero mispredict instants."""
        import json as _json

        from horovod_tpu.obs import tracing

        ctrls = make_world(2)
        tracing.install(str(tmp_path), rank=0, size=1)
        try:
            self._run_steady(ctrls, steps=30)
            for c in ctrls:
                assert c.quiesce(timeout=10) is True
        finally:
            stop_world(ctrls)
            tracing.uninstall()
        with open(tmp_path / "rank0.trace.json") as f:
            evs = _json.load(f)
        confirms = [e for e in evs
                    if e.get("name") == "predict_confirm"]
        assert confirms, "no predict_confirm instants traced"
        for e in confirms:
            assert e["args"]["how"] in ("hash", "byte-verify")
            assert e["args"]["names"]
        assert not any(e.get("name") == "mispredict" for e in evs)

    def test_gate_and_predict_state_reset_across_cache_resync(self, hvt):
        """Satellite: a coordinator-forced resync must reset the burst
        gate's _expected_burst ITSELF (and the predict eligibility
        latch), not just the stability counter — a stale steady size
        from before a resize would gate the wrong burst shape."""
        ctrl = EagerController(0, 1, manual=True)
        try:
            with ctrl._lock:
                ctrl._expected_burst = 4
                ctrl._burst_stable = 5
                ctrl._verified_bits.add((1, 2, 3))
                ctrl._observe.append(((1, 2), [], []))
                ctrl._predicted.append(
                    {"hash": 0x1234, "responses": [], "names": ["rx"]})
            ctrl._dispatch_execution(
                wire.ResponseList(cache_resync_needed=True), [])
            assert ctrl._expected_burst == 0
            assert ctrl._burst_stable == 0
            assert not ctrl._verified_bits
            assert not ctrl._observe
            assert not ctrl._predicted
            # abandoned predicted names are tolerated, not fatal, if
            # their real responses arrive later
            assert "rx" in ctrl._mispredict_names
        finally:
            ctrl.stop()

    def test_gate_and_predict_state_reset_on_membership_change(self, hvt):
        """Same latch reset on an elastic membership change
        (join_last_rank >= 0) and on a mismatch error response."""
        for rl in (
            wire.ResponseList(join_last_rank=1),
            wire.ResponseList(responses=[wire.Response(
                tensor_names=["e"], tensor_shapes=[(2,)],
                error="cross-rank mismatch")]),
        ):
            ctrl = EagerController(0, 1, manual=True)
            try:
                with ctrl._lock:
                    ctrl._expected_burst = 3
                    ctrl._burst_stable = 7
                ctrl._dispatch_execution(rl, [])
                assert ctrl._expected_burst == 0
                assert ctrl._burst_stable == 0
            finally:
                ctrl.stop()

    def test_mispredict_forces_resync_and_converges(self, hvt):
        """Satellite: the mispredict recovery path — counter bump,
        forced full negotiation + cache-resync re-anchor — converges:
        the world keeps producing correct results afterwards."""
        from horovod_tpu.obs import metrics as obs_metrics

        pred = obs_metrics.counter(
            "hvtpu_controller_predicted_cycles_total")
        misp = obs_metrics.counter("hvtpu_controller_mispredicts_total")
        ctrls = make_world(2)
        try:
            base_p, base_m = pred.value(), misp.value()
            self._run_steady(ctrls, steps=30)
            assert pred.value() > base_p  # steady state reached
            with ctrls[0]._lock:
                ctrls[0]._on_mispredict("test-injected disagreement")
            assert misp.value() == base_m + 1
            # forced resync converges: further steps correct, threads
            # healthy, and the gate latch was dropped
            self._run_steady(ctrls, steps=10, start=30)
            for c in ctrls:
                assert c._thread_error is None
                assert c.quiesce(timeout=10) is True
        finally:
            stop_world(ctrls)

    def test_preempt_pending_blocks_new_predictions(self, hvt, monkeypatch):
        """Satellite: once a drain is pending, no NEW speculation may
        start (quiesce handles predictions already in flight)."""
        from horovod_tpu.core import preempt
        from horovod_tpu.obs import metrics as obs_metrics

        pred = obs_metrics.counter(
            "hvtpu_controller_predicted_cycles_total")
        monkeypatch.setattr(preempt, "PENDING", True)
        base = pred.value()
        ctrls = make_world(2)
        try:
            self._run_steady(ctrls, steps=20)
            assert pred.value() == base
        finally:
            stop_world(ctrls)

    def test_quiesce_rolls_back_unconfirmed_predictions(
            self, hvt, monkeypatch):
        """Satellite: a predicted cycle whose confirmation never
        arrives must not block the emergency commit forever — at the
        quiesce deadline the predictor rolls back to full negotiation
        and re-anchors exactly as if the coordinator had requested
        cache_resync_needed."""
        monkeypatch.setenv("HVTPU_FORCE_PY_CONTROLLER", "1")
        ctrl = EagerController(0, 1, manual=True)
        try:
            with ctrl._lock:
                ctrl._predicted.append(
                    {"hash": 0xDEAD, "responses": [], "names": ["q1"]})
            t0 = time.monotonic()
            assert ctrl.quiesce(timeout=0.4) is True
            # it WAITED for the confirmation before giving up on it
            assert time.monotonic() - t0 >= 0.35
            assert not ctrl._predicted
            assert "q1" in ctrl._mispredict_names
            # rollback re-anchors: next drain is a full resync frame
            assert ctrl._ctrl._resync_flush
        finally:
            ctrl.stop()

    def test_burst_hint_arms_gate_and_is_consumed_by_drain(self, hvt):
        """The frontend burst hint (torch optimizer's per-step grad
        count) arms the gate before stability forms, and a drain that
        covers the hinted count consumes it — a partial drain keeps
        the hint armed for the rest of the burst."""
        ctrl = EagerController(0, 1, manual=True)
        try:
            ctrl.hint_burst(4)
            assert ctrl._burst_hint == 4
            blob = wire.serialize_request_list(wire.RequestList(rank=0))
            ctrl._note_drained(2, blob)  # burst split: hint survives
            assert ctrl._burst_hint == 4
            ctrl._note_drained(4, blob)  # full burst: hint consumed
            assert ctrl._burst_hint == 0
            ctrl.hint_burst(-3)  # defensive clamp, never negative
            assert ctrl._burst_hint == 0
        finally:
            ctrl.stop()

    def test_burst_cap_drains_one_unit(self, hvt, monkeypatch):
        """With a verified steady burst, each drain is capped at the
        burst size so one wire unit == one application burst; the
        opt-out knob restores unbounded drains."""
        monkeypatch.setenv("HVTPU_EAGER_BURST_CAP", "0")
        ctrl = EagerController(0, 1, manual=True)
        try:
            assert ctrl._burst_cap_on is False
        finally:
            ctrl.stop()
        monkeypatch.delenv("HVTPU_EAGER_BURST_CAP")
        ctrl = EagerController(0, 1, manual=True)
        try:
            assert ctrl._burst_cap_on is True
        finally:
            ctrl.stop()


# --------------------------------------------------------------------------
# zero-copy fusion buffers: the fallback lattice
# ({predicted, mispredicted} x {lockstep, streamed}), pool hygiene on
# quiesce, and the non-steady enqueue overhead guard.  Packing-level
# contracts live in tests/test_fusion_buffers.py.
# --------------------------------------------------------------------------

def _fusion_counters():
    from horovod_tpu.obs import metrics as obs_metrics

    return (obs_metrics.counter("hvtpu_fusion_zero_copy_ops_total"),
            obs_metrics.counter("hvtpu_fusion_staged_copies_total"))


class TestZeroCopyFusion:
    def _steady_manual(self, ctrl, steps, start=0, names=2):
        """Lockstep analog of TestPredictedSchedules._run_steady: the
        same 2-op burst each cycle, driven by run_cycle_once."""
        for step in range(start, start + steps):
            futs = [ctrl.enqueue("allreduce",
                                 jnp.full((4,), float(step)),
                                 name=f"zc/{i}")
                    for i in range(names)]
            ctrl.run_cycle_once()
            for f in futs:
                np.testing.assert_allclose(
                    np.asarray(f.result(timeout=10)), float(step))

    def test_predicted_lockstep_packs_at_enqueue(self, hvt):
        """Cell 1: steady lockstep bursts learn a pack plan from the
        staged path, then every later burst rides the zero-copy path
        (enqueue-time pack, typed-view wire tensor, lazy unpack)."""
        zc, st = _fusion_counters()
        ctrl = EagerController(0, 1, manual=True)
        try:
            b_zc, b_st = zc.value(), st.value()
            self._steady_manual(ctrl, steps=4)
            # warmup bursts staged (stability bar + plan learning)...
            assert st.value() - b_st >= 2
            assert ctrl._pack_plan is not None
            assert set(ctrl._pack_plan) == {"zc/0", "zc/1"}
            mid = zc.value()
            self._steady_manual(ctrl, steps=3, start=4)
            # ...then EVERY op of every burst is zero-copy
            assert zc.value() - mid == 3 * 2
            # drained packs went back to the pool, none left open
            assert not ctrl._open_packs
            assert ctrl._fusion_pool.stats()["pooled"] >= 1
        finally:
            ctrl.stop()

    def test_mispredicted_lockstep_falls_back_staged(self, hvt):
        """Cell 2: a mispredict between enqueue (payloads already
        packed) and drain releases the open packs, drops the plan, and
        the drain takes the staged path — correct results, staged
        counter increment, resync forced."""
        zc, st = _fusion_counters()
        ctrl = EagerController(0, 1, manual=True)
        try:
            self._steady_manual(ctrl, steps=4)
            assert ctrl._pack_plan is not None
            futs = [ctrl.enqueue("allreduce", jnp.full((4,), 9.0),
                                 name=f"zc/{i}") for i in range(2)]
            assert ctrl._open_packs  # enqueue-time pack happened
            b_zc, b_st = zc.value(), st.value()
            with ctrl._lock:
                ctrl._on_mispredict("test-injected disagreement")
            # rollback released the packed-but-undrained buffers and
            # forgot the plan: fail back to correct, never to fast
            assert not ctrl._open_packs
            assert ctrl._pack_plan is None
            ctrl.run_cycle_once()
            for f in futs:
                np.testing.assert_allclose(
                    np.asarray(f.result(timeout=10)), 9.0)
            assert st.value() - b_st == 2
            assert zc.value() == b_zc
        finally:
            ctrl.stop()

    def test_stale_grouping_releases_pack_and_stages(self, hvt):
        """A burst whose agreed grouping no longer matches the learned
        plan (extra op joins the fusion group) must not ride a
        partial pack: staged path, correct results."""
        zc, st = _fusion_counters()
        ctrl = EagerController(0, 1, manual=True)
        try:
            self._steady_manual(ctrl, steps=4)
            assert ctrl._pack_plan is not None
            b_zc, b_st = zc.value(), st.value()
            futs = [ctrl.enqueue("allreduce", jnp.full((4,), 5.0),
                                 name=f"zc/{i}") for i in range(2)]
            futs.append(ctrl.enqueue("allreduce", jnp.full((4,), 5.0),
                                     name="zc/extra"))
            ctrl.run_cycle_once()
            for f in futs:
                np.testing.assert_allclose(
                    np.asarray(f.result(timeout=10)), 5.0)
            assert st.value() - b_st == 3  # whole group staged
            assert zc.value() == b_zc
            # the stranded 2-name pack is reclaimed by quiesce
            assert ctrl.quiesce(timeout=5) is True
            assert not ctrl._open_packs
        finally:
            ctrl.stop()

    def test_predicted_streamed_goes_zero_copy(self, hvt):
        """Cell 3: the streamed plane's steady predicted schedule
        drives the same enqueue-time pack — zero-copy ops accumulate,
        zero mispredicts, results exact."""
        from horovod_tpu.obs import metrics as obs_metrics

        zc, st = _fusion_counters()
        misp = obs_metrics.counter("hvtpu_controller_mispredicts_total")
        ctrls = make_world(2)
        try:
            b_zc, b_m = zc.value(), misp.value()
            TestPredictedSchedules._run_steady(self, ctrls, steps=30)
            assert zc.value() - b_zc > 0
            assert misp.value() == b_m
            for c in ctrls:
                assert c._pack_plan is not None
                assert c.quiesce(timeout=10) is True
                assert not c._open_packs
        finally:
            stop_world(ctrls)

    def test_mispredicted_streamed_re_anchors_and_recovers(self, hvt):
        """Cell 4: a streamed mispredict re-anchors through resync —
        the plan drops, later bursts stage (counter increment), results
        stay exact, and a re-proven schedule resumes zero-copy."""
        zc, st = _fusion_counters()
        ctrls = make_world(2)
        try:
            TestPredictedSchedules._run_steady(self, ctrls, steps=30)
            assert zc.value() > 0
            b_st = st.value()
            with ctrls[0]._lock:
                ctrls[0]._on_mispredict("test-injected disagreement")
            assert ctrls[0]._pack_plan is None
            TestPredictedSchedules._run_steady(self, ctrls, steps=10,
                                               start=30)
            assert st.value() - b_st > 0  # post-mispredict bursts staged
            mid_zc = zc.value()
            TestPredictedSchedules._run_steady(self, ctrls, steps=25,
                                               start=40)
            assert zc.value() > mid_zc  # schedule re-proven, fast again
            for c in ctrls:
                assert c._thread_error is None
                assert c.quiesce(timeout=10) is True
        finally:
            stop_world(ctrls)

    def test_quiesce_returns_pooled_buffers_before_commit(self, hvt):
        """Preempt-drain hygiene: quiesce() returns open exchange
        buffers to the pool before reporting idle, so the emergency
        commit never snapshots around a dangling pack."""
        ctrl = EagerController(0, 1, manual=True)
        try:
            specs = [((4,), np.dtype(np.float32), 16)]
            with ctrl._lock:
                ctrl._open_packs[(0, ("qa", "qb"))] = (
                    ctrl._fusion_pool.acquire(0, specs))
            assert ctrl._fusion_pool.stats()["pooled"] == 0
            assert ctrl.quiesce(timeout=5) is True
            assert not ctrl._open_packs
            assert ctrl._fusion_pool.stats()["pooled"] == 1
        finally:
            ctrl.stop()

    def test_nonsteady_enqueue_prepack_is_under_5us(self, hvt):
        """Acceptance: with no pack plan (the non-steady state every
        rank starts in), the enqueue-path hook is one None check —
        same budget discipline as the flight recorder's disabled-path
        guard."""
        import timeit

        from horovod_tpu.comm.compression import NoneCompressor
        from horovod_tpu.eager.controller import _Payload

        ctrl = EagerController(0, 1, manual=True)
        try:
            assert ctrl._pack_plan is None
            p = _Payload(
                seq=1, name="t/0", future=None, tensor=jnp.ones(4),
                rop=ReduceOp.SUM, prescale=1.0, postscale=1.0,
                compressor=NoneCompressor, splits=None,
                kind="allreduce", process_set=None, psid=0,
                root_rank=-1, t_enqueue=0.0)
            n = 100_000
            t = timeit.timeit(lambda: ctrl._maybe_prepack(p), number=n)
            assert t / n < 5e-6, f"prepack hook: {t / n * 1e9:.0f} ns/op"
        finally:
            ctrl.stop()
