"""Cross-rank integrity layer (PR 4): named-rank mismatch diagnostics,
the coordinated non-finite guard, and the parameter divergence audit.

Unit coverage drives the controllers and the optimizer directly;
acceptance coverage launches REAL 2-process jobs (the reference's
`horovodrun -np 2` pattern) and proves a mismatched shape produces a
typed error naming the offending rank on every rank — no hang — across
both controller implementations and both control-plane modes, and that
a NaN-poisoned gradient results in a coordinated skip with replicas
proven digest-identical by the audit afterward.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu
from horovod_tpu.native import core as ncore
from horovod_tpu.native import fallback, wire
from horovod_tpu.runner import run


@pytest.fixture(autouse=True)
def _leave_horovod_shut_down():
    """Several tests here call ``horovod_tpu.init()`` in this process.
    ``init()`` on an initialized process returns the state as it is and
    reads no environment, so a test of another file that ran after one
    of these on the same worker and set ``HVTPU_TRACE`` (or any other
    ``HVTPU_*`` knob) before its own ``init()`` found it ignored
    (``tests/test_tracing.py::TestLifecycle::test_shutdown_flushes_trace``
    failed whenever ``--dist loadfile`` gave its worker this file
    first).  Every test leaves the process shut down."""
    yield
    horovod_tpu.shutdown()

NATIVE = ncore.available()

_REPO_ROOT = os.path.dirname(os.path.dirname(horovod_tpu.__file__))
_ENV = {"PYTHONPATH": _REPO_ROOT + os.pathsep
        + os.environ.get("PYTHONPATH", "")}

CONTROLLER_IMPLS = [fallback.PyController] + (
    [ncore.NativeController] if NATIVE else []
)


def _pair(cls, size=2):
    return [cls(r, size, 1 << 20) for r in range(size)]


def _cycle(controllers):
    blobs = [c.drain_requests() for c in controllers]
    for b in blobs:
        controllers[0].ingest(b)
    resp = controllers[0].compute_responses()
    fins = [c.apply_responses(resp) for c in controllers]
    return wire.parse_response_list(resp), fins


# --------------------------------------------------------------------------
# controller mismatch diagnostics (unit, both impls)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("impl", CONTROLLER_IMPLS)
class TestMismatchDiagnostics:
    def test_shape_mismatch_names_offending_rank(self, impl):
        c0, c1 = _pair(impl)
        c0.enqueue(1, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4, 4))
        c1.enqueue(1, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4, 8))
        rl, _ = _cycle([c0, c1])
        assert len(rl.responses) == 1
        err = rl.responses[0].error
        assert err.startswith("cross-rank tensor mismatch for 'g'")
        assert "rank 1 submitted" in err and "shape=[4,8]" in err
        # the error broadcast forces a full resync so the bypass plane
        # re-anchors
        assert rl.cache_resync_needed

    def test_red_op_and_dtype_mismatch(self, impl):
        c0, c1 = _pair(impl)
        c0.enqueue(1, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        c1.enqueue(1, "g", wire.ALLREDUCE, wire.RED_AVERAGE, 4, (4,))
        rl, _ = _cycle([c0, c1])
        err = rl.responses[0].error
        assert "red_op=0" in err and "red_op=1" in err
        assert "dtype=6" in err and "dtype=4" in err

    def test_group_id_is_not_part_of_the_agreement_surface(self, impl):
        """Grouping is rank-local bookkeeping: ranks may number groups
        differently without tripping the diagnostics."""
        c0, c1 = _pair(impl)
        for c in (c0, c1):
            c.declare_group(c.rank + 1, 1)
        c0.enqueue(1, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4,),
                   0, 1, -1)
        c1.enqueue(1, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4,),
                   0, 2, -1)
        rl, fins = _cycle([c0, c1])
        assert rl.responses[0].error == ""
        assert fins == [[1], [1]]

    def test_ragged_allgather_and_alltoall_are_legitimate(self, impl):
        """Per-rank DIM 0 is the allgather/alltoall contract (ragged
        gathers, variable splits) — it must NOT trip the diagnostics;
        trailing-dim disagreement still must."""
        c0, c1 = _pair(impl)
        c0.enqueue(1, "ag", wire.ALLGATHER, wire.RED_SUM, 6, (2, 5))
        c1.enqueue(1, "ag", wire.ALLGATHER, wire.RED_SUM, 6, (3, 5))
        c0.enqueue(2, "a2a", wire.ALLTOALL, wire.RED_SUM, 6, (4,))
        c1.enqueue(2, "a2a", wire.ALLTOALL, wire.RED_SUM, 6, (6,))
        rl, _ = _cycle([c0, c1])
        assert [rs.error for rs in rl.responses] == ["", ""]
        # trailing dims must still agree
        c0.enqueue(3, "bad", wire.ALLGATHER, wire.RED_SUM, 6, (2, 5))
        c1.enqueue(3, "bad", wire.ALLGATHER, wire.RED_SUM, 6, (2, 7))
        rl, _ = _cycle([c0, c1])
        err = rl.responses[0].error
        assert "cross-rank tensor mismatch for 'bad'" in err
        assert "shape=[2,7]" in err
        # so must the number of dims
        c0.enqueue(4, "nd", wire.ALLGATHER, wire.RED_SUM, 6, (2, 5))
        c1.enqueue(4, "nd", wire.ALLGATHER, wire.RED_SUM, 6, (2,))
        rl, _ = _cycle([c0, c1])
        assert "cross-rank tensor mismatch for 'nd'" in \
            rl.responses[0].error

    def test_matching_resubmission_recovers(self, impl):
        """After a mismatch error, a correctly-matched re-enqueue of
        the same name completes normally (the table entry was
        consumed by the error response)."""
        c0, c1 = _pair(impl)
        c0.enqueue(1, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        c1.enqueue(1, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (8,))
        rl, _ = _cycle([c0, c1])
        assert rl.responses[0].error
        c0.enqueue(2, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        c1.enqueue(2, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        rl, fins = _cycle([c0, c1])
        assert rl.responses[0].error == ""
        assert fins == [[2], [2]]

    def test_bypass_bit_vs_full_entry_mismatch(self, impl):
        """A steady-state rank negotiating via the cache-bit bypass
        must still be diagnosed against a peer's conflicting full
        submission (the bit expands through the coordinator's cache)."""
        c0, c1 = _pair(impl)
        # cycle 1: both agree -> signature cached on every rank
        c0.enqueue(1, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        c1.enqueue(1, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        rl, _ = _cycle([c0, c1])
        assert rl.responses[0].error == ""
        # cycle 2: rank 0 re-announces (pure cache hit -> bypass blob),
        # rank 1 submits a DIFFERENT shape (cache miss -> full entry)
        c0.enqueue(2, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        c1.enqueue(2, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (5,))
        b0, b1 = c0.drain_requests(), c1.drain_requests()
        assert wire.parse_request_list(b0).cache_bypass
        assert not wire.parse_request_list(b1).cache_bypass
        c0.ingest(b0)
        c0.ingest(b1)
        rl = wire.parse_response_list(c0.compute_responses())
        err = rl.responses[0].error
        assert err.startswith("cross-rank tensor mismatch")
        assert "rank 1 submitted" in err and "shape=[5]" in err


# --------------------------------------------------------------------------
# coordinated non-finite guard (unit, eager path)
# --------------------------------------------------------------------------

class TestNonfiniteGuard:
    @pytest.fixture(autouse=True)
    def _init(self):
        import optax  # noqa: F401  (import check before init cost)

        horovod_tpu.init()
        yield

    def _tx(self, monkeypatch, action):
        import optax

        monkeypatch.setenv("HVTPU_NONFINITE_ACTION", action)
        return horovod_tpu.DistributedOptimizer(optax.adam(0.1))

    def test_skip_leaves_state_untouched(self, monkeypatch):
        from horovod_tpu.obs import metrics as obs_metrics

        tx = self._tx(monkeypatch, "skip")
        params = {"w": jnp.ones((3,))}
        st = tx.init(params)
        before = obs_metrics.counter(
            "hvtpu_optimizer_nonfinite_skips_total").value()
        upd, st2 = tx.update(
            {"w": jnp.array([1.0, float("nan"), 1.0])}, st, params)
        assert np.all(np.asarray(upd["w"]) == 0.0)
        import jax

        # adam state (count, mu, nu) byte-identical to the pre-step one
        for a, b in zip(jax.tree_util.tree_leaves(st),
                        jax.tree_util.tree_leaves(st2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        after = obs_metrics.counter(
            "hvtpu_optimizer_nonfinite_skips_total").value()
        assert after == before + 1

    def test_zero_applies_with_poison_zeroed(self, monkeypatch):
        import optax

        monkeypatch.setenv("HVTPU_NONFINITE_ACTION", "zero")
        tx = horovod_tpu.DistributedOptimizer(optax.sgd(0.1))
        params = {"w": jnp.ones((3,))}
        st = tx.init(params)
        upd, _ = tx.update(
            {"w": jnp.array([1.0, float("inf"), 1.0])}, st, params)
        got = np.asarray(upd["w"])
        assert np.isfinite(got).all()
        assert got[1] == 0.0 and got[0] != 0.0

    def test_abort_raises(self, monkeypatch):
        tx = self._tx(monkeypatch, "abort")
        st = tx.init({"w": jnp.ones((2,))})
        with pytest.raises(horovod_tpu.HorovodInternalError):
            tx.update({"w": jnp.array([float("nan"), 0.0])}, st, None)

    def test_off_disables_the_check(self, monkeypatch):
        tx = self._tx(monkeypatch, "off")
        st = tx.init({"w": jnp.ones((2,))})
        upd, _ = tx.update({"w": jnp.array([float("nan"), 1.0])}, st,
                           None)
        assert not np.isfinite(np.asarray(upd["w"])).all()

    def test_bad_action_is_loud(self, monkeypatch):
        import optax

        monkeypatch.setenv("HVTPU_NONFINITE_ACTION", "explode")
        with pytest.raises(ValueError, match="HVTPU_NONFINITE_ACTION"):
            horovod_tpu.DistributedOptimizer(optax.sgd(0.1))

    def test_finite_step_applies_normally(self, monkeypatch):
        tx = self._tx(monkeypatch, "skip")
        params = {"w": jnp.ones((3,))}
        st = tx.init(params)
        upd, _ = tx.update({"w": jnp.full((3,), 2.0)}, st, params)
        assert np.asarray(upd["w"]).std() >= 0  # produced real updates
        assert np.any(np.asarray(upd["w"]) != 0.0)


# --------------------------------------------------------------------------
# parameter divergence audit (unit, single process)
# --------------------------------------------------------------------------

class TestAuditUnit:
    def test_digest_is_stable_and_content_sensitive(self):
        from horovod_tpu.core import audit

        t1 = {"a": jnp.arange(4.0), "b": {"c": jnp.ones((2, 2))}}
        t2 = {"a": jnp.arange(4.0), "b": {"c": jnp.ones((2, 2))}}
        d1, d2 = audit.digest_tree(t1), audit.digest_tree(t2)
        assert d1 == d2
        t3 = {"a": jnp.arange(4.0).at[0].set(9.0),
              "b": {"c": jnp.ones((2, 2))}}
        d3 = audit.digest_tree(t3)
        assert d1.keys() == d3.keys()
        assert any(d1[k] != d3[k] for k in d1)
        # dtype/shape are part of the digest, not just bytes
        assert (audit.digest_tree({"x": jnp.zeros((4,), jnp.float32)})
                != audit.digest_tree({"x": jnp.zeros((2, 2),
                                                     jnp.float32)}))

    def test_single_process_verify_is_clean(self):
        from horovod_tpu.core import audit

        horovod_tpu.init()
        report = audit.verify({"w": jnp.ones((3,))}, "unit")
        assert report["divergent"] == {} and report["ranks"] == []

    def test_maybe_audit_gating(self, monkeypatch):
        from horovod_tpu.core import audit

        monkeypatch.delenv("HVTPU_AUDIT_EVERY", raising=False)
        assert audit.maybe_audit({"w": jnp.ones(2)}, 10) is None
        monkeypatch.setenv("HVTPU_AUDIT_EVERY", "5")
        assert audit.maybe_audit({"w": jnp.ones(2)}, 7) is None
        assert audit.maybe_audit({"w": jnp.ones(2)}, 10) is not None

    def test_outlier_attribution_prefers_majority(self):
        from horovod_tpu.core import audit

        divergent = audit._find_divergence({
            0: {"w": "aaaa"}, 1: {"w": "bbbb"}, 2: {"w": "aaaa"},
        })
        assert audit._majority_outliers(divergent["w"]) == [1]
        # 2-rank tie: the lowest rank's digest is the reference
        divergent = audit._find_divergence({0: {"w": "aaaa"},
                                            1: {"w": "bbbb"}})
        assert audit._majority_outliers(divergent["w"]) == [1]
        # missing tensor on one rank is divergence too
        divergent = audit._find_divergence({0: {"w": "aaaa", "x": "cc"},
                                            1: {"w": "aaaa"}})
        assert list(divergent) == ["x"]

    def test_elastic_state_audit_gating(self, monkeypatch):
        """ObjectState.audit is a no-op until HVTPU_AUDIT_EVERY > 0,
        then digests exactly the tracked attributes (the elastic run
        wrapper calls it after every sync so incarnations start
        verified-identical)."""
        import horovod_tpu.elastic as elastic

        horovod_tpu.init()
        state = elastic.ObjectState(epoch=3, w=jnp.ones((2,)))
        monkeypatch.delenv("HVTPU_AUDIT_EVERY", raising=False)
        assert state.audit() is None
        monkeypatch.setenv("HVTPU_AUDIT_EVERY", "1")
        report = state.audit("unit.sync")
        assert report is not None and report["divergent"] == {}

    def test_commit_runs_periodic_audit(self, monkeypatch):
        """State.commit() drives the periodic audit at the
        HVTPU_AUDIT_EVERY cadence (the commit counter, identical on
        every rank, is the step clock)."""
        import horovod_tpu.elastic as elastic
        from horovod_tpu.obs import metrics as obs_metrics

        horovod_tpu.init()
        monkeypatch.setenv("HVTPU_AUDIT_EVERY", "2")
        state = elastic.ObjectState(epoch=0, w=jnp.ones((2,)))
        runs = obs_metrics.counter("hvtpu_audit_runs_total")
        before = runs.value()
        state.commit()   # count 1: not due
        assert runs.value() == before
        state.commit()   # count 2: audit fires
        assert runs.value() == before + 1

    def test_bad_knobs_are_loud(self, monkeypatch):
        from horovod_tpu.core import audit

        monkeypatch.setenv("HVTPU_AUDIT_EVERY", "soon")
        with pytest.raises(ValueError, match="HVTPU_AUDIT_EVERY"):
            audit.audit_every()
        monkeypatch.setenv("HVTPU_AUDIT_ACTION", "panic")
        with pytest.raises(ValueError, match="HVTPU_AUDIT_ACTION"):
            audit.audit_action()


# --------------------------------------------------------------------------
# 2-process acceptance
# --------------------------------------------------------------------------

def _run(body, np_=2, env=None, **kw):
    merged = dict(_ENV)
    if env:
        merged.update(env)
    return run(body, np=np_, cpu_devices=1, env=merged,
               start_timeout=300.0, **kw)


@pytest.mark.multiprocess
@pytest.mark.parametrize("force_py", ["0", "1"]
                         if NATIVE else ["1"])
@pytest.mark.parametrize("stream", ["0", "1"])
def test_mismatch_acceptance_2proc(force_py, stream):
    """An injected shape mismatch produces HvtpuMismatchError naming
    rank 1 on EVERY rank — no hang — in both controller impls and both
    control-plane modes (streamed / lockstep)."""

    def body():
        import numpy as np

        import horovod_tpu as hvt
        import jax.numpy as jnp

        hvt.init()
        r = hvt.rank()
        # a matched op first proves the controller works in this mode
        ok = hvt.synchronize(hvt.allreduce_async(
            jnp.full((4,), float(r + 1)), name="warm", op=hvt.Sum))
        assert float(np.asarray(ok)[0]) == 3.0
        # rank 1 submits a mismatched shape under the same name
        shape = (4,) if r == 0 else (6,)
        h = hvt.allreduce_async(jnp.ones(shape), name="conflicted",
                                op=hvt.Sum)
        try:
            hvt.synchronize(h)
        except hvt.HvtpuMismatchError as e:
            msg = str(e)
            assert "cross-rank tensor mismatch for 'conflicted'" in msg
            assert "rank 1 submitted" in msg
            assert "shape=[6]" in msg
        else:
            raise AssertionError(
                f"rank {r}: mismatched collective did not raise")
        # the channel survives: a matched op still completes afterwards
        again = hvt.synchronize(hvt.allreduce_async(
            jnp.full((4,), 1.0), name="recovered", op=hvt.Sum))
        assert float(np.asarray(again)[0]) == 2.0
        return r

    results = _run(body, env={
        "HVTPU_FORCE_PY_CONTROLLER": force_py,
        "HVTPU_EAGER_STREAM": stream,
    }, timeout=300.0)
    assert sorted(results) == [0, 1]


@pytest.mark.multiprocess
def test_nan_skip_and_audit_2proc():
    """A NaN-poisoned gradient on ONE rank results in a coordinated
    skip on BOTH (the NaN rides the allreduce), leaving optimizer
    state digest-identical — proven by the divergence audit — and a
    post-collective corruption on one rank is then caught by the same
    audit naming that rank."""

    def body():
        import jax
        import numpy as np

        import horovod_tpu as hvt
        import jax.numpy as jnp
        import optax
        from horovod_tpu.core import audit, faults
        from horovod_tpu.obs import metrics as obs_metrics

        hvt.init()
        r = hvt.rank()
        tx = hvt.DistributedOptimizer(optax.adam(0.1))
        params = {"w": jnp.ones((8,)), "b": jnp.zeros((2,))}
        st = tx.init(params)
        # step 1: healthy
        g = {"w": jnp.full((8,), float(r + 1)), "b": jnp.ones((2,))}
        upd, st = tx.update(g, st, params)
        params = optax.apply_updates(params, upd)
        # step 2: rank 1's gradient is NaN-poisoned
        g = {"w": jnp.full((8,), 1.0), "b": jnp.ones((2,))}
        if r == 1:
            g = {"w": g["w"].at[3].set(float("nan")), "b": g["b"]}
        upd, st = tx.update(g, st, params)
        assert np.all(np.asarray(upd["w"]) == 0.0), "step not skipped"
        params = optax.apply_updates(params, upd)
        skips = obs_metrics.counter(
            "hvtpu_optimizer_nonfinite_skips_total").value()
        assert skips == 1.0
        # replicas byte-identical after the coordinated skip
        report = audit.verify(
            {"params": params, "opt": st}, "post-skip")
        assert report["divergent"] == {}
        runs = obs_metrics.counter("hvtpu_audit_runs_total").value()
        assert runs >= 1.0
        # now manufacture REAL divergence: corrupt rank 1's allreduce
        # RESULT (collective.post) and prove the audit names rank 1
        faults.install("collective.post:corrupt@rank=1", rank=r)
        diverged = hvt.allreduce(jnp.ones((4,)), op=hvt.Sum)
        faults.uninstall()
        report = audit.verify({"x": diverged}, "post-corrupt",
                              action="warn")
        assert report["ranks"] == [1], report
        div = obs_metrics.counter(
            "hvtpu_audit_divergences_total").value()
        assert div == 1.0
        # abort action raises the typed error on every rank
        try:
            audit.verify({"x": diverged}, "post-corrupt-abort",
                         action="abort")
            raise AssertionError("abort action did not raise")
        except hvt.HvtpuDivergenceError as e:
            assert "divergent ranks [1]" in str(e)
        return r

    results = _run(body, timeout=300.0)
    assert sorted(results) == [0, 1]


@pytest.mark.multiprocess
def test_pre_corrupt_exercises_guard_end_to_end_2proc():
    """`collective.pre:corrupt@rank=0` (the fault-spec grammar, as a
    user would pass it) NaN-poisons rank 0's INPUT; the poison rides
    the wire, and BOTH ranks skip together."""

    def body():
        import numpy as np

        import horovod_tpu as hvt
        import jax.numpy as jnp
        import optax
        from horovod_tpu.obs import metrics as obs_metrics

        hvt.init()
        tx = hvt.DistributedOptimizer(optax.sgd(0.1))
        params = {"w": jnp.ones((4,))}
        st = tx.init(params)
        upd, st = tx.update({"w": jnp.full((4,), 2.0)}, st, params)
        assert np.all(np.asarray(upd["w"]) == 0.0)
        assert obs_metrics.counter(
            "hvtpu_optimizer_nonfinite_skips_total").value() == 1.0
        return hvt.rank()

    results = _run(body, env={
        "HVTPU_FAULT_SPEC": "collective.pre:corrupt@rank=0",
    }, timeout=300.0)
    assert sorted(results) == [0, 1]
