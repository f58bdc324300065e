"""Timeline + autotuner tests (parity targets: timeline.cc Chrome-trace
output, ParameterManager sampling/pinning)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.core.config import Config
from horovod_tpu.obs.autotune import Autotuner
from horovod_tpu.obs.timeline import ICI_ALLREDUCE, QUEUE, Timeline


class TestTimeline:
    def test_chrome_trace_roundtrip(self, tmp_path):
        path = str(tmp_path / "tl.json")
        tl = Timeline(path, rank=0)
        tl.begin("grad/w1", QUEUE)
        tl.end("grad/w1")
        tl.begin("grad/w1", ICI_ALLREDUCE)
        tl.end("grad/w1")
        tl.instant("cycle_start", index=0)
        tl.mark_cycle(1)
        tl.close()
        events = json.load(open(path))
        names = [e["name"] for e in events]
        assert QUEUE in names and ICI_ALLREDUCE in names
        # B/E pairs balance
        assert names.count(QUEUE) == 2 or (
            sum(1 for e in events if e.get("ph") == "B")
            == sum(1 for e in events if e.get("ph") == "E")
        )

    def test_close_idempotent_and_end_without_begin(self, tmp_path):
        path = str(tmp_path / "tl2.json")
        tl = Timeline(path, rank=1)
        tl.end("never-started")  # no-op, no crash
        tl.close()
        tl.close()
        json.load(open(path))

    def test_api_start_stop(self, hvt, tmp_path):
        path = str(tmp_path / "tl3.json")
        tl = hvt.start_timeline(path)
        tl.begin("t", QUEUE)
        tl.end("t")
        hvt.stop_timeline()
        assert json.load(open(path))


class TestAutotuner:
    def _mk(self, steps_per_sample=2, warmup=0):
        cfg = Config(
            autotune=True,
            autotune_steps_per_sample=steps_per_sample,
            autotune_warmup_samples=warmup,
        )
        return Autotuner(cfg)

    def test_sweeps_then_pins(self):
        tuner = self._mk()
        seen = set()
        for _ in range(100):
            seen.add(tuner.current)
            tuner.record_step(1 << 20)
            if tuner.done:
                break
        assert tuner.done
        assert len(seen) > 1  # actually explored
        assert tuner.current in seen

    def test_warmup_skipped(self):
        tuner = self._mk(warmup=3)
        first = tuner.current
        for _ in range(3):
            tuner.record_step(1)
        assert tuner.current == first  # still on first candidate

    def test_log_csv(self, tmp_path):
        cfg = Config(
            autotune=True, autotune_steps_per_sample=1,
            autotune_warmup_samples=0,
            autotune_log=str(tmp_path / "at.csv"),
        )
        tuner = Autotuner(cfg)
        while not tuner.done:
            tuner.record_step(1 << 20)
        lines = open(cfg.autotune_log).read().strip().splitlines()
        assert lines[0].startswith("fusion_threshold")
        assert len(lines) > 1


AXIS = "world"


class TestQuantizedAllreduce:
    def _mesh(self):
        return Mesh(np.asarray(jax.devices(), dtype=object), (AXIS,))

    def test_int8_wire_matches_fp32_within_tolerance(self):
        from horovod_tpu.comm import Compression, ReduceOp, spmd

        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(8, 1000).astype(np.float32))

        def body(s):
            return spmd.allreduce(
                s[0], axis_name=AXIS, op=ReduceOp.SUM,
                compression=Compression.int8,
            )[None]

        out = jax.jit(
            jax.shard_map(
                body, mesh=self._mesh(), in_specs=(P(AXIS),),
                out_specs=P(AXIS), check_vma=False,
            )
        )(x)
        exact = np.asarray(x).sum(0)
        got = np.asarray(out[0])
        # two quantization stages ⇒ bounded relative error
        scale = np.abs(np.asarray(x)).max()
        assert np.abs(got - exact).max() < 0.05 * scale * 8

    def test_average_and_shape_restore(self):
        from horovod_tpu.comm import Compression, ReduceOp, spmd

        x = jnp.ones((8, 3, 7), jnp.float32) * 2.0

        def body(s):
            return spmd.allreduce(
                s[0], axis_name=AXIS, op=ReduceOp.AVERAGE,
                compression=Compression.int8,
            )[None]

        out = jax.jit(
            jax.shard_map(
                body, mesh=self._mesh(), in_specs=(P(AXIS),),
                out_specs=P(AXIS), check_vma=False,
            )
        )(x)
        assert out.shape == (8, 3, 7)
        np.testing.assert_allclose(np.asarray(out[0]), np.full((3, 7), 2.0),
                                   rtol=2e-2)

    def test_int8_with_groups_rejected(self):
        from horovod_tpu.comm import Compression, ReduceOp, spmd

        x = jnp.ones((8, 4))
        with pytest.raises(NotImplementedError):
            def body(s):
                return spmd.allreduce(
                    s[0], axis_name=AXIS, op=ReduceOp.SUM,
                    compression=Compression.int8,
                    groups=[[0, 1, 2, 3], [4, 5, 6, 7]],
                )[None]

            jax.jit(
                jax.shard_map(
                    body, mesh=self._mesh(), in_specs=(P(AXIS),),
                    out_specs=P(AXIS), check_vma=False,
                )
            )(x)


class TestGroupedEdgeCases:
    def test_empty_list(self, hvt):
        assert hvt.grouped_allreduce([]) == []

    def test_min_op_keeps_per_tensor_semantics(self, hvt):
        outs = hvt.grouped_allreduce(
            [jnp.asarray([1.0, 5.0]), jnp.asarray([2.0])], op=hvt.Min
        )
        np.testing.assert_allclose(np.asarray(outs[0]), [1.0, 5.0])


class TestReviewRegressions:
    """Regression tests for code-review findings on the initial build."""

    def test_int8_instance_also_routes_to_quantized(self):
        from horovod_tpu.comm import ReduceOp, spmd
        from horovod_tpu.comm.compression import Int8Compressor

        x = jnp.ones((8, 64), jnp.float32) * 3.0

        def body(s):
            return spmd.allreduce(
                s[0], axis_name=AXIS, op=ReduceOp.AVERAGE,
                compression=Int8Compressor(),  # instance, not class
            )[None]

        out = jax.jit(
            jax.shard_map(
                body,
                mesh=Mesh(np.asarray(jax.devices(), dtype=object), (AXIS,)),
                in_specs=(P(AXIS),), out_specs=P(AXIS), check_vma=False,
            )
        )(x)
        np.testing.assert_allclose(np.asarray(out[0]), np.full((64,), 3.0),
                                   rtol=2e-2)

    def test_unequal_groups_rejected_for_gather_ops(self):
        from horovod_tpu.comm import spmd

        with pytest.raises(ValueError, match="equal-size"):
            spmd._require_equal_groups([[0, 1, 2], [3]], "allgather")
        # equal groups pass
        spmd._require_equal_groups([[0, 1], [2, 3]], "allgather")

    def test_device_groups_equal_chunks(self, hvt):
        # single-process world: global set → None (nothing to chunk)
        table = hvt.core.global_state().process_set_table
        assert table.global_process_set.device_groups() is None

    def test_mark_cycles_gated(self, tmp_path):
        import json as _json

        p1 = str(tmp_path / "on.json")
        tl = Timeline(p1, 0, mark_cycles=True)
        tl.mark_cycle(0)
        tl.close()
        assert any(e["name"] == "CYCLE" for e in _json.load(open(p1)))
        p2 = str(tmp_path / "off.json")
        tl = Timeline(p2, 0, mark_cycles=False)
        tl.mark_cycle(0)
        tl.close()
        assert not any(e["name"] == "CYCLE" for e in _json.load(open(p2)))

    def test_autotuner_cleared_on_shutdown(self, monkeypatch):
        import horovod_tpu as hvt_mod

        monkeypatch.setenv("HVTPU_AUTOTUNE", "1")
        hvt_mod.init()
        assert hvt_mod.core.global_state().autotuner is not None
        hvt_mod.shutdown()
        monkeypatch.delenv("HVTPU_AUTOTUNE")
        hvt_mod.init()
        try:
            assert hvt_mod.core.global_state().autotuner is None
        finally:
            hvt_mod.shutdown()

    def test_poll_handles_tuple_results(self, hvt):
        h = hvt.alltoall_async(jnp.ones((2, 1)), splits=[2])
        assert hvt.poll(h) in (True, False)  # no crash on tuple
        out, splits = hvt.synchronize(h)
        assert out.shape == (2, 1)


class TestFusedAdasumSegments:
    """Fused Adasum must be bucketing-invariant (per-tensor dots)."""

    def _run_fused(self, tree, threshold):
        from horovod_tpu.comm import ReduceOp
        from horovod_tpu.comm.fusion import fused_tree_allreduce

        def body(t):
            return fused_tree_allreduce(
                t, axis_name=AXIS, threshold_bytes=threshold,
                op=ReduceOp.ADASUM,
            )

        return jax.jit(
            jax.shard_map(
                body,
                mesh=Mesh(np.asarray(jax.devices(), dtype=object), (AXIS,)),
                in_specs=(P(AXIS),), out_specs=P(AXIS), check_vma=False,
            )
        )(tree)

    def test_threshold_invariance(self):
        rng = np.random.RandomState(21)
        tree = {
            "big": jnp.asarray(rng.randn(8, 64).astype(np.float32) * 100.0),
            "small": jnp.asarray(rng.randn(8, 16).astype(np.float32) * 0.01),
        }
        fused = self._run_fused(tree, 1 << 30)      # one bucket
        unfused = self._run_fused(tree, 1)          # per-tensor buckets
        for k in tree:
            np.testing.assert_allclose(
                np.asarray(fused[k]), np.asarray(unfused[k]),
                rtol=1e-4, atol=1e-6,
            )

    def test_adasum_int8_rejected(self):
        from horovod_tpu.comm import Compression, ReduceOp, spmd

        with pytest.raises(ValueError, match="int8"):
            def body(s):
                return spmd.allreduce(
                    s[0], axis_name=AXIS, op=ReduceOp.ADASUM,
                    compression=Compression.int8,
                )[None]

            jax.jit(
                jax.shard_map(
                    body,
                    mesh=Mesh(np.asarray(jax.devices(), dtype=object), (AXIS,)),
                    in_specs=(P(AXIS),), out_specs=P(AXIS), check_vma=False,
                )
            )(jnp.ones((8, 4)))


class TestAutotunerWiring:
    def test_eager_path_consumes_autotuner(self, monkeypatch):
        import optax

        import horovod_tpu as hvt_mod
        from horovod_tpu.api.optimizer import allreduce_gradients

        monkeypatch.setenv("HVTPU_AUTOTUNE", "1")
        monkeypatch.setenv("HVTPU_AUTOTUNE_WARMUP_SAMPLES", "0")
        monkeypatch.setenv("HVTPU_AUTOTUNE_STEPS_PER_SAMPLE", "1")
        hvt_mod.init()
        try:
            tuner = hvt_mod.core.global_state().autotuner
            assert tuner is not None
            grads = {"w": jnp.ones((8, 8))}
            first = tuner.current
            while not tuner.done:
                allreduce_gradients(grads, axis_name=None)
            # the sweep ran: candidates consumed via the eager path
            assert tuner.done
        finally:
            hvt_mod.shutdown()

    def test_timeline_records_eager_allreduce(self, monkeypatch, tmp_path):
        import json as _json

        import horovod_tpu as hvt_mod

        hvt_mod.init()
        try:
            hvt_mod.start_timeline(str(tmp_path / "t.json"))
            hvt_mod.allreduce(jnp.ones((4,)), name="grad/w")
            hvt_mod.stop_timeline()
            events = _json.load(open(tmp_path / "t.json"))
            assert any(
                e.get("args", {}).get("tensor") == "grad/w" for e in events
            )
        finally:
            hvt_mod.shutdown()


class TestAutotunerControllerWiring:
    """The autotuner's cycle-time AND fusion
    threshold must reach the LIVE controller, not just the jit path."""

    def test_autotuner_applies_to_controller(self, hvt):
        from horovod_tpu.core.config import Config
        from horovod_tpu.eager.controller import EagerController
        from horovod_tpu.obs.autotune import Autotuner

        grid = [(1 << 20, 2.0), (4 << 20, 7.5)]
        cfg = Config(autotune=True, autotune_warmup_samples=0,
                     autotune_steps_per_sample=1)
        tuner = Autotuner(cfg, grid=grid)
        ctrl = EagerController(0, 1, manual=True, autotuner=tuner,
                               fusion_threshold=64 << 20,
                               cycle_time_ms=1.0)
        try:
            import jax.numpy as jnp

            # candidate 0 scores and candidate 1 is PUBLISHED in the
            # next ResponseList (ParameterManager-broadcast parity);
            # one more cycle applies it on every rank
            ctrl.enqueue("allreduce", jnp.ones(8), name="t0")
            ctrl.run_cycle_once()
            ctrl.run_cycle_once()  # empty cycle carries tuned params
            assert ctrl.cycle_time_s == grid[1][1] / 1000.0
            assert ctrl._ctrl.fusion_threshold == grid[1][0]
            # second scored step pins the best and keeps applying it
            ctrl.enqueue("allreduce", jnp.ones(8), name="t1")
            ctrl.run_cycle_once()
            ctrl.run_cycle_once()
            assert tuner.done
            assert (ctrl._ctrl.fusion_threshold, ctrl.cycle_time_s * 1000.0) \
                == tuner.current
        finally:
            ctrl.stop()


class TestLogLevelWiring:
    def test_log_level_applied_at_init(self):
        import logging

        import horovod_tpu as hvt_mod
        from horovod_tpu.core.config import Config

        hvt_mod.shutdown()
        try:
            hvt_mod.init(Config(log_level="debug"))
            assert (logging.getLogger("horovod_tpu").level
                    == logging.DEBUG)
        finally:
            hvt_mod.shutdown()
            hvt_mod.init(Config(log_level="warning"))
            assert (logging.getLogger("horovod_tpu").level
                    == logging.WARNING)
            hvt_mod.shutdown()


class TestGaussianProcess:
    def test_gp_fits_and_predicts(self):
        import numpy as np

        from horovod_tpu.obs.gaussian_process import GaussianProcess

        rng = np.random.RandomState(0)
        x = rng.rand(20, 1)
        y = np.sin(6 * x[:, 0])
        gp = GaussianProcess(length_scale=0.2, noise=1e-6)
        gp.fit(x, y)
        mu, sigma = gp.predict(x)
        np.testing.assert_allclose(mu, y, atol=1e-2)
        # uncertainty grows away from data
        _, s_far = gp.predict(np.asarray([[5.0]]))
        assert s_far[0] > sigma.mean() * 3

    def test_ei_prefers_promising_region(self):
        import numpy as np

        from horovod_tpu.obs.gaussian_process import (
            GaussianProcess,
            expected_improvement,
        )

        x = np.asarray([[0.0], [0.5], [1.0]])
        y = np.asarray([0.0, 1.0, 0.1])
        gp = GaussianProcess(length_scale=0.2, noise=1e-6)
        gp.fit(x, y)
        cand = np.linspace(0, 1, 101)[:, None]
        ei = expected_improvement(gp, cand, best_y=1.0)
        # best EI near the known max, not at the poor edges
        assert 0.25 <= float(cand[np.argmax(ei)][0]) <= 0.75

    def test_bayesian_optimizer_finds_peak(self):
        import numpy as np

        from horovod_tpu.obs.gaussian_process import BayesianOptimizer

        def score(pt):  # peak at (0.3, 0.7) in unit coords
            u = (np.asarray(pt) - np.asarray([0.0, 0.0])) / 10.0
            return -((u[0] - 0.3) ** 2 + (u[1] - 0.7) ** 2)

        bo = BayesianOptimizer([(0.0, 10.0), (0.0, 10.0)],
                               seed_points=[(5.0, 5.0)])
        for _ in range(20):
            x = bo.suggest()
            bo.observe(x, score(x))
        best_x, _ = bo.best
        assert abs(best_x[0] - 3.0) < 2.5
        assert abs(best_x[1] - 7.0) < 2.5


class TestGpAutotuner:
    def test_gp_mode_pins_good_candidate(self):
        from horovod_tpu.core.config import Config
        from horovod_tpu.obs.autotune import Autotuner

        cfg = Config(autotune=True, autotune_warmup_samples=0,
                     autotune_steps_per_sample=1, autotune_gp_samples=10)
        tuner = Autotuner(cfg, mode="gp")
        # synthetic landscape: throughput peaks at large fusion
        # thresholds with ~2.5 ms cycle time
        import math

        def throughput(thr, cyc):
            t = math.log2(thr)
            return -((t - 26.5) ** 2) - ((cyc - 2.5) ** 2) * 0.3

        steps = 0
        while not tuner.done and steps < 100:
            thr, cyc = tuner.current
            # feed bytes so score == throughput deterministically:
            # monkeypatch via direct observe path instead
            tuner._bytes = 0
            tuner._steps = 0
            tuner._t_start = __import__("time").monotonic() - 1.0
            tuner._bytes = max(throughput(thr, cyc) + 100.0, 1e-3)
            tuner._steps = tuner._steps_per_sample - 1
            tuner.record_step(0)
            steps += 1
        assert tuner.done
        thr, cyc = tuner.current
        assert 2**24 <= thr <= 2**28.5
        assert 0.5 <= cyc <= 10.0

    def test_grid_mode_still_selects_best(self):
        from horovod_tpu.core.config import Config
        from horovod_tpu.obs.autotune import Autotuner

        grid = [(1 << 20, 1.0), (8 << 20, 2.0), (64 << 20, 4.0)]
        cfg = Config(autotune=True, autotune_warmup_samples=0,
                     autotune_steps_per_sample=1)
        tuner = Autotuner(cfg, grid=grid)
        assert tuner.mode == "grid"
        import time as _t

        scores = {grid[0]: 10, grid[1]: 99, grid[2]: 20}
        while not tuner.done:
            cand = tuner.current
            tuner._t_start = _t.monotonic() - 1.0
            tuner._bytes = scores[cand]
            tuner._steps = tuner._steps_per_sample - 1
            tuner.record_step(0)
        assert tuner.current == grid[1]
