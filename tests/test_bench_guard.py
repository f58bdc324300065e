"""bench.py's guards: every report names the device it ran on and the
run refuses to time anything off a TPU, and the embedded metrics
snapshot (every bench JSON line must carry the condensed registry
snapshot so bench trajectories stay schema-comparable on wire-bytes
and cycle stats)."""

import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)


@pytest.fixture
def bench():
    import importlib

    import bench as bench_mod

    return importlib.reload(bench_mod)


class TestDeviceIdentity:
    """A number is only a device number if the line says which device:
    the report carries JAX's own identity of it, and a CPU run ends
    before anything is built or timed."""

    def test_identity_is_what_jax_reports(self, bench):
        import jax

        ident = bench.device_identity()
        assert ident == {
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
        }

    def test_report_carries_the_identity(self, bench):
        report = bench.build_report(
            metric="m", value=1.0, unit="u", **bench.device_identity())
        for key in ("platform", "device_kind", "device_count"):
            assert key in report
        json.dumps(report)

    def test_require_tpu_names_what_it_found(self, bench):
        with pytest.raises(SystemExit) as exc:
            bench.require_tpu()
        msg = str(exc.value)
        assert exc.value.code != 0
        assert "platform='cpu'" in msg and "JAX_PLATFORMS" in msg

    def test_main_exits_before_building_anything(self, bench,
                                                 monkeypatch):
        import horovod_tpu as hvt

        def untouched(*a, **kw):
            raise AssertionError("bench built a model off a TPU")

        monkeypatch.setattr(bench.hvt, "enable_compile_cache",
                            lambda: "unused")
        monkeypatch.setitem(
            bench.MODELS, bench.MODEL,
            (untouched,) + bench.MODELS[bench.MODEL][1:])
        try:
            with pytest.raises(SystemExit, match="Not timing anything"):
                bench.main()
        finally:
            hvt.shutdown()


class TestMetricsEmbedding:
    """The bench JSON schema REQUIRES the embedded metrics snapshot —
    future bench rounds must stay comparable on wire bytes and cycle
    stats, not just img/s."""

    def test_report_always_embeds_metrics(self, bench):
        report = bench.build_report(metric="m", value=1.0, unit="u")
        assert "metrics" in report
        for key in bench.REQUIRED_METRIC_KEYS:
            assert key in report["metrics"], key
        # the report must stay a single JSON-serializable line
        json.dumps(report)

    def test_condensed_schema_shapes(self, bench):
        from horovod_tpu.obs import metrics as obs_metrics

        reg = obs_metrics.MetricsRegistry()
        reg.counter("hvtpu_wire_bytes_total").inc(4096)
        reg.histogram("hvtpu_controller_cycle_seconds",
                      buckets=[0.1]).observe(0.05)
        out = bench.condense_metrics(reg.snapshot())
        assert out["hvtpu_wire_bytes_total"] == 4096
        cell = out["hvtpu_controller_cycle_seconds"]
        assert cell["count"] == 1 and cell["sum"] == 0.05
        # untouched required families appear as zeros, never missing
        assert out["hvtpu_allreduce_total"] == 0
        assert out["hvtpu_optimizer_steps_total"] == 0

    def test_required_keys_cover_wire_and_cycles(self, bench):
        required = set(bench.REQUIRED_METRIC_KEYS)
        assert "hvtpu_wire_bytes_total" in required
        assert "hvtpu_controller_cycle_seconds" in required
        assert "hvtpu_optimizer_steps_total" in required
        # PR 7: straggler signal rides in every bench line
        assert "hvtpu_collective_arrival_skew_seconds" in required

    def test_report_embeds_arrival_skew_summary(self, bench):
        report = bench.build_report(metric="m", value=1.0, unit="u")
        skew = report["arrival_skew"]
        assert set(skew) == {"collectives", "mean_seconds"}
        # 1-proc run: no multi-rank collectives, schema still stable
        assert skew["collectives"] == report["metrics"][
            "hvtpu_collective_arrival_skew_seconds"]["count"]
        json.dumps(report)

    def test_required_keys_cover_data_pipeline(self, bench):
        # PR 9: input-pipeline counters ride in every bench line
        required = set(bench.REQUIRED_METRIC_KEYS)
        assert "hvtpu_data_wait_seconds" in required
        assert "hvtpu_data_batches_delivered_total" in required
        assert "hvtpu_data_samples_delivered_total" in required

    def test_required_keys_cover_durable_state_plane(self, bench):
        required = set(bench.REQUIRED_METRIC_KEYS)
        assert {"hvtpu_ckpt_commit_seconds",
                "hvtpu_ckpt_bytes_written_total",
                "hvtpu_ckpt_verify_failures_total",
                "hvtpu_ckpt_restore_quorum_rounds_total"} <= required
        # histogram condenses to {count, sum}; counters to scalars
        m = bench.condense_metrics({})
        assert m["hvtpu_ckpt_commit_seconds"] == {"count": 0,
                                                 "sum": 0.0}
        assert m["hvtpu_ckpt_verify_failures_total"] == 0

    def test_report_embeds_data_stall_row(self, bench):
        from horovod_tpu.obs import metrics as obs_metrics

        # the histogram is the process's: whatever loaders ran in this
        # worker before have added to it, so the row is checked against
        # the one snapshot its report embeds, at a sum that is not zero
        obs_metrics.histogram("hvtpu_data_wait_seconds").observe(0.1234567)
        report = bench.build_report(metric="m", value=1.0, unit="u",
                                    elapsed_seconds=10.0)
        stall = report["data_stall"]
        assert set(stall) == {"batches", "wait_seconds",
                              "stall_fraction"}
        wait = report["metrics"]["hvtpu_data_wait_seconds"]
        assert stall["batches"] == wait["count"] >= 1
        assert stall["wait_seconds"] == round(wait["sum"], 6)
        # derived against the caller's wall time (both figures are
        # rounded to six places, each from the unrounded sum); null
        # without it
        assert stall["stall_fraction"] == round(wait["sum"] / 10.0, 6)
        assert stall["stall_fraction"] == pytest.approx(
            stall["wait_seconds"] / 10.0, abs=1e-6)
        no_elapsed = bench.build_report(metric="m", value=1.0, unit="u")
        assert no_elapsed["data_stall"]["stall_fraction"] is None
        json.dumps(report)


class TestOverlapSchema:
    """PR 12: the measured overlap/MFU columns ride in every bench
    line and distinguish measured-zero from never-measured."""

    def test_required_keys_cover_overlap(self, bench):
        required = set(bench.REQUIRED_METRIC_KEYS)
        assert "hvtpu_step_exposed_comm_seconds" in required
        assert "hvtpu_step_overlap_fraction" in required
        assert "hvtpu_mfu" in required

    def test_report_embeds_overlap_row(self, bench):
        report = bench.build_report(metric="m", value=1.0, unit="u")
        row = report["overlap"]
        assert set(row) == {"steps", "exposed_comm_seconds",
                            "overlap_fraction", "mfu"}
        assert row["steps"] == report["metrics"][
            "hvtpu_step_exposed_comm_seconds"]["count"]
        json.dumps(report)

    def test_unmeasured_gauges_report_null_not_zero(self, bench):
        from horovod_tpu.obs import stepprof

        stepprof.OVERLAP_FRACTION.set(0.0)
        stepprof.MFU.set(0.0)
        row = bench.build_report(metric="m", value=1.0,
                                 unit="u")["overlap"]
        # 0 means "never joined / no FLOPs provided", reported null so
        # a recorded 0.31 always means measured-0.31
        assert row["overlap_fraction"] is None
        assert row["mfu"] is None
        stepprof.OVERLAP_FRACTION.set(0.31)
        stepprof.MFU.set(0.42)
        try:
            row = bench.build_report(metric="m", value=1.0,
                                     unit="u")["overlap"]
            assert row["overlap_fraction"] == 0.31
            assert row["mfu"] == 0.42
        finally:
            stepprof.OVERLAP_FRACTION.set(0.0)
            stepprof.MFU.set(0.0)


class TestTorchStepSchema:
    """bench_eager's torch DistributedOptimizer step-time row: the
    schema is enforced so future rounds stay comparable, and
    BENCH_EAGER.json must actually carry a recorded P=4 row."""

    @pytest.fixture
    def bench_eager(self):
        import importlib

        import bench_eager as mod

        return importlib.reload(mod)

    def test_row_builder_schema(self, bench_eager):
        row = bench_eager.build_torch_step_row(4, 16, 1 << 20, 2.5)
        assert set(bench_eager.TORCH_STEP_KEYS) <= set(row)
        assert row["bench"] == "eager_torch_step"
        assert row["np"] == 4
        assert row["steps_per_s"] == pytest.approx(400.0)
        json.dumps(row)  # single JSON-serializable line

    def test_recorded_bench_has_torch_step_row(self, bench_eager):
        with open(os.path.join(_ROOT, "BENCH_EAGER.json")) as f:
            data = json.load(f)
        row = data["torch_step"]
        assert row["np"] == 4
        for key in bench_eager.TORCH_STEP_KEYS:
            assert key in row, key
        assert row["ms_per_step"] > 0


class TestPredictSchema:
    """Round 7: every controller-driven async row carries the
    schedule-prediction columns (predicted_fraction, mispredicts,
    mispredict_rate), and the recorded steady-state rows prove the
    default-on fast path actually engaged — predicted_fraction above
    0.8 with zero unrecovered mispredicts.  Round 8 adds
    zero_copy_fraction (fused ops riding the enqueue-time-packed
    exchange buffer) and requires it to be 1.0 on steady np=4 rows."""

    @pytest.fixture
    def bench_eager(self):
        import importlib

        import bench_eager as mod

        return importlib.reload(mod)

    def test_stats_builder_schema(self, bench_eager):
        before = {"cycles": 10, "predicted": 2, "mispredicts": 0,
                  "zero_copy": 4, "staged": 8}
        after = {"cycles": 74, "predicted": 58, "mispredicts": 1,
                 "zero_copy": 52, "staged": 24}
        stats = bench_eager.build_predict_stats(before, after)
        assert set(stats) == set(bench_eager.PREDICT_ROW_KEYS)
        assert stats["predicted_fraction"] == pytest.approx(56 / 64)
        assert stats["mispredicts"] == 1
        assert stats["mispredict_rate"] == pytest.approx(
            1 / 64, abs=1e-4)
        assert stats["zero_copy_fraction"] == pytest.approx(48 / 64)
        json.dumps(stats)

    def test_stats_builder_accepts_round7_snapshots(self, bench_eager):
        """Three-key snapshots (pre-round-8 recordings) still build:
        the fusion-path keys default to 0 -> null fraction."""
        before = {"cycles": 10, "predicted": 2, "mispredicts": 0}
        after = {"cycles": 74, "predicted": 58, "mispredicts": 1}
        stats = bench_eager.build_predict_stats(before, after)
        assert set(stats) == set(bench_eager.PREDICT_ROW_KEYS)
        assert stats["zero_copy_fraction"] is None

    def test_zero_cycle_window_is_null_not_crash(self, bench_eager):
        snap = {"cycles": 5, "predicted": 1, "mispredicts": 0,
                "zero_copy": 0, "staged": 0}
        stats = bench_eager.build_predict_stats(snap, dict(snap))
        assert stats["predicted_fraction"] is None
        assert stats["mispredict_rate"] is None
        assert stats["mispredicts"] == 0
        assert stats["zero_copy_fraction"] is None

    def test_recorded_steady_rows_predicted_without_mispredicts(
            self, bench_eager):
        with open(os.path.join(_ROOT, "BENCH_EAGER.json")) as f:
            data = json.load(f)
        async_np4 = [r for r in data["results"]
                     if r.get("np") == 4
                     and r["mode"].startswith("async")]
        assert async_np4
        for row in async_np4:
            for key in bench_eager.PREDICT_ROW_KEYS:
                assert key in row, (row["mode"], row["nbytes"], key)
            assert row["predicted_fraction"] > 0.8, row
            assert row["mispredicts"] == 0, row
            # round 8: the whole timed window rode the zero-copy path
            assert row["zero_copy_fraction"] == 1.0, row
        # the torch e2e step row rides the same schema
        for key in bench_eager.PREDICT_ROW_KEYS:
            assert key in data["torch_step"], key


class TestControlPlaneSimSchema:
    """BENCH_SCALING.json carries MEASURED control-plane rows from the
    fabric simulator (tools/hvtpusim bench): negotiation cycle,
    rendezvous, drain notice->commit vs world size.  These rows
    supersede the coordination_vs_P projection for control-plane
    scaling claims, so the schema is load-bearing: every row must be
    marked measured, cover the contracted world sizes, and carry
    finite positive virtual-time numbers."""

    REQUIRED_ROW_KEYS = {
        "ranks", "negotiation_cycle_p50_s", "negotiation_cycle_max_s",
        "rendezvous_s", "rendezvous_p50_s", "drain_notice_to_commit_s",
        "measured", "method",
    }

    @pytest.fixture
    def doc(self):
        with open(os.path.join(_ROOT, "BENCH_SCALING.json")) as f:
            return json.load(f)

    def test_measured_rows_present_and_complete(self, doc):
        sim = doc["control_plane_sim"]
        assert "supersede" in sim["note"].lower()
        rows = sim["rows"]
        assert {r["ranks"] for r in rows} >= {64, 256, 1024}
        for row in rows:
            assert self.REQUIRED_ROW_KEYS <= set(row), row.get("ranks")
            assert row["measured"] is True
            assert "fabric-sim" in row["method"]

    def test_timings_are_finite_positive_virtual_seconds(self, doc):
        for row in doc["control_plane_sim"]["rows"]:
            for key in ("negotiation_cycle_p50_s",
                        "negotiation_cycle_max_s", "rendezvous_s",
                        "rendezvous_p50_s", "drain_notice_to_commit_s"):
                v = row[key]
                assert isinstance(v, (int, float)) and 0 < v < 3600, (
                    f"ranks={row['ranks']} {key}={v!r}")
            assert row["negotiation_cycle_p50_s"] <= (
                row["negotiation_cycle_max_s"])

    def test_projection_is_marked_superseded(self, doc):
        # the old extrapolation stays for history but must point at
        # the measured rows
        note = doc.get("coordination_note", "")
        assert "control_plane_sim" in note, (
            "coordination_vs_P must reference the measured "
            "control_plane_sim rows that supersede it")


class TestFleetArbiterSimSchema:
    """BENCH_SCALING.json carries MEASURED multi-job arbiter rows from
    the fabric simulator (tools/hvtpusim bench-fleet): gang queue wait,
    preemption notice->commit, and victim resize latency vs pool size.
    These back the docs/fleet.md latency claims, so the schema is
    load-bearing like the control-plane rows above."""

    REQUIRED_ROW_KEYS = {
        "ranks", "queue_wait_s", "preempt_notice_to_commit_s",
        "resize_s", "victims", "measured", "method",
    }

    @pytest.fixture
    def doc(self):
        with open(os.path.join(_ROOT, "BENCH_SCALING.json")) as f:
            return json.load(f)

    def test_measured_rows_present_and_complete(self, doc):
        sim = doc["fleet_arbiter_sim"]
        assert "drain" in sim["note"].lower()
        rows = sim["rows"]
        assert {r["ranks"] for r in rows} >= {64, 256, 1024}
        for row in rows:
            assert self.REQUIRED_ROW_KEYS <= set(row), row.get("ranks")
            assert row["measured"] is True
            assert "fabric-sim" in row["method"]

    def test_timings_are_finite_positive_virtual_seconds(self, doc):
        for row in doc["fleet_arbiter_sim"]["rows"]:
            for key in ("queue_wait_s", "preempt_notice_to_commit_s",
                        "resize_s"):
                v = row[key]
                assert isinstance(v, (int, float)) and 0 < v < 3600, (
                    f"ranks={row['ranks']} {key}={v!r}")
            # drain commit happens strictly inside the resize window
            assert row["preempt_notice_to_commit_s"] < row["resize_s"]
            # half the low-priority world is reclaimed for the arrival
            assert row["victims"] == row["ranks"] // 2


class TestCheckpointStormSimSchema:
    """BENCH_SCALING.json carries MEASURED durable-state-plane rows
    from the fabric simulator (tools/hvtpusim bench-ckpt): commit
    latency through the real commit protocol and restore-quorum
    latency at 64-1024 virtual ranks.  These back the
    docs/robustness.md durable-plane latency claims."""

    REQUIRED_ROW_KEYS = {
        "ranks", "commit_p50_s", "commit_p99_s", "quorum_p50_s",
        "quorum_max_s", "agreed_seq", "measured", "method",
    }

    @pytest.fixture
    def doc(self):
        with open(os.path.join(_ROOT, "BENCH_SCALING.json")) as f:
            return json.load(f)

    def test_measured_rows_present_and_complete(self, doc):
        sim = doc["checkpoint_storm_sim"]
        assert "measured" in sim["note"].lower()
        rows = sim["rows"]
        assert {r["ranks"] for r in rows} >= {64, 256, 1024}
        for row in rows:
            assert self.REQUIRED_ROW_KEYS <= set(row), row.get("ranks")
            assert row["measured"] is True
            assert "fabric-sim" in row["method"]

    def test_timings_are_finite_positive_virtual_seconds(self, doc):
        for row in doc["checkpoint_storm_sim"]["rows"]:
            for key in ("commit_p50_s", "commit_p99_s", "quorum_p50_s",
                        "quorum_max_s"):
                v = row[key]
                assert isinstance(v, (int, float)) and 0 < v < 3600, (
                    f"ranks={row['ranks']} {key}={v!r}")
            assert row["commit_p50_s"] <= row["commit_p99_s"]
            assert row["quorum_p50_s"] <= row["quorum_max_s"]
            # both storage victims fell back one commit: the agreed
            # restore point is commits-1 (the scenario default is 4)
            assert row["agreed_seq"] == 3


class TestAnomalyDetectionSimSchema:
    """BENCH_SCALING.json carries MEASURED straggler-detection-latency
    rows from the fabric simulator (tools/hvtpusim bench-anomaly): the
    real AnomalyEngine fed per-cycle arrival skew while one virtual
    rank's link degrades mid-run.  These back the
    docs/observability.md incident-detection claims."""

    REQUIRED_ROW_KEYS = {
        "ranks", "detection_latency_p50_s", "detection_latency_max_s",
        "seeds", "measured", "method",
    }

    @pytest.fixture
    def doc(self):
        with open(os.path.join(_ROOT, "BENCH_SCALING.json")) as f:
            return json.load(f)

    def test_measured_rows_present_and_complete(self, doc):
        sim = doc["anomaly_detection_sim"]
        assert "straggler" in sim["note"].lower()
        rows = sim["rows"]
        assert {r["ranks"] for r in rows} >= {256, 1024}
        for row in rows:
            assert self.REQUIRED_ROW_KEYS <= set(row), row.get("ranks")
            assert row["measured"] is True
            assert "fabric-sim" in row["method"]

    def test_latencies_are_finite_positive_virtual_seconds(self, doc):
        for row in doc["anomaly_detection_sim"]["rows"]:
            p50 = row["detection_latency_p50_s"]
            mx = row["detection_latency_max_s"]
            for v in (p50, mx):
                assert isinstance(v, (int, float)) and 0 < v < 3600, (
                    f"ranks={row['ranks']} latency={v!r}")
            assert p50 <= mx
            assert row["seeds"] >= 3

    def test_required_keys_cover_flight_and_incidents(self):
        import bench

        required = set(bench.REQUIRED_METRIC_KEYS)
        assert {"hvtpu_flight_events_total", "hvtpu_incidents_total",
                "hvtpu_fleet_job_step_rate",
                "hvtpu_fleet_job_incidents"} <= required


class TestCoordinatorLossSimSchema:
    """BENCH_SCALING.json carries MEASURED coordinator-loss recovery
    rows from the fabric simulator: coordinator death -> every
    survivor's lease-expiry self-fence (detect), then re-election +
    durable-key journal replay into the fresh KV (recover).  These
    back the docs/robustness.md coordination-plane claims."""

    REQUIRED_ROW_KEYS = {
        "ranks", "detect_p50_s", "detect_max_s", "fence_exits",
        "replayed_keys", "fence_to_recover_s", "measured", "method",
    }

    @pytest.fixture
    def doc(self):
        with open(os.path.join(_ROOT, "BENCH_SCALING.json")) as f:
            return json.load(f)

    def test_measured_rows_present_and_complete(self, doc):
        sim = doc["coordinator_loss_sim"]
        assert "journal" in sim["note"].lower()
        rows = sim["rows"]
        assert {r["ranks"] for r in rows} >= {64, 256, 1024}
        for row in rows:
            assert self.REQUIRED_ROW_KEYS <= set(row), row.get("ranks")
            assert row["measured"] is True
            assert "fabric-sim" in row["method"]

    def test_timings_are_finite_positive_virtual_seconds(self, doc):
        for row in doc["coordinator_loss_sim"]["rows"]:
            for key in ("detect_p50_s", "detect_max_s",
                        "fence_to_recover_s"):
                v = row[key]
                assert isinstance(v, (int, float)) and 0 < v < 3600, (
                    f"ranks={row['ranks']} {key}={v!r}")
            assert row["detect_p50_s"] <= row["detect_max_s"]
            # every rank fenced (split-brain window fully closed) and
            # every rank's journaled vote landed in the fresh KV
            assert row["fence_exits"] == row["ranks"]
            assert row["replayed_keys"] == row["ranks"]

    def test_required_keys_cover_fencing(self):
        import bench

        required = set(bench.REQUIRED_METRIC_KEYS)
        assert {"hvtpu_kv_fenced_writes_total",
                "hvtpu_fence_exits_total",
                "hvtpu_partition_suspect_seconds"} <= required


class TestPartitionStormSimSchema:
    """BENCH_SCALING.json carries MEASURED partition-storm rows from
    the fabric simulator: partition(MS) windows on three victims,
    peers classifying the silent ranks as partitioned-vs-dead by lease
    age, two thaw-and-recover, one lease-starved self-fence."""

    REQUIRED_ROW_KEYS = {
        "ranks", "detect_p50_s", "detect_max_s", "victims",
        "recovered", "fence_latency_s", "suspect_observations",
        "measured", "method",
    }

    @pytest.fixture
    def doc(self):
        with open(os.path.join(_ROOT, "BENCH_SCALING.json")) as f:
            return json.load(f)

    def test_measured_rows_present_and_complete(self, doc):
        sim = doc["partition_storm_sim"]
        assert "suspect" in sim["note"].lower()
        rows = sim["rows"]
        assert {r["ranks"] for r in rows} >= {64, 256, 1024}
        for row in rows:
            assert self.REQUIRED_ROW_KEYS <= set(row), row.get("ranks")
            assert row["measured"] is True
            assert "fabric-sim" in row["method"]

    def test_timings_are_finite_positive_virtual_seconds(self, doc):
        for row in doc["partition_storm_sim"]["rows"]:
            for key in ("detect_p50_s", "detect_max_s",
                        "fence_latency_s"):
                v = row[key]
                assert isinstance(v, (int, float)) and 0 < v < 3600, (
                    f"ranks={row['ranks']} {key}={v!r}")
            assert row["detect_p50_s"] <= row["detect_max_s"]
            # exactly one victim fences; the thawed rest recover
            assert row["recovered"] == row["victims"] - 1
            assert row["suspect_observations"] > 0


class TestFleetServiceSimSchema:
    """BENCH_SCALING.json carries MEASURED fleet front-door rows from
    the fabric simulator (tools/hvtpusim bench-service): a seeded
    multi-tenant submission storm through the indexed journal into the
    real arbiter, with quotas, fair share, the starvation guard,
    torus placement, backpressure and an injected arbiter crash.
    These back the docs/fleet.md service-level claims, so the schema
    is load-bearing like the other sim families."""

    REQUIRED_ROW_KEYS = {
        "ranks", "jobs", "queue_wait_p50_s", "queue_wait_p99_s",
        "intake_p50_s", "intake_p99_s", "max_batch",
        "queue_full_rejections", "quota_rejections",
        "replayed_duplicates", "frag_mean", "preemptions",
        "aged_jobs", "starvation_gap_max_s", "measured", "method",
    }

    @pytest.fixture
    def doc(self):
        with open(os.path.join(_ROOT, "BENCH_SCALING.json")) as f:
            return json.load(f)

    def test_measured_rows_present_and_complete(self, doc):
        sim = doc["fleet_service_sim"]
        assert "exactly-once" in sim["note"].lower()
        rows = sim["rows"]
        # the tier-1 storm plus the 4096/16384 scale proofs
        assert {r["ranks"] for r in rows} >= {256, 4096, 16384}
        for row in rows:
            assert self.REQUIRED_ROW_KEYS <= set(row), row.get("ranks")
            assert row["measured"] is True
            assert "fabric-sim" in row["method"]

    def test_timings_are_finite_virtual_seconds(self, doc):
        for row in doc["fleet_service_sim"]["rows"]:
            # per-tier percentile maps: every tier present, finite,
            # p50 <= p99
            p50, p99 = row["queue_wait_p50_s"], row["queue_wait_p99_s"]
            assert set(p50) == set(p99) == {"0", "5", "10"}
            for tier in p50:
                assert 0 <= p50[tier] <= p99[tier] < 3600, (
                    f"ranks={row['ranks']} tier={tier}")
            assert 0 < row["intake_p50_s"] <= row["intake_p99_s"] < 3600
            assert 0 <= row["frag_mean"] <= 1
            assert 0 <= row["starvation_gap_max_s"] < 3600

    def test_front_door_invariants(self, doc):
        for row in doc["fleet_service_sim"]["rows"]:
            # the intake budget bound held at every pool size
            assert 0 < row["max_batch"] <= 256, row["ranks"]
            # backpressure, quota rejection and crash replay all
            # actually fired — rows from a storm that exercised
            # nothing would vacuously pass the timing checks
            assert row["queue_full_rejections"] >= 1
            assert row["quota_rejections"] >= 1
            assert row["replayed_duplicates"] >= 1
            assert row["jobs"] >= 2 * row["ranks"] // 8

    def test_required_keys_cover_front_door(self):
        import bench

        required = set(bench.REQUIRED_METRIC_KEYS)
        assert {"hvtpu_fleet_queue_depth", "hvtpu_fleet_intake_lag",
                "hvtpu_fleet_admission_rejections_total",
                "hvtpu_fleet_fragmentation"} <= required


class TestLossyLinkSimSchema:
    """BENCH_SCALING.json carries MEASURED lossy-link recovery rows
    from the fabric simulator (tools/hvtpusim bench-lossy): a seeded
    lossy fabric drops collective exchanges mid-step; the wire plane
    recovers them by consensus abort-and-retry plus ring route-around
    instead of restarting, and every row pairs the recovery cost with
    the restart-baseline cost of the SAME seed with retries disabled.
    These back the docs/robustness.md degradation-ladder claims."""

    REQUIRED_ROW_KEYS = {
        "ranks", "steps", "retry_rounds", "recovered_collectives",
        "consensus_p50_s", "consensus_max_s", "reroutes", "torn",
        "steps_lost_with_retries", "baseline_restarts",
        "baseline_steps_lost", "measured", "method",
    }

    @pytest.fixture
    def doc(self):
        with open(os.path.join(_ROOT, "BENCH_SCALING.json")) as f:
            return json.load(f)

    def test_measured_rows_present_and_complete(self, doc):
        sim = doc["lossy_link_sim"]
        assert "lossy" in sim["note"].lower()
        rows = sim["rows"]
        assert {r["ranks"] for r in rows} >= {64, 256, 1024}
        for row in rows:
            assert self.REQUIRED_ROW_KEYS <= set(row), row.get("ranks")
            assert row["measured"] is True
            assert "fabric-sim" in row["method"]

    def test_recovery_beats_restart_baseline(self, doc):
        for row in doc["lossy_link_sim"]["rows"]:
            # the lossy fabric actually bit, and retries absorbed it:
            # no torn results, no steps lost — while the SAME seed
            # with retries disabled restarted and lost work
            assert row["retry_rounds"] >= 1, row["ranks"]
            assert row["recovered_collectives"] >= 1, row["ranks"]
            assert row["torn"] == 0, row["ranks"]
            assert row["steps_lost_with_retries"] == 0, row["ranks"]
            assert row["baseline_restarts"] >= 1, row["ranks"]
            assert row["baseline_steps_lost"] > 0, row["ranks"]
            v = row["consensus_p50_s"]
            assert isinstance(v, (int, float)) and 0 < v < 3600, (
                f"ranks={row['ranks']} consensus_p50_s={v!r}")
            assert row["consensus_p50_s"] <= row["consensus_max_s"]

    def test_required_keys_cover_wire_plane(self):
        import bench

        required = set(bench.REQUIRED_METRIC_KEYS)
        assert {"hvtpu_collective_retries_total",
                "hvtpu_collective_abort_consensus_seconds",
                "hvtpu_link_health",
                "hvtpu_ring_reroutes_total"} <= required
