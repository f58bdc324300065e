"""The fabric simulator's records: BENCH_SCALING.json holds the
virtual-time rows that ``python -m tools.hvtpusim bench* --update``
writes and that docs/simulation.md, robustness.md, fleet.md and
observability.md quote.  Each block must be marked measured, cover the
contracted world sizes and carry finite numbers.  None of them is a
speed: those live in PERF_LEDGER.jsonl."""

import json
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(_ROOT, "BENCH_SCALING.json")) as f:
        return json.load(f)


class TestControlPlaneSimSchema:
    """BENCH_SCALING.json carries MEASURED control-plane rows from the
    fabric simulator (tools/hvtpusim bench): negotiation cycle,
    rendezvous, drain notice->commit vs world size.  These rows are
    what docs/simulation.md quotes for control-plane scaling, so the
    schema is load-bearing: every row must be marked measured, cover
    the contracted world sizes, and carry finite positive
    virtual-time numbers."""

    REQUIRED_ROW_KEYS = {
        "ranks", "negotiation_cycle_p50_s", "negotiation_cycle_max_s",
        "rendezvous_s", "rendezvous_p50_s", "drain_notice_to_commit_s",
        "measured", "method",
    }

    def test_measured_rows_present_and_complete(self, doc):
        sim = doc["control_plane_sim"]
        assert "measured" in sim["note"].lower()
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
