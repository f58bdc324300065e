"""hvtpulint: fixture corpus + clean-tree gate.

Each pass gets at least one known-bad and one known-clean fixture tree
under tests/lint_fixtures/ (the trees replicate the repo-relative
layout the passes expect).  `test_repo_is_clean` is the tier-1 gate:
the shipped tree must lint clean, so ABI/knob/metric drift fails CI
before it fails a real job.
"""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from tools.hvtpulint import (Project, load_suppressions, run_passes)
from tools.hvtpulint import (knob_registry, kv_discipline, metrics_catalog,
                             rank_divergence, sim_purity, thread_safety,
                             wire_twin)

REPO_ROOT = Path(__file__).resolve().parent.parent
FIXTURES = REPO_ROOT / "tests" / "lint_fixtures"


def run_pass(module, case: str):
    return module.run(Project(FIXTURES / case))


def keys(findings):
    return {f.key for f in findings}


# --------------------------------------------------------------------------
# wire-twin
# --------------------------------------------------------------------------

class TestWireTwin:
    def test_clean_twin_has_no_findings(self):
        assert run_pass(wire_twin, "wire_twin_clean") == []

    def test_bad_twin_flags_every_seeded_drift(self):
        findings = run_pass(wire_twin, "wire_twin_bad")
        assert keys(findings) == {
            "const:kWireVersion",
            "enum:OpType:Allreduce",
            "enum:OpType:Barrier",
            "order:SerializeResponseList",
            "table-key-separator",
            "burst-delimiter",
        }
        by_key = {f.key: f for f in findings}
        ver = by_key["const:kWireVersion"]
        assert ver.pass_name == "wire-twin"
        assert ver.path == "horovod_tpu/native/wire.py"
        assert ver.line == 5  # the WIRE_VERSION assignment
        assert "kWireVersion=0x4" in ver.message

    def test_bad_twin_burst_delimiter_fires_for_both_twins(self):
        # The bad fixture moves the burst_id/burst_len pair before the
        # flag bytes IDENTICALLY in both twins: the generic order
        # check is blind to it, so only the absolute-position check
        # stands between that edit and silent v5 framing drift.
        findings = run_pass(wire_twin, "wire_twin_bad")
        burst = [f for f in findings if f.key == "burst-delimiter"]
        assert {f.path for f in burst} == {
            wire_twin.MESSAGE_CC, wire_twin.WIRE_PY}
        assert all("burst-unit delimiter" in f.message for f in burst)

    def test_missing_surface_fails_closed(self, tmp_path):
        # An empty tree must produce missing-file findings, not a
        # silent pass.
        findings = wire_twin.run(Project(tmp_path))
        assert any(f.key.startswith("missing-file:") for f in findings)

    def test_real_tree_catches_bumped_wire_version(self, tmp_path):
        """Regression: copy the *real* native sources, bump
        kWireVersion, and the pass must name the drift."""
        for rel in (wire_twin.MESSAGE_H, wire_twin.COMMON_H,
                    wire_twin.MESSAGE_CC, wire_twin.CONTROLLER_CC,
                    wire_twin.WIRE_PY, wire_twin.FALLBACK_PY):
            src = REPO_ROOT / rel
            dst = tmp_path / rel
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(src, dst)

        clean = wire_twin.run(Project(tmp_path))
        assert clean == [], [f.format_text() for f in clean]

        hdr = tmp_path / wire_twin.MESSAGE_H
        text = hdr.read_text(encoding="utf-8")
        assert "kWireVersion = 5" in text
        hdr.write_text(text.replace("kWireVersion = 5", "kWireVersion = 6"),
                       encoding="utf-8")

        findings = wire_twin.run(Project(tmp_path))
        assert keys(findings) == {"const:kWireVersion"}
        f = findings[0]
        assert f.path == wire_twin.WIRE_PY and f.line > 0


# --------------------------------------------------------------------------
# rank-divergence
# --------------------------------------------------------------------------

class TestRankDivergence:
    def test_clean_patterns_are_silent(self):
        findings = run_pass(rank_divergence, "rank_div")
        assert not any("clean.py" in f.path for f in findings)

    def test_bad_patterns_all_flagged(self):
        findings = run_pass(rank_divergence, "rank_div")
        assert keys(findings) == {
            "examples/bad.py:direct_rank_test:broadcast",
            "examples/bad.py:tainted_local:allreduce",
            "examples/bad.py:else_arm:barrier",
            "examples/bad.py:ternary:allreduce",
        }
        for f in findings:
            assert f.pass_name == "rank-divergence"
            assert f.path == "examples/bad.py"
            assert f.line > 0


# --------------------------------------------------------------------------
# thread-safety
# --------------------------------------------------------------------------

class TestThreadSafety:
    def test_clean_discipline_is_silent(self):
        findings = run_pass(thread_safety, "thread_safety")
        assert not any("clean.py" in f.path for f in findings)

    def test_bad_discipline_flagged(self):
        findings = run_pass(thread_safety, "thread_safety")
        assert keys(findings) == {
            "BadWorker._loop:call:_drain",
            "BadWorker.submit:_queue",
            "BadWorker.submit:call:_drain",
            "BadWorker.bump:_depth",
        }
        by_key = {f.key: f for f in findings}
        # racy-read-ok permits the unlocked read in peek_depth but not
        # the write in bump.
        assert "write to self._depth" in by_key["BadWorker.bump:_depth"].message


# --------------------------------------------------------------------------
# knob-registry
# --------------------------------------------------------------------------

class TestKnobRegistry:
    def test_clean_docs_are_silent(self):
        assert run_pass(knob_registry, "knob_clean") == []

    def test_drift_in_every_direction(self):
        findings = run_pass(knob_registry, "knob_bad")
        assert keys(findings) == {
            "HVTPU_FIXTURE_UNDOC",       # read, undocumented
            "HVTPU_FIXTURE_DEAD",        # documented, never read
            "describe:HVTPU_FIXTURE_TODO",  # documented with TODO
        }


# --------------------------------------------------------------------------
# metrics-catalog
# --------------------------------------------------------------------------

class TestMetricsCatalog:
    def test_clean_catalog_is_silent(self):
        assert run_pass(metrics_catalog, "metrics_clean") == []

    def test_drift_in_every_direction(self):
        findings = run_pass(metrics_catalog, "metrics_bad")
        assert keys(findings) == {
            "hvtpu_fixture_undocumented_total",        # registered, uncataloged
            "hvtpu_fixture_stale",                     # cataloged, unregistered
        }


# --------------------------------------------------------------------------
# suppressions
# --------------------------------------------------------------------------

class TestSuppressions:
    def test_entry_without_justification_is_a_finding(self, tmp_path):
        sup = tmp_path / ".hvtpulint.suppress"
        sup.write_text("rank-divergence some:key\n", encoding="utf-8")
        entries, bad = load_suppressions(sup)
        assert entries == []
        assert len(bad) == 1 and bad[0].key == "malformed:1"

    def test_unused_entry_is_a_finding(self, tmp_path):
        (tmp_path / "horovod_tpu").mkdir()
        sup = tmp_path / ".hvtpulint.suppress"
        sup.write_text("rank-divergence no/such:key stale justification\n",
                       encoding="utf-8")
        findings = run_passes(tmp_path, only=["rank-divergence"],
                              suppress_path=sup)
        assert [f.key for f in findings] == \
            ["unused:rank-divergence:no/such:key"]

    def test_suppression_silences_matching_finding(self, tmp_path):
        case = FIXTURES / "rank_div"
        shutil.copytree(case / "examples", tmp_path / "examples")
        sup = tmp_path / ".hvtpulint.suppress"
        sup.write_text(
            "rank-divergence examples/bad.py:direct_rank_test:broadcast "
            "fixture: intentional root-rank broadcast\n", encoding="utf-8")
        findings = run_passes(tmp_path, only=["rank-divergence"],
                              suppress_path=sup)
        got = keys(findings)
        assert "examples/bad.py:direct_rank_test:broadcast" not in got
        assert "examples/bad.py:tainted_local:allreduce" in got

    def test_repo_suppression_file_is_well_formed(self):
        entries, bad = load_suppressions(REPO_ROOT / ".hvtpulint.suppress")
        assert bad == []
        for e in entries:
            assert e.justification  # every entry carries a written reason


# --------------------------------------------------------------------------
# CLI + tier-1 clean-tree gate
# --------------------------------------------------------------------------

class TestCli:
    def test_json_output_and_exit_code_on_fixture(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.hvtpulint",
             "--root", str(FIXTURES / "wire_twin_bad"),
             "--passes", "wire-twin", "--format", "json"],
            cwd=REPO_ROOT, capture_output=True, text=True)
        assert proc.returncode == 1
        payload = json.loads(proc.stdout)
        findings = payload["findings"]
        assert {f["pass_name"] for f in findings} == {"wire-twin"}
        assert any(f["key"] == "const:kWireVersion" for f in findings)

    def test_unknown_pass_is_a_usage_error(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.hvtpulint",
             "--passes", "no-such-pass"],
            cwd=REPO_ROOT, capture_output=True, text=True)
        assert proc.returncode == 2

    def test_list_passes(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.hvtpulint", "--list-passes"],
            cwd=REPO_ROOT, capture_output=True, text=True)
        assert proc.returncode == 0
        listed = set(proc.stdout.split())
        assert {"wire-twin", "rank-divergence", "thread-safety",
                "knob-registry", "metrics-catalog", "sim-purity",
                "kv-discipline"} <= listed


# --------------------------------------------------------------------------
# sim-purity
# --------------------------------------------------------------------------

class TestSimPurity:
    def test_clean_sim_tree_has_no_findings(self):
        assert run_pass(sim_purity, "sim_purity_clean") == []

    def test_bad_tree_flags_every_leak(self):
        findings = run_pass(sim_purity, "sim_purity_bad")
        assert keys(findings) == {
            "time.time:bad.py:1",
            "time.monotonic:bad.py:1",
            "time.sleep:bad.py:1",
            "time.sleep:bad.py:2",      # occurrence-indexed keys
            "time.sleep:bad.py:3",      # from-import alias
            "random.random:bad.py:1",
            "random.seed:bad.py:1",
            "random.randint:bad.py:1",  # from-import of a module fn
        }
        # random.Random(7) instantiation in the same fixture is allowed
        assert not any("random.Random" in k for k in keys(findings))

    def test_real_sim_package_is_pure(self):
        # the shipped simulator itself honours its own contract
        findings = sim_purity.run(Project(REPO_ROOT))
        assert findings == [], "\n".join(
            f.format_text() for f in findings)


# --------------------------------------------------------------------------
# kv-discipline
# --------------------------------------------------------------------------

class TestKvDiscipline:
    def test_clean_wrapper_patterns_are_silent(self):
        assert run_pass(kv_discipline, "kv_disc_clean") == []

    def test_bad_tree_flags_every_leak(self):
        findings = run_pass(kv_discipline, "kv_disc_bad")
        assert keys(findings) == {
            "call:key_value_set:bad.py:1",
            "call:key_value_set:bad.py:2",        # occurrence-indexed
            "call:blocking_key_value_get:bad.py:1",
            "call:key_value_dir_get:bad.py:1",    # chained, no binding
            "call:key_value_delete:bad.py:1",     # taint through alias
            "escape:_kv:bad.py:1",                # raw client on self
        }
        by_key = {f.key: f for f in findings}
        esc = by_key["escape:_kv:bad.py:1"]
        assert esc.pass_name == "kv-discipline"
        assert esc.path == "horovod_tpu/bad.py"
        assert "self._kv" in esc.message
        assert "FencedKV/ResilientKV" in esc.message

    def test_real_tree_has_only_the_transport_escape(self):
        # The eager KVTransport deliberately holds the raw client (see
        # the justified entry in .hvtpulint.suppress); everything else
        # in the shipped tree must go through core/retry.py wrappers.
        findings = kv_discipline.run(Project(REPO_ROOT))
        assert keys(findings) == {"escape:_kv:controller.py:1"}, \
            "\n".join(f.format_text() for f in findings)


def test_repo_is_clean():
    """Tier-1 gate: the shipped tree lints clean (with the checked-in
    suppression file).  A failure here IS the lint finding — run
    `python -m tools.hvtpulint` for the full text."""
    findings = run_passes(REPO_ROOT)
    assert findings == [], "\n" + "\n".join(f.format_text() for f in findings)


def test_knobs_md_regeneration_is_stable():
    """--write-knobs over the current tree must be a no-op: the checked
    in docs/knobs.md matches what the extractor produces."""
    project = Project(REPO_ROOT)
    regenerated = knob_registry.generate_knobs_md(project)
    on_disk = (REPO_ROOT / "docs" / "knobs.md").read_text(encoding="utf-8")
    assert regenerated == on_disk
