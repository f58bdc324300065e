"""obs/stepprof.py + hvtputrace overlap — measured overlap profiling.

Covers (ISSUE PR 12): the six-way interval-algebra decomposition and
its ``sum(parts) == step_wall`` invariant over synthetic interval sets
(full/zero/partial/multi-stream overlap), the hardened xplane loader
(absent/empty/truncated -> explicit status, never an IndexError
mid-varint), the device-profile join against the checked-in fixture
xplane, the runtime collector's metrics, measured MFU provenance via
``cost_analysis()``, and the 2-proc acceptance where an injected
pre-collective delay on rank 1 surfaces as rank-0 *exposed* comm in
``python -m tools.hvtputrace overlap``.
"""

import json
import os

import pytest

import horovod_tpu
from horovod_tpu.obs import metrics as obs_metrics
from horovod_tpu.obs import profile, stepprof, tracing
from horovod_tpu.runner import run
from tools import hvtputrace

_REPO_ROOT = os.path.dirname(os.path.dirname(horovod_tpu.__file__))
_FIXTURE_XPLANE = os.path.join(_REPO_ROOT, "tests", "fixtures")
_ENV = {"PYTHONPATH": _REPO_ROOT + os.pathsep
        + os.environ.get("PYTHONPATH", "")}

_PART_KEYS = ("compute", "overlapped_comm", "exposed_comm",
              "data_wait", "host", "idle")


def _sum_parts(parts):
    return sum(parts[k] for k in _PART_KEYS)


# --------------------------------------------------------------------------
# interval algebra
# --------------------------------------------------------------------------

class TestIntervalAlgebra:
    def test_union_merges_overlaps_and_sorts(self):
        assert stepprof.union([(5, 7), (0, 2), (1, 3), (7, 7)]) \
            == [(0, 3), (5, 7)]

    def test_intersect(self):
        assert stepprof.intersect([(0, 4), (6, 10)], [(3, 7)]) \
            == [(3, 4), (6, 7)]

    def test_subtract(self):
        assert stepprof.subtract([(0, 10)], [(2, 3), (5, 7)]) \
            == [(0, 2), (3, 5), (7, 10)]

    def test_clip_and_total(self):
        assert stepprof.clip([(0, 4), (3, 8)], 2, 6) == [(2, 6)]
        assert stepprof.total([(0, 2), (5, 8)]) == 5


class TestDecompose:
    """The six-way split's invariant across overlap regimes."""

    def test_full_overlap(self):
        p = stepprof.decompose(0, 10, compute=[(0, 10)], comm=[(2, 6)])
        assert p["overlapped_comm"] == 4
        assert p["exposed_comm"] == 0
        assert p["compute"] == 6
        assert p["overlap_fraction"] == 1.0
        assert _sum_parts(p) == p["step_wall"] == 10

    def test_zero_overlap(self):
        p = stepprof.decompose(0, 10, compute=[(0, 4)], comm=[(5, 9)])
        assert p["overlapped_comm"] == 0
        assert p["exposed_comm"] == 4
        assert p["overlap_fraction"] == 0.0
        assert p["idle"] == 2
        assert _sum_parts(p) == 10

    def test_partial_overlap(self):
        p = stepprof.decompose(0, 10, compute=[(0, 6)], comm=[(4, 8)])
        assert p["overlapped_comm"] == 2
        assert p["exposed_comm"] == 2
        assert p["compute"] == 4
        assert p["overlap_fraction"] == 0.5
        assert _sum_parts(p) == 10

    def test_multi_stream_overlap(self):
        """Several comm streams + fragmented compute: union semantics,
        not per-stream double counting."""
        p = stepprof.decompose(
            0, 20,
            compute=[(0, 5), (8, 12), (15, 20)],
            comm=[(3, 9), (4, 10), (11, 16)],   # overlapping streams
            data=[(9, 11)], host=[(5, 8)])
        # comm union [3,10)+[11,16) = 12; compute covers [3,5)+[8,10)+
        # [11,12)+[15,16) of it -> overlapped 6, exposed 6
        assert p["overlapped_comm"] == 6
        assert p["exposed_comm"] == 6
        assert p["data_wait"] == 0  # [9,11) is inside comm
        assert _sum_parts(p) == pytest.approx(p["step_wall"])

    def test_priority_comm_then_data_then_host(self):
        p = stepprof.decompose(
            0, 10, comm=[(0, 4)], data=[(2, 6)], host=[(5, 8)])
        assert p["exposed_comm"] == 4
        assert p["data_wait"] == 2   # [4,6): the part outside comm
        assert p["host"] == 2        # [6,8): outside comm+data
        assert p["idle"] == 2
        assert _sum_parts(p) == 10

    def test_no_comm_has_null_fraction(self):
        p = stepprof.decompose(0, 5, compute=[(0, 5)])
        assert p["overlap_fraction"] is None
        assert _sum_parts(p) == 5

    def test_windows_clip_to_step(self):
        p = stepprof.decompose(10, 20, compute=[(0, 12)], comm=[(18, 40)])
        assert p["compute"] == 2
        assert p["exposed_comm"] == 2
        assert _sum_parts(p) == 10

    def test_tool_decompose_matches_runtime(self):
        """hvtputrace carries a jax-free mirror of the decomposition;
        the two implementations must agree bucket for bucket."""
        cases = [
            dict(compute=[(0, 6)], comm=[(4, 8)], data=[(8, 9)],
                 host=[(9, 10)]),
            dict(compute=[(0, 5), (8, 12), (15, 20)],
                 comm=[(3, 9), (4, 10), (11, 16)], data=[(9, 11)],
                 host=[(5, 8)]),
            dict(comm=[(1, 2)], host=[(0, 20)]),
            dict(),
        ]
        for kw in cases:
            a = stepprof.decompose(0, 20, **kw)
            b = hvtputrace.decompose_window(0, 20, **kw)
            for k in _PART_KEYS + ("step_wall",):
                assert a[k] == pytest.approx(b[k]), (k, kw)

    def test_exposed_span_blame(self):
        comp = stepprof.union([(0, 4), (6, 8)])
        assert stepprof.exposed_span((2, 7), comp) == 2  # [4,6)


# --------------------------------------------------------------------------
# hardened xplane loader (satellite: CPU-only CI must not raise)
# --------------------------------------------------------------------------

class TestLoadProfile:
    def test_absent_dir_is_no_profile(self, tmp_path):
        res = profile.load_profile(str(tmp_path / "nope"))
        assert res["status"] == "no-profile"
        assert "xplane" in res["reason"]

    def test_zero_byte_file_is_empty(self, tmp_path):
        (tmp_path / "x.xplane.pb").write_bytes(b"")
        res = profile.load_profile(str(tmp_path))
        assert res["status"] == "empty"

    def test_truncated_file_is_explicit_not_indexerror(self, tmp_path):
        with open(os.path.join(_FIXTURE_XPLANE,
                               "stepprof.xplane.pb"), "rb") as f:
            good = f.read()
        for cut in (1, 7, len(good) // 2, len(good) - 1):
            (tmp_path / "x.xplane.pb").write_bytes(good[:cut])
            res = profile.load_profile(str(tmp_path))
            assert res["status"] in ("truncated", "empty"), cut
        # the raising API raises a *clean* error, not IndexError
        with pytest.raises(ValueError):
            profile.op_summary(str(tmp_path))

    def test_fixture_intervals_and_comm_classification(self):
        res = profile.load_profile(_FIXTURE_XPLANE)
        assert res["status"] == "ok"
        ivs = res["planes"]["/device:TPU:0"]
        assert [(iv["t0_us"], iv["t1_us"], iv["comm"]) for iv in ivs] \
            == [(0.0, 400.0, False), (300.0, 700.0, True),
                (600.0, 1000.0, False)]

    def test_comm_op_regex(self):
        for name in ("all-reduce.1", "all-gather-start",
                     "reduce-scatter.3", "collective-permute.7",
                     "fusion.all_reduce.2", "AllReduce"):
            assert profile.is_comm_op(name), name
        for name in ("fusion.23", "convolution.1", "ascend.2",
                     "recvive"):  # no bare-substring false positives
            assert not profile.is_comm_op(name), name

    def test_summary_still_raises_on_missing(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            profile.op_summary(str(tmp_path))


# --------------------------------------------------------------------------
# runtime collector + device join
# --------------------------------------------------------------------------

class TestCollector:
    @pytest.fixture(autouse=True)
    def fresh(self):
        stepprof.reset()
        yield
        stepprof.reset()

    def test_step_boundary_observes_exposed_comm(self):
        import time as _time

        c = stepprof.get_collector()
        before = _hist_cells("hvtpu_step_exposed_comm_seconds")
        c.note_step_boundary()           # opens the window
        _time.sleep(0.015)               # comm must land inside it
        now = _time.time()
        c.note_comm("g", now - 0.010, now - 0.004, nbytes=64)
        c.note_comm("h", now - 0.006, now - 0.002, nbytes=64)
        c.note_step_boundary()
        after = _hist_cells("hvtpu_step_exposed_comm_seconds")
        assert after["count"] == before["count"] + 1
        # union [t-10ms, t-2ms] = 8 ms, not 6+4
        assert 0.004 < after["sum"] - before["sum"] < 0.5

    def test_mfu_gauge_from_step_flops(self, monkeypatch):
        # the CPU test device has no table entry: name the peak
        monkeypatch.setenv("HVTPU_STEPPROF_PEAK_TFLOPS", "197")
        c = stepprof.get_collector()
        c.set_step_flops(stepprof.peak_flops() * 0.01)  # 1% of peak/s
        try:
            c.note_step_boundary()
            import time as _time
            _time.sleep(0.01)
            c.note_step_boundary()
        finally:
            c.set_step_flops(None)
        v = stepprof.MFU.value()
        assert v > 0

    def test_debug_state_shape(self):
        stepprof.install()
        try:
            from horovod_tpu.obs.metrics import debug_snapshot
            dbg = debug_snapshot()
            assert "stepprof" in dbg
            st = dbg["stepprof"]
            for key in ("active", "steps", "mfu",
                        "overlap_fraction", "last_step"):
                assert key in st
        finally:
            stepprof.uninstall()

    def test_join_device_profile_fixture(self):
        res = stepprof.join_device_profile(
            _FIXTURE_XPLANE, window=(0.0, 1000e-6))
        assert res["status"] == "ok"
        # fixture: comm [300,700), compute [0,400)+[600,1000) ->
        # 200 us overlapped, 200 us exposed
        assert res["overlap_fraction"] == pytest.approx(0.5)
        assert res["exposed_comm_s"] == pytest.approx(200e-6)
        assert res["overlapped_comm_s"] == pytest.approx(200e-6)
        assert stepprof.OVERLAP_FRACTION.value() == pytest.approx(0.5)

    def test_join_degrades_without_profile(self, tmp_path):
        res = stepprof.join_device_profile(str(tmp_path))
        assert res["status"] == "no-profile"
        assert res["overlap_fraction"] is None

    def test_align_device_intervals(self):
        ivs = [{"t0_us": 5.0, "t1_us": 7.0, "comm": True}]
        out, shift = stepprof.align_device_intervals(ivs, 1e15)
        assert shift == pytest.approx(1e15 - 5.0)
        assert out[0]["t0_us"] == pytest.approx(1e15)
        # wall-like timestamps pass through unshifted
        out2, shift2 = stepprof.align_device_intervals(ivs, 10.0)
        assert shift2 == 0.0 and out2 is ivs


def _hist_cells(name):
    fam = obs_metrics.snapshot().get(name) or {"values": {}}
    cells = fam["values"].values()
    return {"count": sum(c["count"] for c in cells),
            "sum": sum(c["sum"] for c in cells)}


class TestMeasuredFlops:
    def test_cost_analysis_provenance(self):
        """The MFU numerator comes from the compiled program itself."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(a, b):
            return a @ b

        spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        flops = stepprof.measured_flops(f.lower(spec, spec).compile())
        if flops is None:
            pytest.skip("backend exposes no cost analysis")
        # 2*M*N*K with some tolerance for backend accounting
        assert 64 ** 3 < flops < 8 * 64 ** 3
        v5e = stepprof.peak_flops("TPU v5 lite")
        assert v5e == 197e12
        assert stepprof.mfu(flops, 1.0, peak=v5e) == pytest.approx(
            flops / v5e)

    def test_measured_flops_tolerates_junk(self):
        class NoCA:
            def cost_analysis(self):
                raise NotImplementedError

        class ListCA:
            def cost_analysis(self):
                return [{"flops": 42.0}]

        assert stepprof.measured_flops(NoCA()) is None
        assert stepprof.measured_flops(ListCA()) == 42.0


# --------------------------------------------------------------------------
# hvtputrace overlap (offline tool)
# --------------------------------------------------------------------------

def _write_rank_trace(path, events):
    with open(path, "w", encoding="utf-8") as f:
        f.write(json.dumps(events))


def _synthetic_rank0(tmp_path, *, with_boundaries=True):
    """One rank: step window [0, 1000) us, EXEC span [250, 750),
    matching the fixture xplane's comm [300,700) / compute
    [0,400)+[600,1000)."""
    evs = [
        {"name": "clock_anchor", "ph": "i", "ts": 0, "pid": 0, "tid": 0,
         "args": {"wall_t0_us": 0}},
        {"name": "clock_offset", "ph": "i", "ts": 0, "pid": 0, "tid": 0,
         "args": {"offset_us": 0.0, "error_bound_us": 1.0}},
        {"name": "EXEC", "cat": "tensor", "ph": "B", "ts": 250.0,
         "pid": 0, "tid": 5,
         "args": {"trace_id": "g#0", "tensor": "g"}},
        {"name": "EXEC", "ph": "E", "ts": 750.0, "pid": 0, "tid": 5},
    ]
    if with_boundaries:
        evs += [
            {"name": "step_boundary", "ph": "i", "ts": 0.0, "pid": 0,
             "tid": 0, "args": {"wall_us": 0.0, "steps": 1}},
            {"name": "step_boundary", "ph": "i", "ts": 1000.0, "pid": 0,
             "tid": 0, "args": {"wall_us": 1000.0, "steps": 1}},
        ]
    _write_rank_trace(str(tmp_path / "rank0.trace.json"), evs)
    return str(tmp_path)


class TestOverlapTool:
    def test_device_join_decomposition(self, tmp_path):
        trace_dir = _synthetic_rank0(tmp_path)
        rep = hvtputrace.overlap(trace_dir, xplane_dir=_FIXTURE_XPLANE)
        assert rep["xplane"]["status"] == "ok"
        row = rep["per_rank"][0]
        assert row["mode"] == "device"
        assert row["overlapped_comm"] == pytest.approx(200.0)
        assert row["exposed_comm"] == pytest.approx(200.0)
        assert row["compute"] == pytest.approx(600.0)
        assert row["overlap_fraction"] == pytest.approx(0.5)
        assert _sum_parts(row) == pytest.approx(row["step_wall"])
        # blame: the EXEC span's exposed share is the non-compute part
        assert rep["top_exposed"][0]["trace_id"] == "g#0"
        assert rep["top_exposed"][0]["exposed_us"] == pytest.approx(200.0)

    def test_degrades_gracefully_without_xplane(self, tmp_path):
        trace_dir = _synthetic_rank0(tmp_path)
        rep = hvtputrace.overlap(trace_dir)
        row = rep["per_rank"][0]
        assert row["mode"] == "host-only"
        assert row["overlapped_comm"] == 0.0
        assert row["exposed_comm"] == pytest.approx(500.0)  # EXEC span
        assert row["compute"] == pytest.approx(500.0)       # inferred
        assert row["overlap_fraction"] is None
        assert _sum_parts(row) == pytest.approx(row["step_wall"])
        text = hvtputrace.render_overlap(rep)
        assert "host-only" in text and "g#0" in text

    def test_extent_fallback_without_boundaries(self, tmp_path):
        trace_dir = _synthetic_rank0(tmp_path, with_boundaries=False)
        rep = hvtputrace.overlap(trace_dir)
        row = rep["per_rank"][0]
        assert row["step_wall"] > 0
        assert _sum_parts(row) == pytest.approx(row["step_wall"])

    def test_cli_overlap(self, tmp_path, capsys):
        from tools.hvtputrace.__main__ import main

        trace_dir = _synthetic_rank0(tmp_path)
        assert main(["overlap", trace_dir, "--xplane", _FIXTURE_XPLANE,
                     "--json"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["per_rank"]["0"]["overlap_fraction"] \
            == pytest.approx(0.5)
        assert main(["overlap", trace_dir]) == 0
        assert "overlap" in capsys.readouterr().out


# --------------------------------------------------------------------------
# tracing integration: boundaries + predict confirmation instants
# --------------------------------------------------------------------------

class TestTracingIntegration:
    def test_note_step_emits_boundary_instant(self, tmp_path):
        stepprof.reset()
        tracing.install(str(tmp_path), rank=0, size=1)
        try:
            obs_metrics.note_step(examples=8, steps=2)
            obs_metrics.note_step(examples=8, steps=2)
        finally:
            tracing.uninstall()
        with open(tmp_path / "rank0.trace.json") as f:
            evs = json.load(f)
        bounds = [e for e in evs if e.get("name") == "step_boundary"]
        assert len(bounds) == 2
        assert bounds[0]["args"]["steps"] == 2
        assert bounds[0]["args"]["wall_us"] > 0

    def test_allreduce_done_carries_wall_window(self, tmp_path,
                                                monkeypatch):
        """comm/eager's DONE instant carries the device-joinable wall
        window, and the collector records the same dispatch."""
        import jax.numpy as jnp

        stepprof.reset()
        monkeypatch.setenv("HVTPU_TRACE", str(tmp_path))
        horovod_tpu.init()
        try:
            horovod_tpu.allreduce(jnp.ones((16,), jnp.float32))
        finally:
            horovod_tpu.shutdown()
        with open(tmp_path / "rank0.trace.json") as f:
            evs = json.load(f)
        done = [e for e in evs if e.get("name") == "DONE"]
        assert done, "no DONE instant traced"
        args = done[0]["args"]
        assert args["wall_t1_us"] >= args["wall_t0_us"] > 0
        with stepprof.get_collector()._lock:
            assert len(stepprof.get_collector()._comm) >= 1


# --------------------------------------------------------------------------
# 2-process acceptance: injected delay -> rank-0 exposed comm
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.multiprocess
def test_overlap_acceptance_2proc(tmp_path):
    """`python -m tools.hvtputrace overlap` on a 2-proc run with a
    50 ms pre-collective delay on rank 1: every rank's six parts sum
    to its step wall, rank 0's exposed comm absorbs the peer's delay,
    and the delayed collective tops the exposed list."""

    trace_dir = str(tmp_path)

    def body():
        import jax.numpy as jnp

        import horovod_tpu as hvt
        from horovod_tpu.obs import metrics as _m

        hvt.init()
        _m.note_step(steps=1)  # opens the first step window
        for _ in range(3):
            hvt.allreduce(jnp.ones((1024,), jnp.float32))
            _m.note_step(steps=1)
        hvt.shutdown()
        return "ok"

    env = dict(
        _ENV,
        HVTPU_TRACE=trace_dir,
        HVTPU_FAULT_SPEC="collective.pre:delay(50)@rank=1",
    )
    assert run(body, np=2, cpu_devices=1, env=env,
               start_timeout=300.0) == ["ok", "ok"]

    rep = hvtputrace.overlap(trace_dir)
    assert rep["ranks"] == [0, 1]
    for r in (0, 1):
        row = rep["per_rank"][r]
        assert _sum_parts(row) == pytest.approx(row["step_wall"],
                                                rel=1e-6, abs=1.0)
    # rank 0 dispatches on time and then waits out rank 1's injected
    # 50 ms delay inside its EXEC spans: exposed comm > 2 x 50 ms
    # across the 3 collectives (host-only mode: EXEC == exposed).
    assert rep["per_rank"][0]["exposed_comm"] > 100_000.0
    # rank 1 is the skewed rank: it arrives late (the delay burns
    # outside its spans), so its own exposed comm stays well below
    # rank 0's wait time
    assert rep["per_rank"][1]["exposed_comm"] \
        < rep["per_rank"][0]["exposed_comm"]
    # the delayed allreduce is blamed by name in the top-N list
    assert rep["top_exposed"]
    assert rep["top_exposed"][0]["tensor"].startswith("allreduce")
    assert rep["top_exposed"][0]["exposed_us"] > 40_000.0
    # CLI end to end
    from tools.hvtputrace.__main__ import main

    assert main(["overlap", trace_dir]) == 0
