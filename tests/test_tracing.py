"""Cross-rank distributed tracing (obs/tracing.py + tools/hvtputrace).

Acceptance shape (ISSUE PR 7): a 2-process CPU job with
``HVTPU_TRACE`` set and a 50 ms pre-collective fault on rank 1 must
yield per-rank traces that ``hvtputrace merge`` fuses into one valid
Chrome-trace JSON with correlated spans for the same collective on
both ranks plus a recorded clock offset, and ``hvtputrace report``
must attribute the straggling to rank 1.  With ``HVTPU_TRACE`` unset
the hot-path guard must be a single module-attribute check (same
contract as core/faults.ACTIVE).
"""

import json
import os
import time

import pytest

import horovod_tpu
from horovod_tpu.obs import tracing
from horovod_tpu.runner import run
from tools import hvtputrace

_REPO_ROOT = os.path.dirname(os.path.dirname(horovod_tpu.__file__))
_ENV = {"PYTHONPATH": _REPO_ROOT + os.pathsep
        + os.environ.get("PYTHONPATH", "")}


def _events(path):
    with open(path) as f:
        return json.load(f)


# --------------------------------------------------------------------------
# tracer unit tests
# --------------------------------------------------------------------------

class TestTracer:
    def test_trace_ids_are_rank_agnostic_occurrence_counts(self, tmp_path):
        tr = tracing.Tracer(str(tmp_path), rank=0, size=1)
        tr.op_begin("g", "allreduce")
        tr.op_phase("g", tracing.QUEUE)
        tr.op_phase("g", tracing.EXEC)
        tr.op_done("g", bytes=64)
        tr.op_begin("g", "allreduce")  # second occurrence: g#1
        tr.op_done("g")
        tr.close()
        evs = _events(tmp_path / "rank0.trace.json")
        ids = [e["args"]["trace_id"] for e in evs
               if e.get("ph") in ("B", "i")
               and "trace_id" in e.get("args", {})]
        assert ids == ["g#0", "g#0", "g#0", "g#0", "g#1", "g#1"]
        # DONE instant carries the result metadata
        done = [e for e in evs if e.get("name") == "DONE"]
        assert done[0]["args"]["bytes"] == 64

    def test_phase_and_done_ignore_untracked_names(self, tmp_path):
        """Responses for process sets this rank is not a member of
        arrive with names that never began a span here: no-ops."""
        tr = tracing.Tracer(str(tmp_path), rank=0, size=1)
        tr.op_phase("ghost", tracing.EXEC)
        tr.op_done("ghost")
        tr.close()
        evs = _events(tmp_path / "rank0.trace.json")
        assert not any(e.get("ph") in ("B", "E") and e.get("cat") == "tensor"
                       for e in evs)

    def test_anchor_written_first_survives_truncation(self, tmp_path):
        tr = tracing.Tracer(str(tmp_path), rank=0, size=2)
        tr.op_begin("g", "allreduce")
        # simulate a crash: never op_done / close — file has no closing
        # bracket and a dangling B event
        tr._tl._file.flush()
        evs = hvtputrace._load_events(str(tmp_path / "rank0.trace.json"))
        wall_t0_us, _off, _err = hvtputrace.clock_metadata(evs)
        assert wall_t0_us is not None
        tr.close()

    def test_install_uninstall_flip_active_flag(self, tmp_path):
        assert tracing.ACTIVE is False
        try:
            tr = tracing.install(str(tmp_path), rank=0, size=1)
            assert tracing.ACTIVE is True
            assert tracing.get_tracer() is tr
            tracing.op_begin("x", "allreduce")
            tracing.op_done("x")
        finally:
            tracing.uninstall()
            tracing.uninstall()  # idempotent
        assert tracing.ACTIVE is False and tracing.get_tracer() is None
        evs = _events(tmp_path / "rank0.trace.json")
        assert any(e.get("name") == "DONE" for e in evs)

    def test_clock_sync_over_kv(self, tmp_path):
        """Same-process FakeKV handshake: the peer's min-RTT offset is
        near zero with a positive error bound, and both facts land in
        the trace metadata."""
        from test_eager_controller import FakeKV

        kv = FakeKV()
        t0 = tracing.Tracer(str(tmp_path), rank=0, size=2)
        t1 = tracing.Tracer(str(tmp_path), rank=1, size=2)
        t0.sync_clock(kv, pings=4)   # spawns the responder daemon
        t1.sync_clock(kv, pings=4)
        assert t1.offset_us is not None
        assert abs(t1.offset_us) < 1e6      # same host: well under 1 s
        assert t1.offset_error_us > 0
        t0.close()
        t1.close()
        _w, off, err = hvtputrace.clock_metadata(
            _events(tmp_path / "rank1.trace.json"))
        assert off == t1.offset_us and err == t1.offset_error_us

    def test_clock_sync_degrades_without_client(self, tmp_path):
        tr = tracing.Tracer(str(tmp_path), rank=1, size=2)
        tr.sync_clock(None, pings=4)
        assert tr.offset_us is None  # merge falls back to offset 0
        tr.close()


# --------------------------------------------------------------------------
# merge / report over synthetic two-rank traces
# --------------------------------------------------------------------------

class TestMergeReport:
    @pytest.fixture
    def skewed_dir(self, tmp_path):
        """Two same-process tracers; rank 1 begins each collective
        ~40 ms late (deterministic straggler, shared wall clock)."""
        t0 = tracing.Tracer(str(tmp_path), rank=0, size=2)
        t1 = tracing.Tracer(str(tmp_path), rank=1, size=2)
        for _ in range(2):
            t0.op_begin("g", "allreduce")
            t0.op_done("g", bytes=64)
            time.sleep(0.04)
            t1.op_begin("g", "allreduce")
            t1.op_done("g", bytes=64)
        t0.close()
        t1.close()
        return tmp_path

    def test_merge_rebases_onto_one_clock(self, skewed_dir):
        merged = hvtputrace.merge(str(skewed_dir))
        json.dumps(merged)  # Perfetto-loadable event array
        assert {e.get("pid") for e in merged if e.get("ph") == "B"} \
            == {0, 1}
        # the same trace_id appears on both process lanes
        by_rank = {r: {e["args"]["trace_id"] for e in merged
                       if e.get("ph") == "B" and e.get("pid") == r}
                   for r in (0, 1)}
        assert by_rank[0] & by_rank[1] == {"g#0", "g#1"}

    def test_report_attributes_straggler(self, skewed_dir):
        rep = hvtputrace.report(str(skewed_dir))
        assert rep["ranks"] == [0, 1]
        assert len(rep["collectives"]) == 2
        for c in rep["collectives"]:
            assert c["last_rank"] == 1
            assert c["arrival_skew_us"] > 20_000
        assert rep["stragglers"][0]["rank"] == 1
        assert rep["stragglers"][0]["times_last"] == 2
        for r in (0, 1):
            row = rep["per_rank"][r]
            assert row["wait_us"] >= 0
            assert row["trace_extent_us"] >= row["wait_us"]
        # render path stays exception-free and names the straggler
        assert "rank 1" in hvtputrace.render_report(rep)

    def test_cli_merge_and_report(self, skewed_dir, capsys):
        from tools.hvtputrace.__main__ import main

        assert main(["merge", str(skewed_dir)]) == 0
        out = skewed_dir / "merged.trace.json"
        assert {e.get("pid") for e in _events(out)} == {0, 1}
        capsys.readouterr()  # drain the merge status line
        assert main(["report", str(skewed_dir), "--json"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["stragglers"][0]["rank"] == 1

    def test_truncated_rank_file_tolerated(self, skewed_dir):
        path = skewed_dir / "rank1.trace.json"
        text = path.read_text()
        path.write_text(text[: int(len(text) * 0.8)])
        rep = hvtputrace.report(str(skewed_dir))
        assert 1 in rep["per_rank"]

    def test_empty_dir_names_the_knob(self, tmp_path):
        with pytest.raises(FileNotFoundError, match="HVTPU_TRACE"):
            hvtputrace.load_rank_traces(str(tmp_path))


# --------------------------------------------------------------------------
# lifecycle: init/shutdown wiring, timeline swap, flush on exit
# --------------------------------------------------------------------------

class TestLifecycle:
    def test_shutdown_flushes_trace(self, tmp_path, monkeypatch):
        """HVTPU_TRACE at init() installs the tracer; shutdown() (also
        the atexit hook's path) flushes a strictly-valid JSON file."""
        import jax.numpy as jnp

        # init() on an initialized process reads no environment: a test
        # of another file that left this worker initialized would have
        # HVTPU_TRACE ignored here.  Start from a process shut down.
        horovod_tpu.shutdown()
        assert tracing.ACTIVE is False and tracing.get_tracer() is None
        monkeypatch.setenv("HVTPU_TRACE", str(tmp_path))
        horovod_tpu.init()
        try:
            assert tracing.ACTIVE is True
            horovod_tpu.allreduce(jnp.ones((16,), jnp.float32))
            h = horovod_tpu.allreduce_async(jnp.ones((8,), jnp.float32))
            horovod_tpu.synchronize(h)
        finally:
            horovod_tpu.shutdown()
        assert tracing.ACTIVE is False
        # strict parse: close() wrote the bracket, no repair needed
        evs = _events(tmp_path / "rank0.trace.json")
        assert any(e.get("name") == "DONE" for e in evs)
        # single-rank report still works (no multi-rank collectives)
        rep = hvtputrace.report(str(tmp_path))
        assert rep["stragglers"] == []

    def test_timeline_swap_under_live_controller(self, hvt, tmp_path):
        """start_timeline/stop_timeline while a live eager controller
        holds `_timeline`: the rebind must reach the controller and
        both files must stay parseable."""
        import jax.numpy as jnp

        from horovod_tpu.core import state as core_state

        f1, f2 = str(tmp_path / "t1.json"), str(tmp_path / "t2.json")
        hvt.start_timeline(f1)
        h = hvt.allreduce_async(jnp.ones((8,), jnp.float32))
        hvt.synchronize(h)
        st = core_state._state
        assert st.controller is not None
        tl2 = hvt.start_timeline(f2)  # swap under the live controller
        assert st.controller._timeline is tl2
        h = hvt.allreduce_async(jnp.ones((8,), jnp.float32))
        hvt.synchronize(h)
        hvt.stop_timeline()
        assert st.controller._timeline is None
        # one more op after stop: no timeline, no crash
        h = hvt.allreduce_async(jnp.ones((8,), jnp.float32))
        hvt.synchronize(h)
        for f in (f1, f2):
            assert isinstance(_events(f), list)
        # the second file captured the post-swap op
        assert any(e.get("cat") == "tensor" for e in _events(f2))


# --------------------------------------------------------------------------
# disabled path: one attribute check (mirrors test_faults' guard)
# --------------------------------------------------------------------------

def test_inactive_guard_is_zero_overhead():
    """Acceptance: with HVTPU_TRACE unset the hot-path hook is one
    module-attribute read — far under a microsecond per op, so traced
    builds cost nothing when tracing is off."""
    import timeit

    assert tracing.ACTIVE is False
    n = 100_000
    t = timeit.timeit(
        lambda: tracing.ACTIVE and tracing.op_begin("x", "allreduce"),
        number=n)
    assert t / n < 5e-6, f"{t / n * 1e9:.0f} ns/op"


# --------------------------------------------------------------------------
# 2-process acceptance: fault-skewed job -> merged trace + attribution
# --------------------------------------------------------------------------

@pytest.mark.multiprocess
def test_trace_acceptance_2proc(tmp_path):
    """End to end: rank 1 suffers a 50 ms pre-collective delay; the
    merged trace correlates both ranks' spans per collective, records
    the KV clock offset, the report blames rank 1, and /debug answers
    live controller state while the job runs."""

    trace_dir = str(tmp_path)

    def body():
        import json as _json
        import urllib.request

        import jax.numpy as jnp

        import horovod_tpu as hvt
        from horovod_tpu.obs import tracing as _tracing

        hvt.init()
        assert _tracing.ACTIVE is True
        r = hvt.rank()
        for _ in range(3):
            hvt.allreduce(jnp.ones((1024,), jnp.float32))
        h = hvt.allreduce_async(jnp.full((8,), float(r)))
        hvt.synchronize(h)
        # live /debug probe while the controller is up
        port = 19750 + hvt.local_rank()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug", timeout=30) as resp:
            assert resp.status == 200
            dbg = _json.loads(resp.read().decode())
        ctrl = dbg["controller"]
        assert ctrl["size"] == 2 and ctrl["queue_depth"] >= 0
        assert "capacity" in ctrl["cache"]
        assert dbg["job"]["initialized"] is True
        assert "mode" in dbg["stall"]
        if dbg["stall"]["mode"] == "amortized":
            assert "peer_heartbeat_age_s" in dbg["stall"]
        hvt.shutdown()
        return "ok"

    env = dict(
        _ENV,
        HVTPU_TRACE=trace_dir,
        HVTPU_METRICS_PORT="19750",
        HVTPU_FAULT_SPEC="collective.pre:delay(50)@rank=1",
    )
    assert run(body, np=2, cpu_devices=1, env=env,
               start_timeout=300.0) == ["ok", "ok"]

    # one valid Chrome-trace JSON with a lane per rank
    from tools.hvtputrace.__main__ import main

    assert main(["merge", trace_dir]) == 0
    merged = _events(tmp_path / "merged.trace.json")
    assert {e.get("pid") for e in merged if e.get("ph") == "B"} == {0, 1}

    # correlated spans: the same collective's trace_id on both lanes
    ids = {r: {e["args"]["trace_id"] for e in merged
               if e.get("ph") == "B" and e.get("pid") == r
               and "trace_id" in e.get("args", {})}
           for r in (0, 1)}
    assert ids[0] & ids[1], "no cross-rank correlated collectives"

    # rank 1 recorded a KV clock offset with its error bound
    traces = hvtputrace.load_rank_traces(trace_dir)
    _w, off1, err1 = hvtputrace.clock_metadata(traces[1])
    assert off1 is not None and err1 is not None and err1 > 0

    # attribution: the injected 50 ms delay makes rank 1 the straggler
    rep = hvtputrace.report(trace_dir)
    assert rep["stragglers"], "report found no stragglers"
    top = rep["stragglers"][0]
    assert top["rank"] == 1
    assert top["total_skew_us"] > 10_000
