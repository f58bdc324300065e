"""Unified metrics registry (obs/metrics.py): registry semantics,
Prometheus exposition (text format + HTTP endpoint), cross-rank
aggregation over the coordination KV, and the instrumentation hooks in
the hot layers (controller cycle marks, stall counters, step rates).

The acceptance shape mirrors the reference's always-on telemetry goal:
with HVTPU_METRICS_PORT set, a live 2-process CPU job must answer an
HTTP GET with nonzero collective counters; with it unset, the registry
must stay a sub-microsecond dict update (idle-cost test).
"""

import json
import os
import threading
import time
import urllib.request

import pytest

import horovod_tpu
from horovod_tpu.obs import metrics
from horovod_tpu.obs.metrics import MetricsRegistry
from horovod_tpu.runner import run

_REPO_ROOT = os.path.dirname(os.path.dirname(horovod_tpu.__file__))
_ENV = {"PYTHONPATH": _REPO_ROOT + os.pathsep
        + os.environ.get("PYTHONPATH", "")}


# --------------------------------------------------------------------------
# registry unit tests
# --------------------------------------------------------------------------

class TestRegistry:
    def test_counter_monotonic_and_labeled(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total", "help")
        c.inc()
        c.inc(2.5)
        c.inc(1, op="allreduce")
        assert c.value() == 3.5
        assert c.value(op="allreduce") == 1
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_family_idempotent_and_kind_clash(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total")
        assert reg.counter("x_total") is a
        with pytest.raises(TypeError):
            reg.gauge("x_total")

    def test_gauge_set_inc_dec(self):
        reg = MetricsRegistry()
        g = reg.gauge("g")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value() == 6

    def test_concurrent_increments_exact(self):
        """N threads hammering one counter lose no increments."""
        reg = MetricsRegistry()
        c = reg.counter("c_total")
        h = reg.histogram("h_seconds", buckets=[0.5])
        n_threads, per_thread = 8, 5000

        def work():
            for _ in range(per_thread):
                c.inc()
                h.observe(0.1)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == n_threads * per_thread
        assert h.value() == n_threads * per_thread

    def test_histogram_bucketing(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=[0.1, 1.0, 10.0])
        for v in (0.05, 0.1, 0.5, 5.0, 50.0):
            h.observe(v)
        snap = reg.snapshot()["lat"]
        assert snap["buckets"] == [0.1, 1.0, 10.0]
        cell = snap["values"][""]
        # bisect_left: 0.05 and the exact 0.1 boundary land in le=0.1
        assert cell["counts"] == [2, 1, 1, 1]
        assert cell["count"] == 5
        assert cell["sum"] == pytest.approx(55.65)

    def test_prometheus_text_golden(self):
        """Exact text-format 0.0.4 output for a small registry."""
        reg = MetricsRegistry()
        c = reg.counter("hvt_ops_total", "Ops executed.")
        c.inc(2, op="allreduce")
        reg.gauge("hvt_depth", "Queue depth.").set(1.5)
        h = reg.histogram("hvt_lat", "Latency.", buckets=[0.1, 1.0, 10.0])
        h.observe(0.05)
        h.observe(5.0)
        h.observe(50.0)
        expected = "\n".join([
            "# HELP hvt_depth Queue depth.",
            "# TYPE hvt_depth gauge",
            "hvt_depth 1.5",
            "# HELP hvt_lat Latency.",
            "# TYPE hvt_lat histogram",
            'hvt_lat_bucket{le="0.1"} 1',
            'hvt_lat_bucket{le="1"} 1',
            'hvt_lat_bucket{le="10"} 2',
            'hvt_lat_bucket{le="+Inf"} 3',
            "hvt_lat_sum 55.05",
            "hvt_lat_count 3",
            "# HELP hvt_ops_total Ops executed.",
            "# TYPE hvt_ops_total counter",
            'hvt_ops_total{op="allreduce"} 2',
        ]) + "\n"
        assert reg.exposition() == expected

    def test_snapshot_json_serializable_and_reset(self):
        reg = MetricsRegistry()
        reg.counter("a_total").inc(3)
        reg.histogram("b", buckets=[1.0]).observe(0.5)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["a_total"]["values"][""] == 3
        reg.reset()
        assert reg.counter("a_total").value() == 0
        assert reg.snapshot()["b"]["values"] == {}

    def test_idle_cost_sanity(self):
        """A counter increment stays a dict-update, not an I/O call:
        generous bound (100 us/op amortized) that only a pathological
        regression (locking the exposition path, syscalls) would trip."""
        reg = MetricsRegistry()
        c = reg.counter("hot_total")
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            c.inc()
        per_op = (time.perf_counter() - t0) / n
        assert c.value() == n
        assert per_op < 100e-6, f"counter.inc cost {per_op * 1e6:.1f} us/op"

    def test_log_buckets(self):
        b = metrics.log_buckets(1e-5, 4.0, 3)
        assert b == pytest.approx((1e-5, 4e-5, 1.6e-4))


class TestMergeSnapshots:
    def test_counters_and_histograms_sum(self):
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        for reg, k in ((r1, 1), (r2, 2)):
            reg.counter("ops_total").inc(k)
            reg.histogram("lat", buckets=[1.0, 10.0]).observe(k)
        merged = metrics.merge_snapshots([r1.snapshot(), r2.snapshot()])
        assert merged["ops_total"]["values"][""] == 3
        cell = merged["lat"]["values"][""]
        assert cell["count"] == 2 and cell["sum"] == 3.0
        # rank 1's 1.0 sits on the le=1 boundary; rank 2's 2.0 in le=10
        assert cell["counts"] == [1, 1, 0]

    def test_bucket_mismatch_rejected(self):
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        r1.histogram("lat", buckets=[1.0]).observe(0.5)
        r2.histogram("lat", buckets=[2.0]).observe(0.5)
        with pytest.raises(ValueError):
            metrics.merge_snapshots([r1.snapshot(), r2.snapshot()])


# --------------------------------------------------------------------------
# exposition endpoint round-trip (localhost)
# --------------------------------------------------------------------------

class TestHttpEndpoint:
    def test_round_trip(self):
        reg = MetricsRegistry()
        reg.counter("rt_total", "Round trip.").inc(7)
        # the module-level server is shared; make sure no stale one
        metrics.stop_http_server()
        port = metrics.start_http_server(0, registry=reg)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/plain")
                body = r.read().decode()
            assert "rt_total 7" in body
            # scrape twice: the server thread must survive a request
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/", timeout=10) as r:
                assert r.status == 200
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/nope", timeout=10)
        finally:
            metrics.stop_http_server()

    def test_debug_endpoint_round_trip(self):
        metrics.stop_http_server()
        metrics.register_debug_provider(
            "okprov", lambda: {"depth": 3})
        metrics.register_debug_provider(
            "badprov", lambda: 1 / 0)
        port = metrics.start_http_server(0, registry=MetricsRegistry())
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug", timeout=10) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith(
                    "application/json")
                dbg = json.loads(r.read().decode())
            assert dbg["okprov"] == {"depth": 3}
            # a raising provider is isolated, not a 500
            assert "error" in dbg["badprov"]
            assert dbg["time_unix"] > 0
        finally:
            metrics.stop_http_server()
            metrics.unregister_debug_provider("okprov")
            metrics.unregister_debug_provider("badprov")
        assert "okprov" not in metrics.debug_snapshot()

    def test_serve_from_env_disabled_and_bad_values(self, monkeypatch):
        monkeypatch.delenv("HVTPU_METRICS_PORT", raising=False)
        monkeypatch.delenv("HOROVOD_METRICS_PORT", raising=False)
        assert metrics.serve_from_env() is None
        monkeypatch.setenv("HVTPU_METRICS_PORT", "not-a-port")
        assert metrics.serve_from_env() is None
        monkeypatch.setenv("HVTPU_METRICS_PORT", "0")
        assert metrics.serve_from_env() is None


# --------------------------------------------------------------------------
# instrumentation hooks
# --------------------------------------------------------------------------

class TestHooks:
    def test_note_step_counts_steps_and_examples(self):
        before_steps = metrics.REGISTRY.counter(
            "hvtpu_optimizer_steps_total").value()
        before_ex = metrics.REGISTRY.counter(
            "hvtpu_examples_total").value()
        metrics.note_step(examples=128, steps=4)
        metrics.note_step(examples=128, steps=4)
        assert metrics.REGISTRY.counter(
            "hvtpu_optimizer_steps_total").value() == before_steps + 8
        assert metrics.REGISTRY.counter(
            "hvtpu_examples_total").value() == before_ex + 256
        assert metrics.REGISTRY.gauge(
            "hvtpu_steps_per_second").value() > 0

    @pytest.mark.parametrize("width, path", [(16, "ragged_dot"),
                                             (128, "grouped")])
    def test_moe_products_are_counted_by_path_when_traced(
            self, monkeypatch, width, path):
        """``hvtpu_moe_products_total``: one a trace of a call site,
        under the path the layer read off the backend and the shapes,
        and none when the traced program runs again."""
        import jax
        import jax.numpy as jnp

        from horovod_tpu.parallel import moe

        monkeypatch.setenv("HVTPU_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(moe, "_TILE_ROWS", 16)
        counter = metrics.REGISTRY.counter("hvtpu_moe_products_total")
        before = {p: counter.value(path=p) for p in ("grouped", "ragged_dot")}
        x = jnp.ones((32, width))
        experts = {"w_gate": jnp.ones((2, width, width)),
                   "w_up": jnp.ones((2, width, width)),
                   "w_down": jnp.ones((2, width, width))}
        layer = jax.jit(lambda x, experts: moe.dropless_topk_moe(
            x, jnp.ones((width, 4)), experts, top_k=2, num_experts=4,
            first_expert=0, renormalise=True)[0])
        layer(x, experts)
        layer(x, experts)
        other = ({"grouped", "ragged_dot"} - {path}).pop()
        assert counter.value(path=path) == before[path] + 1
        assert counter.value(path=other) == before[other]
        assert metrics.snapshot()["hvtpu_moe_products_total"][
            "type"] == "counter"

    @pytest.mark.parametrize("rule, form", [
        ("softmax", "gated"), ("softmax", "relu2"),
        ("sigmoid_bias", "gated"), ("sigmoid_bias", "relu2")])
    def test_the_expert_layers_rule_and_form_are_counted_when_traced(
            self, monkeypatch, rule, form):
        """``hvtpu_moe_router_total{rule=}`` and
        ``hvtpu_moe_experts_form_total{form=}``: one each a trace of a
        call site, by the routing rule (a selection bias or none) and by
        the weights the experts bring, chosen apart; none when the traced
        program runs again."""
        import jax
        import jax.numpy as jnp

        from horovod_tpu.parallel import moe

        monkeypatch.setattr(moe, "_TILE_ROWS", 16)
        router = metrics.REGISTRY.counter("hvtpu_moe_router_total")
        forms = metrics.REGISTRY.counter("hvtpu_moe_experts_form_total")
        rules, shapes = ("softmax", "sigmoid_bias"), ("gated", "relu2")
        before = ({r: router.value(rule=r) for r in rules},
                  {f: forms.value(form=f) for f in shapes})
        experts = {"w_up": jnp.ones((2, 16, 16)),
                   "w_down": jnp.ones((2, 16, 16))}
        if form == "gated":
            experts["w_gate"] = jnp.ones((2, 16, 16))
        bias = None if rule == "softmax" else jnp.zeros((4,))
        layer = jax.jit(lambda x, experts: moe.dropless_topk_moe(
            x, jnp.ones((16, 4)), experts, top_k=2, num_experts=4,
            first_expert=0, renormalise=True, selection_bias=bias)[0])
        layer(jnp.ones((32, 16)), experts)
        layer(jnp.ones((32, 16)), experts)
        assert {r: router.value(rule=r) for r in rules} == {
            **before[0], rule: before[0][rule] + 1}
        assert {f: forms.value(form=f) for f in shapes} == {
            **before[1], form: before[1][form] + 1}
        for name in ("hvtpu_moe_router_total",
                     "hvtpu_moe_experts_form_total"):
            assert metrics.snapshot()[name]["type"] == "counter"

    def test_the_scans_groups_are_a_gauge(self):
        metrics.note_ssm_groups(8)
        assert metrics.REGISTRY.gauge("hvtpu_ssm_groups").value() == 8.0
        metrics.note_ssm_groups(1)
        assert metrics.REGISTRY.gauge("hvtpu_ssm_groups").value() == 1.0
        assert metrics.snapshot()["hvtpu_ssm_groups"]["type"] == "gauge"

    def test_the_delta_rules_chunks_and_their_size(self):
        chunks = metrics.REGISTRY.counter("hvtpu_kda_chunks_total")
        before = chunks.value()
        metrics.note_kda_chunks(256, 64)
        metrics.note_kda_chunks(4, 16)
        assert chunks.value() == before + 260
        assert metrics.REGISTRY.gauge("hvtpu_kda_chunk_size").value() == 16.0
        snapshot = metrics.snapshot()
        assert snapshot["hvtpu_kda_chunks_total"]["type"] == "counter"
        assert snapshot["hvtpu_kda_chunk_size"]["type"] == "gauge"

    @pytest.mark.parametrize("widths", [(192, 128), (64, 64), (128, 128)])
    def test_a_heads_two_widths_are_a_gauge(self, widths):
        metrics.note_attention_head_width(*widths)
        noted = metrics.REGISTRY.gauge("hvtpu_attention_head_width")
        assert (noted.value(kind="key"), noted.value(kind="value")) == widths
        assert metrics.snapshot()["hvtpu_attention_head_width"][
            "type"] == "gauge"

    def test_eager_allreduce_counts_ops_and_bytes(self, hvt):
        import jax.numpy as jnp

        base_ops = metrics.op_counter("allreduce").value()
        base_bytes = metrics.WIRE_BYTES.value()
        hvt.allreduce(jnp.ones((4,), jnp.float32))
        assert metrics.op_counter("allreduce").value() == base_ops + 1
        # single-process world: nothing crosses the wire
        assert metrics.WIRE_BYTES.value() == base_bytes

    def test_aggregate_single_process_degrades_to_local(self, hvt):
        reg = MetricsRegistry()
        reg.counter("solo_total").inc(5)
        out = metrics.aggregate(registry=reg)
        assert out["merged"]["solo_total"]["values"][""] == 5
        assert list(out["per_rank"]) == [0]


# --------------------------------------------------------------------------
# mark_cycle regression (satellite): CYCLE instants must reach the trace
# --------------------------------------------------------------------------

def test_mark_cycle_emits_cycle_instant(tmp_path):
    """Controller + Timeline(mark_cycles=True): the dead-path bug probed
    ``mark_cycles`` as an attribute that didn't exist and called
    ``mark_cycle()`` without its cycle index — a trace written through a
    live controller must now contain CYCLE instants."""
    from horovod_tpu.eager.controller import EagerController, KVTransport
    from horovod_tpu.obs.timeline import Timeline
    from tests.test_eager_controller import FakeKV

    trace_file = tmp_path / "trace.json"
    tl = Timeline(str(trace_file), rank=0, mark_cycles=True)
    assert tl.mark_cycles is True
    kv = FakeKV()
    ctrl = EagerController(
        0, 1,
        transport=KVTransport(0, 1, client=kv, timeout_s=20.0),
        cycle_time_ms=0.5,
        timeline=tl,
    )
    ctrl.start()
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if metrics.REGISTRY.counter(
                    "hvtpu_controller_cycles_total").value() > 2:
                break
            time.sleep(0.01)
    finally:
        ctrl.request_shutdown()
        ctrl.stop()
        tl.close()
    events = json.loads(trace_file.read_text())
    cycles = [e for e in events if e.get("name") == "CYCLE"
              and e.get("ph") == "i"]
    assert cycles, "no CYCLE instant in the written trace"
    assert "index" in cycles[0]["args"]


def test_timeline_begin_ends_open_span(tmp_path):
    """Phase transitions (NEGOTIATE -> QUEUE) must close the previous
    span: every B needs a matching E, in nesting order."""
    from horovod_tpu.obs.timeline import Timeline

    trace_file = tmp_path / "trace.json"
    tl = Timeline(str(trace_file), rank=0)
    tl.begin("t0", "NEGOTIATE_ALLREDUCE")
    tl.begin("t0", "QUEUE")
    tl.begin("t0", "ICI_ALLREDUCE")
    tl.end("t0")
    tl.close()
    events = json.loads(trace_file.read_text())
    spans = [(e["ph"], e["name"]) for e in events
             if e.get("cat") == "tensor"]
    assert spans == [
        ("B", "NEGOTIATE_ALLREDUCE"), ("E", "NEGOTIATE_ALLREDUCE"),
        ("B", "QUEUE"), ("E", "QUEUE"),
        ("B", "ICI_ALLREDUCE"), ("E", "ICI_ALLREDUCE"),
    ]


# --------------------------------------------------------------------------
# cross-rank aggregation + live exposition endpoint (2 real processes)
# --------------------------------------------------------------------------

@pytest.mark.multiprocess
def test_aggregate_2proc():
    """aggregate() allgathers per-rank snapshots over the coordination
    KV; both ranks get the identical merged view."""

    def body():
        import horovod_tpu as hvt
        from horovod_tpu.obs import metrics as m

        hvt.init()
        r = hvt.rank()
        m.REGISTRY.counter("agg_test_total").inc(r + 1)
        m.REGISTRY.histogram(
            "agg_test_seconds", buckets=[1.0, 10.0]).observe(r + 1)
        out = m.aggregate()
        assert sorted(out["per_rank"]) == [0, 1]
        merged = out["merged"]
        assert merged["agg_test_total"]["values"][""] == 3
        cell = merged["agg_test_seconds"]["values"][""]
        assert cell["count"] == 2 and cell["sum"] == 3.0
        assert out["per_rank"][1]["agg_test_total"]["values"][""] == 2
        # a second round must not collide with the first's KV keys
        out2 = m.aggregate()
        assert out2["merged"]["agg_test_total"]["values"][""] == 3
        hvt.shutdown()
        return "ok"

    assert run(body, np=2, cpu_devices=1, env=_ENV,
               start_timeout=300.0) == ["ok", "ok"]


@pytest.mark.multiprocess
def test_metrics_endpoint_live_2proc():
    """Acceptance shape: with HVTPU_METRICS_PORT set, an HTTP GET
    during a 2-process CPU run returns Prometheus text including
    nonzero collective counters, wire bytes, a cycle-duration
    histogram, and the elastic worker-count gauge."""

    def body():
        import urllib.request

        import jax.numpy as jnp

        import horovod_tpu as hvt

        hvt.init()
        r = hvt.rank()
        # sync plane: counted by _record_collective
        hvt.allreduce(jnp.ones((1024,), jnp.float32))
        # async plane: drives controller cycles (cycle histogram)
        h = hvt.allreduce_async(jnp.full((8,), float(r)))
        hvt.synchronize(h)
        port = 19650 + hvt.local_rank()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            assert resp.status == 200
            body_txt = resp.read().decode()
        lines = body_txt.splitlines()

        def sample(name):
            for ln in lines:
                if ln.startswith(name + " "):
                    return float(ln.split()[-1])
            return None

        assert sample("hvtpu_allreduce_total") >= 2
        assert sample("hvtpu_wire_bytes_total") > 0
        assert sample("hvtpu_tensor_bytes_total") >= 4096
        assert sample("hvtpu_elastic_workers") == 2
        assert "# TYPE hvtpu_controller_cycle_seconds histogram" \
            in body_txt
        assert sample("hvtpu_controller_cycle_seconds_count") > 0
        hvt.shutdown()
        return "ok"

    env = dict(_ENV, HVTPU_METRICS_PORT="19650")
    assert run(body, np=2, cpu_devices=1, env=env,
               start_timeout=300.0) == ["ok", "ok"]
