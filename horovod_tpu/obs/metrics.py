"""Unified metrics registry: always-on numeric telemetry for every layer.

The reference Horovod's observability stops at the Timeline (trace
files, opt-in) and the stall inspector (log lines); neither answers
"how many bytes crossed the wire this minute" for a live job.  This
module is the single sink the hot layers report into:

- a dependency-free, thread-safe registry of **Counter** / **Gauge** /
  **Histogram** (fixed log-scale buckets) families, with optional
  Prometheus-style labels;
- a **Prometheus text-format exposition endpoint** served from a
  background ``http.server`` thread — enabled by ``HVTPU_METRICS_PORT``
  (or ``hvtpurun --metrics-port``); each worker binds
  ``port + local_rank`` so multi-slot hosts don't collide;
- ``snapshot()`` (JSON-serializable dump of every family) and
  ``aggregate(process_set)`` — an allgather of per-rank snapshots over
  the JAX coordination KV (the same store the eager controller and the
  stall heartbeat ride), so rank 0 can export a cluster-wide view.

Instrumented producers (metric catalog in docs/observability.md):
``comm/eager.py`` (per-collective counts, wire bytes pre/post
compression, allreduce latency), ``eager/controller.py`` (cycle
duration, queue depth, negotiation latency, cache hits),
``comm/stall.py`` (heartbeat age, warnings/aborts), ``elastic/*``
(rendezvous duration, restarts, live worker gauge),
``api/optimizer.py`` (steps, skipped steps, examples/sec), and
``data/loader.py`` (input wait time, prefetch queue depth,
samples/batches delivered, resize re-shards).

Cost model: a counter increment is a lock + dict add (~1 µs) — two
orders of magnitude under the cheapest eager collective — so the
registry always counts; only the HTTP endpoint is opt-in.
"""

from __future__ import annotations

import bisect
import http.server
import json
import logging
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

logger = logging.getLogger("horovod_tpu")

# ---------------------------------------------------------------------------
# buckets
# ---------------------------------------------------------------------------


def log_buckets(start: float, factor: float, count: int) -> Tuple[float, ...]:
    """``count`` log-scale bucket upper bounds: start * factor**k."""
    return tuple(start * factor ** k for k in range(count))


# 10 µs .. ~42 s in 4x steps — spans a sub-ms CPU op to a stalled pod.
DEFAULT_TIME_BUCKETS = log_buckets(1e-5, 4.0, 12)
# 256 B .. ~1 GiB in 4x steps — a scalar barrier to a fused VGG bucket.
DEFAULT_BYTE_BUCKETS = log_buckets(256.0, 4.0, 12)


def _labelstr(labels: Dict[str, str]) -> str:
    """Canonical (sorted) Prometheus label block, '' when unlabeled."""
    if not labels:
        return ""
    parts = []
    for k in sorted(labels):
        v = str(labels[k]).replace("\\", r"\\").replace('"', r"\"") \
            .replace("\n", r"\n")
        parts.append(f'{k}="{v}"')
    return "{" + ",".join(parts) + "}"


def _fmt(v: float) -> str:
    """Prometheus sample value: integral counters render without '.0'."""
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


# ---------------------------------------------------------------------------
# metric families
# ---------------------------------------------------------------------------


class _Family:
    kind = "untyped"

    def __init__(self, name: str, help: str, lock: threading.Lock):
        self.name = name
        self.help = help
        self._lock = lock
        self._values: Dict[str, float] = {}

    def value(self, **labels) -> float:
        with self._lock:
            return self._values.get(_labelstr(labels), 0.0)

    def _reset(self):
        self._values.clear()

    # -- snapshot / exposition ------------------------------------------
    def _snapshot_values(self):
        return dict(self._values)

    def _expo_lines(self) -> List[str]:
        return [f"{self.name}{k} {_fmt(v)}"
                for k, v in sorted(self._values.items())]


class Counter(_Family):
    """Monotonically increasing count (Prometheus counter)."""

    kind = "counter"

    def inc(self, amount: float = 1.0, **labels):
        if amount < 0:
            raise ValueError("counters only go up")
        key = _labelstr(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount


class Gauge(_Family):
    """Point-in-time value (Prometheus gauge)."""

    kind = "gauge"

    def set(self, value: float, **labels):
        with self._lock:
            self._values[_labelstr(labels)] = float(value)

    def inc(self, amount: float = 1.0, **labels):
        key = _labelstr(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def dec(self, amount: float = 1.0, **labels):
        self.inc(-amount, **labels)


class Histogram(_Family):
    """Distribution over fixed log-scale buckets (Prometheus histogram).

    Internally stores per-bucket (non-cumulative) counts plus an
    overflow slot; exposition emits the cumulative ``_bucket{le=...}``
    series, ``_sum`` and ``_count``.
    """

    kind = "histogram"

    def __init__(self, name, help, lock, buckets=None):
        super().__init__(name, help, lock)
        self.buckets: Tuple[float, ...] = tuple(
            sorted(buckets if buckets is not None else DEFAULT_TIME_BUCKETS)
        )
        # label key -> [counts (len buckets + 1 overflow), sum, count]
        self._values: Dict[str, list] = {}

    def observe(self, value: float, **labels):
        key = _labelstr(labels)
        i = bisect.bisect_left(self.buckets, value)
        with self._lock:
            cell = self._values.get(key)
            if cell is None:
                cell = self._values[key] = [
                    [0] * (len(self.buckets) + 1), 0.0, 0]
            cell[0][i] += 1
            cell[1] += float(value)
            cell[2] += 1

    def observe_many(self, values, **labels):
        """Record a batch of observations under ONE lock acquisition —
        the per-fused-group bookkeeping path of the eager controller
        (one metrics update per group instead of per op)."""
        values = [float(v) for v in values]
        if not values:
            return
        key = _labelstr(labels)
        idxs = [bisect.bisect_left(self.buckets, v) for v in values]
        with self._lock:
            cell = self._values.get(key)
            if cell is None:
                cell = self._values[key] = [
                    [0] * (len(self.buckets) + 1), 0.0, 0]
            for i in idxs:
                cell[0][i] += 1
            cell[1] += sum(values)
            cell[2] += len(values)

    def value(self, **labels):
        with self._lock:
            cell = self._values.get(_labelstr(labels))
            return 0 if cell is None else cell[2]

    def _snapshot_values(self):
        return {
            k: {"counts": list(c[0]), "sum": c[1], "count": c[2]}
            for k, c in self._values.items()
        }

    def _expo_lines(self) -> List[str]:
        lines = []
        for key, (counts, total, n) in sorted(self._values.items()):
            base = key[1:-1] if key else ""  # strip {} to splice 'le' in

            def lbl(le: str) -> str:
                return "{" + (base + "," if base else "") + \
                    f'le="{le}"' + "}"

            cum = 0
            for b, c in zip(self.buckets, counts):
                cum += c
                lines.append(
                    f"{self.name}_bucket{lbl('{:g}'.format(b))} {cum}")
            lines.append(f"{self.name}_bucket{lbl('+Inf')} {n}")
            lines.append(f"{self.name}_sum{key} {repr(float(total))}")
            lines.append(f"{self.name}_count{key} {n}")
        return lines


class MetricsRegistry:
    """Named families, created idempotently; one coarse lock (metric
    updates are far off any sub-microsecond path)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._families: Dict[str, _Family] = {}

    def _family(self, cls, name, help, **kw) -> _Family:
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if not isinstance(fam, cls):
                    raise TypeError(
                        f"metric {name!r} already registered as "
                        f"{fam.kind}, not {cls.kind}")
                return fam
            fam = cls(name, help, self._lock, **kw)
            self._families[name] = fam
            return fam

    def counter(self, name: str, help: str = "") -> Counter:
        return self._family(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._family(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets=None) -> Histogram:
        return self._family(Histogram, name, help, buckets=buckets)

    def reset(self):
        """Zero every family's samples (families stay registered so
        cached accessor objects remain valid) — test hook."""
        with self._lock:
            for fam in self._families.values():
                fam._reset()

    # -- export ----------------------------------------------------------
    def snapshot(self) -> Dict[str, dict]:
        """JSON-serializable dump of every family (the unit that rides
        the coordination KV in ``aggregate``)."""
        with self._lock:
            return {
                name: {
                    "type": fam.kind,
                    "help": fam.help,
                    **({"buckets": list(fam.buckets)}
                       if isinstance(fam, Histogram) else {}),
                    "values": fam._snapshot_values(),
                }
                for name, fam in sorted(self._families.items())
            }

    def exposition(self) -> str:
        """Prometheus text format 0.0.4."""
        out = []
        with self._lock:
            for name, fam in sorted(self._families.items()):
                help_ = fam.help.replace("\\", r"\\").replace("\n", r"\n")
                out.append(f"# HELP {name} {help_}")
                out.append(f"# TYPE {name} {fam.kind}")
                out.extend(fam._expo_lines())
        return "\n".join(out) + "\n"


REGISTRY = MetricsRegistry()


def counter(name: str, help: str = "") -> Counter:
    return REGISTRY.counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return REGISTRY.gauge(name, help)


def histogram(name: str, help: str = "", buckets=None) -> Histogram:
    return REGISTRY.histogram(name, help, buckets=buckets)


def snapshot() -> Dict[str, dict]:
    return REGISTRY.snapshot()


# ---------------------------------------------------------------------------
# hot-path accessors (pre-registered so call sites are one cached lookup)
# ---------------------------------------------------------------------------

_OP_COUNTERS: Dict[str, Counter] = {}
_OP_LOCK = threading.Lock()


def op_counter(kind: str) -> Counter:
    """Per-collective-kind counter, e.g. ``hvtpu_allreduce_total``."""
    c = _OP_COUNTERS.get(kind)
    if c is None:
        with _OP_LOCK:
            c = _OP_COUNTERS.setdefault(kind, REGISTRY.counter(
                f"hvtpu_{kind}_total",
                f"Eager {kind} collectives executed by this rank."))
    return c


TENSOR_BYTES = REGISTRY.counter(
    "hvtpu_tensor_bytes_total",
    "Collective payload bytes BEFORE wire compression/quantization.")
WIRE_BYTES = REGISTRY.counter(
    "hvtpu_wire_bytes_total",
    "Bytes actually moved on the wire (after compression/quantization, "
    "including quantization scale sidecars).")
ALLREDUCE_LATENCY = REGISTRY.histogram(
    "hvtpu_allreduce_latency_seconds",
    "Eager allreduce dispatch-to-ready latency as seen by the caller.",
    buckets=DEFAULT_TIME_BUCKETS)

_STEP_STATE = {"t": None}
_STEP_LOCK = threading.Lock()
# EWMA weight for the steps/examples-per-second gauges: ~last 10 steps.
_RATE_ALPHA = 0.2


def note_step(examples: float = 0.0, steps: float = 1.0):
    """Record optimizer/training progress.  Increments the step and
    example counters and maintains EWMA ``*_per_second`` gauges from
    inter-call time.  Called by the eager ``allreduce_gradients`` path
    once per step; jit training loops (whose update is traced once)
    call it from the host loop, passing the steps and examples per
    dispatch."""
    REGISTRY.counter(
        "hvtpu_optimizer_steps_total", "Optimizer steps applied."
    ).inc(steps)
    if examples:
        REGISTRY.counter(
            "hvtpu_examples_total", "Training examples processed."
        ).inc(examples)
    # Step-boundary hook for the overlap profiler (import deferred:
    # stepprof imports this module for its registry).  The returned
    # step record feeds the flight ring and the anomaly detectors —
    # both behind single module-attribute guards when disabled.
    from . import stepprof as _stepprof
    if _stepprof.ACTIVE:
        rec = _stepprof.note_step_boundary(steps=steps)
        if rec is not None:
            from . import anomaly as _anomaly
            from . import flight as _flight
            if _flight.ACTIVE:
                _flight.note("step", **rec)
            if _anomaly.ACTIVE:
                _anomaly.on_step(rec)
    now = time.monotonic()
    with _STEP_LOCK:
        prev = _STEP_STATE["t"]
        _STEP_STATE["t"] = now
    if prev is None or now <= prev:
        return
    dt = now - prev
    sps = REGISTRY.gauge(
        "hvtpu_steps_per_second", "EWMA optimizer steps per second.")
    old = sps.value()
    rate = steps / dt
    sps.set((1 - _RATE_ALPHA) * old + _RATE_ALPHA * rate
            if old else rate)
    if examples:
        eps = REGISTRY.gauge(
            "hvtpu_examples_per_second", "EWMA training examples per "
            "second (requires callers to pass examples to note_step).")
        old = eps.value()
        rate = examples / dt
        eps.set((1 - _RATE_ALPHA) * old + _RATE_ALPHA * rate
                if old else rate)


# ---------------------------------------------------------------------------
# live /debug introspection plane
# ---------------------------------------------------------------------------
# Subsystems (eager controller, stall inspector, core state) register a
# zero-argument callable returning a JSON-serializable dict; the HTTP
# server's /debug route snapshots all of them so "what is my job doing"
# is one curl away.  A provider that raises is reported in place as an
# {"error": ...} entry — introspection never takes the endpoint down.

_debug_providers: Dict[str, Callable[[], dict]] = {}
_debug_lock = threading.Lock()


def register_debug_provider(name: str, fn: Callable[[], dict]) -> None:
    with _debug_lock:
        _debug_providers[name] = fn


def unregister_debug_provider(name: str) -> None:
    with _debug_lock:
        _debug_providers.pop(name, None)


def debug_snapshot() -> dict:
    """One coherent-ish dump of every registered provider (each
    provider snapshots under its own lock; cross-provider skew is the
    wall time between calls)."""
    with _debug_lock:
        items = list(_debug_providers.items())
    out: dict = {"time_unix": time.time()}
    for name, fn in items:
        try:
            out[name] = fn()
        except Exception as e:  # noqa: BLE001 — isolate provider faults
            out[name] = {"error": str(e)}
    return out


# ---------------------------------------------------------------------------
# Prometheus exposition endpoint
# ---------------------------------------------------------------------------

_server: Optional[http.server.ThreadingHTTPServer] = None
_server_thread: Optional[threading.Thread] = None
_server_lock = threading.Lock()


def _make_handler(registry: MetricsRegistry):
    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 — http.server API
            path = self.path.split("?", 1)[0]
            if path == "/debug":
                body = json.dumps(
                    debug_snapshot(), indent=2, default=str,
                ).encode("utf-8")
                ctype = "application/json"
            elif path in ("/", "/metrics"):
                body = registry.exposition().encode("utf-8")
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            else:
                self.send_error(404)
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # silence per-scrape stderr noise
            pass

    return Handler


def start_http_server(port: int, addr: str = "",
                      registry: Optional[MetricsRegistry] = None) -> int:
    """Serve ``registry`` (default: the global one) at
    ``http://<addr>:<port>/metrics`` from a daemon thread.  ``port=0``
    binds an ephemeral port.  Returns the bound port.  Idempotent per
    process: a second call while a server is live returns its port."""
    global _server, _server_thread
    with _server_lock:
        if _server is not None:
            return _server.server_address[1]
        srv = http.server.ThreadingHTTPServer(
            (addr, port), _make_handler(registry or REGISTRY))
        srv.daemon_threads = True
        t = threading.Thread(target=srv.serve_forever,
                             name="hvt-metrics-http", daemon=True)
        t.start()
        _server, _server_thread = srv, t
        return srv.server_address[1]


def stop_http_server():
    global _server, _server_thread
    with _server_lock:
        srv, t = _server, _server_thread
        _server = _server_thread = None
    if srv is not None:
        srv.shutdown()
        srv.server_close()
    if t is not None:
        t.join(timeout=5)


def serve_from_env(local_rank: int = 0) -> Optional[int]:
    """Start the endpoint when ``HVTPU_METRICS_PORT`` (reference
    spelling ``HOROVOD_METRICS_PORT`` honored too) is set: each worker
    binds ``port + local_rank`` so multi-slot hosts don't collide.  A
    bind failure logs a warning and returns None — telemetry must never
    take a healthy job down."""
    raw = (os.environ.get("HVTPU_METRICS_PORT")
           or os.environ.get("HOROVOD_METRICS_PORT"))
    if not raw:
        return None
    try:
        base = int(raw)
    except ValueError:
        logger.warning("HVTPU_METRICS_PORT=%r is not an integer; "
                       "metrics endpoint disabled", raw)
        return None
    if base <= 0:
        return None
    try:
        return start_http_server(base + local_rank)
    except OSError as e:
        logger.warning(
            "metrics endpoint disabled: could not bind port %d: %s",
            base + local_rank, e)
        return None


# ---------------------------------------------------------------------------
# cross-rank aggregation over the coordination KV
# ---------------------------------------------------------------------------

_agg_seq: Dict[Tuple[int, int], int] = {}
_agg_lock = threading.Lock()
_AGG_NS = "hvtmetrics"


def merge_snapshots(snaps: List[Dict[str, dict]]) -> Dict[str, dict]:
    """Element-wise merge of per-rank snapshots: counters, gauges and
    histogram cells SUM across ranks (a summed gauge is the natural
    cluster view for worker counts and rates; per-rank values stay
    available in ``aggregate``'s per_rank map)."""
    merged: Dict[str, dict] = {}
    for snap in snaps:
        for name, fam in snap.items():
            m = merged.get(name)
            if m is None:
                merged[name] = json.loads(json.dumps(fam))  # deep copy
                continue
            if fam["type"] == "histogram":
                if fam.get("buckets") != m.get("buckets"):
                    raise ValueError(
                        f"histogram {name!r} bucket mismatch across ranks")
                for key, cell in fam["values"].items():
                    mc = m["values"].get(key)
                    if mc is None:
                        m["values"][key] = json.loads(json.dumps(cell))
                    else:
                        mc["counts"] = [a + b for a, b in
                                        zip(mc["counts"], cell["counts"])]
                        mc["sum"] += cell["sum"]
                        mc["count"] += cell["count"]
            else:
                for key, v in fam["values"].items():
                    m["values"][key] = m["values"].get(key, 0.0) + v
    return merged


def aggregate(process_set=None, timeout_s: float = 60.0,
              registry: Optional[MetricsRegistry] = None) -> dict:
    """Allgather every member rank's ``snapshot()`` through the JAX
    coordination KV and return ``{"per_rank": {rank: snap},
    "merged": snap}``.

    COLLECTIVE contract: every member rank of the process set must call
    ``aggregate`` the same number of times (each call uses a fresh
    per-set sequence number, like a controller cycle).  Single-process
    worlds — or processes without a coordination client — degrade to the
    local snapshot.
    """
    registry = registry or REGISTRY
    snap = registry.snapshot()

    try:
        from ..core import state as core_state

        st = core_state.global_state()
    except Exception:
        st = None
    if st is None or not st.initialized or st.size <= 1:
        rank = st.rank if st is not None else 0
        return {"per_rank": {rank: snap}, "merged": snap}

    try:
        from jax._src import distributed as _jd

        client = _jd.global_state.client
    except Exception:
        client = None
    if client is None:
        return {"per_rank": {st.rank: snap}, "merged": snap}

    if process_set is None:
        ps = st.process_set_table.global_process_set
    elif isinstance(process_set, int):
        ps = st.process_set_table.get(process_set)
    else:
        ps = process_set
    members = list(ps.ranks) if ps.ranks is not None else list(
        range(st.size))
    if st.rank not in members:
        raise ValueError(
            f"rank {st.rank} is not a member of process set "
            f"{ps.process_set_id}")

    # One shared retry engine (core/retry.py) instead of the ad-hoc
    # loop this function used to carry: the KV wrapper retries
    # transient put failures with backoff (counted in
    # hvtpu_kv_retries_total), and the per-peer blocking poll rides a
    # deadline-bounded policy where NOT_FOUND/timeout just means "the
    # peer hasn't posted yet".
    from ..core import retry as core_retry

    kv = core_retry.resilient_kv(client, rank=st.rank)

    with _agg_lock:
        key = (st.init_generation, ps.process_set_id)
        seq = _agg_seq.get(key, 0)
        _agg_seq[key] = seq + 1
    prefix = (f"{_AGG_NS}/{st.init_generation}/{ps.process_set_id}/"
              f"{seq}/")
    kv.key_value_set(prefix + str(st.rank), json.dumps(snap))

    per_rank: Dict[int, dict] = {st.rank: snap}
    deadline = time.monotonic() + timeout_s
    poll_policy = core_retry.RetryPolicy(
        name="metrics-aggregate",
        max_attempts=1_000_000,  # the deadline is the real bound
        base_delay_s=0.02, max_delay_s=0.25,
        deadline_s=timeout_s,
        retryable=core_retry.kv_blocking_retryable)

    def _fetch(r: int) -> dict:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            # non-retryable by design: the GLOBAL deadline bounds the
            # whole aggregate, not each peer's poll loop
            raise RuntimeError("aggregate budget spent")
        budget_ms = max(1, int(remaining * 1000))
        return json.loads(kv.blocking_key_value_get(
            prefix + str(r), min(budget_ms, 2000)))

    for r in sorted(members):
        if r == st.rank:
            continue
        try:
            per_rank[r] = core_retry.call(poll_policy, _fetch, r)
        except Exception:
            raise TimeoutError(
                f"metrics snapshot from rank {r} not posted "
                f"within {timeout_s:.0f}s") from None
    # rolling cleanup: every member posted seq, so nobody still needs
    # this rank's previous round (each rank deletes only its own key)
    if seq > 0:
        try:
            kv.key_value_delete(
                f"{_AGG_NS}/{st.init_generation}/{ps.process_set_id}/"
                f"{seq - 1}/{st.rank}")
        except Exception:
            pass
    return {"per_rank": per_rank, "merged": merge_snapshots(
        [per_rank[r] for r in sorted(per_rank)])}


# ---------------------------------------------------------------------------
# attention and expert layers
# ---------------------------------------------------------------------------

def note_attention_path(path: str) -> None:
    """Count one call of ``models.attention.masked_attention``, under
    either mask, by the implementation it took: ``"pallas"`` (the
    kernels of ``ops/flash_attention.py``) or ``"xla"``.  Called while a
    program is traced, once a call site and a trace, never from inside
    the step: a step that scans its layers counts one call however many
    layers run it."""
    REGISTRY.counter(
        "hvtpu_attention_calls_total",
        "Calls of the models' attention in tiles, counted when a program "
        "is traced, by the implementation that was built in: the Pallas "
        "kernels or XLA tiles.").inc(path=path)


def note_attention_block(query_heads: int, kv_heads: int) -> None:
    """Record the heads one grid step of the attention kernels carries,
    as ``ops.flash_attention.block_heads`` chose them from the shapes of
    the last attention built.  Called while a program is traced, like
    ``note_attention_path``, never from inside the step."""
    heads = REGISTRY.gauge(
        "hvtpu_attention_block_heads",
        "Heads a grid step of the Pallas attention kernels carries in the "
        "last attention built, by kind: the query heads walked inside a "
        "step and the key/value heads its blocks hold side by side.")
    heads.set(float(query_heads), kind="query")
    heads.set(float(kv_heads), kind="key_value")


def note_attention_head_width(key: int, value: int) -> None:
    """Record a head's two widths in the last attention the Pallas
    kernels were built for: of its queries and keys, and of its values
    and results (192 against 128 in latent attention; the same number
    twice elsewhere).  Called while a program is traced, like
    ``note_attention_block``."""
    width = REGISTRY.gauge(
        "hvtpu_attention_head_width",
        "Width of a head in the last attention built for the Pallas "
        "kernels, by kind: of its queries and keys, and of its values and "
        "results.")
    width.set(float(key), kind="key")
    width.set(float(value), kind="value")


def note_attention_pairs(run: int, skipped: int) -> None:
    """Count the block pairs the attention kernels ran and skipped on
    the batches noted: a pair whose blocks share no document is decided
    by the batch's data when the step runs, so the count comes from the
    host's loop (``models.attention.note_attention_pairs(segment)``
    makes it from the flags the step itself computes), never from inside
    the step."""
    pairs = REGISTRY.counter(
        "hvtpu_attention_pairs_total",
        "Block pairs (row, query block, key block) of the document "
        "attention's schedule on the batches noted, by whether the "
        "kernels ran them or skipped them because the blocks share no "
        "document.")
    pairs.inc(float(run), kind="run")
    pairs.inc(float(skipped), kind="skipped")


def note_moe_products(path: str) -> None:
    """Count one call of ``parallel.moe.dropless_topk_moe`` by how its
    experts' products run: ``"grouped"`` (the Pallas kernels of
    ``ops/grouped_ffn.py``, a grouped product a kernel call) or
    ``"ragged_dot"`` (``lax.ragged_dot`` over the same buffers).  Called
    while a program is traced, once a call site and a trace, like
    ``note_attention_path``: a step that scans its layers counts one
    call however many layers run it."""
    REGISTRY.counter(
        "hvtpu_moe_products_total",
        "Calls of the dropless expert layer, counted when a program is "
        "traced, by how the experts' products were built in: the grouped "
        "Pallas kernels or lax.ragged_dot.").inc(path=path)


def note_moe_backward_kernel(kernel: str) -> None:
    """Count one backward pass of the grouped expert kernels
    (``ops.grouped_ffn.backward``) by the kernel it was built with:
    ``"whole"`` (a group's whole inner width at once, its weights and
    f32 sums twice over in VMEM) or ``"blocked"`` (the inner width in
    blocks, the weights twice over, the sums once).  Called while a
    program is traced, like ``note_moe_products``."""
    REGISTRY.counter(
        "hvtpu_moe_backward_kernel_total",
        "Backward passes of the grouped expert kernels, counted when a "
        "program is traced, by whether the kernel takes a group's whole "
        "inner width at once or walks it in blocks.").inc(kernel=kernel)


def note_moe_layer(rule: str, form: str) -> None:
    """Count one call of ``parallel.moe.dropless_topk_moe`` by its
    routing rule (``"softmax"``, or ``"sigmoid_bias"``: sigmoid scores,
    the choice by score plus a bias, the weights without it) and by its
    experts' form (``"gated"``: three weights, SiLU-gated; ``"relu2"``:
    two weights with a squared ReLU between).  Called while a program
    is traced, once a call site and a trace, like
    ``note_moe_products``."""
    REGISTRY.counter(
        "hvtpu_moe_router_total",
        "Calls of the dropless expert layer, counted when a program is "
        "traced, by the rule its router was built with: softmax "
        "probabilities, or sigmoid scores chosen by score plus a "
        "selection bias.").inc(rule=rule)
    REGISTRY.counter(
        "hvtpu_moe_experts_form_total",
        "Calls of the dropless expert layer, counted when a program is "
        "traced, by the form of its experts: three SiLU-gated weights, or "
        "two weights with a squared ReLU between.").inc(form=form)


def note_moe_routing(rows_per_expert, buffer_rows=None) -> None:
    """Record what a step's expert layers saw: ``rows_per_expert`` is
    the ``[layers, experts_held]`` (or ``[experts_held]``) count that
    ``parallel.moe.dropless_topk_moe`` returns and a model hands back in
    its ``model_state``; ``buffer_rows``, where the caller knows it
    (``parallel.moe.buffer_rows`` of the layer's sizes), the rows of the
    buffer the layer keeps for the worst routing.  Call it from the
    host loop at logging cadence, on a state the loop has already
    fetched: it reads the array (a device-to-host copy if it still
    lives on the chip) and is never called from inside the step."""
    import numpy as np

    rows = np.asarray(rows_per_expert, np.float64)
    rows = rows.reshape(-1, rows.shape[-1])
    mean = rows.mean(axis=-1)
    worst = np.max(np.where(mean > 0, rows.max(axis=-1)
                            / np.maximum(mean, 1.0), 0.0))
    REGISTRY.gauge(
        "hvtpu_moe_rows_per_expert",
        "Rows the busiest expert held here got over the mean of the "
        "experts held, the worst layer of the last step noted: 1.0 is an "
        "even routing; the layer's tiles, and with an exchange its "
        "slowest peer, grow with it.").set(float(worst))
    REGISTRY.counter(
        "hvtpu_moe_local_rows_total",
        "Rows (token, expert) the experts held here multiplied, summed "
        "over layers and over the steps noted.").inc(float(rows.sum()))
    if buffer_rows:
        REGISTRY.gauge(
            "hvtpu_moe_buffer_live_share",
            "Rows the experts held here got over the rows of the buffer "
            "the layer keeps for a routing that sends every token here, "
            "the fullest layer of the last step noted: the buffer is "
            "allocated and never cleared, so the rest costs memory and "
            "no time.").set(float(rows.sum(axis=-1).max() / buffer_rows))


# ---------------------------------------------------------------------------
# state-space layers and packed documents
# ---------------------------------------------------------------------------

def note_ssm_chunks(chunks: int) -> None:
    """Count the chunks one call of ``models.hybrid_ssm.ssd_scan`` walks
    (rows x chunks a row).  Called while a program is traced, once a
    call site and a trace, like ``note_attention_path``: a step that
    scans its layers counts one layer's chunks."""
    REGISTRY.counter(
        "hvtpu_ssm_chunks_total",
        "Chunks the chunked state-space scan walks in one call (rows x "
        "chunks a row), counted when a program is traced.").inc(float(chunks))


def note_ssm_groups(groups: int) -> None:
    """Record the B/C groups of the last ``models.hybrid_ssm.ssd_scan``
    built: the heads of a group share one ``B`` and one ``C``.  Called
    while a program is traced, like ``note_ssm_chunks``."""
    REGISTRY.gauge(
        "hvtpu_ssm_groups",
        "B/C groups of the last chunked state-space scan built: 1 where "
        "every head reads the same B and C.").set(float(groups))


def note_kda_chunks(chunks: int, chunk_size: int) -> None:
    """Count the chunks one call of ``models.kimi_linear.chunked_delta_
    rule`` solves and walks (rows x chunks a row), and record their
    size.  Called while a program is traced, once a call site and a
    trace, like ``note_ssm_chunks``."""
    REGISTRY.counter(
        "hvtpu_kda_chunks_total",
        "Chunks the chunked delta rule walks in one call (rows x chunks a "
        "row), counted when a program is traced.").inc(float(chunks))
    REGISTRY.gauge(
        "hvtpu_kda_chunk_size",
        "Positions of a chunk of the last chunked delta rule built: the "
        "size of the triangular system a chunk and a head solve.").set(
            float(chunk_size))


def note_kda_path(path: str) -> None:
    """Count one call of ``models.kimi_linear.chunked_delta_rule`` by the
    implementation it took: ``"pallas"`` (the kernels of
    ``ops/delta_rule.py``) or ``"xla"``.  Called while a program is
    traced, once a call site and a trace, like ``note_attention_path``:
    a step that scans its layers counts one call however many layers run
    it."""
    REGISTRY.counter(
        "hvtpu_kda_calls_total",
        "Calls of the chunked delta rule, counted when a program is "
        "traced, by the implementation that was built in: the Pallas "
        "kernels or XLA.").inc(path=path)


def note_short_conv(path: str, taps: int) -> None:
    """Count one call of ``models.gated_conv.short_conv`` (the gated
    short convolution's taps and gates) by the implementation it took
    (``"xla"``: no kernel computes it yet), and record its taps.  Called
    while a program is traced, once a call site and a trace, like
    ``note_kda_path``: a step that scans its layers counts one call
    however many layers run it."""
    REGISTRY.counter(
        "hvtpu_short_conv_calls_total",
        "Calls of the gated short convolution, counted when a program is "
        "traced, by the implementation that was built in.").inc(path=path)
    REGISTRY.gauge(
        "hvtpu_short_conv_taps",
        "Taps of the last gated short convolution built: the positions, "
        "its own and those before it in its document, that a position "
        "reads.").set(float(taps))


def note_packed_batch(segment) -> None:
    """Record what a packed batch holds: ``segment`` is the int ``[rows,
    T]`` array of the document's index at every position, as the batch
    of ``models.hybrid_ssm.next_token_loss`` brings it.  Call it from
    the host loop at logging cadence on a batch the loop still holds on
    the host; never from inside the step."""
    import numpy as np

    seg = np.asarray(segment)
    started = seg.shape[0] + int(np.count_nonzero(seg[:, 1:] != seg[:, :-1]))
    REGISTRY.counter(
        "hvtpu_ssm_state_resets_total",
        "Documents started in the batches noted: each resets the "
        "state-space layers' state, cuts the convolution's taps and "
        "bounds the keys a query sees.").inc(float(started))
    REGISTRY.gauge(
        "hvtpu_packed_documents_per_row",
        "Documents a row of the last batch noted holds, the mean over "
        "its rows: 1.0 is an unpacked batch.").set(started / seg.shape[0])


# ---------------------------------------------------------------------------
# looped decoders
# ---------------------------------------------------------------------------

def note_loop_layer_uses(uses: int) -> None:
    """Count the layer uses of one forward pass of ``models.looped``
    (layers held x passes over them).  Called while a program is traced,
    once a call site and a trace, like ``note_attention_path``."""
    REGISTRY.counter(
        "hvtpu_loop_layer_uses_total",
        "Layer uses a forward pass of a looped decoder makes (layers held "
        "x passes over them), counted when a program is traced.").inc(
            float(uses))


def note_loop_exits(model_state) -> None:
    """Record what a step's exits saw: ``model_state`` is what
    ``models.looped.expected_exit_loss`` hands back, the batch's mean
    exit probability ``loop_exit_mass`` and mean cross-entropy
    ``loop_exit_loss`` of every exit, f32 ``[passes]`` each.  Call it
    from the host loop at logging cadence on a state the loop has
    already fetched, like ``note_moe_routing``; never from inside the
    step."""
    import numpy as np

    mass = REGISTRY.gauge(
        "hvtpu_loop_exit_mass",
        "Mean probability the exit distribution gave an exit (label exit, "
        "from 1) over the weighted positions of the last step noted: they "
        "add up to 1, and a mass that drifts to one exit is a loop that "
        "stopped using the others.")
    loss = REGISTRY.gauge(
        "hvtpu_loop_exit_loss",
        "Mean next-token cross-entropy of an exit's logits (label exit, "
        "from 1) over the weighted positions of the last step noted: "
        "later exits reading lower is what the further passes buy.")
    for gauge, values in ((mass, model_state["loop_exit_mass"]),
                          (loss, model_state["loop_exit_loss"])):
        for i, value in enumerate(np.asarray(values, np.float64)):
            gauge.set(float(value), exit=str(i + 1))
