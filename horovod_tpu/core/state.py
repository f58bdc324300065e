"""Process-wide global state, the analog of the reference's
``HorovodGlobalState`` (horovod/common/operations.cc) plus the init /
shutdown choreography of ``InitializeHorovodOnce`` / ``horovod_init``.

Key design departure (SURVEY.md §7.0): on the jitted SPMD path there is
no background controller thread — program order *is* the coordination.
``init()`` therefore only (1) joins the JAX coordination service when a
multi-process launch is detected (replacing the MPI/Gloo rendezvous),
(2) snapshots config from env, and (3) builds the topology / process-set
table.  The eager mini-controller (horovod_tpu.eager) is started lazily
on first use of the async eager API.
"""

from __future__ import annotations

import atexit
import threading
import time
from typing import Optional

import jax

from .config import Config
from .exceptions import NotInitializedError
from .process_set import ProcessSet, ProcessSetTable
from .topology import Topology


class GlobalState:
    def __init__(self):
        self.initialized = False
        self.config: Optional[Config] = None
        self.topology: Optional[Topology] = None
        self.process_set_table: Optional[ProcessSetTable] = None
        self.rank = 0
        self.size = 1
        self.local_rank = 0
        self.local_size = 1
        self.cross_rank = 0
        self.cross_size = 1
        self.distributed_initialized_by_us = False
        # Lazily-started eager mini-controller (horovod_tpu.eager).
        self.controller = None
        # Sync-path stall inspector (comm/stall.py), created lazily on
        # the first guarded eager collective; False = probed, no client.
        self.sync_stall = None
        # Monotonic per-process init counter: namespaces the stall KV
        # marks so a shutdown→init cycle (elastic in-process resync)
        # can never read the previous session's stale marks.
        self.init_generation = 0
        # Timeline writer (horovod_tpu.obs.timeline), if enabled.
        self.timeline = None
        # Autotuner (horovod_tpu.obs.autotune), if enabled.
        self.autotuner = None
        # Fleet health publisher (fleet/health.py), rank 0 only when
        # HVTPU_FLEET_JOB names the owning fleet job.
        self.health_reporter = None


_state = GlobalState()
_init_lock = threading.Lock()


def _job_debug_state() -> dict:
    """Job identity for the metrics server's /debug endpoint
    (registered by init(), removed by shutdown())."""
    import os as _os

    return {
        "initialized": _state.initialized,
        "rank": _state.rank,
        "size": _state.size,
        "local_rank": _state.local_rank,
        "local_size": _state.local_size,
        "init_generation": _state.init_generation,
        "elastic_generation": int(
            _os.environ.get("HVTPU_ELASTIC_GENERATION", "0") or 0),
    }


def _replay_journal(kv, rank: int) -> None:
    """Relaunched incarnation: re-publish this rank's journaled durable
    keys (restore-quorum votes, drain accounting — core/journal.py)
    into the fresh coordination KV.  Every elastic relaunch starts an
    EMPTY KV (new coordinator port, possibly a re-elected coordinator
    host), so without replay a coordinator loss also loses the
    accounting the recovery protocols need.  Best-effort: a failed
    replay degrades to the protocols recomputing from scratch."""
    import logging as _logging
    import os as _os

    if kv is None:
        return
    if int(_os.environ.get("HVTPU_ELASTIC_GENERATION", "0") or 0) <= 0:
        return
    try:
        from .journal import default_journal

        journal = default_journal(rank)
        if journal is None or len(journal) == 0:
            return
        replayed = journal.replay(kv)
        from ..obs import flight as _flight

        _flight.note("journal_replayed", rank=rank, keys=replayed,
                     journaled=len(journal))
        _logging.getLogger("horovod_tpu").info(
            "kv journal: rank %d replayed %d of %d durable key(s) "
            "into the fresh coordinator", rank, replayed, len(journal))
    except Exception:
        _logging.getLogger("horovod_tpu").warning(
            "kv journal: replay failed (protocols will recompute)",
            exc_info=True)


def _coordination_client_active() -> bool:
    """True if jax.distributed is already initialized, checked WITHOUT
    triggering XLA backend initialization (jax.process_count() would)."""
    try:
        from jax._src import distributed as _jd

        return _jd.global_state.client is not None
    except Exception:
        return False


def force_cpu_devices(n: int):
    """Request the CPU platform with ``n`` XLA devices.  Must run before
    anything initializes the XLA backend (a ``jax.devices()`` call
    locks the platform in)."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


def global_state() -> GlobalState:
    return _state


def initialized() -> bool:
    return _state.initialized


def require_init(name: str = "this operation") -> GlobalState:
    if not _state.initialized:
        raise NotInitializedError(name)
    return _state


def init(config: Optional[Config] = None) -> GlobalState:
    """Initialize horovod_tpu (idempotent, like ``InitializeHorovodOnce``)."""
    with _init_lock:
        if _state.initialized:
            return _state
        cfg = config or Config.from_env()

        # Apply the configured log level to the framework's logger tree
        # (parity: HOROVOD_LOG_LEVEL gating logging.cc's LOG macros).
        import logging as _logging

        _LEVELS = {
            "trace": _logging.DEBUG, "debug": _logging.DEBUG,
            "info": _logging.INFO, "warning": _logging.WARNING,
            "error": _logging.ERROR, "fatal": _logging.CRITICAL,
        }
        _logger = _logging.getLogger("horovod_tpu")
        _level = _LEVELS.get(str(cfg.log_level).lower(), _logging.WARNING)
        _logger.setLevel(_level)

        # Elastic worker: install the driver-notification (SIGUSR1)
        # handler BEFORE the (potentially long) rendezvous below, so a
        # membership change during startup sets the flag instead of
        # killing the process with the default disposition.
        if cfg.elastic:
            from ..elastic.worker import _install_sigusr1_handler

            _install_sigusr1_handler()

        # CPU-simulation mode (hvtpurun --cpu-devices N): must be set
        # before the first backend touch below.
        if cfg.cpu_devices > 0:
            force_cpu_devices(cfg.cpu_devices)

        # Multi-process launch (set up by hvtpurun, like HOROVOD_RANK/SIZE
        # env from the reference launcher): join the JAX coordination
        # service — the TPU-native replacement for the Gloo HTTP
        # rendezvous KV store (horovod/runner/http/http_server.py).
        # NOTE: this must happen before anything touches the XLA backend
        # (jax.devices()/process_count() would lock in a local-only view),
        # so membership is decided from config + coordination-client
        # state alone.
        if cfg.size > 1 and not _coordination_client_active():
            if not cfg.coordinator_addr:
                raise ValueError(
                    "HVTPU_SIZE > 1 but HVTPU_COORDINATOR_ADDR is unset; "
                    "launch with hvtpurun or set coordinator env vars"
                )
            from ..obs import metrics as _metrics

            _t_rdv = time.monotonic()
            jax.distributed.initialize(
                coordinator_address=(
                    f"{cfg.coordinator_addr}:{cfg.coordinator_port}"
                ),
                num_processes=cfg.size,
                process_id=cfg.rank,
                initialization_timeout=int(cfg.start_timeout),
            )
            _metrics.histogram(
                "hvtpu_rendezvous_seconds",
                "Coordination-service rendezvous duration at init "
                "(per incarnation; elastic restarts re-observe it).",
            ).observe(time.monotonic() - _t_rdv)
            _state.distributed_initialized_by_us = True

        _state.config = cfg
        _state.rank = jax.process_index()
        _state.size = jax.process_count()

        # Fault-injection harness (core/faults.py): armed once the true
        # rank is known so rank-selected clauses bind correctly.  A
        # malformed HVTPU_FAULT_SPEC fails init loudly (FaultSpecError)
        # — a chaos run that silently tests nothing is worse than one
        # that refuses to start.
        if cfg.fault_spec:
            from . import faults as _faults

            _faults.install_from_config(cfg, _state.rank)

        # Below-WARNING levels need a real handler: Python's lastResort
        # handler only emits WARNING+, so an explicit HVTPU_LOG_LEVEL of
        # info/debug would otherwise be silently inert (the reference's
        # LOG() always writes to stderr when HOROVOD_LOG_LEVEL allows).
        # Runs AFTER rank resolution so the label is the true rank even
        # when the user initialized jax.distributed themselves, and only
        # when the app has configured no logging of its own anywhere on
        # the hierarchy (hasHandlers walks ancestors) — an app-routed
        # sink keeps receiving hvtpu records via normal propagation.
        if _level < _logging.WARNING and not _logger.hasHandlers():
            _h = _logging.StreamHandler()
            _h.setFormatter(_logging.Formatter(
                f"[hvtpu rank {_state.rank}] %(levelname)s %(message)s"
            ))
            _logger.addHandler(_h)
            _logger.propagate = False
        # local/cross topology comes from the launcher when present;
        # single-host default is local == world.
        if cfg.size > 1:
            _state.local_rank = cfg.local_rank
            _state.local_size = cfg.local_size
            _state.cross_rank = cfg.cross_rank
            _state.cross_size = cfg.cross_size
        else:
            _state.local_rank = _state.rank
            _state.local_size = _state.size
            _state.cross_rank = 0
            _state.cross_size = 1

        _state.topology = Topology()
        _state.process_set_table = ProcessSetTable(
            _state.topology, _state.size
        )

        # Pod shape (P processes x D>1 local devices): say the quiet
        # part out loud — eager collectives are process-granularity
        # (one rank = one process, contribution on the first local
        # device); the other local devices serve the jit/SPMD path
        # over world_mesh().  Without this note a user could read
        # "2 of 8 devices active" off a profile of an eager-only
        # program and suspect a bug.
        if _state.size > 1:
            n_local = _state.topology.num_local_devices
            if n_local > 1:
                lanes = (
                    f"allreduce streams over all {n_local} local "
                    "lanes, other ops use the first local device"
                    if cfg.eager_multidevice
                    and not cfg.hierarchical_allreduce
                    else "transport device = first local device"
                )
                _logging.getLogger("horovod_tpu").info(
                    "pod shape: %d processes x %d local devices; eager "
                    "collectives run at process granularity (rank = "
                    "process; %s); the jit/SPMD path (world_mesh + "
                    "shard_map) engages all %d devices",
                    _state.size, n_local, lanes,
                    _state.size * n_local,
                )

        # Always-on telemetry (obs/metrics.py): identity gauges for the
        # cluster view, plus the Prometheus endpoint when enabled.  The
        # worker-count gauge is the per-rank view of the live world —
        # summed by metrics.aggregate, it is the cluster worker count;
        # an elastic relaunch re-initializes it at the new world size.
        import os as _os

        from ..obs import metrics as _metrics

        _metrics.gauge(
            "hvtpu_elastic_workers",
            "Live worker (rank) count of this incarnation's world as "
            "seen by this rank.",
        ).set(_state.size)
        _metrics.gauge(
            "hvtpu_elastic_generation",
            "Elastic incarnation counter (0 = first launch; bumps on "
            "every driver relaunch).",
        ).set(int(_os.environ.get("HVTPU_ELASTIC_GENERATION", "0") or 0))
        # HVTPU_METRICS_PORT (or --metrics-port): each worker binds
        # port + local_rank so multi-slot hosts don't collide.
        _metrics.serve_from_env(local_rank=_state.local_rank)

        if cfg.timeline_filename:
            from ..obs.timeline import Timeline

            _state.timeline = Timeline(
                cfg.timeline_filename,
                _state.rank,
                mark_cycles=cfg.timeline_mark_cycles,
            )
        if cfg.trace_dir:
            # Cross-rank distributed tracing (obs/tracing.py): per-rank
            # span files + a KV clock handshake so tools/hvtputrace can
            # merge them onto one clock.  Any failure disables tracing
            # rather than failing init.
            try:
                from ..obs import tracing as _tracing

                _client = None
                if _state.size > 1:
                    try:
                        from jax._src import distributed as _jd

                        _client = _jd.global_state.client
                        if _client is not None:
                            from .retry import fenced_kv

                            _client = fenced_kv(
                                _client, rank=_state.rank)
                    except Exception:
                        _client = None
                _tracing.install(
                    cfg.trace_dir, rank=_state.rank, size=_state.size,
                    client=_client, pings=cfg.trace_clock_pings)
            except Exception:
                _logging.getLogger("horovod_tpu").warning(
                    "distributed tracing disabled: install failed",
                    exc_info=True)
        if cfg.elastic:
            # Graceful-preemption watcher (core/preempt.py): catch the
            # configured preemption signal / notice file and run the
            # coordinated drain protocol over the coordination KV.
            # Failure degrades to plain SIGTERM death, not a broken
            # init.
            try:
                from . import preempt as _preempt

                _pclient = None
                if _state.size > 1:
                    try:
                        from jax._src import distributed as _jd

                        _pclient = _jd.global_state.client
                        if _pclient is not None:
                            from .journal import default_journal
                            from .retry import fenced_kv

                            # The drain coordinator authors DURABLE
                            # keys (accounting a relaunch must see):
                            # fence its writes and journal them for
                            # replay into a fresh coordinator.
                            _pclient = fenced_kv(
                                _pclient, rank=_state.rank,
                                journal=default_journal(_state.rank))
                    except Exception:
                        _pclient = None
                _preempt.install(
                    cfg, rank=_state.rank, size=_state.size,
                    client=_pclient)
                _replay_journal(_pclient, _state.rank)
            except Exception:
                _logging.getLogger("horovod_tpu").warning(
                    "graceful preemption disabled: install failed",
                    exc_info=True)
        # Live /debug job identity (rank/world/elastic generation).
        _metrics.register_debug_provider("job", _job_debug_state)
        # Overlap profiler (obs/stepprof): per-step exposed-comm /
        # overlap / MFU metrics plus a /debug provider.  Collection is
        # passive (comm windows + step boundaries); HVTPU_STEPPROF=0
        # disables it.
        try:
            from ..obs import stepprof as _stepprof

            if _stepprof.ACTIVE:
                _stepprof.install()
        except Exception:
            pass
        # Flight recorder (obs/flight.py): always-on bounded event ring
        # + postmortem dumps on fatal paths; HVTPU_FLIGHT=0 opts out.
        # Failure degrades to no black box, never a broken init.
        try:
            from ..obs import flight as _flight

            if _flight.env_enabled():
                _flight.install(
                    rank=_state.rank, size=_state.size,
                    generation=int(_os.environ.get(
                        "HVTPU_ELASTIC_GENERATION", "0") or 0),
                    out_dir=(_os.environ.get("HVTPU_FLIGHT_DIR")
                             or cfg.trace_dir
                             or _os.environ.get(
                                 "HVTPU_ELASTIC_STATE_DIR")
                             or "."),
                    window=_flight.env_window())
        except Exception:
            _logging.getLogger("horovod_tpu").warning(
                "flight recorder disabled: install failed",
                exc_info=True)
        # Online anomaly detection (obs/anomaly.py): robust-z detectors
        # over the step/comm/skew series; HVTPU_ANOMALY=0 opts out.
        try:
            from ..obs import anomaly as _anomaly

            if _anomaly.env_enabled():
                _anomaly.install(rank=_state.rank, size=_state.size)
        except Exception:
            _logging.getLogger("horovod_tpu").warning(
                "anomaly detection disabled: install failed",
                exc_info=True)
        # Fleet health publisher (fleet/health.py): when this worker
        # belongs to a fleet job (HVTPU_FLEET_JOB, injected by the
        # fleet runner), rank 0 publishes a compact health summary
        # under the job's prefixed KV namespace each interval.
        if _state.rank == 0 and _os.environ.get("HVTPU_FLEET_JOB"):
            try:
                _hclient = None
                if _state.size >= 1:
                    try:
                        from jax._src import distributed as _jd

                        _hclient = _jd.global_state.client
                        if _hclient is not None:
                            # Fenced so a superseded zombie can never
                            # publish a stale health summary; the
                            # arbiter-side reader is stamp-tolerant
                            # (fleet/health.py uses core.retry.unstamp).
                            from .retry import fenced_kv

                            _hclient = fenced_kv(
                                _hclient, rank=_state.rank)
                    except Exception:
                        _hclient = None
                # A KV client is optional: without one the reporter
                # still mirrors summaries to HVTPU_FLEET_HEALTH_DIR
                # (the file channel the arbiter actually polls — it is
                # not a member of this job's coordination world).
                if (_hclient is not None
                        or _os.environ.get("HVTPU_FLEET_HEALTH_DIR")):
                    from ..fleet import health as _health

                    _state.health_reporter = _health.HealthReporter(
                        _hclient,
                        _os.environ["HVTPU_FLEET_JOB"],
                        rank=_state.rank)
                    _state.health_reporter.start()
            except Exception:
                _logging.getLogger("horovod_tpu").warning(
                    "fleet health publisher disabled: install failed",
                    exc_info=True)
        if cfg.autotune:
            from ..obs.autotune import Autotuner

            _state.autotuner = Autotuner(cfg)

        _state.init_generation += 1
        _state.initialized = True
        atexit.register(_shutdown_at_exit)
        return _state


def shutdown():
    """Tear down (parity: ``horovod_shutdown``)."""
    with _init_lock:
        if not _state.initialized:
            return
        if _state.controller is not None:
            try:
                _state.controller.stop()
            except Exception:
                pass
            _state.controller = None
        if _state.timeline is not None:
            try:
                _state.timeline.close()
            except Exception:
                pass
            _state.timeline = None
        # Flush trace files BEFORE the coordination client goes away
        # (and from _shutdown_at_exit on abnormal exits) so traces
        # survive; uninstall is idempotent.
        try:
            from ..obs import tracing as _tracing

            _tracing.uninstall()
        except Exception:
            pass
        # Stop the fleet health publisher BEFORE the coordination
        # client goes away (its loop writes the fleet KV namespace).
        if _state.health_reporter is not None:
            try:
                _state.health_reporter.stop()
            except Exception:
                pass
            _state.health_reporter = None
        # Anomaly engine + flight recorder: uninstall is idempotent and
        # restores the SIGUSR2 handler; the ring is dropped (postmortems
        # only exist for fatal paths, not clean shutdowns).
        try:
            from ..obs import anomaly as _anomaly

            _anomaly.uninstall()
        except Exception:
            pass
        try:
            from ..obs import flight as _flight

            _flight.uninstall()
        except Exception:
            pass
        _state.autotuner = None
        try:
            from ..obs import metrics as _m

            _m.unregister_debug_provider("job")
        except Exception:
            pass
        try:
            from ..obs import stepprof as _stepprof

            _stepprof.uninstall()
        except Exception:
            pass
        try:
            from ..obs import metrics as _metrics

            _metrics.stop_http_server()
        except Exception:
            pass
        # Stop the preemption watcher before the client goes away (its
        # poll loop reads the coordination KV); uninstall is idempotent
        # and restores the previous signal handler.
        try:
            from . import preempt as _preempt

            _preempt.uninstall()
        except Exception:
            pass
        # The stall inspector's stop posts a goodbye tombstone over the
        # coordination KV (so still-running peers don't blame this
        # rank for a stall) — it must run BEFORE the client goes away.
        try:
            from ..comm import stall as _stall

            _stall.stop(_state)
        except Exception:
            _state.sync_stall = None
        if _state.distributed_initialized_by_us:
            try:
                from ..comm.stall import poisoned as _stall_poisoned

                if _stall_poisoned():
                    # The shutdown barrier rides the coordination gRPC
                    # channel (independent of the wedged XLA
                    # execution), and joining it lets still-healthy
                    # peers finish before the leader goes away — but a
                    # peer that DIED mid-collective never arrives, so
                    # bound the wait instead of parking for the full
                    # coordination timeout.
                    _t = threading.Thread(
                        target=jax.distributed.shutdown, daemon=True)
                    _t.start()
                    _t.join(timeout=15.0)
                else:
                    jax.distributed.shutdown()
            except Exception:
                pass
            _state.distributed_initialized_by_us = False
        _state.initialized = False
        _state.sync_stall = None
        _state.config = None
        _state.topology = None
        _state.process_set_table = None
        _state.rank, _state.size = 0, 1
        _state.local_rank, _state.local_size = 0, 1
        _state.cross_rank, _state.cross_size = 0, 1


def _shutdown_at_exit():
    try:
        shutdown()
    except Exception:
        pass
    try:
        from ..comm.stall import poison_exit_status, poisoned

        if poisoned():
            # Interpreter teardown would park on the stuck collective
            # (XLA client destructor joins pending executions) —
            # hard-exit like the reference's stall shutdown does.
            # Trade-off, stated out loud since os._exit skips
            # anything registered before horovod_tpu's atexit hook
            # (LIFO): those handlers are sacrificed to avoid a
            # teardown that never finishes.
            import logging as _logging

            _logging.getLogger("horovod_tpu").critical(
                "hard-exiting past a wedged collective abandoned by "
                "the stall watchdog; atexit handlers registered "
                "before horovod_tpu will not run")
            # hard-exit like the
            # reference's stall shutdown does.  Status 0 if the
            # process re-initialized past the poisoned generation
            # (elastic recovery succeeded), 1 otherwise.
            import os as _os
            import sys as _sys

            _sys.stdout.flush()
            _sys.stderr.flush()
            _os._exit(poison_exit_status())
    except ImportError:
        pass


def add_process_set(ps) -> ProcessSet:
    st = require_init("add_process_set")
    if not isinstance(ps, ProcessSet):
        ps = ProcessSet(ps)
    st.process_set_table.add(ps)
    if st.controller is not None and ps.ranks is not None:
        st.controller.register_process_set(ps.process_set_id, ps.ranks)
    return ps


def remove_process_set(ps) -> bool:
    st = require_init("remove_process_set")
    psid = ps.process_set_id if isinstance(ps, ProcessSet) else int(ps)
    try:
        st.process_set_table.remove(psid)
        return True
    except ValueError:
        return False
