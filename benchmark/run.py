#!/usr/bin/env python3
"""One run of one cell of hvtpu's benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Drives the user's path (``benchmark/job.py``) on the TPU this process
finds: sets up (imports, ``hvt.init``, a host pool of samples and the
weights from ``--seed``, the cell's programs compiled or loaded from
the persistent cache, the exchange of ``hvt.DistributedOptimizer``
checked against the plain reference), then measures for ``--seconds``.
The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device``: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, a device profile of ``trace_steps`` more steps having
been taken after the untraced window.  Everything else it has to say goes
on earlier lines.

It refuses to measure anything but a TPU with exactly the chips the
cell asks for (exit 2, no result line), and it starts no other process.

``--rehearse-on-cpu`` walks the same code at the toy size each file
names for itself, on as many virtual CPU devices as the cell has chips,
to debug a new cell's files without chip time.  Every line it prints
says REHEARSAL, no timing is printed under a metric's name, and it can
never print the result line.
"""

import time

_T_PROCESS_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
# import as the package ``benchmark`` from the checkout's root: the
# script's own directory on the path would let its modules shadow the
# standard library's
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
sys.path.insert(0, _ROOT)

_PREFIX = ""  # "REHEARSAL " under --rehearse-on-cpu
_PHASES = [("process start", _T_PROCESS_START)]  # where set-up's time goes


def phase_done(name: str) -> None:
    _PHASES.append((name, time.perf_counter()))


def say(msg: str) -> None:
    print(f"{_PREFIX}{msg}", flush=True)


def device_identity() -> dict:
    """The devices as JAX reports them (after ``bench.device_identity``)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def require_chips(ident: dict, chips: int) -> None:
    """Every number this prints is a device number: refuse another
    platform, and refuse a machine that is not the cell's."""
    if ident["platform"] == "tpu" and ident["count"] == chips:
        return
    print(f"benchmark/run.py: the cell asks for {chips} TPU chip(s); JAX "
          f"reports platform={ident['platform']!r} ({ident['kind']}, "
          f"{ident['count']} device(s), JAX_PLATFORMS="
          f"{os.environ.get('JAX_PLATFORMS')!r}). Nothing was measured.",
          file=sys.stderr)
    sys.exit(2)


def memory_peak_bytes():
    """Peak bytes held on the fullest chip: the allocator's peak of live
    buffers plus its peak reservation for running programs.  On this
    runtime ``peak_bytes_in_use`` leaves a program's temporaries out
    (0.8 GB for a ResNet-50 step that reserves 9.0 GB of them), and
    those are most of what a training job holds; the two together are
    what ``bytes_limit`` less ``largest_free_block_bytes`` comes to.
    None where the backend keeps no such figures (the CPU)."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    keys = ("peak_bytes_in_use", "peak_bytes_reserved")
    if any(not s or any(k not in s for k in keys) for s in stats):
        return None
    say(f"memory: the allocator of the first chip says {stats[0]}")
    return max(sum(s[k] for k in keys) for s in stats)


def check_exchange_and_warm_up(job):
    """The exchange on synthetic gradients, through the system's
    optimizer and through the plain reference from the same state
    (``benchmark/correctness.py`` says why this and not whole steps),
    then WARMUP_STEPS steps of the system.  Returns its state after
    them, the last batch, their losses, and the verdict."""
    import jax
    import numpy as np

    from benchmark import correctness
    from benchmark.reference import data_parallel_sgd

    state = job.fresh_state()
    key = jax.random.PRNGKey(job.seed)

    def probe(tx, reduce_grads):
        return np.asarray(correctness.make_exchange_probe(
            job.mesh, "world", tx, reduce_grads)(key, state[0], state[2]))

    exchange_ok, seen = correctness.exchange_agrees(
        probe(job.tx, lambda grads: grads),
        probe(job.plain_tx, data_parallel_sgd.average_over("world")))
    losses = []
    for _ in range(correctness.WARMUP_STEPS):
        batch = next(job.batches)
        *state, loss = job.step(*state, batch)
        losses.append(float(loss))
    say(f"reference: {seen}; {correctness.WARMUP_STEPS} warm-up steps, "
        f"losses {losses}")
    return tuple(state), batch, losses, exchange_ok


def trace_window(job, state, cell, save_extract):
    """A device profile of ``trace_steps`` more steps of the same loop;
    returns (state, Window, TraceReduction or None)."""
    import jax

    from benchmark import xplane
    from benchmark.loop import run_window

    logdir = os.path.join(_ROOT, ".benchmark_out", "trace", cell.name)
    shutil.rmtree(logdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    # no per-call Python hooks and only the spans a caller asked for
    # (TraceAnnotation): at the default level the runtime's transfer
    # threads write some 800,000 events a thread in fourteen steps.  At
    # either level the copies to the chip slow to a few hundred MB/s
    # while the profiler records (PERF.md, findings of PR 22)
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        state, window = run_window(
            job, state, fence_lag=cell.traffic["fence_lag"],
            max_steps=cell.traffic["trace_steps"], annotate=True)
    finally:
        jax.profiler.stop_trace()
    extract = xplane.extract(xplane.find_xplane(logdir))
    if save_extract:
        xplane.save_extract(extract, save_extract)
        say(f"trace: extract saved to {save_extract}")
    reduction = xplane.reduce(extract)
    if reduction:
        say("trace: step periods kept on each chip "
            f"{[len(d.step_ns) for d in reduction.devices]}, left out as "
            "stretched by the profiler "
            f"{[d.dropped for d in reduction.devices]}; the kept ones add "
            f"up to {reduction.window_s:.4f} s a chip, an op ran in "
            f"{reduction.busy_s:.4f} s of them")
    return state, window, reduction


def measure(cell, args, ident, watch, rehearse):
    import jax

    from benchmark import cells, correctness
    from benchmark.job import TrainJob
    from benchmark.loop import run_window
    from benchmark.observations import Observations
    from benchmark.quantiles import (
        highest_supported_percentile, median, samples_beyond)

    traffic, config = cell.traffic, cell.config
    if traffic["steps_per_dispatch"] != 1:
        raise SystemExit("the loop dispatches one optimizer step at a time; "
                         f"{traffic['name']} asks for "
                         f"{traffic['steps_per_dispatch']}")
    workload = cells.load_builder(config).build(config)
    job = TrainJob(workload, config, traffic, args.seed)
    try:
        phase_done("host pool, loader, job")
        state, batch, first_losses, exchange_ok = (
            check_exchange_and_warm_up(job))
        phase_done("exchange check and warm-up steps")
        leaves = jax.tree_util.tree_leaves(state[0])
        n_params = sum(x.size for x in leaves)
        gradient_bytes = sum(x.nbytes for x in leaves)
        say(f"model: {config['name']} {n_params} parameters, "
            f"{workload.train_flops_per_sample} FLOPs per "
            f"{workload.sample_unit} forward+backward (analytic), batch "
            f"{job.batch_per_chip}/chip x {job.n_dev} chip(s)")
        checks = {
            "exchange_agrees_with_reference": exchange_ok,
            "first_loss_in_band": correctness.first_loss_in_band(
                first_losses[0], workload.expected_first_loss),
            "is_the_configuration":
                rehearse or n_params == config["parameters"],
        }
        digest = None
        if job.n_dev > 1:
            digest = correctness.make_replica_digest(job.mesh, "world")
            checks["replicas_equal_at_start"] = (
                correctness.replicas_bit_equal(digest(state[0])))
        compiled_text = None
        if args.trace:
            # the executable the warm-up steps built, asked about itself
            compiled = job.step.lower(*jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=a.sharding),
                (*state, batch))).compile()
            compiled_text = compiled.as_text()
            cost = compiled.cost_analysis() or {}
            say("cross-check: cost_analysis() counts "
                f"{cost.get('flops')} FLOPs for one step on one chip, the "
                "analytic figure is "
                f"{workload.train_flops_per_sample * job.batch_per_chip}")

        compiles, compile_s, hits = watch.snapshot()
        phase_done("step program asked about itself" if args.trace
                   else "last checks")
        setup_s = time.perf_counter() - _T_PROCESS_START
        state, window = run_window(
            job, state, fence_lag=traffic["fence_lag"],
            seconds=args.seconds)
        losses, attempted = list(window.losses), window.steps
        reduction = None
        if args.trace:
            state, traced, reduction = trace_window(
                job, state, cell, args.save_trace_extract)
            losses += traced.losses
            attempted += traced.steps
        compiled_in_window = watch.snapshot()[0] - compiles
        failed = correctness.count_not_finite(losses)
        checks["nothing_compiled_in_window"] = compiled_in_window == 0
        checks["every_loss_finite"] = failed == 0
        if job.n_dev > 1:
            spread, wrong = correctness.batch_is_spread(
                window.last_batch, jax.devices(), job.batch_per_chip)
            checks["one_batch_shard_per_chip"] = spread
            checks["replicas_bit_equal"] = correctness.replicas_bit_equal(
                digest(state[0]))
            for line in wrong:
                say(f"width: {line}")
    finally:
        job.close()

    from horovod_tpu.obs import metrics as program_metrics

    waited = program_metrics.snapshot().get("hvtpu_data_wait_seconds")
    say("program counter: hvtpu_data_wait_seconds "
        f"{waited and waited['values']}")
    intervals = window.step_intervals()
    say(f"window: {window.steps} steps in {window.seconds:.3f} s; step "
        f"interval median {1e3 * median(intervals):.3f} ms, longest "
        f"{1e3 * max(intervals):.3f} ms; {len(intervals)} intervals, "
        f"{samples_beyond(len(intervals), 95.0):.1f} of them beyond the "
        "95th percentile (highest percentile with ten beyond: "
        f"{highest_supported_percentile(len(intervals))}); losses "
        f"{window.losses[0]:.4f} -> {window.losses[-1]:.4f}; "
        f"{compiled_in_window} compilation(s) in the window; set-up "
        f"compiled {compiles} program(s), {hits} from the cache")
    say("host: share of the window the loop spent in " + ", ".join(
        f"{name} {100 * window.span_share(name):.2f} %"
        for name in window.spans))
    if reduction:
        # two sources, so no metric: the trace's own idle share is the
        # metric, and in a cell the input paces it overstates
        busy_ms, interval_ms = (reduction.busy_ms_per_step,
                                1e3 * median(intervals))
        say(f"cross-check: a step keeps a chip busy {busy_ms:.3f} ms "
            "(trace); at the untraced window's median interval of "
            f"{interval_ms:.3f} ms (host clock) that would leave it idle "
            f"{100 * (1 - busy_ms / interval_ms):.2f} % of the time")
    say("set-up: " + ", ".join(
        f"{name} {t - t_before:.1f} s"
        for (_, t_before), (name, t) in zip(_PHASES, _PHASES[1:])))
    say(f"checks: {checks}")

    obs = Observations(
        config=config, traffic=traffic, chips=cell.chips,
        device_kind=ident["kind"], window=window, setup_s=setup_s,
        samples_per_step_per_chip=(
            job.batch_per_chip * workload.samples_per_row),
        train_flops_per_sample=workload.train_flops_per_sample,
        compile_s=compile_s, cache_hits=hits,
        gradient_bytes=gradient_bytes,
        memory_peak_bytes=memory_peak_bytes(),
        compiled_text=compiled_text, trace=reduction)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry in getattr(cell, kind):
        reader = cells.load_metric(kind, entry["name"])
        try:
            value = reader.read(obs)
        except LookupError as e:
            if not rehearse:
                raise
            say(f"{entry['name']}: {e}")  # the CPU has no peaks
            value = None
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": reader.UNIT}
    if rehearse:
        absent = {e["name"] for e in getattr(cell, kind)} - set(metrics)
        say(f"{kind} metrics read: {sorted(metrics)}; nothing to read for "
            f"{sorted(absent)}")
        return None
    device = dict(ident, memory_peak_bytes=obs.memory_peak_bytes)
    line = {"correct": all(checks.values()), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if args.trace:
        if reduction is None:
            raise RuntimeError(
                "the profile holds no device plane with three step periods "
                "the profiler left alone")
        device.update(busy_s=reduction.busy_s, window_s=reduction.window_s)
        line["breakdown"] = {"device_ops": reduction.top_ops(10),
                             "idle_gaps": reduction.longest_gaps(5)}
    return line


def main() -> int:
    global _PREFIX
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a name under workloads in BENCHMARK.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace-extract", metavar="PATH",
                    help="with --trace 1, keep the events the reduction "
                         "read as gzipped JSON")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="toy size on virtual CPU devices; never a result")
    args = ap.parse_args()

    from benchmark import cells

    cell = cells.load_cell(args.workload, rehearse=args.rehearse_on_cpu)
    try:
        import horovod_tpu as hvt
    except ImportError as e:
        print(f"benchmark/run.py: the program under test is not in this "
              f"checkout ({e}). Nothing was measured.", file=sys.stderr)
        return 2
    if args.rehearse_on_cpu:
        _PREFIX = "REHEARSAL "
        from horovod_tpu.core.state import force_cpu_devices

        force_cpu_devices(cell.chips)

    from benchmark.compile_watch import CompileWatch

    cache_dir = hvt.enable_compile_cache()
    hvt.init()
    ident = device_identity()
    say(f"device: platform={ident['platform']} device_kind="
        f"{ident['kind']!r} count={ident['count']}; cell {cell.name} "
        f"({cell.chips} chip(s)), seed {args.seed}, {args.seconds} s, "
        f"trace {args.trace}; compile cache {cache_dir}")
    if not args.rehearse_on_cpu:
        require_chips(ident, cell.chips)
    phase_done("imports, hvt.init, first touch of the device")
    watch = CompileWatch()
    try:
        line = measure(cell, args, ident, watch, args.rehearse_on_cpu)
    finally:
        hvt.shutdown()
    if line is None:
        say("not a chip result")
        return 0
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
