"""Eager-path micro-benchmark: allreduce GB/s vs tensor size, fused vs
unfused, through the torch frontend adapter.

The reference measures its eager path with
examples/pytorch/pytorch_synthetic_benchmark.py; this is the
collective-level equivalent.  Runs single-process by default (adapter +
engine dispatch overheads dominate — the quantity of interest for the
zero-copy work); pass --np 2+ to run the same sweep across real worker
processes via the runner.

Prints one JSON line per configuration:
  {"bench": "eager_allreduce", "nbytes": ..., "mode": "sync|async_fused",
   "gbps": ..., "us_per_op": ...}
"""

import argparse
import json
import time

# Schema of the torch DistributedOptimizer end-to-end step-time row
# (enforced by tests/test_bench_guard.py so future rounds stay
# comparable): one row per run, produced by build_torch_step_row.
TORCH_STEP_KEYS = (
    "bench", "np", "param_tensors", "param_bytes", "ms_per_step",
    "steps_per_s",
)

# Schedule-prediction columns carried by every controller-driven row
# since round 7 (enforced by tests/test_bench_guard.py): the fraction
# of cycles in the timed window that skipped the KV round trip, and
# the mispredict count/rate — a steady-state row with prediction
# healthy shows predicted_fraction near 1 and zero mispredicts.
# Round 8 adds zero_copy_fraction: the share of fused-allreduce ops in
# the window that rode the enqueue-time-packed exchange buffer instead
# of the drain-time staged copy (None when the window fused nothing).
PREDICT_ROW_KEYS = ("predicted_fraction", "mispredicts",
                    "mispredict_rate", "zero_copy_fraction")


def snapshot_predict_counters():
    """Controller cycle/prediction/fusion-path counter values for THIS
    process (rank 0 when run under the runner: per_rank[0] is what
    lands in the report)."""
    from horovod_tpu.obs import metrics as obs_metrics

    return {
        "cycles": obs_metrics.counter(
            "hvtpu_controller_cycles_total").value(),
        "predicted": obs_metrics.counter(
            "hvtpu_controller_predicted_cycles_total").value(),
        "mispredicts": obs_metrics.counter(
            "hvtpu_controller_mispredicts_total").value(),
        "zero_copy": obs_metrics.counter(
            "hvtpu_fusion_zero_copy_ops_total").value(),
        "staged": obs_metrics.counter(
            "hvtpu_fusion_staged_copies_total").value(),
    }


def build_predict_stats(before, after):
    """The PREDICT_ROW_KEYS columns from two snapshot_predict_counters
    readings bracketing a timed window.  Fractions are None when the
    window ran no controller cycles (e.g. a 1-proc dispatch bench
    short-circuiting the wire).  The fusion-path keys default to 0 so
    older 3-key snapshots (and the schema test's fixtures) still
    build."""
    cycles = after["cycles"] - before["cycles"]
    predicted = after["predicted"] - before["predicted"]
    mis = after["mispredicts"] - before["mispredicts"]
    zc = after.get("zero_copy", 0) - before.get("zero_copy", 0)
    staged = after.get("staged", 0) - before.get("staged", 0)
    return {
        "predicted_fraction": (round(predicted / cycles, 3)
                               if cycles else None),
        "mispredicts": int(mis),
        "mispredict_rate": (round(mis / cycles, 4)
                            if cycles else None),
        "zero_copy_fraction": (round(zc / (zc + staged), 3)
                               if (zc + staged) else None),
    }


def build_torch_step_row(np_, param_tensors, param_bytes, ms_per_step):
    """One JSON row for the torch DistributedOptimizer step-time bench
    (bench == "eager_torch_step")."""
    return {
        "bench": "eager_torch_step",
        "np": int(np_),
        "param_tensors": int(param_tensors),
        "param_bytes": int(param_bytes),
        "ms_per_step": round(float(ms_per_step), 3),
        "steps_per_s": (round(1000.0 / ms_per_step, 3)
                        if ms_per_step > 0 else 0.0),
    }


def run_torch_step(sizes_mb, iters, warmup=3):
    """End-to-end torch ``DistributedOptimizer`` step time: forward +
    backward +
    per-parameter async allreduce through the eager controller +
    step(), on a model with the many-same-shape-buckets structure real
    training produces.  ``sizes_mb`` selects the total gradient
    payload; run with --np 4 for the headline row."""
    import torch

    import horovod_tpu.torch as hvd

    hvd.init()
    results = []
    for mb in sizes_mb:
        # 8 equal square layers -> 16 parameter tensors (8 weights +
        # 8 biases): one async allreduce per tensor per step, the
        # optimizer bucket pattern the controller's steady-state
        # bypass + burst gate exist for.
        n_layers = 8
        dim = max(16, int((mb * (1 << 20) / 4 / n_layers) ** 0.5))
        torch.manual_seed(0)  # identical init on every rank
        model = torch.nn.Sequential(*[
            torch.nn.Linear(dim, dim) for _ in range(n_layers)
        ])
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=1e-3),
            named_parameters=model.named_parameters(),
        )
        loss_fn = torch.nn.MSELoss()
        x = torch.randn(32, dim)
        y = torch.randn(32, dim)

        def step():
            opt.zero_grad()
            loss_fn(model(x), y).backward()
            opt.step()

        for _ in range(warmup):
            step()
        snap = snapshot_predict_counters()
        t0 = time.perf_counter()
        for _ in range(iters):
            step()
        dt = (time.perf_counter() - t0) / iters
        params = list(model.parameters())
        row = build_torch_step_row(
            hvd.size(), len(params),
            sum(p.numel() * 4 for p in params), dt * 1e3,
        )
        row["dim"] = dim
        row.update(build_predict_stats(snap, snapshot_predict_counters()))
        results.append(row)
    return results


def run_sweep(sizes_mb, iters, warmup=3):
    import numpy as np
    import torch

    import horovod_tpu.torch as hvd

    hvd.init()
    results = []
    for mb in sizes_mb:
        n = int(mb * (1 << 20) / 4)
        t = torch.ones(n, dtype=torch.float32)

        # sync path
        for _ in range(warmup):
            hvd.allreduce(t, op=hvd.Sum, name=f"warm.{n}")
        t0 = time.perf_counter()
        for i in range(iters):
            hvd.allreduce(t, op=hvd.Sum, name=f"sync.{n}")
        dt = (time.perf_counter() - t0) / iters
        results.append({
            "bench": "eager_allreduce", "nbytes": n * 4, "mode": "sync",
            "gbps": n * 4 / dt / 1e9, "us_per_op": dt * 1e6,
        })

        # async fused path: 8 tensors of n/8 through the controller
        k = 8
        chunk = torch.ones(max(n // k, 1), dtype=torch.float32)
        # warm up on the SAME names the timed loop uses: the row
        # measures the steady state, and since round 7 that includes
        # the predictor (first occurrence of a name set is observed,
        # not predicted — distinct warmup names would bill that
        # verification to the timed window)
        for _ in range(2 * warmup):
            hs = [hvd.allreduce_async(chunk, op=hvd.Sum,
                                      name=f"as.{n}.{i}")
                  for i in range(k)]
            for h in hs:
                hvd.synchronize(h)
        snap = snapshot_predict_counters()
        t0 = time.perf_counter()
        for it in range(iters):
            hs = [hvd.allreduce_async(chunk, op=hvd.Sum,
                                      name=f"as.{n}.{i}")
                  for i in range(k)]
            for h in hs:
                hvd.synchronize(h)
        dt = (time.perf_counter() - t0) / iters
        total = chunk.numel() * 4 * k
        results.append({
            "bench": "eager_allreduce", "nbytes": total,
            "mode": "async_fused", "gbps": total / dt / 1e9,
            "us_per_op": dt * 1e6 / k,
            **build_predict_stats(snap, snapshot_predict_counters()),
        })

        # pipelined async: iteration k+1's batch is enqueued BEFORE
        # iteration k's handles synchronize (depth-2 software
        # pipeline), so batch k+1's negotiation/KV exchange overlaps
        # batch k's data-plane execution on the controller's executor
        # thread — the overlap the async API exists for (a training
        # step's early grads negotiate while later layers' backward
        # still runs).  Two alternating name sets keep pending names
        # unique; both are steady-state cache hits after warmup.
        def batch(it):
            return [hvd.allreduce_async(chunk, op=hvd.Sum,
                                        name=f"ap.{n}.{it % 2}.{i}")
                    for i in range(k)]
        for it in range(2 * warmup):
            for h in batch(it):
                hvd.synchronize(h)
        snap = snapshot_predict_counters()
        t0 = time.perf_counter()
        prev = None
        for it in range(iters):
            hs = batch(it)
            if prev is not None:
                for h in prev:
                    hvd.synchronize(h)
            prev = hs
        for h in prev:
            hvd.synchronize(h)
        dt = (time.perf_counter() - t0) / iters
        results.append({
            "bench": "eager_allreduce", "nbytes": total,
            "mode": "async_fused_pipe", "gbps": total / dt / 1e9,
            "us_per_op": dt * 1e6 / k,
            **build_predict_stats(snap, snapshot_predict_counters()),
        })
    return results


def run_compression_ab(sizes_mb, iters, warmup=3):
    """Compression A/B on the sync eager wire (makes
    fp16's '~2x on comm-bound models' claim measurable).  Runs
    per-rank inside real worker processes (P>=2, CPU gloo — the wire
    is actual cross-process traffic); reports GB/s of PAYLOAD moved per
    compression mode, so the speedup column is the wire shrink made
    visible end-to-end (compress + smaller exchange + decompress)."""
    import numpy as np
    import jax.numpy as jnp

    import horovod_tpu as hvt
    from horovod_tpu.comm.compression import Compression

    hvt.init()
    modes = [("none", Compression.none), ("fp16", Compression.fp16),
             ("bf16", Compression.bf16), ("int8", Compression.int8)]
    results = []
    for mb in sizes_mb:
        n = int(mb * (1 << 20) / 4)
        x = jnp.ones((n,), jnp.float32)
        base = None
        for name, comp in modes:
            def op():
                return np.asarray(
                    hvt.allreduce(x, op=hvt.Sum, compression=comp,
                                  name=f"ab.{name}.{n}"))
            for _ in range(warmup):
                op()
            t0 = time.perf_counter()
            for _ in range(iters):
                op()
            dt = (time.perf_counter() - t0) / iters
            gbps = n * 4 / dt / 1e9
            if name == "none":
                base = gbps
            results.append({
                "bench": "eager_allreduce_compression",
                "nbytes": n * 4, "compression": name,
                "payload_gbps": round(gbps, 3),
                "us_per_op": round(dt * 1e6, 1),
                "speedup_vs_none": round(gbps / base, 3),
            })
    hvt.shutdown()
    return results


def run_tf_graph_sweep(sizes_mb, iters, warmup=3):
    """tf.py_function collective overhead:
    the graph-mode TF frontend routes collectives through
    tf.py_function; this measures eager vs traced dispatch so the
    round-trip cost is a tracked number, not folklore."""
    import numpy as np
    import tensorflow as tf

    import horovod_tpu.tensorflow as hvd

    hvd.init()
    results = []
    for mb in sizes_mb:
        n = int(mb * (1 << 20) / 4)
        t = tf.ones((n,), tf.float32)

        for mode in ("eager", "graph"):
            if mode == "graph":
                @tf.function
                def red(x):
                    return hvd.allreduce(x, op=hvd.Sum)
                fn = red
            else:
                def fn(x):
                    return hvd.allreduce(x, op=hvd.Sum)
            for _ in range(warmup):
                fn(t)
            t0 = time.perf_counter()
            for _ in range(iters):
                fn(t)
            dt = (time.perf_counter() - t0) / iters
            results.append({
                "bench": "eager_allreduce_tf", "nbytes": n * 4,
                "mode": mode, "gbps": n * 4 / dt / 1e9,
                "us_per_op": dt * 1e6,
            })
    return results


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--sizes-mb", default="0.25,1,4,16,64")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--np", type=int, default=1,
                   help="worker processes (1 = in-process)")
    p.add_argument("--cpu-devices", type=int, default=None,
                   help="force the CPU platform with this many devices "
                        "per process (default: the machine's own "
                        "accelerator — one chip per process at --np>1)")
    p.add_argument("--tf", action="store_true",
                   help="run the TF frontend sweep (eager vs "
                        "tf.function/py_function dispatch)")
    p.add_argument("--compression-ab", action="store_true",
                   help="A/B the sync wire across compression modes "
                        "(use with --np 4)")
    p.add_argument("--torch-step", action="store_true",
                   help="end-to-end torch DistributedOptimizer step "
                        "time (use with --np 4)")
    args = p.parse_args()
    sizes = [float(s) for s in args.sizes_mb.split(",")]

    import horovod_tpu as hvt

    hvt.enable_compile_cache()
    sweep = (run_torch_step if args.torch_step
             else run_compression_ab if args.compression_ab
             else run_tf_graph_sweep if args.tf else run_sweep)
    if args.np == 1:
        if args.cpu_devices:
            from horovod_tpu.core.state import force_cpu_devices

            force_cpu_devices(args.cpu_devices)
        results = sweep(sizes, args.iters)
    else:
        from horovod_tpu.core import retry as core_retry
        from horovod_tpu.runner import run as hvt_run

        # np>1 on localhost occasionally trips the jaxlib/gloo CPU
        # teardown race (a rank SIGSEGVs; docs/robustness.md): retry
        # via the named policy, classifying the crash exit too.
        policy = core_retry.gloo_teardown_policy()
        per_rank = core_retry.call(
            core_retry.RetryPolicy(
                name=policy.name, max_attempts=policy.max_attempts,
                base_delay_s=policy.base_delay_s,
                retryable=lambda e: (core_retry.is_gloo_infra_error(str(e))
                                     or "-11" in str(e)),
            ),
            hvt_run, sweep,
            args=(sizes, args.iters), np=args.np,
            cpu_devices=args.cpu_devices, timeout=1800.0,
        )
        results = per_rank[0]
        for r in results:
            r["np"] = args.np
    for r in results:
        print(json.dumps(r))


if __name__ == "__main__":
    main()
