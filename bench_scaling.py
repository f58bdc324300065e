"""Scaling harness: measurement + the defended 256-chip projection.

The reference's headline claim is '~90% scaling efficiency at 128
GPUs' (README.rst Benchmarks / docs/benchmarks.rst); this repo's north
star is >=90% linear on a v5e-256 pod (BASELINE.md).  One chip cannot
measure that, so this harness defends the claim three ways:

  --mode sweep          throughput vs device count on a virtual CPU
                        mesh (mechanics only: virtual devices share one
                        core pool, so efficiency ~ 1/N by construction;
                        on a pod the same code measures real ICI).
  --mode coordination   MEASURED per-op coordination cost vs P over
                        real worker processes: sync eager collectives
                        (including the stall-watchdog rendezvous) and
                        the async controller cycle.  These costs bound
                        the *eager* path; the jitted DP step has no
                        per-step coordination at all (XLA's schedule is
                        static), which is the structural argument.
  --mode project        the analytic v5e-256 projection: measured
                        single-chip step times (a deleted record of a
                        machine that is gone; see MEASURED below)
                        + gradient bytes vs ICI ring bandwidth with an
                        overlap budget, every assumption stated in the
                        output.
  --mode all            run coordination + project (+ sweep unless
                        --skip-sweep) and write BENCH_SCALING.json.

Methodology matches docs/benchmarks.rst (synthetic data, images/sec at
N over N x images/sec at 1); the projection model is the standard ring
allreduce cost 2*S*(N-1)/N bytes/chip (scaling-book recipe) against
the round-3 profiled step.
"""

import argparse
import json
import time

# ---------------------------------------------------------------------------
# single-chip inputs and public hardware constants — every number the
# projection uses, in one visible table.  The img/s column was recorded
# by the driver (resnet50) and by earlier builders (the rest) on a v5e
# behind a remote-chip plug-in that no longer exists; the record files
# were deleted with it and the figures have NOT been re-measured on the
# current machine, so the projection is exactly that (ROADMAP S2).
# ---------------------------------------------------------------------------

MEASURED = {
    # model: (params_millions, batch_per_chip, img_per_sec single chip)
    "resnet50": (25.56, 256, 2631.9),
    "resnet101": (44.55, 128, 1871.5),
    "inception3": (23.83, 128, 2132.8),
    "vgg16": (138.36, 64, 1076.4),
}

ASSUMPTIONS = {
    "wire_bytes_per_param": 2,          # bf16 gradient wire
    "ici_per_chip_gbps": 1600,          # v5e public spec: 1,600 Gbps ICI/chip
    "ici_allreduce_usable_fraction": 0.5,   # one direction of the torus
    #   links carries the ring's payload flow; 0.5 of aggregate is the
    #   conservative usable share (2D-torus multi-ring recovers more)
    "ici_derate_case": 0.125,           # pessimistic case: 4x worse than
    #   the usable-fraction estimate (200 GB/s -> 25 GB/s)
    "overlap_budget_fraction": 0.5,     # allreduce overlaps backprop;
    #   half the step is a conservative overlappable window (the
    #   reference's pipelined fusion cycle achieves its 90% with this)
    "v5e_slice_note": "v5e-256 (16x16 torus) is ONE ICI domain; DCN "
                      "enters only across slices (>256 chips), where "
                      "hierarchical allreduce reduces the cross-slice "
                      "payload to S/256 per chip — negligible.",
}


def project(ns=(8, 32, 256)):
    """Predicted DP scaling efficiency on v5e from the ring-allreduce
    cost model: eff(N) = t_step / (t_step + max(0, t_ar - overlap))."""
    a = ASSUMPTIONS
    bw_base = a["ici_per_chip_gbps"] / 8 * a["ici_allreduce_usable_fraction"]
    bw_worst = a["ici_per_chip_gbps"] / 8 * a["ici_derate_case"]
    out = []
    for model, (mparams, batch, ips) in MEASURED.items():
        t_step = batch / ips  # seconds
        s_bytes = mparams * 1e6 * a["wire_bytes_per_param"]
        overlap = a["overlap_budget_fraction"] * t_step
        for label, bw in (("base", bw_base), ("derate4x", bw_worst)):
            for n in ns:
                t_ar = 2 * s_bytes * (n - 1) / n / (bw * 1e9)
                exposed = max(0.0, t_ar - overlap)
                eff = t_step / (t_step + exposed)
                eff_noov = t_step / (t_step + t_ar)
                out.append({
                    "model": model, "chips": n, "bw_case": label,
                    "bw_GBps_per_chip": round(bw, 1),
                    "t_step_ms": round(t_step * 1e3, 2),
                    "t_allreduce_ms": round(t_ar * 1e3, 2),
                    "predicted_efficiency": round(eff, 4),
                    "predicted_efficiency_no_overlap": round(eff_noov, 4),
                })
    return out


# ---------------------------------------------------------------------------
# coordination cost vs P (REAL processes over the launcher)
# ---------------------------------------------------------------------------

def _coordination_body(iters):
    """Per-rank measurement body (runs in a launcher worker)."""
    import numpy as np
    import jax.numpy as jnp

    import horovod_tpu as hvt

    hvt.init()

    def timed(fn, reps):
        fn()  # warm (compile + cache)
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) / reps * 1e3  # ms

    small = jnp.ones((1024,), jnp.float32)          # 4 KB
    big = jnp.ones((1024 * 1024,), jnp.float32)     # 4 MB

    res = {
        "sync_allreduce_4KB_ms": timed(
            lambda: np.asarray(hvt.allreduce(small, op=hvt.Sum)), iters),
        "sync_allreduce_4MB_ms": timed(
            lambda: np.asarray(hvt.allreduce(big, op=hvt.Sum)), iters),
        "async_cycle_4KB_ms": timed(
            lambda: hvt.synchronize(hvt.allreduce_async(small, op=hvt.Sum)),
            iters),
    }
    hvt.shutdown()
    return res


def coordination(iters=30, ps=(1, 2, 4, 8), cpu_devices=1):
    """Mean per-op latency vs P: the coordination floor of the eager
    path (KV rendezvous + collective + dispatch).  The jit path
    carries none of this — coordination there is compile-time.
    ``cpu_devices=None`` runs on the machine's accelerator, one chip
    per process (the launcher refuses a P the host has no chips for)."""
    from horovod_tpu.runner import run

    rows = []
    for p in ps:
        results = run(_coordination_body, args=(iters,), np=p,
                      cpu_devices=cpu_devices, timeout=900.0)
        agg = {k: round(max(r[k] for r in results), 3)
               for k in results[0]}
        rows.append({"processes": p, **agg})
    # stall-watchdog cost isolated at P=4: the default rows above run
    # the amortized mode; compare against the round-4 strict per-op
    # rendezvous and against checking disabled (the amortized target:
    # within noise of disabled)
    for label, env in (
            ("amortized", {}),
            ("strict", {"HVTPU_STALL_CHECK_MODE": "strict"}),
            ("disabled", {"HVTPU_STALL_CHECK_DISABLE": "1"})):
        results = run(_coordination_body, args=(iters,), np=4,
                      cpu_devices=cpu_devices, env=env or None,
                      timeout=900.0)
        rows.append({
            "processes": 4, "stall_check": label,
            **{k: round(max(r[k] for r in results), 3)
               for k in results[0]},
        })
    return rows


# ---------------------------------------------------------------------------
# the virtual-mesh sweep (round-3 harness, unchanged mechanics)
# ---------------------------------------------------------------------------

def sweep(args):
    import jax

    if args.platform == "cpu":
        from horovod_tpu.core.state import force_cpu_devices

        force_cpu_devices(args.devices)

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    import horovod_tpu as hvt

    hvt.init()
    all_devs = jax.devices()

    if args.model == "mlp":
        from horovod_tpu.models.mlp import MLP

        model = MLP(features=(1024, 1024, 256), num_classes=100)
        x_shape = (784,)
    else:
        from horovod_tpu.models import ResNet18

        model = ResNet18(num_classes=100, dtype=jnp.bfloat16)
        x_shape = (64, 64, 3)

    rng = jax.random.PRNGKey(0)

    def throughput(devs):
        d = len(devs)
        mesh = Mesh(np.array(devs), ("dp",))
        gb = args.batch_per_device * d
        x = jax.random.normal(rng, (gb,) + x_shape,
                              jnp.bfloat16 if args.model == "resnet18"
                              else jnp.float32)
        y = jax.random.randint(rng, (gb,), 0, 100)
        variables = model.init(rng, x[:2]) if args.model == "mlp" else \
            model.init(rng, x[:2], train=True)

        tx = hvt.DistributedOptimizer(optax.sgd(0.1), axis_name="dp")
        params = variables["params"]
        extra = {k: v for k, v in variables.items() if k != "params"}
        opt_state = tx.init(params)

        def loss_fn(params, x, y):
            if extra:
                logits, _ = model.apply(
                    {"params": params, **extra}, x, train=True,
                    mutable=list(extra),
                )
            else:
                logits = model.apply({"params": params}, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        def body(params, opt_state, x, y):
            loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
            updates, opt_state = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state,
                    jax.lax.pmean(loss, "dp"))

        step = jax.jit(
            jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(), P("dp"), P("dp")),
                out_specs=(P(), P(), P()),
                check_vma=False,
            ),
            donate_argnums=(0, 1),
        )
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, x, y)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(args.iters):
            params, opt_state, loss = step(params, opt_state, x, y)
        float(loss)
        dt = time.perf_counter() - t0
        return gb * args.iters / dt

    results = []
    base = None
    d = 1
    while d <= len(all_devs):
        ips = throughput(all_devs[:d])
        if base is None:
            base = ips
        eff = ips / (base * d)
        results.append({
            "bench": "scaling", "model": args.model, "devices": d,
            "img_per_sec": round(ips, 1),
            "efficiency_vs_linear": round(eff, 4),
        })
        d *= 2
    return results


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--mode", default="sweep",
                   choices=["sweep", "coordination", "project", "all"])
    p.add_argument("--platform", default="cpu", choices=["cpu", "tpu"])
    p.add_argument("--devices", type=int, default=8,
                   help="virtual device count for --platform cpu")
    p.add_argument("--batch-per-device", type=int, default=64)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--model", default="mlp", choices=["mlp", "resnet18"])
    p.add_argument("--skip-sweep", action="store_true")
    p.add_argument("--out", default="BENCH_SCALING.json",
                   help="output file for --mode all")
    args = p.parse_args()

    import horovod_tpu as hvt

    hvt.enable_compile_cache()
    cpu_devices = 1 if args.platform == "cpu" else None
    if args.mode == "sweep":
        for r in sweep(args):
            print(json.dumps(r))
        return
    if args.mode == "coordination":
        for r in coordination(iters=args.iters, cpu_devices=cpu_devices):
            print(json.dumps(r))
        return
    if args.mode == "project":
        for r in project():
            print(json.dumps(r))
        return

    # --mode all: assemble BENCH_SCALING.json
    doc = {
        "bench": "scaling_sweep",
        "recorded": "round 4",
        "north_star": "≥90% linear DP scaling on v5e-256 (BASELINE.md)",
        "verdict": None,  # filled below
        "projection_assumptions": ASSUMPTIONS,
        "projection": project(),
        "coordination_vs_P": coordination(iters=args.iters,
                                          cpu_devices=cpu_devices),
    }
    if not args.skip_sweep:
        doc["virtual_mesh_sweep_note"] = (
            "Mechanics record only: virtual CPU devices share one "
            "physical core pool, so efficiency ~ 1/N by construction; "
            "on a pod the sweep runs unmodified for real ICI numbers.")
        doc["virtual_mesh_sweep"] = sweep(args)
    # verdict derives every number from the projection rows so it can
    # never contradict (or outlive) its own table
    def cell(model, case, key="predicted_efficiency_no_overlap"):
        return next(r[key] for r in doc["projection"]
                    if r["chips"] == 256 and r["model"] == model
                    and r["bw_case"] == case)

    r50_worst = cell("resnet50", "derate4x")
    r50_base = cell("resnet50", "base")
    r50_overlap = cell("resnet50", "derate4x", "predicted_efficiency")
    vgg_worst = cell("vgg16", "derate4x")
    s_mb = MEASURED["resnet50"][0] * ASSUMPTIONS["wire_bytes_per_param"]
    t_ms = next(r["t_step_ms"] for r in doc["projection"]
                if r["model"] == "resnet50")
    ar_ms = [r["t_allreduce_ms"] for r in doc["projection"]
             if r["chips"] == 256 and r["model"] == "resnet50"]
    doc["verdict"] = (
        f"ResNet-50 predicted efficiency at 256 chips: {r50_worst:.3f} "
        "with ZERO overlap credit AND a 4x ICI bandwidth derate (the "
        f"worst modeled case; {r50_base:.3f} at base bandwidth, "
        f"{r50_overlap:.2f} with the stated overlap budget) — the ≥90% "
        "north star holds with margin because the jitted DP step "
        "carries no per-step coordination and the bf16 gradient "
        f"allreduce ({s_mb:.0f} MB/chip) is "
        f"{min(ar_ms):.1f}-{max(ar_ms):.1f} ms against a {t_ms:.0f} ms "
        f"step. The comm-bound outlier is VGG-16 ({vgg_worst:.2f} in "
        "the same worst case), matching the reference's own ~68% claim "
        "shape. See docs/benchmarks.md §Scaling.")
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({"wrote": args.out, "verdict": doc["verdict"]}))


if __name__ == "__main__":
    main()
