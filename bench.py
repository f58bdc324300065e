#!/usr/bin/env python
"""Headline benchmark: ResNet-50 synthetic-ImageNet throughput, the same
measurement the reference ships (examples/pytorch/pytorch_synthetic_benchmark.py
/ examples/tensorflow2/tensorflow2_synthetic_benchmark.py — random data,
timed training steps, images/sec).

Runs data-parallel over every available device through the framework's
own DistributedOptimizer path (bucketed fused allreduce inside the
jitted step).  Prints exactly ONE JSON line:

    {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": R}

vs_baseline compares against NCCL-on-A100 images/sec/chip for the same
model/precision (~2500 img/s at bf16/AMP per BASELINE.json's north-star
"images/sec/chip parity with NCCL-on-A100").
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import horovod_tpu as hvt
from horovod_tpu.models import InceptionV3, ResNet50, ResNet101, VGG16
from horovod_tpu.obs import metrics as obs_metrics
from horovod_tpu.obs import stepprof as obs_stepprof

A100_BASELINE_IMG_PER_SEC_PER_CHIP = 2500.0

# The reference's README benchmark trio + the north-star model
# (docs/benchmarks.rst: Inception V3 / ResNet-101 / VGG-16; BASELINE
# north star: ResNet-50).
# (ctor, input_px, default_batch, takes_bn_axis, default_steps_per_call)
# vgg16 (138M params) scans fewer steps per dispatch to bound compile
# time; none of these defaults has been re-derived on the current
# machine (ROADMAP S1/S4).
MODELS = {
    "resnet50": (ResNet50, 224, 256, True, 32),
    "resnet101": (ResNet101, 224, 128, True, 32),
    "inception3": (InceptionV3, 299, 128, True, 32),
    "vgg16": (VGG16, 224, 64, False, 8),
}
MODEL = os.environ.get("HVTPU_BENCH_MODEL", "resnet50")
if MODEL not in MODELS:
    raise SystemExit(
        f"HVTPU_BENCH_MODEL={MODEL!r} unknown; choose from "
        f"{sorted(MODELS)}"
    )

BATCH_PER_CHIP = int(os.environ.get("HVTPU_BENCH_BATCH", "0")) \
    or MODELS[MODEL][2]
WARMUP = int(os.environ.get("HVTPU_BENCH_WARMUP", "2"))
ITERS = int(os.environ.get("HVTPU_BENCH_ITERS", "6"))
# Training steps fused into one device dispatch via lax.scan (amortizes
# host->device dispatch; ROADMAP S4 replaces it with a fresh batch per
# step).
STEPS_PER_CALL = int(os.environ.get("HVTPU_BENCH_STEPS_PER_CALL", "0")) \
    or MODELS[MODEL][4]


# Families the embedded snapshot must always carry so BENCH_* rounds
# stay comparable (tests/test_bench_guard.py enforces the schema):
# step accounting from this host loop, the eager data plane's byte and
# op counters, and the controller cycle histogram.
REQUIRED_METRIC_KEYS = (
    "hvtpu_optimizer_steps_total",
    "hvtpu_examples_total",
    "hvtpu_allreduce_total",
    "hvtpu_tensor_bytes_total",
    "hvtpu_wire_bytes_total",
    "hvtpu_controller_cycles_total",
    "hvtpu_controller_cycle_seconds",
    # integrity layer (PR 4): cross-rank mismatch diagnostics, the
    # coordinated non-finite guard, and the divergence audit — all 0
    # on a healthy run, which is exactly what the trajectory proves.
    "hvtpu_controller_mismatch_errors_total",
    "hvtpu_optimizer_nonfinite_skips_total",
    "hvtpu_audit_runs_total",
    "hvtpu_audit_divergences_total",
    # observability layer (PR 7): arrival-skew histogram — the report's
    # straggler signal; {count, sum} gives mean skew per collective.
    "hvtpu_collective_arrival_skew_seconds",
    # graceful preemption (PR 8): notice/drain counters and the
    # drain-commit latency histogram — 0 on a healthy bench run, and a
    # nonzero count here flags that the round absorbed a preemption.
    "hvtpu_preempt_notices_total",
    "hvtpu_elastic_drains_total",
    "hvtpu_drain_commit_seconds",
    # input pipeline (PR 9): per-batch input wait and delivery counters
    # from data/loader.py — the data-stall half of the straggler
    # decomposition; the report derives data_stall.stall_fraction from
    # the wait histogram against wall time.
    "hvtpu_data_wait_seconds",
    "hvtpu_data_batches_delivered_total",
    "hvtpu_data_samples_delivered_total",
    # overlap profiler (PR 12, obs/stepprof.py): measured per-step
    # exposed-communication time, the device-joined overlap fraction
    # (0 until a profile join runs), and measured MFU (0 until the
    # host loop provides cost_analysis FLOPs).
    "hvtpu_step_exposed_comm_seconds",
    "hvtpu_step_overlap_fraction",
    "hvtpu_mfu",
    # durable state plane (PR 15, core/durable.py): commit latency and
    # bytes written by the crash-consistent checkpoint protocol,
    # manifest-verification rejections (0 on a healthy run — nonzero
    # means a torn/corrupt snapshot was caught and skipped), and
    # restore-quorum rounds (one per elastic sync that consulted
    # peers before picking a restore point).
    "hvtpu_ckpt_commit_seconds",
    "hvtpu_ckpt_bytes_written_total",
    "hvtpu_ckpt_verify_failures_total",
    "hvtpu_ckpt_restore_quorum_rounds_total",
    # flight recorder + anomaly detection (PR 16, obs/flight.py,
    # obs/anomaly.py, fleet/health.py): ring appends prove the black
    # box was recording; incident count is 0 on a healthy run — a
    # nonzero value names a round that tripped a detector.  The fleet
    # gauges stay 0 outside an arbiter-run fleet (no _seconds suffix:
    # condense_metrics zero-fills gauges as scalars).
    "hvtpu_flight_events_total",
    "hvtpu_incidents_total",
    "hvtpu_fleet_job_step_rate",
    "hvtpu_fleet_job_incidents",
    # coordination-plane fault tolerance (PR 17, core/retry.py,
    # comm/stall.py): fencing-token rejections and fence exits are 0
    # on a healthy run — nonzero names a round where a superseded or
    # lease-expired writer was stopped; the suspect histogram counts
    # seconds peers held stall blame for a silent-but-leased rank
    # instead of declaring it dead.
    "hvtpu_kv_fenced_writes_total",
    "hvtpu_fence_exits_total",
    "hvtpu_partition_suspect_seconds",
    # zero-copy fusion buffers (PR 18, comm/packing.py,
    # eager/controller.py): which fused-allreduce path ran.  A steady
    # run shows zero_copy climbing and staged flat after warmup;
    # staged rising mid-run means the pack plan kept falling back
    # (mispredicts, shape churn, compression).
    "hvtpu_fusion_zero_copy_ops_total",
    "hvtpu_fusion_staged_copies_total",
    # fleet front door (PR 19, fleet/{intake,admission,placement}.py):
    # queue depth by tier and journal intake lag show the backlog a
    # submission storm builds and how fast the bounded-budget intake
    # drains it; admission rejections are 0 unless a tenant blew a
    # quota (or a spec was malformed); fragmentation is the measured
    # contiguity of the pool's free capacity on the host torus.
    "hvtpu_fleet_queue_depth",
    "hvtpu_fleet_intake_lag",
    "hvtpu_fleet_admission_rejections_total",
    "hvtpu_fleet_fragmentation",
    # wire-plane fault tolerance (PR 20, comm/wirefault.py): retries
    # and the consensus histogram are 0 on a healthy run — a nonzero
    # count names a round where a collective attempt was agreed dead
    # and reissued instead of restarting the job; link_health is the
    # worst per-peer degradation score (0 = every link clean) and
    # reroutes counts ring permutations taken around a sick link.
    "hvtpu_collective_retries_total",
    "hvtpu_collective_abort_consensus_seconds",
    "hvtpu_link_health",
    "hvtpu_ring_reroutes_total",
)


def condense_metrics(snap=None) -> dict:
    """Registry snapshot -> the compact form embedded in the bench JSON
    line: counters/gauges collapse to a scalar total across label sets,
    histograms to {count, sum}.  Families in REQUIRED_METRIC_KEYS are
    always present (0 when never touched) so BENCH_* trajectories keep
    a stable schema across rounds."""
    if snap is None:
        snap = obs_metrics.snapshot()
    out = {}
    for name, fam in snap.items():
        if fam["type"] == "histogram":
            cells = fam["values"].values()
            out[name] = {
                "count": sum(c["count"] for c in cells),
                "sum": round(sum(c["sum"] for c in cells), 6),
            }
        else:
            out[name] = sum(fam["values"].values())
    for name in REQUIRED_METRIC_KEYS:
        if name not in out:
            out[name] = (
                {"count": 0, "sum": 0.0} if name.endswith("_seconds")
                else 0)
    return out


def build_report(**fields) -> dict:
    """Assemble the ONE-JSON-line bench report.  Every report embeds
    the condensed registry snapshot under ``metrics`` so BENCH_*
    trajectories capture wire-bytes and cycle stats alongside img/s
    (schema enforced by tests/test_bench_guard.py)."""
    report = dict(fields)
    report["metrics"] = condense_metrics()
    # Straggler headline: mean cross-rank arrival skew per collective
    # (rank 0 observes the skew histogram; 0 collectives -> 0.0 mean so
    # the row is schema-stable even on 1-proc runs).
    skew = report["metrics"]["hvtpu_collective_arrival_skew_seconds"]
    report["arrival_skew"] = {
        "collectives": skew["count"],
        "mean_seconds": round(skew["sum"] / skew["count"], 6)
        if skew["count"] else 0.0,
    }
    # Input-stall headline: time the host loop blocked on the data
    # pipeline vs wall time.  Near-0 stall_fraction with nonzero
    # batches is the prefetch-overlap proof; null when the caller
    # passed no elapsed_seconds (schema-stable either way).
    wait = report["metrics"]["hvtpu_data_wait_seconds"]
    elapsed = fields.get("elapsed_seconds")
    report["data_stall"] = {
        "batches": wait["count"],
        "wait_seconds": round(wait["sum"], 6),
        "stall_fraction": round(wait["sum"] / elapsed, 6)
        if elapsed else None,
    }
    # Overlap headline (PR 12): per-step exposed-comm time from the
    # stepprof collector plus the measured overlap/MFU gauges.  The
    # gauges default to 0 (never joined / no FLOPs provided) and are
    # reported as null then, so a recorded 0.31 means "measured 0.31",
    # never "not measured".
    exposed = report["metrics"]["hvtpu_step_exposed_comm_seconds"]
    report["overlap"] = {
        "steps": exposed["count"],
        "exposed_comm_seconds": round(exposed["sum"], 6),
        "overlap_fraction":
            report["metrics"]["hvtpu_step_overlap_fraction"] or None,
        "mfu": report["metrics"]["hvtpu_mfu"] or None,
    }
    return report


def device_identity() -> dict:
    """The device this process runs on, as JAX reports it — every
    report names it, so a number can never be read as another
    machine's."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def require_tpu() -> dict:
    """The benchmark's metric is a device metric: refuse to time
    anything on another platform."""
    ident = device_identity()
    if ident["platform"] != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; found platform="
            f"{ident['platform']!r} ({ident['device_kind']}, "
            f"{ident['device_count']} device(s), JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}). Not timing anything.")
    return ident


def main():
    hvt.enable_compile_cache()
    hvt.init()  # before the first backend touch (multi-process rendezvous)
    ident = require_tpu()
    mesh = hvt.world_mesh()
    n_dev = hvt.num_devices()
    global_batch = BATCH_PER_CHIP * n_dev

    # bn_axis_name keeps the replicated batch_stats actually consistent
    # across devices (sync BatchNorm over the dp axis).
    ctor, px, _, takes_bn, _steps = MODELS[MODEL]
    kwargs = dict(num_classes=1000, dtype=jnp.bfloat16)
    if takes_bn:
        kwargs["bn_axis_name"] = "world" if n_dev > 1 else None
    model = ctor(**kwargs)
    rng = jax.random.PRNGKey(0)
    images = jax.random.normal(
        rng, (global_batch, px, px, 3), jnp.bfloat16
    )
    labels = jax.random.randint(rng, (global_batch,), 0, 1000)

    variables = model.init(rng, images[:2], train=True)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})

    # VGG (no BatchNorm) diverges at the 0.1 default; the reference's
    # synthetic benchmark uses SGD lr=0.01 — LR does not affect img/s.
    lr = 0.01 if MODEL == "vgg16" else 0.1
    tx = hvt.DistributedOptimizer(
        optax.sgd(lr, momentum=0.9), axis_name="world"
    )
    opt_state = tx.init(params)

    def loss_fn(params, batch_stats, x, y):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            x, train=True, mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()
        return loss, mutated.get("batch_stats", {})

    def one_step(params, batch_stats, opt_state, x, y):
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params, batch_stats, x, y)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, jax.lax.pmean(loss, "world")

    def body(params, batch_stats, opt_state, x, y):
        # STEPS_PER_CALL optimizer steps in one dispatch (lax.scan keeps
        # it one compiled program; XLA reuses buffers across steps).
        def scan_step(carry, _):
            params, batch_stats, opt_state = carry
            params, batch_stats, opt_state, loss = one_step(
                params, batch_stats, opt_state, x, y
            )
            return (params, batch_stats, opt_state), loss

        (params, batch_stats, opt_state), losses = jax.lax.scan(
            scan_step, (params, batch_stats, opt_state), None,
            length=STEPS_PER_CALL,
        )
        return params, batch_stats, opt_state, losses[-1]

    step = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(), P(), P("world"), P("world")),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1, 2),
    )

    def fence(loss):
        # the timing fence: a host read of a value the dispatch produced
        return float(loss)

    # Feed dispatches through the elastic input pipeline so the bench
    # measures (and reports, via data_stall) the prefetch overlap: the
    # loader's thread places batch k+1 on the mesh while dispatch k
    # runs.  shuffle=False over exactly one global batch keeps the fed
    # tensors byte-identical to the direct arrays, so compute — and the
    # regression floors — are unaffected.  HVTPU_BENCH_DATA_LOADER=0
    # restores the direct path.
    loader = None
    if os.environ.get("HVTPU_BENCH_DATA_LOADER", "1") != "0" \
            and hvt.size() == 1:
        # single-controller path only: in a multi-process bench each
        # process already holds its own per-process global batch, which
        # the loader's world-sharding would re-split
        from jax.sharding import NamedSharding

        from horovod_tpu import data as hvt_data

        sharding = NamedSharding(mesh, P("world"))

        def place(batch):
            return {"x": jax.device_put(batch["x"], sharding),
                    "y": jax.device_put(batch["y"], sharding)}

        loader = hvt_data.ElasticDataLoader(
            hvt_data.ArraySource(
                {"x": np.asarray(images), "y": np.asarray(labels)}),
            batch_size=global_batch, shuffle=False, device_put=False,
            transform=place, name="bench")
        batches = loader.stream()

        def next_batch():
            b = next(batches)
            return b["x"], b["y"]
    else:
        def next_batch():
            return images, labels

    # Shape specs for the post-run AOT lowering (measured-MFU FLOPs):
    # captured before the loop because donated buffers are deleted by
    # then; lowering from ShapeDtypeStructs never touches data.
    aval_specs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype),
        (params, batch_stats, opt_state, images, labels))

    loss = None
    for _ in range(WARMUP):
        x, y = next_batch()
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, x, y
        )
    if loss is not None:
        fence(loss)

    t0 = time.perf_counter()
    for _ in range(ITERS):
        x, y = next_batch()
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, x, y
        )
        # jit path: the traced update can't count itself, so the host
        # loop reports steps/examples per dispatch (obs/metrics.py).
        obs_metrics.note_step(examples=global_batch * STEPS_PER_CALL,
                              steps=STEPS_PER_CALL)
    final_loss = fence(loss)
    elapsed = time.perf_counter() - t0

    # Optional device-profile capture of one extra (untimed) dispatch:
    # joins the XLA op timeline against the collective windows and
    # publishes the measured overlap fraction (hvtpu_step_overlap_
    # fraction).  HVTPU_BENCH_PROFILE names the capture dir.
    overlap_fraction = None
    prof_dir = os.environ.get("HVTPU_BENCH_PROFILE", "")
    if prof_dir:
        with obs_stepprof.profile_window(prof_dir) as join:
            x, y = next_batch()
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, x, y
            )
            fence(loss)
        overlap_fraction = join.get("overlap_fraction")
    if loader is not None:
        loader.close()

    if not np.isfinite(final_loss):
        raise RuntimeError(f"non-finite loss {final_loss}; benchmark invalid")

    img_per_sec = global_batch * ITERS * STEPS_PER_CALL / elapsed
    img_per_sec_per_chip = img_per_sec / n_dev
    # MFU context: approx train FLOPs/image (fwd+bwd) per model against
    # the device's bf16 peak (resnet50 figure from XLA cost analysis:
    # 6.08e12 flops at batch 256; others are standard 3x-forward
    # estimates).
    peak = obs_stepprof.peak_flops(ident["device_kind"])
    flops_per_img = {"resnet50": 23.8e9, "resnet101": 47e9,
                     "inception3": 34e9, "vgg16": 93e9}[MODEL]
    mfu = img_per_sec_per_chip * flops_per_img / peak
    # Measured MFU (PR 12): the FLOPs numerator comes from the compiled
    # program's own cost model — jit(...).lower().compile().
    # cost_analysis() — instead of the hand table above; cost_analysis
    # counts the per-device program, so dividing by per-dispatch steps
    # and per-chip batch yields FLOPs/image/chip directly.  mfu_est is
    # retained for comparison; a backend without cost analysis reports
    # null rather than guessing.
    mfu_measured = None
    flops_call = obs_stepprof.measured_flops(
        step.lower(*aval_specs).compile())
    if flops_call:
        flops_img = flops_call / (STEPS_PER_CALL * BATCH_PER_CHIP)
        mfu_measured = round(img_per_sec_per_chip * flops_img / peak, 4)
        obs_stepprof.set_step_flops(flops_call / STEPS_PER_CALL)

    exposed = condense_metrics()["hvtpu_step_exposed_comm_seconds"]
    exposed_comm_ms = (
        round(exposed["sum"] / exposed["count"] * 1e3, 3)
        if exposed["count"] else 0.0)
    # vs_baseline is defined against the north-star ResNet-50 A100
    # parity bar; other models report null (no published per-chip bar)
    vs_baseline = (
        round(img_per_sec_per_chip / A100_BASELINE_IMG_PER_SEC_PER_CHIP, 4)
        if MODEL == "resnet50" else None
    )
    print(
        json.dumps(
            build_report(
                metric=(
                    f"{MODEL}_synthetic_bf16_images_per_sec_per_chip"
                ),
                value=round(img_per_sec_per_chip, 1),
                unit="images/sec/chip",
                vs_baseline=vs_baseline,
                **ident,
                model=MODEL,
                batch_per_chip=BATCH_PER_CHIP,
                mfu_est=round(mfu, 4),
                mfu_measured=mfu_measured,
                overlap_fraction=overlap_fraction,
                exposed_comm_ms=exposed_comm_ms,
                elapsed_seconds=round(elapsed, 3),
                notes=f"{STEPS_PER_CALL} steps/dispatch via lax.scan",
            )
        )
    )


if __name__ == "__main__":
    main()
