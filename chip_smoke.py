#!/usr/bin/env python3
"""The quickest proof that hvtpu's main path still starts on the chip.

    python3 chip_smoke.py

drives, once and through the public API only, the path a user pays for
— ``hvt.init()`` -> ``hvt.world_mesh()`` -> ``hvt.DistributedOptimizer``
inside ``jax.jit(jax.shard_map(...))`` fed by
``hvt.data.ElasticDataLoader`` — on ResNet-50 at full width over every
local chip, then the Pallas kernels under ``ops/`` compiled by Mosaic
against their XLA twins, the eager plane, and the ICI ring kernels
against the XLA collectives (more than one chip).  It is a smoke,
not a benchmark: the only times it prints are its own wall times.

Contract: exit 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
only if every phase passed on a TPU.  No accelerator -> exit 2, no
result line.  A failed phase raises -> exit 1, no result line; no phase
is wrapped in a handler that lets the run end in 0, and a phase that
does not apply prints that it was not run and why.  One process: it
starts no children, so nothing competes for the chip.

``--rehearse-on-cpu`` walks the same phases at toy size on virtual CPU
devices (Pallas under the test interpreter) to debug the script itself
before spending chip time.  Every line it prints says REHEARSAL and it
can never print the result line.
"""

import argparse
import importlib.metadata
import json
import math
import os
import statistics
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)

STEPS = 4  # optimizer steps after the compiling one
INPUT_BATCHES = 300  # batches phase_input reads back
_PREFIX = ""  # "REHEARSAL " under --rehearse-on-cpu


def say(msg: str) -> None:
    print(f"{_PREFIX}{msg}", flush=True)


class CompileWatch:
    """Counts what JAX compiles, through ``jax.monitoring``: backend
    compiles (and their seconds) and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits


# ----------------------------------------------------------------------
# phase 0: what are we running on
# ----------------------------------------------------------------------


def report_device(rehearse: bool):
    import jax
    import jaxlib

    import horovod_tpu as hvt
    from horovod_tpu import native

    cache_dir = hvt.enable_compile_cache()
    hvt.init()
    devices = jax.devices()
    ident = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    env = {k: os.environ.get(k) for k in
           ("JAX_PLATFORMS", "HVTPU_CPU_DEVICES", "HVTPU_PALLAS",
            "JAX_COMPILATION_CACHE_DIR")}
    say(f"device: platform={ident['platform']} "
        f"device_kind={ident['kind']!r} count={ident['count']}")
    say(f"versions: python={sys.version.split()[0]} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} "
        f"libtpu={importlib.metadata.version('libtpu')} "
        f"horovod_tpu={hvt.__version__}")
    say(f"compile cache: {cache_dir}")
    say(f"eager controller: {native.controller_kind()}")
    say(f"environment: {env}")
    if ident["platform"] != "tpu" and not rehearse:
        print(f"chip_smoke: no accelerator: JAX reports platform="
              f"{ident['platform']!r} ({ident['kind']}, {ident['count']} "
              f"device(s)); environment {env}. Nothing was run.",
              file=sys.stderr)
        sys.exit(2)
    return ident


# ----------------------------------------------------------------------
# phase 1: the train step
# ----------------------------------------------------------------------


class TrainJob:
    """ResNet-50 data-parallel over ``hvt.world_mesh()`` the way
    README's quick start writes it, one optimizer step per dispatch."""

    def __init__(self, toy: bool):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        import horovod_tpu as hvt
        from horovod_tpu.models import ResNet50

        self.mesh = hvt.world_mesh()
        self.n_dev = hvt.num_devices()
        if toy:
            width, classes, px, per_chip = 8, 10, 32, 4
        else:
            width, classes, px, per_chip = 64, 1000, 224, 256
        self.classes, self.per_chip = classes, per_chip
        self.global_batch = per_chip * self.n_dev
        # sync BatchNorm over the data-parallel axis, at any chip count
        self.model = ResNet50(num_classes=classes, num_filters=width,
                              dtype=jnp.bfloat16, bn_axis_name="world")
        say(f"train: ResNet-50 num_filters={width} classes={classes} "
            f"{px}x{px} bf16, batch {per_chip}/chip x {self.n_dev} chip(s), "
            f"SGD+momentum under hvt.DistributedOptimizer(axis_name="
            f"'world'), bn_axis_name={self.model.bn_axis_name!r}")

        rng = np.random.default_rng(0)
        pool = 2 * self.global_batch  # two global batches, reshuffled
        images = rng.standard_normal(
            (pool, px, px, 3), dtype=np.float32).astype(jnp.bfloat16)
        labels = rng.integers(0, classes, (pool,), dtype=np.int32)

        # one jitted program: eager init would compile per parameter
        variables = jax.jit(
            lambda key, x: self.model.init(key, x, train=True)
        )(jax.random.PRNGKey(0), jnp.asarray(images[:2]))
        self.replicated = NamedSharding(self.mesh, P())
        self.batch_sharding = NamedSharding(self.mesh, P("world"))
        self.params = jax.device_put(variables["params"], self.replicated)
        self.batch_stats = jax.device_put(
            variables["batch_stats"], self.replicated)
        self.n_params = sum(
            x.size for x in jax.tree_util.tree_leaves(self.params))

        def place(batch):
            return {k: jax.device_put(v, self.batch_sharding)
                    for k, v in batch.items()}

        self.pool = {"x": images, "y": labels}
        self.source = hvt.data.ArraySource(self.pool)
        self.place = place
        # device_put=False + an explicit placing transform: a failed
        # transfer fails the prefetch, it is not retried on the host
        self.loader = hvt.data.ElasticDataLoader(
            self.source, batch_size=self.global_batch, shuffle=True, seed=0,
            device_put=False, transform=place, name="chip_smoke")
        self.batches = self.loader.stream()
        self.opt_state = None

    def make_step(self, compression):
        import jax
        import optax
        from jax.sharding import PartitionSpec as P

        import horovod_tpu as hvt

        model = self.model
        tx = hvt.DistributedOptimizer(
            optax.sgd(0.1, momentum=0.9), axis_name="world",
            compression=compression)
        if self.opt_state is None:
            self.opt_state = jax.device_put(
                tx.init(self.params), self.replicated)

        def loss_fn(params, batch_stats, x, y):
            logits, mutated = model.apply(
                {"params": params, "batch_stats": batch_stats}, x,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, mutated["batch_stats"]

        def one_step(params, batch_stats, opt_state, x, y):
            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch_stats, x, y)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, stats, opt_state, jax.lax.pmean(loss, "world")

        return jax.jit(
            jax.shard_map(
                one_step, mesh=self.mesh,
                in_specs=(P(), P(), P(), P("world"), P("world")),
                out_specs=(P(), P(), P(), P()), check_vma=False),
            donate_argnums=(0, 1, 2))

    def run_step(self, step):
        """One dispatch on a fresh batch, ended by block_until_ready;
        returns (loss, seconds, the batch)."""
        import jax

        t0 = time.perf_counter()
        batch = next(self.batches)
        self.params, self.batch_stats, self.opt_state, loss = step(
            self.params, self.batch_stats, self.opt_state,
            batch["x"], batch["y"])
        jax.block_until_ready((self.params, self.opt_state, loss))
        dt = time.perf_counter() - t0
        loss = float(loss)
        if not math.isfinite(loss):
            raise AssertionError(f"non-finite loss {loss}")
        return loss, dt, batch


def phase_input(job: TrainJob, rehearse: bool):
    """The loader's batches are the pool's rows, bit for bit, on the
    device.  ``ArraySource.fetch`` gathers into blocks of memory it used
    before, as soon as nothing refers to a block any more, while
    ``device_put`` returns long before its copy to the chips has read
    the block: a batch written over in flight would train on as if
    nothing had happened (random images, random labels), so this is the
    check that can see one.  A second loader over the job's source and
    placing transform, with the indices; every batch read back after
    ``block_until_ready``."""
    import jax
    import numpy as np

    import horovod_tpu as hvt
    from horovod_tpu.obs import metrics as program_metrics

    counter = program_metrics.REGISTRY.counter(
        "hvtpu_data_fetch_blocks_total")
    reused0, fresh0 = (counter.value(block="reused"),
                       counter.value(block="fresh"))
    in_flight = []  # per batch: (copy not landed yet, references it holds)

    def place_and_count(batch):
        # whoever holds the leaf holds the pool's block it is a view of
        # (``base``: None where the result was a fresh one of numpy's)
        leaf, block = batch["x"], batch["x"].base
        before = sys.getrefcount(leaf) + sys.getrefcount(block)
        placed = job.place(batch)
        added = sys.getrefcount(leaf) + sys.getrefcount(block) - before
        # read after the count: a copy still under way now was under way
        # when its references were counted
        in_flight.append((not placed["x"].is_ready(), added,
                          block is not None))
        return placed

    loader = hvt.data.ElasticDataLoader(
        job.source, batch_size=job.global_batch, shuffle=True, seed=1,
        device_put=False, transform=place_and_count, with_indices=True,
        name="chip_smoke_input")
    # read back as rows of bits: copied out in the layout the chip keeps
    # a batch of images in, 77 MB took 0.55 s (PERF.md section 6, PR 26)
    as_rows = jax.jit(lambda a: a.reshape(a.shape[0], -1))
    bits = {k: v.view(f"u{v.dtype.itemsize}").reshape(len(v), -1)
            for k, v in job.pool.items()}
    want = {k: np.empty((job.global_batch, v.shape[1]), v.dtype)
            for k, v in bits.items()}
    t0 = time.perf_counter()
    try:
        batches = loader.stream()
        for n in range(INPUT_BATCHES):
            indices, batch = next(batches)
            jax.block_until_ready(batch)
            for k, rows in bits.items():
                got = np.asarray(as_rows(batch[k]))
                np.take(rows, indices, axis=0, out=want[k], mode="clip")
                if (got.dtype != job.pool[k].dtype
                        or not np.array_equal(
                            got.view(rows.dtype), want[k])):
                    raise AssertionError(
                        f"input: batch {n}, leaf {k!r}: the device holds "
                        "other bits than the pool's rows at its indices")
    finally:
        loader.close()
    reused = counter.value(block="reused") - reused0
    fresh = counter.value(block="fresh") - fresh0
    held = [h for flying, h, is_block in in_flight if flying and is_block]
    say(f"input: {INPUT_BATCHES} batches of {job.global_batch} rows equal "
        f"to pool[indices] bit for bit on the device(s), in "
        f"{time.perf_counter() - t0:.1f} s; blocks gathered into: "
        f"{reused:.0f} reused, {fresh:.0f} fresh; of {len(in_flight)} "
        f"batches placed, {len(held)} had their copy still under way "
        "when device_put returned, and it then held "
        f"{sorted(set(held))} reference(s) to the leaf or its block")
    if any(h < 1 for h in held):
        raise AssertionError(
            "input: a copy to the device was under way and held no "
            "reference to the block it was reading: the pool would hand "
            "that block out again")
    if rehearse:
        return  # toy batches are under the pooled size, CPU copies instant
    if not held:
        raise AssertionError(
            "input: no copy was seen under way, so nothing was shown "
            "about who holds a block meanwhile")
    if reused < 0.9 * INPUT_BATCHES:
        raise AssertionError(
            f"input: only {reused:.0f} of {INPUT_BATCHES} batches reused "
            "a block; something holds on to the host batches")


def phase_train(job: TrainJob, watch: CompileWatch):
    import horovod_tpu as hvt

    step = job.make_step(hvt.Compression.none)
    c0, s0, h0 = watch.snapshot()
    loss, first_s, batch = job.run_step(step)
    c1, s1, h1 = watch.snapshot()
    say(f"train: first step (trace + compile + run) {first_s:.1f} s; "
        f"backend compile {s1 - s0:.1f} s in {c1 - c0} program(s), "
        f"{h1 - h0} persistent-cache hit(s); loss {loss:.4f}")
    # random weights, zero-initialised last BN scale in every block:
    # the first loss sits near ln(classes)
    expect = math.log(job.classes)
    if not expect - 1.5 < loss < expect + 3.0:
        raise AssertionError(
            f"first loss {loss:.4f} is far from ln({job.classes})="
            f"{expect:.4f} for random weights")
    losses, times = [], []
    for _ in range(STEPS):
        loss, dt, batch = job.run_step(step)
        losses.append(loss)
        times.append(dt)
    c2, _, _ = watch.snapshot()
    say("train: steady steps, one per dispatch, fresh batch each, "
        f"block_until_ready each: losses {[round(x, 4) for x in losses]}; "
        f"seconds {[round(t, 3) for t in times]} (median "
        f"{statistics.median(times):.3f}); compilations after warm-up: "
        f"{c2 - c1}")
    if c2 != c1:
        raise AssertionError(
            f"{c2 - c1} compilation(s) after warm-up; the step retraced")
    return batch


def phase_width(job: TrainJob, batch):
    """More than one chip: the work really is spread over all of them."""
    import jax

    n = job.n_dev
    if n == 1:
        say("width: not run — one device, nothing to spread")
        return
    devices = set(jax.devices())
    for name, arr in (("images", batch["x"]), ("labels", batch["y"])):
        shards = arr.addressable_shards
        on = {s.device for s in shards}
        rows = {s.data.shape[0] for s in shards}
        if on != devices or rows != {job.per_chip}:
            raise AssertionError(
                f"{name}: shards on {len(on)} of {n} devices with "
                f"leading sizes {sorted(rows)}, want one shard of "
                f"{job.per_chip} on each device")
    for name, tree in (("params", job.params),
                       ("batch_stats", job.batch_stats),
                       ("opt_state", job.opt_state)):
        for leaf in jax.tree_util.tree_leaves(tree):
            shards = leaf.addressable_shards
            if ({s.device for s in shards} != devices
                    or any(s.data.shape != leaf.shape for s in shards)):
                raise AssertionError(
                    f"{name}: a leaf of shape {leaf.shape} is not fully "
                    f"addressable on every one of the {n} devices")
    # replicas that drifted apart would show here: every device's copy
    # of the first parameter leaf is bit-equal after the steps
    leaf = jax.tree_util.tree_leaves(job.params)[0]
    import numpy as np

    copies = [np.asarray(s.data) for s in leaf.addressable_shards]
    if any(not np.array_equal(copies[0], c) for c in copies[1:]):
        raise AssertionError("parameter replicas differ across devices")
    if job.model.bn_axis_name != "world":
        raise AssertionError("sync BatchNorm is off")
    say(f"width: one batch shard of {job.per_chip} on each of {n} devices; "
        "params, batch_stats and optimizer state addressable (and params "
        "bit-equal) on every device; sync-BN over 'world'")


def phase_reference():
    """DistributedOptimizer against a plain reference on a small input:
    the same optax optimizer fed ``lax.pmean`` of the gradient tree."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvt

    mesh, n = hvt.world_mesh(), hvt.num_devices()
    rng = np.random.default_rng(1)
    params = {"w1": rng.standard_normal((64, 128), dtype=np.float32) * 0.1,
              "b1": np.zeros((128,), np.float32),
              "w2": rng.standard_normal((128, 10), dtype=np.float32) * 0.1}
    x = rng.standard_normal((8 * n, 64), dtype=np.float32)
    y = rng.integers(0, 10, (8 * n,), dtype=np.int32)

    def loss_fn(p, x, y):
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return optax.softmax_cross_entropy_with_integer_labels(
            h @ p["w2"], y).mean()

    def stepper(tx, reduce_grads):
        def body(p, s, x, y):
            grads = jax.grad(loss_fn)(p, x, y)
            updates, s = tx.update(reduce_grads(grads), s, p)
            return optax.apply_updates(p, updates), s

        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P(), P("world"), P("world")),
            out_specs=(P(), P()), check_vma=False))

    base = optax.sgd(0.1, momentum=0.9)
    hvt_tx = hvt.DistributedOptimizer(base, axis_name="world")
    got, _ = stepper(hvt_tx, lambda g: g)(params, hvt_tx.init(params), x, y)
    want, _ = stepper(base, lambda g: jax.lax.pmean(g, "world"))(
        params, base.init(params), x, y)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), rtol=1e-5, atol=1e-6,
            err_msg=f"DistributedOptimizer vs pmean reference: {k}")
        if np.array_equal(np.asarray(got[k]), params[k]) and k != "b1":
            raise AssertionError(f"{k} did not move")
    say(f"reference: DistributedOptimizer == optax + lax.pmean on a small "
        f"MLP over {n} device(s) (rtol 1e-5)")


# ----------------------------------------------------------------------
# phase 2: the Pallas kernels, compiled, against their XLA twins
# ----------------------------------------------------------------------


def phase_kernels(job: TrainJob, watch: CompileWatch, rehearse: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvt
    from horovod_tpu.comm.fusion import plan_for_tree
    from horovod_tpu.ops import pallas_ops

    # (a) the same model, one step with the int8 wire
    step = job.make_step(hvt.Compression.int8)
    c0, s0, _ = watch.snapshot()
    loss, dt, _ = job.run_step(step)
    c1, s1, _ = watch.snapshot()
    say(f"kernels: one more ResNet-50 step with compression=int8: loss "
        f"{loss:.4f}, {dt:.1f} s of which backend compile {s1 - s0:.1f} s")

    # (b) the kernels themselves at the model's real bucket shapes
    plan, _ = plan_for_tree(
        job.params, hvt.Config.from_env().fusion_threshold_bytes)
    sizes = sorted({sum(e.size for e in b) for b in plan.buckets})
    say(f"kernels: {job.n_params} f32 gradient elements -> "
        f"{plan.num_buckets} bucket(s) under the default plan, sizes "
        f"{sizes}")
    int8 = hvt.Compression.int8
    for n in sizes:
        flat = jnp.asarray(np.random.default_rng(n).standard_normal(
            n, dtype=np.float32))

        @jax.jit
        def roundtrip(flat):
            wire, ctx = int8.compress(flat)
            return wire, ctx[3], int8.decompress(wire, ctx)

        wire, scales, back = roundtrip(flat)
        hlo = roundtrip.lower(flat).as_text()
        if not rehearse and "tpu_custom_call" not in hlo:
            raise AssertionError(
                "Compression.int8 did not lower to a Mosaic kernel")
        q_ref, s_ref, _ = jax.jit(pallas_ops._quantize_xla)(flat)
        rows = q_ref.shape[0]
        codes = np.asarray(wire).reshape(-1, 128)
        np.testing.assert_array_equal(
            np.asarray(scales)[: s_ref.shape[0]], np.asarray(s_ref),
            err_msg=f"quantize scales vs _quantize_xla at n={n}")
        np.testing.assert_array_equal(
            codes[:rows], np.asarray(q_ref),
            err_msg=f"quantize codes vs _quantize_xla at n={n}")
        want = (np.asarray(q_ref, np.float32).reshape(-1, 1024)
                * np.asarray(s_ref)).reshape(-1)[:n]
        np.testing.assert_array_equal(
            np.asarray(back), want,
            err_msg=f"dequantize vs q*scale at n={n}")
        bound = np.repeat(np.asarray(s_ref).reshape(-1), 1024)[:n] * 0.5
        err = np.abs(np.asarray(back) - np.asarray(flat))
        if not (err <= bound * 1.0001 + 1e-12).all():
            raise AssertionError(
                f"int8 round trip outside the scale/2 bound at n={n}")
        say(f"kernels: quantize_int8_blocks / dequantize_int8_blocks at "
            f"n={n}: codes and scales byte-identical to _quantize_xla, "
            f"round trip inside scale/2")

    n = sizes[-1]
    flat = jnp.asarray(np.random.default_rng(7).standard_normal(
        n, dtype=np.float32))
    for out_dtype in (jnp.bfloat16, jnp.float32):
        got = jax.jit(lambda f: pallas_ops.fused_scale_cast(
            f, 0.125, out_dtype))(flat)
        want = pallas_ops._scale_cast_xla(flat, 0.125, jnp.dtype(out_dtype))
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)),
            err_msg=f"fused_scale_cast vs _scale_cast_xla -> {out_dtype}")
    say(f"kernels: fused_scale_cast at n={n} -> bf16 and f32: "
        "byte-identical to _scale_cast_xla")

    if rehearse:
        say("kernels: stochastic rounding not run — the on-core PRNG has "
            "no interpreter")
        return
    st = hvt.Compression.int8_stochastic

    @jax.jit
    def stochastic_roundtrip(flat):
        wire, ctx = st.compress(flat)
        return ctx[3], st.decompress(wire, ctx)

    scales, back = stochastic_roundtrip(flat)
    bound = np.repeat(np.asarray(scales).reshape(-1), 1024)[:n]
    err = np.asarray(back) - np.asarray(flat)
    if not (np.abs(err) <= bound * 1.0001 + 1e-12).all():
        raise AssertionError("stochastic int8 outside one scale step")
    if abs(float(err.mean())) > 1e-2 * float(bound.mean()):
        raise AssertionError(
            f"stochastic rounding looks biased: mean error {err.mean()}")
    say("kernels: int8_stochastic (on-core PRNG) compiled; error within "
        f"one scale step, mean error {float(err.mean()):.2e}")


# ----------------------------------------------------------------------
# phase 3: the eager plane
# ----------------------------------------------------------------------


def phase_eager(platform: str):
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvt

    def on_device(x, what):
        plats = {d.platform for d in x.devices()}
        if plats != {platform}:
            raise AssertionError(f"{what} came back on {plats}")

    x = jnp.arange(1024, dtype=jnp.float32)
    out = hvt.allreduce(x, op=hvt.Sum, name="smoke.sync")
    on_device(out, "hvt.allreduce")
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(x) * hvt.size())
    out = hvt.synchronize(
        hvt.allreduce_async(x, op=hvt.Average, name="smoke.async"))
    on_device(out, "hvt.allreduce_async")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    tree = {"w": jnp.ones((64, 64)), "b": jnp.arange(64.0)}
    out = hvt.broadcast_parameters(tree, root_rank=0)
    for k in tree:
        on_device(out[k], f"hvt.broadcast_parameters[{k}]")
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(tree[k]))
    say(f"eager: hvt.allreduce, hvt.allreduce_async + hvt.synchronize and "
        f"hvt.broadcast_parameters answered on {platform} across "
        f"{hvt.size()} process(es)")


# ----------------------------------------------------------------------
# phase 4: the ICI ring kernels against the XLA collectives (last: a
# wedged ring must not hide what the other phases would have said)
# ----------------------------------------------------------------------


def phase_ring():
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvt
    from horovod_tpu.ops import ring_allgather_2d, ring_allreduce

    mesh, n = hvt.world_mesh(), hvt.num_devices()
    if n == 1:
        say("ring: not run — a ring needs more than one chip")
        return

    def run(body, x, out_specs):
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("world"),), out_specs=out_specs,
            check_vma=False))(x)

    sharded = NamedSharding(mesh, P("world"))
    rng = np.random.default_rng(3)
    # integer-valued f32: every partial sum is exact, so ring order and
    # psum order must agree to the bit.  3e6 elements per rank span
    # several kernel calls (slicing) plus a ragged tail (padding).
    for per_rank in (5, 4096, 3_000_001):
        x = jax.device_put(
            rng.integers(-1000, 1000, (n, per_rank)).astype(np.float32),
            sharded)
        got = run(lambda xs: ring_allreduce(xs[0], axis_name="world")[None],
                  x, P("world"))
        want = run(lambda xs: jax.lax.psum(xs[0], "world")[None],
                   x, P("world"))
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg=f"ring_allreduce vs psum, {per_rank} per rank")
    say(f"ring: ring_allreduce (f32) == lax.psum exactly on {n} devices, "
        "5 / 4096 / 3000001 elements per rank, every rank's copy checked")

    x_np = rng.standard_normal((n, 1_000_000), dtype=np.float32)
    x = jax.device_put(x_np, sharded)
    got = np.asarray(run(
        lambda xs: ring_allreduce(
            xs[0], axis_name="world", quantized=True)[None],
        x, P("world")))
    for r in range(1, n):
        np.testing.assert_array_equal(
            got[0], got[r], err_msg=f"quantized ring: rank {r} != rank 0")
    want = x_np.sum(0)
    # one requantization per hop, 2(n-1) hops, each at most half a code
    # of the running absmax (bounded by the sum of absolute values)
    bound = 2 * (n - 1) * np.abs(x_np).sum(0).max() / 127
    err = np.abs(got[0] - want)
    if err.max() > bound or err.mean() > 0.1:
        raise AssertionError(
            f"quantized ring error max {err.max()} mean {err.mean()} vs "
            f"bound {bound}")
    say(f"ring: ring_allreduce(quantized=True) bit-equal on all {n} ranks, "
        f"max error {err.max():.4f} (bound {bound:.4f})")

    x = jax.device_put(
        rng.standard_normal((n * 512, 128), dtype=np.float32), sharded)
    got = run(lambda xs: ring_allgather_2d(xs, axis_name="world"), x, P())
    want = run(lambda xs: jax.lax.all_gather(xs, "world", tiled=True),
               x, P())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    say(f"ring: ring_allgather_2d == lax.all_gather exactly on {n} devices")


def main() -> int:
    global _PREFIX
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-on-cpu", type=int, metavar="N", default=0,
        help="debug the script at toy size on N virtual CPU devices; "
             "never a chip result")
    args = ap.parse_args()
    rehearse = args.rehearse_on_cpu > 0
    t_start = time.perf_counter()

    if os.environ.get("HVTPU_PALLAS_INTERPRET"):
        print("chip_smoke: HVTPU_PALLAS_INTERPRET is set in the "
              "environment; the check is of kernels compiled for the "
              "chip, and an interpreted run proves nothing about it.",
              file=sys.stderr)
        return 1
    if rehearse:
        _PREFIX = "REHEARSAL "
        from horovod_tpu.core.state import force_cpu_devices

        force_cpu_devices(args.rehearse_on_cpu)
        os.environ["HVTPU_PALLAS_INTERPRET"] = "1"

    ident = report_device(rehearse)
    watch = CompileWatch()
    job = TrainJob(toy=rehearse)
    try:
        phase_input(job, rehearse)
        batch = phase_train(job, watch)
        phase_width(job, batch)
        phase_reference()
        phase_kernels(job, watch, rehearse)
    finally:
        job.loader.close()
    phase_eager(ident["platform"])
    phase_ring()

    import horovod_tpu as hvt

    hvt.shutdown()
    compiles, compile_s, hits = watch.snapshot()
    say(f"compile: {compiles} program(s), {compile_s:.1f} s of backend "
        f"compile, {hits} persistent-cache hit(s) in this run")
    say(f"all phases passed in {time.perf_counter() - t_start:.1f} s wall")
    if rehearse:
        say("not a chip result")
        return 0
    print(json.dumps({"ok": True, "device": ident}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
