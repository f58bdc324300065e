"""TF2-frontend synthetic benchmark — the horovod_tpu surface of the
reference's measurement tool (examples/tensorflow2/
tensorflow2_synthetic_benchmark.py): random data, timed training
iterations via ``DistributedGradientTape``, per-rank and aggregate
images/sec with the same log format.

Only the import line changes from the reference idiom
(``import horovod.tensorflow as hvd`` -> ``import
horovod_tpu.tensorflow as hvd``).  A small dense model keeps the
TF-eager data path (the system under test) tractable offline.  It is
the frontend's example, not a record: the jit path's speed on the chip
is measured by ``benchmark/run.py`` (``BENCHMARK.json``, ``PERF.md``).

Run:  hvtpurun -np 2 --cpu-devices 1 python \
          examples/tensorflow2_synthetic_benchmark.py --num-iters 3
"""

import argparse
import time

import numpy as np
import tensorflow as tf

import horovod_tpu.tensorflow as hvd


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--num-warmup-batches", type=int, default=2)
    p.add_argument("--num-batches-per-iter", type=int, default=5)
    p.add_argument("--num-iters", type=int, default=5)
    p.add_argument("--fp16-allreduce", action="store_true")
    args = p.parse_args()

    hvd.init()
    tf.random.set_seed(2 + hvd.rank())

    model = tf.keras.Sequential([
        tf.keras.layers.Dense(256, activation="relu"),
        tf.keras.layers.Dense(256, activation="relu"),
        tf.keras.layers.Dense(10),
    ])
    opt = tf.keras.optimizers.SGD(0.01)
    loss_fn = tf.keras.losses.SparseCategoricalCrossentropy(
        from_logits=True
    )
    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)

    data = tf.random.normal((args.batch_size, 784))
    target = tf.random.uniform(
        (args.batch_size,), 0, 10, dtype=tf.int64
    )

    def benchmark_step(first_batch):
        with tf.GradientTape() as tape:
            loss = loss_fn(target, model(data, training=True))
        # Horovod idiom: wrap the tape; grads come back allreduced.
        tape = hvd.DistributedGradientTape(tape, compression=compression)
        grads = tape.gradient(loss, model.trainable_variables)
        opt.apply_gradients(zip(grads, model.trainable_variables))
        if first_batch:
            hvd.broadcast_variables(model.variables, root_rank=0)
            hvd.broadcast_variables(opt.variables, root_rank=0)

    def log(s):
        if hvd.rank() == 0:
            print(s, flush=True)

    log(f"Model: 3-layer MLP, Batch size: {args.batch_size}, "
        f"number of ranks: {hvd.size()}")

    benchmark_step(first_batch=True)
    for _ in range(args.num_warmup_batches - 1):
        benchmark_step(first_batch=False)

    img_secs = []
    for x in range(args.num_iters):
        t = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            benchmark_step(first_batch=False)
        dt = time.perf_counter() - t
        img_sec = args.batch_size * args.num_batches_per_iter / dt
        log(f"Iter #{x}: {img_sec:.1f} img/sec per rank")
        img_secs.append(img_sec)

    img_sec_mean = np.mean(img_secs)
    img_sec_conf = 1.96 * np.std(img_secs)
    log(f"Img/sec per rank: {img_sec_mean:.1f} +-{img_sec_conf:.1f}")
    log(f"Total img/sec on {hvd.size()} rank(s): "
        f"{hvd.size() * img_sec_mean:.1f} "
        f"+-{hvd.size() * img_sec_conf:.1f}")


if __name__ == "__main__":
    main()
