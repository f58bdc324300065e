"""Torch-frontend synthetic benchmark — the horovod_tpu surface of the
reference's measurement tool (examples/pytorch/
pytorch_synthetic_benchmark.py, the script behind BASELINE.md's
published numbers): random data, timed training iterations, per-rank
and aggregate images/sec with the same log format.

Only the import line changes from the reference idiom
(``import horovod.torch as hvd`` -> ``import horovod_tpu.torch as
hvd``).  The default model is a small conv net so the *eager torch*
data path (DLPack adapter -> eager controller -> fused collectives) is
what's being measured.  It is the frontend's example, not a record:
the jit path's speed on the chip is measured by ``benchmark/run.py``
(``BENCHMARK.json``, ``PERF.md``).

Run:  hvtpurun -np 2 --cpu-devices 1 python \
          examples/pytorch_synthetic_benchmark.py --num-iters 3
"""

import argparse
import time

import numpy as np
import torch
import torch.nn as nn
import torch.nn.functional as F

import horovod_tpu.torch as hvd


class SmallConvNet(nn.Module):
    """Stand-in for torchvision's resnet50 (unavailable offline): same
    training-loop shape, tractable on the CPU-backed torch eager path."""

    def __init__(self, num_classes=10):
        super().__init__()
        self.conv1 = nn.Conv2d(3, 16, 3, stride=2, padding=1)
        self.conv2 = nn.Conv2d(16, 32, 3, stride=2, padding=1)
        self.fc = nn.Linear(32 * 8 * 8, num_classes)

    def forward(self, x):
        x = F.relu(self.conv1(x))
        x = F.relu(self.conv2(x))
        return self.fc(x.flatten(1))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--num-warmup-batches", type=int, default=2)
    p.add_argument("--num-batches-per-iter", type=int, default=5)
    p.add_argument("--num-iters", type=int, default=5)
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--fp16-allreduce", action="store_true",
                   help="compress gradients to fp16 on the wire")
    p.add_argument("--use-adasum", action="store_true",
                   help="Adasum reduction instead of averaging")
    args = p.parse_args()

    hvd.init()
    torch.manual_seed(1 + hvd.rank())

    model = SmallConvNet()
    optimizer = torch.optim.SGD(model.parameters(), lr=0.01)

    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)
    optimizer = hvd.DistributedOptimizer(
        optimizer,
        named_parameters=model.named_parameters(),
        compression=compression,
        op=hvd.Adasum if args.use_adasum else hvd.Average,
    )
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    hvd.broadcast_optimizer_state(optimizer, root_rank=0)

    data = torch.randn(args.batch_size, 3, args.image_size, args.image_size)
    target = torch.randint(0, 10, (args.batch_size,))

    def benchmark_step():
        optimizer.zero_grad()
        output = model(data)
        loss = F.cross_entropy(output, target)
        loss.backward()
        optimizer.step()

    def log(s):
        if hvd.rank() == 0:
            print(s, flush=True)

    log(f"Model: SmallConvNet, Batch size: {args.batch_size}, "
        f"number of ranks: {hvd.size()}")

    for _ in range(args.num_warmup_batches):
        benchmark_step()

    img_secs = []
    for x in range(args.num_iters):
        t = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            benchmark_step()
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
