"""Block-diffusion training of a sparse decoder, data-parallel over
every local chip: the job of the benchmark's cell
``sdar-30b-a3b-1of8-t8k-b2`` (SDAR-30B-A3B-Chat's block as one chip's
share of an 8-chip layer) in the README's form — wrap the optimizer,
one jitted ``shard_map`` step, batches from ``ElasticDataLoader``.

    python examples/block_diffusion_moe.py                 # toy widths
    python examples/block_diffusion_moe.py --published     # one v5e chip

Synthetic ids; a batch row is a sequence ``x``, the tokens replaced by
[MASK] (``mask``) and the loss's weight ``w`` (``docs/design.md``, "The
block-diffusion batch").
"""

import argparse
import os
import sys

# a script runs with its own directory, not the checkout's root, on the
# path: make the repo root importable
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import horovod_tpu as hvt  # noqa: E402
from horovod_tpu.models import block_diffusion as bd  # noqa: E402
from horovod_tpu.obs import metrics  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402

TOY = bd.BlockDiffusionConfig(
    vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
    num_kv_heads=1, head_dim=32, expert_width=64, num_experts=16,
    experts_held=4, first_expert=4, top_k=4, norm_topk_prob=True,
    rope_theta=1e6, rms_norm_eps=1e-6, block_length=4,
    compute_dtype="float32")
# chip 3 of the 8 that share each layer of SDAR-30B-A3B-Chat
PUBLISHED_SHARE = bd.BlockDiffusionConfig(
    vocab_size=18992, hidden_size=2048, num_layers=4, num_heads=4,
    num_kv_heads=1, head_dim=128, expert_width=768, num_experts=128,
    experts_held=16, first_expert=48, top_k=8, norm_topk_prob=True,
    rope_theta=1e6, rms_norm_eps=1e-6, block_length=4)


def make_pool(rng, cfg, rows, seq_len, t_min=0.05):
    """Sequences and their noise: one t ~ U[t_min, 1] a block, each of
    its tokens masked with probability t, weight 1/t where masked."""
    x = rng.integers(0, cfg.mask_token_id, (rows, seq_len), dtype=np.int32)
    t = rng.uniform(t_min, 1.0, (rows, seq_len // cfg.block_length))
    t = np.repeat(t, cfg.block_length, axis=1)
    mask = rng.uniform(size=x.shape) < t
    return {"x": x, "mask": mask.astype(np.int8),
            "w": np.where(mask, 1.0 / t, 0.0).astype(np.float32)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--published", action="store_true",
                    help="the published widths (fills one v5e chip)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int)
    ap.add_argument("--batch-per-chip", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.01)
    args = ap.parse_args()
    cfg = PUBLISHED_SHARE if args.published else TOY
    seq_len = args.seq_len or (8192 if args.published else 128)

    hvt.init()
    mesh, n_dev = hvt.world_mesh(), hvt.num_devices()
    tx = hvt.DistributedOptimizer(
        optax.sgd(args.lr, momentum=0.9), axis_name="world")

    def one_step(params, opt_state, batch):
        (loss, routing), grads = jax.value_and_grad(
            bd.block_diffusion_loss, has_aux=True)(params, batch, cfg)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, "world"), routing)

    step = jax.jit(
        jax.shard_map(one_step, mesh=mesh,
                      in_specs=(P(), P(), P("world")),
                      out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1))

    replicated = NamedSharding(mesh, P())
    params, opt_state = jax.jit(
        lambda key: (lambda p: (p, tx.init(p)))(bd.init_params(key, cfg)),
        out_shardings=replicated)(jax.random.PRNGKey(0))

    global_batch = args.batch_per_chip * n_dev
    pool = make_pool(np.random.default_rng(0), cfg, 4 * global_batch,
                     seq_len)
    batch_sharding = NamedSharding(mesh, P("world"))
    loader = hvt.data.ElasticDataLoader(
        hvt.data.ArraySource(pool), batch_size=global_batch, shuffle=True,
        seed=0, device_put=False, name="train",
        transform=lambda batch: jax.device_put(batch, batch_sharding))
    batches = loader.stream()
    try:
        for i in range(args.steps):
            params, opt_state, loss, routing = step(
                params, opt_state, next(batches))
            if i % 5 == 0 or i == args.steps - 1:
                # logging cadence: the routing's counts come to the host
                metrics.note_moe_routing(
                    routing["moe_rows_per_expert"],
                    buffer_rows=moe.buffer_rows(
                        2 * seq_len * args.batch_per_chip, cfg.top_k,
                        cfg.experts_held))
                snap = metrics.snapshot()
                print(f"step {i}: loss {float(loss):.4f} (ln vocabulary "
                      f"{np.log(cfg.vocab_size):.2f}); busiest expert over "
                      "the mean "
                      f"{snap['hvtpu_moe_rows_per_expert']['values']['']:.2f}"
                      , flush=True)
    finally:
        loader.close()
        hvt.shutdown()


if __name__ == "__main__":
    main()
