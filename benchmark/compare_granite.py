#!/usr/bin/env python3
"""The timed step of a hybrid state-space cell against the plain
reference, at the cell's own sizes, on the chip.

    python3 benchmark/compare_granite.py --workload <cell> --seed <n>

Builds the cell's ``TrainJob`` as ``run.py`` does, so what is compared
is what the timed path itself produces: from ``fresh_state()`` one
``job.step`` on the first batch (``compare_sdar.py`` has the method and
its helpers).  Its loss is held to the reference's
(``benchmark/reference/granite_hybrid.py``: float32, the recurrence one
position at a time, a dense mask, every product at ``highest``), and so
is its gradient, which after one step of SGD from zero momentum *is*
the momentum, leaf by leaf (relative L2 distance and cosine), and the
step's update of the parameters against ``-learning_rate * reference
gradient``.

Two precisions lie next below the configuration's, and each has to fail
a limit:

* a bfloat16 *store of the parameters* (the products are bfloat16
  already, so a gradient hardly shows it): the step's own old and new
  parameters, rounded to such a store, give the update's distance a
  second reading near 1, a state left as it was.
  ``UPDATE_DISTANCE`` lies between the two readings.
* a bfloat16 *decay* (``--bf16-decay``: the cumulative sums of ``delta
  A`` inside a chunk, whose differences the decays are the exponentials
  of, are made and kept in bfloat16, by a patch this script applies to
  ``models.hybrid_ssm`` before the job is built): ``A_log`` and
  ``dt_bias`` get their gradient through the decays alone, and their
  leaves' distances read it.  ``DECAY_LEAF_DISTANCE`` holds those two leaves
  and lies between their two readings.

The limits, and why (readings on the chip at the published widths
over five seeds, the largest given: PERF.md, findings of PR 32):

* ``UPDATE_DISTANCE`` 0.3: ``|dp - dp_ref| / |dp_ref|`` over all
  parameters read 0.0311 to 0.0312, and 0.9972 in a bfloat16 store
  (the one limit that store has to fail, and does on every seed): ten
  times the reading, a third of what a state left unchanged reads.
* ``LOSS_RTOL`` 5e-4, the limit of ``compare_sdar.py``: both losses are
  means of some 16,370 f32 cross-entropies of f32 logits over bf16
  hidden states; read 0 to 5.2e-6.
* ``LEAF_DISTANCE`` 0.25 / ``LEAF_COSINE`` 0.97, every leaf: a leaf is
  a sum over 16,384 positions of products of bf16-rounded activations;
  read 0.020 (``final_norm``) to 0.034, cosines 0.99909 and above.  With
  the decays' sums in bfloat16 ``A_log`` of the first group reads 0.296
  and 0.960.
* ``DECAY_LEAF_DISTANCE`` 0.12, ``A_log`` and ``dt_bias``: read 0.024 to
  0.043 in f32 and 0.183 to 0.296 with the sums in bfloat16; every
  other leaf stays under 0.047 then, so this is the limit a bfloat16
  decay fails.

The last line is one JSON object; exit code 1 if a limit is passed.
``--rehearse-on-cpu`` walks the same code at the files' toy sizes.
"""

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
sys.path.insert(0, _ROOT)

LOSS_RTOL = 5e-4
LEAF_DISTANCE = 0.25
LEAF_COSINE = 0.97
UPDATE_DISTANCE = 0.3
DECAY_LEAF_DISTANCE = 0.12
DECAY_LEAVES = ("A_log", "dt_bias")
# positions of the recurrence and queries of the attention the reference
# computes at a time (and recomputes in its backward pass)
TIME_BLOCK = 128
QUERY_BLOCK = 512


def sizes_of(config, blocks: bool):
    from benchmark.reference import granite_hybrid as ref

    return ref.Sizes(
        layer_types=tuple(config["layer_types"]),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        ssm_heads=config["mamba_n_heads"],
        attention_multiplier=config["attention_multiplier"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"],
        rms_norm_eps=config["rms_norm_eps"],
        time_block=TIME_BLOCK if blocks else None,
        query_block=QUERY_BLOCK if blocks else None,
        recompute_layers=blocks)


def keep_the_decays_in_bfloat16():
    """The nearest precision below f32 for the recurrence's decays:
    inside ``models.hybrid_ssm`` the cumulative sums of ``delta A`` are
    made and kept in bfloat16 (the module's ``jnp`` is replaced by one
    whose ``cumsum`` rounds; nothing else of it calls ``cumsum``)."""
    import jax.numpy as jnp

    from horovod_tpu.models import hybrid_ssm

    class RoundedSums:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def cumsum(a, axis):
            return jnp.cumsum(a.astype(jnp.bfloat16), axis=axis).astype(
                jnp.float32)

    hybrid_ssm.jnp = RoundedSums()


def verdict(loss, ref_loss, leaves, update):
    failures = []
    if not update <= UPDATE_DISTANCE:
        failures.append(f"update distance {update:.4f}")
    if abs(loss - ref_loss) > LOSS_RTOL * abs(ref_loss):
        failures.append(f"loss {loss} against {ref_loss}")
    for name, (distance, cosine) in leaves.items():
        limit = (DECAY_LEAF_DISTANCE if any(
            f"'{leaf}'" in name for leaf in DECAY_LEAVES) else LEAF_DISTANCE)
        if not (distance <= limit and cosine >= LEAF_COSINE):
            failures.append(f"{name}: distance {distance:.3e} (limit "
                            f"{limit}), cosine {cosine:.6f}")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bf16-decay", action="store_true",
                    help="keep the chunks' cumulative sums in bfloat16: the "
                         "comparison then has to fail")
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args()

    from benchmark import cells

    cell = cells.load_cell(args.workload, rehearse=args.rehearse_on_cpu)
    import horovod_tpu as hvt

    prefix = ""
    if args.rehearse_on_cpu:
        from horovod_tpu.core.state import force_cpu_devices

        prefix = "REHEARSAL "
        force_cpu_devices(cell.chips)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.compare_sdar import (
        compare_leaves, system_step, update_distance)
    from benchmark.job import TrainJob
    from benchmark.reference import granite_hybrid as ref

    hvt.enable_compile_cache()
    hvt.init()
    device = jax.devices()[0]
    if not args.rehearse_on_cpu and (
            device.platform != "tpu" or len(jax.devices()) != cell.chips):
        print(f"compare_granite.py: the cell asks for {cell.chips} TPU "
              f"chip(s), found {device.platform}. Nothing was compared.",
              file=sys.stderr)
        return 2
    if args.bf16_decay:
        keep_the_decays_in_bfloat16()
    config = cell.config
    job = TrainJob(cells.load_builder(config).build(config), config,
                   cell.traffic, args.seed)
    try:
        job.first_batch = next(job.batches)
        batch = {k: np.asarray(v) for k, v in job.first_batch.items()}
        params = jax.tree_util.tree_map(np.asarray, job.fresh_state()[0])
        loss, grads, new_params = system_step(job, params)
    finally:
        job.close()
    del job
    jax.clear_caches()      # the step's program and its buffers go

    ref_batch = {**batch, "w": batch["w"].astype(np.float32)}
    ref_loss, ref_grads = ref.loss_and_gradient(
        params, ref_batch, sizes_of(config, blocks=not args.rehearse_on_cpu))

    leaves = compare_leaves(grads, ref_grads)
    rate = config["optimizer"]["learning_rate"]
    update = update_distance(params, new_params, ref_grads, rate)
    # the nearest precision below the configuration's param_dtype
    update_bf16 = update_distance(params, new_params, ref_grads, rate,
                                  store=jnp.bfloat16)
    failures = verdict(loss, ref_loss, leaves, update)
    failures_bf16 = verdict(loss, ref_loss, leaves, update_bf16)
    documents = int(np.sum(batch["segment"][:, 1:] != batch["segment"][:, :-1])
                    ) + batch["segment"].shape[0]
    print(f"{prefix}batch: {documents} documents in "
          f"{batch['segment'].shape[0]} rows, {int(ref_batch['w'].sum())} "
          f"weighted positions; decays' sums kept in bfloat16: "
          f"{args.bf16_decay}")
    print(f"{prefix}loss: system {loss}, reference {ref_loss} (relative "
          f"{abs(loss - ref_loss) / abs(ref_loss):.3e})")
    for name, (distance, cosine) in leaves.items():
        print(f"{prefix}gradient {name}: distance {distance:.3e} cosine "
              f"{cosine:.6f}")
    print(f"{prefix}update: distance {update:.4f} from -rate * reference "
          f"gradient; {update_bf16:.4f} in a bfloat16 store of the "
          "parameters")
    print(f"{prefix}limits: loss {LOSS_RTOL}, leaf distance "
          f"{LEAF_DISTANCE} ({DECAY_LEAF_DISTANCE} for "
          f"{', '.join(DECAY_LEAVES)}), cosine {LEAF_COSINE}, update "
          f"{UPDATE_DISTANCE}; passed by {failures or 'nothing'}; in a "
          f"bfloat16 store by {failures_bf16 or 'nothing'}")
    hvt.shutdown()
    if args.rehearse_on_cpu:
        print("REHEARSAL not a chip result")
        return 0
    print(json.dumps({
        "agrees": not failures, "lower_precision_fails": bool(failures_bf16),
        "bf16_decay": args.bf16_decay,
        "loss": loss, "reference_loss": ref_loss, "leaves": leaves,
        "update_distance": update,
        "update_distance_bf16_store": update_bf16,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())}}))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
