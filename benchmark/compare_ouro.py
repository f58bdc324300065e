#!/usr/bin/env python3
"""The timed step of a looped-decoder cell against the plain reference,
at the cell's own sizes, on the chip.

    python3 benchmark/compare_ouro.py --workload <cell> --seed <n>

Builds the cell's ``TrainJob`` as ``run.py`` does, so what is compared
is what the timed path itself produces: from ``fresh_state()`` one
``job.step`` on the first batch (``compare_sdar.py`` has the method and
its helpers).  Its loss is held to the reference's
(``benchmark/reference/ouro_looped.py``: float32, Python loops over the
passes and the layers, a dense mask, an exit's logits whole, every
product at ``highest``), and so is its gradient, which after one step
of SGD from zero momentum *is* the momentum, leaf by leaf (relative L2
distance and cosine), and the step's update of the parameters against
``-learning_rate * reference gradient``.

Two precisions lie next below the configuration's, and each has to fail
a limit:

* a bfloat16 *store of the parameters* (the products are bfloat16
  already, so a gradient hardly shows it): the step's own old and new
  parameters, rounded to such a store, give the update's distance a
  second reading near 1, a state left as it was.
* bfloat16 *positions under RoPE* (``turn_by_bfloat16_positions``
  patches ``models.looped``; the step is built and run a second time
  and held to the same reference): beyond position 256 only every
  second, fourth, … thirty-second position has a value of its own, so
  queries and keys far apart are turned by angles that are not theirs,
  and ``wq`` and ``wk`` read it first.  The rounding is made with
  ``lax.reduce_precision``: a cast to bfloat16 and back is one the TPU's
  compiler drops (it allows itself excess precision; read on the chip:
  bit-equal results).

Two more were tried on the chip and no limit can tell them (PERF.md,
findings of PR 34): a shared weight's gradient added up over its four
uses in bfloat16 moves no leaf by more than 0.0002 (its 0.4 % is lost
under the 2 % that bfloat16 activations leave in every leaf), and
bfloat16 gates and ``p_t`` read ``gate_w`` 0.010 to 0.055 where float32
ones read 0.009 to 0.038 over the same five seeds.

The limits, and why (readings on the chip at the published widths over
six seeds, the largest given: PERF.md, findings of PR 34):

* ``UPDATE_DISTANCE`` 0.3: ``|dp - dp_ref| / |dp_ref|`` over all
  parameters read 0.0158 to 0.0273, and 0.909 to 0.958 in a bfloat16
  store (the one limit that store has to fail, and does on every seed):
  eleven times the reading, a third of what a state left unchanged
  reads.  Under bfloat16 positions 0.236 to 0.504.
* ``LOSS_RTOL`` 5e-4, the limit of the two other scripts: both losses
  are means of some 8,190 sums over four exits of f32 cross-entropies
  of f32 logits over bf16 hidden states; read 1.2e-6 to 1.6e-5.
* ``LEAF_DISTANCE`` 0.25 / ``LEAF_COSINE`` 0.97, every leaf: a leaf is
  a sum over 8,192 positions and up to four uses of products of
  bf16-rounded activations; read 0.0007 (``gate_b``) to 0.0384
  (``gate_w``, on the one seed whose row is a single document; 0.0256
  for the largest other leaf, ``wq``), cosines 0.99958 and above.
  Under bfloat16 positions ``wq`` reads 0.666 to 0.829 and 0.66 to
  0.78: between the two readings with six times of room below and
  2.7 above, and the limit those positions fail on every seed.

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
# queries of the attention the reference scores at a time (and recomputes
# in its backward pass)
QUERY_BLOCK = 512


def sizes_of(config, blocks: bool):
    from benchmark.reference import ouro_looped as ref

    return ref.Sizes(
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        total_ut_steps=config["total_ut_steps"],
        entropy_weight=config["exit_entropy_weight"],
        query_block=QUERY_BLOCK if blocks else None)


def turn_by_bfloat16_positions():
    """Patch ``models.looped``: RoPE's positions rounded to bfloat16's
    eight bits (and kept in float32: a cast there and back is one the
    compiler may drop, ``reduce_precision`` it may not)."""
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.models import looped

    in_f32 = looped.rope
    looped.rope = lambda x, positions, theta: in_f32(
        x, lax.reduce_precision(positions.astype(jnp.float32),
                                exponent_bits=8, mantissa_bits=7), theta)
    return in_f32


def verdict(loss, ref_loss, leaves, update):
    failures = []
    if not update <= UPDATE_DISTANCE:
        failures.append(f"update distance {update:.4f}")
    if abs(loss - ref_loss) > LOSS_RTOL * abs(ref_loss):
        failures.append(f"loss {loss} against {ref_loss}")
    for name, (distance, cosine) in leaves.items():
        if not (distance <= LEAF_DISTANCE and cosine >= LEAF_COSINE):
            failures.append(f"{name}: distance {distance:.3e}, cosine "
                            f"{cosine:.6f}")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
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
    from benchmark.job import TrainJob, make_step
    from benchmark.reference import ouro_looped as ref
    from horovod_tpu.models import looped

    hvt.enable_compile_cache()
    hvt.init()
    device = jax.devices()[0]
    if not args.rehearse_on_cpu and (
            device.platform != "tpu" or len(jax.devices()) != cell.chips):
        print(f"compare_ouro.py: the cell asks for {cell.chips} TPU "
              f"chip(s), found {device.platform}. Nothing was compared.",
              file=sys.stderr)
        return 2
    config = cell.config
    rate = config["optimizer"]["learning_rate"]
    job = TrainJob(cells.load_builder(config).build(config), config,
                   cell.traffic, args.seed)
    try:
        job.first_batch = next(job.batches)
        batch = {k: np.asarray(v) for k, v in job.first_batch.items()}
        params = jax.tree_util.tree_map(np.asarray, job.fresh_state()[0])
        steps = {"as it is": system_step(job, params)}
        in_f32 = turn_by_bfloat16_positions()
        try:
            job.step = make_step(job.mesh, job.workload.loss_fn, job.tx)
            steps["bf16_rope"] = system_step(job, params)
        finally:
            looped.rope = in_f32
    finally:
        job.close()
    del job
    jax.clear_caches()      # the step's programs and their buffers go

    ref_batch = {**batch, "w": batch["w"].astype(np.float32)}
    ref_loss, ref_grads = ref.loss_and_gradient(
        params, ref_batch, sizes_of(config, blocks=not args.rehearse_on_cpu))

    documents = int(np.sum(batch["segment"][:, 1:] != batch["segment"][:, :-1])
                    ) + batch["segment"].shape[0]
    print(f"{prefix}batch: {documents} documents in "
          f"{batch['segment'].shape[0]} rows, {int(ref_batch['w'].sum())} "
          "weighted positions")
    print(f"{prefix}limits: loss {LOSS_RTOL}, leaf distance "
          f"{LEAF_DISTANCE}, cosine {LEAF_COSINE}, update "
          f"{UPDATE_DISTANCE}")
    results = {}
    for name, (loss, grads, new_params) in steps.items():
        leaves = compare_leaves(grads, ref_grads)
        update = update_distance(params, new_params, ref_grads, rate)
        failures = verdict(loss, ref_loss, leaves, update)
        print(f"{prefix}{name}: loss {loss}, reference {ref_loss} (relative "
              f"{abs(loss - ref_loss) / abs(ref_loss):.3e})")
        for leaf, (distance, cosine) in leaves.items():
            print(f"{prefix}{name}: gradient {leaf}: distance "
                  f"{distance:.3e} cosine {cosine:.6f}")
        print(f"{prefix}{name}: update: distance {update:.4f} from -rate * "
              f"reference gradient; passed by {failures or 'nothing'}")
        results[name] = {"loss": loss, "leaves": leaves,
                         "update_distance": update, "failures": failures}
    # the nearest precision below the configuration's param_dtype
    loss, grads, new_params = steps["as it is"]
    update_bf16 = update_distance(params, new_params, ref_grads, rate,
                                  store=jnp.bfloat16)
    failures_bf16 = verdict(loss, ref_loss, results["as it is"]["leaves"],
                            update_bf16)
    print(f"{prefix}a bfloat16 store of the parameters: update distance "
          f"{update_bf16:.4f}; passed by {failures_bf16 or 'nothing'}")
    hvt.shutdown()
    if args.rehearse_on_cpu:
        print("REHEARSAL not a chip result")
        return 0
    failures = results["as it is"]["failures"]
    print(json.dumps({
        "agrees": not failures,
        "lower_precision_fails": {
            "bf16_store": bool(failures_bf16),
            "bf16_rope": bool(results["bf16_rope"]["failures"])},
        "reference_loss": ref_loss, "results": results,
        "update_distance_bf16_store": update_bf16,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())}}))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
