#!/usr/bin/env python3
"""The timed step of a block-diffusion cell against the plain reference,
at the cell's own sizes, on the chip.

    python3 benchmark/compare_sdar.py --workload <cell> --seed <n>

Builds the cell's ``TrainJob`` as ``run.py`` does, so what is compared
is what the timed path itself produces: from ``fresh_state()`` one
``job.step`` on the first batch.  Its loss is held to the reference's
(``benchmark/reference/sdar_block_diffusion.py``: float32, dense mask,
a loop over the experts, every product at ``highest``), and so is its
gradient: after one step of SGD from zero momentum the momentum *is*
the averaged gradient, so the step's own output gives it, leaf by leaf
(relative L2 distance and cosine).  Also printed: the share of
positions whose ``top_k`` experts differ between the two in some layer
(bf16 activations flip near-ties of the router).

The nearest precision below the configuration's is a bfloat16 store of
the parameters (the products are bfloat16 already, so a gradient hardly
shows it).  What shows it is the step's *update*: the change of the
parameters against ``-learning_rate * reference gradient``, which reads
the gradient's own distance when the store is float32 and 1 when the
store cannot hold the change and the state is left as it was.  The step's
own old and new parameters, rounded to a bfloat16 store, give that
second reading, and ``UPDATE_DISTANCE`` lies between the two.

The limits, and why (readings on the chip at the published widths
over five seeds, the largest given: PERF.md, findings of PR 27):

* ``UPDATE_DISTANCE`` 0.3: ``|dp - dp_ref| / |dp_ref|`` over all
  parameters read 0.019 to 0.032, and 0.893 to 0.911 in a bfloat16
  store (the one limit the lower precision has to fail, and does on
  every seed): nine times the largest reading, a third of what a state
  left unchanged reads.
* ``LOSS_RTOL`` 5e-4: both losses are means of 16,384 f32
  cross-entropies of f32 logits over bf16 hidden states; read 2.7e-5.
* ``LEAF_DISTANCE`` 0.25 / ``LEAF_COSINE`` 0.97: a gradient leaf is a
  sum over 32,768 positions of products of bf16-rounded activations
  (relative 4e-3 each, growing with depth); read 0.013 (``final_norm``)
  to 0.106 (``w_gate``), and 0.077 to 0.153 for ``router``, which moves
  with every position that chose other experts.  An 8-bit product
  rounds sixteen times coarser than bfloat16 and would read past the
  limit on nearly every leaf (reckoned, not run).
* ``FLIPPED_SHARE`` 0.40: 18.6 to 24.9 % of the positions chose, in
  some layer, other experts than the reference (3 to 10 % a layer):
  the 8th and 9th of 128 probabilities lie closer together than bf16
  hidden states resolve.  It is this, not a fault, that sets
  ``router``'s distance.

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
FLIPPED_SHARE = 0.40
UPDATE_DISTANCE = 0.3
# rows of queries the reference scores at a time
QUERY_BLOCK = 2048


def sizes_of(config):
    from benchmark.reference import sdar_block_diffusion as ref

    return ref.Sizes(
        head_dim=config["head_dim"],
        num_experts=config["published"]["num_experts"],
        first_expert=config["deployment"]["first_expert"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        block_length=config["block_length"],
        mask_token_id=config["vocab_size"] - 1)


def system_step(job, params):
    """One ``job.step`` from ``fresh_state()`` with ``params`` in place
    of its parameters: the loss, the gradient (the momentum after one
    step from zero) and the new parameters, on the host."""
    import jax
    import numpy as np
    import optax

    _, model_state, opt_state = job.fresh_state()
    params = jax.device_put(params, job.replicated)
    new_params, _, opt_state, loss = job.step(
        params, model_state, opt_state, job.first_batch)
    grads = optax.tree_utils.tree_get(opt_state, "trace")
    return (float(loss), jax.tree_util.tree_map(np.asarray, grads),
            jax.tree_util.tree_map(np.asarray, new_params))


def update_distance(old, new, ref_grads, learning_rate, store=None):
    """``|dp - dp_ref| / |dp_ref|`` over all parameters, ``dp_ref =
    -learning_rate * ref_grads`` (one step of SGD from zero momentum);
    with ``store`` the old and new parameters as a store of that type
    would hold them."""
    import jax
    import numpy as np

    def held(a):
        return a if store is None else np.asarray(
            a.astype(store), np.float32)

    off = want = 0.0
    for a, b, g in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (old, new, ref_grads))):
        change = (held(held(a) + (b - a)) - held(a)).astype(np.float64)
        target = -learning_rate * g.astype(np.float64)
        off += np.sum((change - target) ** 2)
        want += np.sum(target ** 2)
    return float(np.sqrt(off / want))


def compare_leaves(got, want):
    """{leaf: (relative L2 distance, cosine)}."""
    import jax
    import numpy as np

    out = {}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        g = np.asarray(g, np.float64).ravel()
        w = np.asarray(w, np.float64).ravel()
        norm = np.linalg.norm(w)
        out[jax.tree_util.keystr(path)] = (
            float(np.linalg.norm(g - w) / norm),
            float(g @ w / (np.linalg.norm(g) * norm)))
    return out


def verdict(loss, ref_loss, leaves, flipped, update):
    failures = []
    if not update <= UPDATE_DISTANCE:
        failures.append(f"update distance {update:.4f}")
    if abs(loss - ref_loss) > LOSS_RTOL * abs(ref_loss):
        failures.append(f"loss {loss} against {ref_loss}")
    for name, (distance, cosine) in leaves.items():
        if distance > LEAF_DISTANCE or cosine < LEAF_COSINE:
            failures.append(f"{name}: distance {distance:.3e}, "
                            f"cosine {cosine:.6f}")
    if flipped > FLIPPED_SHARE:
        failures.append(f"{100 * flipped:.2f} % of positions chose other "
                        "experts")
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

    from benchmark.builders import block_diffusion_lm
    from benchmark.job import TrainJob
    from benchmark.reference import sdar_block_diffusion as ref
    from horovod_tpu.models import block_diffusion

    hvt.enable_compile_cache()
    hvt.init()
    device = jax.devices()[0]
    if not args.rehearse_on_cpu and (
            device.platform != "tpu" or len(jax.devices()) != cell.chips):
        print(f"compare_sdar.py: the cell asks for {cell.chips} TPU "
              f"chip(s), found {device.platform}. Nothing was compared.",
              file=sys.stderr)
        return 2
    config = cell.config
    job = TrainJob(cells.load_builder(config).build(config), config,
                   cell.traffic, args.seed)
    try:
        job.first_batch = next(job.batches)
        batch = {k: np.asarray(v) for k, v in job.first_batch.items()}
        params = jax.tree_util.tree_map(np.asarray, job.fresh_state()[0])
        loss, grads, new_params = system_step(job, params)
        cfg = block_diffusion_lm.model_config(config)
        ids = np.concatenate(
            [np.where(batch["mask"] != 0, cfg.mask_token_id, batch["x"]),
             batch["x"]], axis=1)
        chosen = np.asarray(jax.jit(
            lambda p, i: block_diffusion.hidden_states(p, i, cfg)[1][
                "experts"])(params, ids))          # [L, B * 2T, k]
    finally:
        job.close()

    sizes = sizes_of(config)
    ref.check_share(
        params, sizes, experts_held=config["num_experts"],
        heads_held=(config["num_attention_heads"],
                    config["num_key_value_heads"]),
        vocab_held=config["vocab_size"])
    ref_batch = {"x": batch["x"], "mask": batch["mask"],
                 "w": batch["w"].astype(np.float32)}   # the same rounded w
    ref_loss, ref_grads, ref_chosen = ref.loss_and_gradient(
        params, ref_batch, sizes,
        query_block=min(QUERY_BLOCK, 2 * config["sequence_length"]))
    ref_loss = float(ref_loss)
    ref_grads = jax.tree_util.tree_map(np.asarray, ref_grads)
    # [B, L, 2T, k] -> [L, B * 2T, k], each position's choice as a set
    ref_chosen = np.sort(np.asarray(ref_chosen).transpose(1, 0, 2, 3)
                         .reshape(chosen.shape), axis=-1)
    differs = (np.sort(chosen, axis=-1) != ref_chosen).any(axis=-1)
    flipped = float(differs.any(axis=0).mean())

    leaves = compare_leaves(grads, ref_grads)
    rate = config["optimizer"]["learning_rate"]
    update = update_distance(params, new_params, ref_grads, rate)
    # the nearest precision below the configuration's param_dtype
    update_bf16 = update_distance(params, new_params, ref_grads, rate,
                                  store=jnp.bfloat16)
    failures = verdict(loss, ref_loss, leaves, flipped, update)
    failures_bf16 = verdict(loss, ref_loss, leaves, flipped, update_bf16)
    print(f"{prefix}loss: system {loss}, reference {ref_loss} (relative "
          f"{abs(loss - ref_loss) / abs(ref_loss):.3e})")
    for name, (distance, cosine) in leaves.items():
        print(f"{prefix}gradient {name}: distance {distance:.3e} cosine "
              f"{cosine:.6f}")
    print(f"{prefix}update: distance {update:.4f} from -rate * reference "
          f"gradient; {update_bf16:.4f} in a bfloat16 store of the "
          "parameters")
    print(f"{prefix}routing: {100 * flipped:.3f} % of positions chose "
          "other experts than the reference in some layer; by layer "
          f"{[round(100 * float(x), 3) for x in differs.mean(axis=1)]} %")
    print(f"{prefix}limits: loss {LOSS_RTOL}, leaf distance "
          f"{LEAF_DISTANCE}, cosine {LEAF_COSINE}, flipped "
          f"{FLIPPED_SHARE}, update {UPDATE_DISTANCE}; passed by "
          f"{failures or 'nothing'}; in a bfloat16 store by "
          f"{failures_bf16 or 'nothing'}")
    hvt.shutdown()
    if args.rehearse_on_cpu:
        print("REHEARSAL not a chip result")
        return 0
    print(json.dumps({
        "agrees": not failures, "lower_precision_fails": bool(failures_bf16),
        "loss": loss, "reference_loss": ref_loss, "leaves": leaves,
        "flipped_share": flipped, "update_distance": update,
        "update_distance_bf16_store": update_bf16,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())}}))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
