#!/usr/bin/env python3
"""The timed step of a cell of one-mixer layers (Mamba-2 with B/C
groups, attention, sparse experts with a shared expert) against the
plain reference, at the cell's own sizes, on the chip.

    python3 benchmark/compare_nemotron.py --workload <cell> --seed <n>

Builds the cell's ``TrainJob`` as ``run.py`` does, so what is compared
is what the timed path itself produces: from ``fresh_state()`` one
``job.step`` on the first batch (``compare_granite.py`` is the method,
``compare_sdar.py`` has the helpers).  Its loss is held to the
reference's (``benchmark/reference/nemotron_h.py``: float32, the
recurrence one position at a time, a dense mask, every held expert over
every token with a 0/1 choice, every product at ``highest``; given the
same share: the experts held, their first index, the slice of the
vocabulary), and so is its gradient, which after one step of SGD from
zero momentum *is* the momentum, leaf by leaf (relative L2 distance and
cosine), and the step's update of the parameters against
``-learning_rate * reference gradient``.  The selection bias is a
buffer: the reference's gradient of it is exact zeros, and the step's
has to be.

Three precisions lie next below the configuration's, and each has to
fail a limit:

* a bfloat16 *store of the parameters*: the step's own old and new
  parameters, rounded to such a store, give the update's distance a
  second reading near 1, a state left as it was.  ``UPDATE_DISTANCE``
  lies between the two readings.
* a bfloat16 *decay* (``--bf16-decay``: the cumulative sums of ``delta
  A`` inside a chunk are made and kept in bfloat16, by the patch
  ``compare_granite.py`` applies to ``models.hybrid_ssm``): ``A_log``
  and ``dt_bias`` get their gradient through the decays alone, and
  ``DECAY_LEAF_DISTANCE`` holds those leaves.
* bfloat16 *router scores* (``--bf16-router``: the router's product is
  made from bfloat16 operands and its logits are kept in bfloat16, by a
  patch this script applies to ``parallel.moe``): a score then has eight
  bits, and the 6th and 7th of 128 lie closer than that for a good
  share of the tokens.  The routers' *gradient* cannot tell: its
  distance is set by the choices that the bfloat16 hidden states before
  the router flip against the float32 reference's, and reads the same
  with the patch (0.294 against 0.315 on one seed).  What tells is the
  *choices on the step's own inputs*: every expert layer's normed input
  as the program made it, routed by the program's router and by the
  reference's (``nemotron_h.routing_weights``, float32 at ``highest``)
  on the same numbers; ``OWN_INPUT_FLIPPED_SHARE`` holds the share of
  tokens whose six experts differ.

The limits, and why (readings on the chip at the published widths over
five seeds, the largest given: PERF.md, findings of PR 38):

* ``UPDATE_DISTANCE`` 0.25: ``|dp - dp_ref| / |dp_ref|`` over all
  parameters read 0.0517 to 0.0542, and 0.4964 to 0.4983 in a bfloat16
  store (the one limit that store has to fail, and does on every seed;
  it reads a half and not Granite's 1 because a residual branch's last
  matrix starts at 0.02 / sqrt(52), small enough for bfloat16 to hold
  some of its change): five times the reading, half of what the store
  reads.
* ``LOSS_RTOL`` 5e-4, the limit of ``compare_sdar.py``: both losses are
  means of some 16,370 f32 cross-entropies of f32 logits over bf16
  hidden states; read 7.0e-6 to 6.2e-5.
* ``LEAF_DISTANCE`` 0.25 / ``LEAF_COSINE`` 0.97, every leaf that has no
  limit of its own below (the embedding, the head, norms, the mixers'
  and the attention's matrices, the convolution, ``D``, the shared
  expert): read 0.0135 (``final_norm``) to 0.0798, cosines 0.99689 and
  above.
* ``EXPERT_LEAF_DISTANCE`` 0.35 / ``EXPERT_LEAF_COSINE`` 0.93, the held
  experts' ``w_up`` and ``w_down``: read 0.118 to 0.215, cosines 0.9769
  and above, growing with depth (layers 1, 3, 6, 8: 0.12, 0.14, 0.19,
  0.21).  An expert's gradient is a sum over the 768 or so rows it got,
  and the bf16 hidden states before a router flip some tokens' sixth
  choice against the float32 reference's: a row more or less is a tenth
  of a percent of the sum each, at a weight near 2.5 / 6.
* ``ROUTER_LEAF_DISTANCE`` 0.45 / ``ROUTER_LEAF_COSINE`` 0.90: read
  0.148 to 0.315 and 0.9516 and above (layers 1, 3, 6, 8: 0.15, 0.22,
  0.28, 0.31).  A router gets its gradient through the weights of the
  chosen experts held here alone, 8 of 128, so every flipped token moves
  it: it is the flips, not a fault, that set it, as in
  ``compare_sdar.py``; and it reads the same, 0.294, with the router's
  scores from bfloat16 logits, so it is no limit that precision fails.
* ``OWN_INPUT_FLIPPED_SHARE`` 0.003: read 0 of 16,384 tokens in every
  layer on all five seeds (the program's router, f32 at ``HIGHEST``, and
  the reference's agree to the token on the same input), and 1.05 to
  1.32 % of the tokens a layer with the logits in bfloat16 (two seeds):
  a third of the least of those, and room for a token in a thousand
  where a fresh seed holds a tie that f32 sums in another order break
  differently.  Every leaf and the update stay inside their limits
  then, so this is the limit a bfloat16 router fails.
* ``DECAY_LEAF_DISTANCE`` 0.2, ``A_log`` and ``dt_bias``: read 0.035 to
  0.123 in f32 (cosines 0.9933 and above) and, with the decays' sums in
  bfloat16, 0.108 to 0.312 (``A_log`` of layers 4, 2 and 7 0.312, 0.249
  and 0.210, ``dt_bias`` of layer 2 0.219: four leaves past the limit);
  every other leaf stays inside its own limit then (the held experts'
  0.264 of 0.35, the routers' 0.362 of 0.45, the rest 0.098 of 0.25,
  the update 0.0748), so this is the limit a bfloat16 decay fails.

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
UPDATE_DISTANCE = 0.25
DECAY_LEAF_DISTANCE = 0.2
DECAY_LEAVES = ("A_log", "dt_bias")
ROUTER_LEAF_DISTANCE, ROUTER_LEAF_COSINE = 0.45, 0.90
ROUTER_LEAVES = ("router",)
EXPERT_LEAF_DISTANCE, EXPERT_LEAF_COSINE = 0.35, 0.93
EXPERT_LEAVES = ("w_up", "w_down")
OWN_INPUT_FLIPPED_SHARE = 0.003
# positions of the recurrence and queries of the attention the reference
# computes at a time (and recomputes in its backward pass)
TIME_BLOCK = 128
QUERY_BLOCK = 512


def sizes_of(config, blocks: bool):
    from benchmark.reference import nemotron_h as ref

    return ref.Sizes(
        pattern=config["hybrid_override_pattern"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        ssm_heads=config["mamba_num_heads"],
        ssm_groups=config["n_groups"],
        first_expert=config["deployment"]["first_expert"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=config["routed_scaling_factor"],
        rms_norm_eps=config["layer_norm_epsilon"],
        time_block=TIME_BLOCK if blocks else None,
        query_block=QUERY_BLOCK if blocks else None,
        recompute_layers=blocks)


def keep_the_routers_scores_in_bfloat16():
    """The nearest precision below f32 for the router: inside
    ``parallel.moe`` the router's product takes bfloat16 operands and
    its logits are rounded to bfloat16 (the module's ``jnp`` is replaced
    by one whose ``dot`` does; nothing else of it calls ``jnp.dot``)."""
    import jax.numpy as jnp

    from horovod_tpu.parallel import moe

    class RoundedScores:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def dot(a, b, precision=None):
            del precision
            return jnp.dot(
                a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32).astype(
                    jnp.bfloat16).astype(jnp.float32)

    moe.jnp = RoundedScores()


def _limits_of(name):
    """(distance, cosine) a gradient leaf is held to, by its name."""
    def is_one_of(leaves):
        return any(f"'{leaf}'" in name for leaf in leaves)

    if is_one_of(DECAY_LEAVES):
        return DECAY_LEAF_DISTANCE, LEAF_COSINE
    if is_one_of(ROUTER_LEAVES):
        return ROUTER_LEAF_DISTANCE, ROUTER_LEAF_COSINE
    if is_one_of(EXPERT_LEAVES):
        return EXPERT_LEAF_DISTANCE, EXPERT_LEAF_COSINE
    return LEAF_DISTANCE, LEAF_COSINE


def verdict(loss, ref_loss, leaves, buffers, flipped, update):
    failures = []
    if not update <= UPDATE_DISTANCE:
        failures.append(f"update distance {update:.4f}")
    if abs(loss - ref_loss) > LOSS_RTOL * abs(ref_loss):
        failures.append(f"loss {loss} against {ref_loss}")
    for name, (distance, cosine) in leaves.items():
        limit, least = _limits_of(name)
        if not (distance <= limit and cosine >= least):
            failures.append(f"{name}: distance {distance:.3e} (limit "
                            f"{limit}), cosine {cosine:.6f} (limit {least})")
    failures += [f"{name}: a buffer's gradient of norm {norm:.3e}"
                 for name, norm in buffers.items() if norm != 0.0]
    if not max(flipped) <= OWN_INPUT_FLIPPED_SHARE:
        failures.append(
            "on their own inputs the routers chose other experts for "
            f"{[round(100 * share, 3) for share in flipped]} % of the "
            "tokens, layer by layer")
    return failures


def own_input_flipped_shares(params, batch, config, sizes):
    """For every expert layer, the share of the batch's tokens whose
    ``top_k`` experts differ between the program's router and the plain
    reference's *on the same input*: the layer's normed input as the
    program's own forward pass makes it (the step's functions, one more
    jitted program; bf16 activations and all).  With them, the rows
    each held expert got in each of those layers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.builders import hybrid_moe_lm
    from benchmark.reference import nemotron_h as ref
    from horovod_tpu.models import hybrid_moe as hm
    from horovod_tpu.models.block_diffusion import rms_norm

    cfg = hybrid_moe_lm.model_config(config)

    @jax.jit
    def routed(params, ids, segment):
        x = jnp.take(params["embed"], ids, axis=0).astype(
            jnp.dtype(cfg.compute_dtype))
        seen = []
        for letter, p in ref.layers_of(params, sizes):
            if letter == "E":
                u = rms_norm(x, p["norm"], cfg.rms_norm_eps)
                x, routing = hm.expert_layer(cfg, p, x)
                seen.append((u.reshape(-1, u.shape[-1]), routing["experts"],
                             routing["rows_per_expert"]))
            elif letter == "M":
                x = hm.mamba_layer(cfg, p, x, segment)
            else:
                x = hm.attention_layer(cfg, p, x, segment)
        return seen

    weights_of = jax.jit(lambda p, u: ref.routing_weights(
        p, u.astype(jnp.float32), sizes))
    seen = routed(params, batch["x"], batch["segment"])
    expert_layers = [p for letter, p in ref.layers_of(params, sizes)
                     if letter == "E"]
    shares = []
    for p, (u, experts, _) in zip(expert_layers, seen):
        plain = np.asarray(weights_of(p, u)) > 0
        chosen = np.zeros(plain.shape, bool)
        np.put_along_axis(chosen, np.asarray(experts), True, axis=1)
        shares.append(float(np.mean((chosen != plain).any(axis=1))))
    return shares, [np.asarray(rows).tolist() for _, _, rows in seen]


def split_the_buffers(grads, ref_grads):
    """The leaves the reference's gradient is exact zeros of (the
    selection bias) are no part of the leaf-by-leaf comparison: ``(the
    other leaves' two trees as flat dictionaries, {buffer: the norm of
    the step's gradient of it})``."""
    import jax
    import numpy as np

    got, want, buffers = {}, {}, {}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        if np.any(w):
            got[name], want[name] = g, w
        else:
            buffers[name] = float(np.linalg.norm(np.asarray(g, np.float64)))
    return got, want, buffers


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bf16-decay", action="store_true",
                    help="keep the chunks' cumulative sums in bfloat16: the "
                         "comparison then has to fail")
    ap.add_argument("--bf16-router", action="store_true",
                    help="make the router's scores from bfloat16 logits: "
                         "the comparison then has to fail")
    ap.add_argument("--routing-only", action="store_true",
                    help="only the routers' choices on the step's own "
                         "inputs: no reference gradient (a minute, not five)")
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

    from benchmark.compare_granite import keep_the_decays_in_bfloat16
    from benchmark.compare_sdar import (
        compare_leaves, system_step, update_distance)
    from benchmark.job import TrainJob
    from benchmark.reference import nemotron_h as ref

    hvt.enable_compile_cache()
    hvt.init()
    device = jax.devices()[0]
    if not args.rehearse_on_cpu and (
            device.platform != "tpu" or len(jax.devices()) != cell.chips):
        print(f"compare_nemotron.py: the cell asks for {cell.chips} TPU "
              f"chip(s), found {device.platform}. Nothing was compared.",
              file=sys.stderr)
        return 2
    if args.bf16_decay:
        keep_the_decays_in_bfloat16()
    if args.bf16_router:
        keep_the_routers_scores_in_bfloat16()
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

    sizes = sizes_of(config, blocks=not args.rehearse_on_cpu)
    flipped, rows = own_input_flipped_shares(params, batch, config, sizes)
    jax.clear_caches()
    even = (batch["x"].size * config["num_experts_per_tok"]
            // config["published"]["n_routed_experts"])
    print(f"{prefix}routing: rows the held experts got on the first batch, "
          f"layer by layer {rows} (an even routing gives each {even})")
    ref_batch = {**batch, "w": batch["w"].astype(np.float32)}
    if args.routing_only:
        print(f"{prefix}routers on their own inputs: other experts chosen "
              f"for {[round(100 * f, 4) for f in flipped]} % of the tokens, "
              f"layer by layer (limit {100 * OWN_INPUT_FLIPPED_SHARE} %); "
              f"router's scores from bfloat16 logits: {args.bf16_router}")
        hvt.shutdown()
        if not args.rehearse_on_cpu:
            print(json.dumps({"own_input_flipped_shares": flipped,
                              "bf16_router": args.bf16_router}))
        return 0 if max(flipped) <= OWN_INPUT_FLIPPED_SHARE else 1
    ref_loss, ref_grads = ref.loss_and_gradient(params, ref_batch, sizes)

    got, want, buffers = split_the_buffers(grads, ref_grads)
    leaves = compare_leaves(got, want)
    rate = config["optimizer"]["learning_rate"]
    update = update_distance(params, new_params, ref_grads, rate)
    # the nearest precision below the configuration's param_dtype
    update_bf16 = update_distance(params, new_params, ref_grads, rate,
                                  store=jnp.bfloat16)
    failures = verdict(loss, ref_loss, leaves, buffers, flipped, update)
    failures_bf16 = verdict(loss, ref_loss, leaves, buffers, flipped,
                            update_bf16)
    documents = int(np.sum(batch["segment"][:, 1:] != batch["segment"][:, :-1])
                    ) + batch["segment"].shape[0]
    print(f"{prefix}batch: {documents} documents in "
          f"{batch['segment'].shape[0]} rows, {int(ref_batch['w'].sum())} "
          f"weighted positions; decays' sums kept in bfloat16: "
          f"{args.bf16_decay}; router's scores from bfloat16 logits: "
          f"{args.bf16_router}")
    print(f"{prefix}loss: system {loss}, reference {ref_loss} (relative "
          f"{abs(loss - ref_loss) / abs(ref_loss):.3e})")
    for name, (distance, cosine) in leaves.items():
        print(f"{prefix}gradient {name}: distance {distance:.3e} cosine "
              f"{cosine:.6f}")
    print(f"{prefix}buffers (the reference's gradient is exact zeros): "
          f"the step's gradient norms {buffers}")
    print(f"{prefix}routers on their own inputs: other experts chosen for "
          f"{[round(100 * f, 4) for f in flipped]} % of the tokens, layer "
          "by layer")
    print(f"{prefix}update: distance {update:.4f} from -rate * reference "
          f"gradient; {update_bf16:.4f} in a bfloat16 store of the "
          "parameters")
    print(f"{prefix}limits: loss {LOSS_RTOL}, leaf distance "
          f"{LEAF_DISTANCE} ({DECAY_LEAF_DISTANCE} for "
          f"{', '.join(DECAY_LEAVES)}; {ROUTER_LEAF_DISTANCE} for "
          f"{', '.join(ROUTER_LEAVES)}; {EXPERT_LEAF_DISTANCE} for "
          f"{', '.join(EXPERT_LEAVES)}), cosine {LEAF_COSINE} "
          f"({ROUTER_LEAF_COSINE}; {EXPERT_LEAF_COSINE}), update "
          f"{UPDATE_DISTANCE}, flipped on own inputs "
          f"{OWN_INPUT_FLIPPED_SHARE}; passed by {failures or 'nothing'}; "
          f"in a bfloat16 store by {failures_bf16 or 'nothing'}")
    hvt.shutdown()
    if args.rehearse_on_cpu:
        print("REHEARSAL not a chip result")
        return 0
    print(json.dumps({
        "agrees": not failures, "lower_precision_fails": bool(failures_bf16),
        "bf16_decay": args.bf16_decay, "bf16_router": args.bf16_router,
        "loss": loss, "reference_loss": ref_loss, "leaves": leaves,
        "buffers": buffers, "own_input_flipped_shares": flipped,
        "update_distance": update,
        "update_distance_bf16_store": update_bf16,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())}}))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
