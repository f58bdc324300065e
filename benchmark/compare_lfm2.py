#!/usr/bin/env python3
"""The timed step of a cell of LFM2's layers (gated short convolutions,
grouped-query attention with normed queries and keys, a dense MLP,
sigmoid-routed experts) against the plain reference, at the cell's own
sizes, on the chip.

    python3 benchmark/compare_lfm2.py --workload <cell> --seed <n>

Builds the cell's ``TrainJob`` as ``run.py`` does, so what is compared
is what the timed path itself produces: from ``fresh_state()`` one
``job.step`` on the first batch (``compare_kimi.py``'s method and
helpers).  Its loss is held to the reference's
(``benchmark/reference/lfm2_moe.py``: float32, explicit taps that stop
at each document's start, a dense mask with positions restarted at
every document's start, every held expert over every token with a 0/1
choice, every product at ``highest``; given the same share: the experts
held, their first index, the slice of the vocabulary), and so is its
gradient, which after one step of SGD from zero momentum *is* the
momentum, leaf by leaf (relative L2 distance and cosine), and the
step's update of the parameters against ``-learning_rate * reference
gradient``.  The expert bias is a buffer: the reference's gradient of
it is exact zeros, and the step's has to be.

Beside the step, three mechanisms are held to the reference **on the
step's own inputs** (``own_inputs``: one more jitted program of the
model's own functions over the first batch): every expert layer's normed
input routed by the program's router and by the reference's, the share
of tokens whose four experts differ; the first convolution layer's
mixer on its normed input through ``gated_conv.conv_mixer`` and through
the reference's taps, the relative distance of the two outputs; the
attention layer's ``q, k, v`` through ``causal_document_attention`` and
through a plain f32 softmax, the relative distance of the two results.
The last two twice: as the step runs them (bfloat16 operands), and with
the same numbers as float32 operands at ``highest``, where what is left
is the mechanism's own arithmetic (the sum over the taps; the scores and
the softmax) and not the rounding of its operands.

Four precisions lie next below the configuration's, each made by a
patch of code the step runs on the chip, and each has to fail a limit:

* a bfloat16 *store of the parameters*: the step's own old and new
  parameters, rounded to such a store, give the update's distance a
  second reading near 1, a state left as it was.  ``UPDATE_DISTANCE``
  lies between the two readings.
* bfloat16 *router logits* (``--bf16-router``: ``compare_nemotron``'s
  patch of ``parallel.moe``, whose router product the step runs in
  XLA): fails ``OWN_INPUT_FLIPPED_SHARE``.
* bfloat16 *attention scores* (``--bf16-scores``: ``compare_kimi``'s
  patch of ``ops.flash_attention._scores``, which the three Pallas
  kernels call for every pair): fails ``ATTENTION_IN_F32_DISTANCE``.
* the *taps summed in bfloat16* (``--bf16-taps``: the convolution that
  ``models.gated_conv.short_conv`` calls, the step's own XLA path, adds
  its three taps in bfloat16): fails ``CONV_IN_F32_DISTANCE``.

``--own-inputs-only`` makes the three mechanisms' measures alone (no
step, no reference gradient), which is where the last three flags show.

The limits, and why (readings on the chip at the published widths,
seeds 3000000201 and 3000000202; the lowered precisions on 3000000201:
PERF.md, the findings on this cell):

* ``UPDATE_DISTANCE`` 0.3: ``|dp - dp_ref| / |dp_ref|`` over all
  parameters read 0.0498 and 0.0502, and 0.9672 and 0.9674 in a
  bfloat16 store (a state left as it was reads 1: at a rate of 0.01 the
  change of a weight of 0.02 is below bfloat16's eighth bit): the norm
  of the change between the first reading and 1, the more room above
  the reading.
* ``LOSS_RTOL`` 5e-4, the limit of ``compare_sdar.py``: both losses are
  means of some 16,370 f32 cross-entropies of f32 logits over bf16
  hidden states; read 2.5e-5 and 1.4e-5.
* ``LEAF_DISTANCE`` 0.25 / ``LEAF_COSINE`` 0.97, every leaf that has no
  limit of its own below: read 0.0124 ... 0.0567 (the attention layer's
  ``wk`` and ``wq``), cosines 0.99839 and above; the limit of the other
  comparisons, four times the largest reading.
* ``EXPERT_LEAF_DISTANCE`` 0.4 / ``EXPERT_LEAF_COSINE`` 0.9, the held
  experts' ``w_gate``, ``w_up`` and ``w_down`` and the expert layers'
  ``norm2``: read 0.152 ... 0.183, cosines 0.9832 and above, growing with
  depth.  An expert's gradient is a sum over the 1,800 to 2,300 rows it
  got, and the bf16 hidden states before a router flip some tokens'
  fourth choice against the float32 reference's; with no shared expert
  beside them, the norm before the experts gets its gradient through
  them and the router alone, and reads as they do.
* ``ROUTER_LEAF_DISTANCE`` 0.5 / ``ROUTER_LEAF_COSINE`` 0.85: read
  0.212 ... 0.265, cosines 0.9650 and above.  A router gets its gradient
  through the weights of the chosen experts held here alone, 8 of 32, so
  every flipped token moves it.
* ``OWN_INPUT_FLIPPED_SHARE`` 0.003: read 0 of 16,384 tokens in every
  layer on both seeds, and 0.51 ... 0.61 % of the tokens a layer with
  the logits in bfloat16.
* ``CONV_OWN_INPUT_DISTANCE`` 0.015: read 5.24e-3 on both seeds (the
  bfloat16 projections, gates and operands): three times the reading;
  it is there for a wrong convolution, and no lowered precision aims at
  it (taps summed in bfloat16 read 5.52e-3 here).
* ``CONV_IN_F32_DISTANCE`` 1e-5: read 1.21e-7 on both seeds (f32
  products and sums against the reference's); 2.40e-3 with the taps
  summed in bfloat16.
* ``ATTENTION_OWN_INPUT_DISTANCE`` 0.006: read 2.00e-3 on both seeds (the
  bfloat16 probabilities and result): three times the reading.
* ``ATTENTION_IN_F32_DISTANCE`` 1e-5: read 4.7e-7 and 5.1e-7; 2.53e-3
  with the scores rounded to bfloat16.

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
LEAF_DISTANCE, LEAF_COSINE = 0.25, 0.97
UPDATE_DISTANCE = 0.3
ROUTER_LEAF_DISTANCE, ROUTER_LEAF_COSINE = 0.5, 0.85
ROUTER_LEAVES = ("router",)
EXPERT_LEAF_DISTANCE, EXPERT_LEAF_COSINE = 0.4, 0.9
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
OWN_INPUT_FLIPPED_SHARE = 0.003
CONV_OWN_INPUT_DISTANCE = 0.015
CONV_IN_F32_DISTANCE = 1e-5
ATTENTION_OWN_INPUT_DISTANCE = 0.006
ATTENTION_IN_F32_DISTANCE = 1e-5
# queries of the attention the reference computes at a time (and
# recomputes in its backward pass)
QUERY_BLOCK = 512


def sizes_of(config, blocks: bool):
    from benchmark.builders import lfm2_moe_lm
    from benchmark.reference import lfm2_moe as ref

    mixers, ffns = lfm2_moe_lm.layer_kinds(config)
    return ref.Sizes(
        mixers=mixers, ffns=ffns,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        rope_theta=float(config["rope_theta"]),
        first_expert=config["deployment"]["first_expert"],
        top_k=config["num_experts_per_tok"],
        renormalise=config["norm_topk_prob"],
        renormalise_eps=lfm2_moe_lm.RENORMALISE_EPS,
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=config["norm_eps"],
        query_block=QUERY_BLOCK if blocks else None,
        recompute_layers=blocks)


# -- the precisions next below ------------------------------------------------

def sum_the_taps_in_bfloat16():
    """The convolution ``models.gated_conv.short_conv`` calls adds its
    taps in bfloat16: each tap's product and the running sum are
    rounded, where ``layers.causal_conv`` keeps them in f32."""
    import jax.numpy as jnp

    from horovod_tpu.models import gated_conv

    def rounded(x, w, b, segment):
        taps, t = w.shape[0], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        before = jnp.pad(segment, ((0, 0), (taps - 1, 0)),
                         constant_values=-1)
        y = jnp.zeros(x.shape, jnp.bfloat16)
        for tap in range(taps):
            same = before[:, tap:tap + t] == segment
            y = y + jnp.where(same[..., None], padded[:, tap:tap + t],
                              0.0).astype(jnp.bfloat16) * w[tap].astype(
                                  jnp.bfloat16)
        return (y + jnp.asarray(b, jnp.bfloat16)).astype(jnp.float32)

    gated_conv.causal_conv = rounded


def _limits_of(name, routed):
    """(distance, cosine) a gradient leaf is held to, by its name;
    ``routed`` the names' prefixes of the expert layers' groups."""
    def is_one_of(leaves):
        return any(f"'{leaf}'" in name for leaf in leaves)

    if is_one_of(ROUTER_LEAVES):
        return ROUTER_LEAF_DISTANCE, ROUTER_LEAF_COSINE
    if is_one_of(EXPERT_LEAVES) or (
            is_one_of(("norm2",)) and name.rsplit("[", 1)[0] in routed):
        return EXPERT_LEAF_DISTANCE, EXPERT_LEAF_COSINE
    return LEAF_DISTANCE, LEAF_COSINE


def own_input_failures(own):
    failures = []
    if not max(own["flipped"]) <= OWN_INPUT_FLIPPED_SHARE:
        failures.append(
            "on their own inputs the routers chose other experts for "
            f"{[round(100 * share, 3) for share in own['flipped']]} % of "
            "the tokens, layer by layer")
    for name, limit, what in (
            ("conv", CONV_OWN_INPUT_DISTANCE,
             "the gated convolution from the reference's taps"),
            ("conv_in_f32", CONV_IN_F32_DISTANCE,
             "the gated convolution in float32 from the reference's taps"),
            ("attention", ATTENTION_OWN_INPUT_DISTANCE,
             "the attention from the plain softmax"),
            ("attention_in_f32", ATTENTION_IN_F32_DISTANCE,
             "the attention in float32 from the plain softmax")):
        if not own[name] <= limit:
            failures.append(f"on its own inputs {what} lies "
                            f"{own[name]:.3e} (limit {limit})")
    return failures


def verdict(loss, ref_loss, leaves, buffers, own, update):
    failures = []
    if not update <= UPDATE_DISTANCE:
        failures.append(f"update distance {update:.4f}")
    if abs(loss - ref_loss) > LOSS_RTOL * abs(ref_loss):
        failures.append(f"loss {loss} against {ref_loss}")
    routed = {name.rsplit("[", 1)[0] for name in leaves
              if any(f"'{leaf}'" in name for leaf in ROUTER_LEAVES)}
    for name, (distance, cosine) in leaves.items():
        limit, least = _limits_of(name, routed)
        if not (distance <= limit and cosine >= least):
            failures.append(f"{name}: distance {distance:.3e} (limit "
                            f"{limit}), cosine {cosine:.6f} (limit {least})")
    failures += [f"{name}: a buffer's gradient of norm {norm:.3e}"
                 for name, norm in buffers.items() if norm != 0.0]
    return failures + own_input_failures(own)


def own_inputs(params, batch, config, sizes):
    """The three mechanisms on the step's own inputs (the module's
    docstring): ``{"flipped": [share a layer], "rows": [[rows an expert]
    a layer], "conv": distance, "attention": distance}`` and their
    ``_in_f32`` twins.  The layers' inputs are the program's own forward
    pass's (the step's functions, one more jitted program; bf16
    activations and all)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.builders import lfm2_moe_lm
    from benchmark.compare_kimi import _relative_distance
    from benchmark.reference import lfm2_moe as ref
    from horovod_tpu.models import gated_conv as gc
    from horovod_tpu.models.layers import rms_norm

    cfg = lfm2_moe_lm.model_config(config)
    f32 = lambda a: a.astype(jnp.float32)      # noqa: E731

    def in_f32(mechanism, operands):
        """The same numbers as float32 operands, every product at
        ``highest``: what is left is the mechanism's own arithmetic."""
        with jax.default_matmul_precision("highest"):
            return mechanism(*(f32(a) for a in operands))

    @jax.jit
    def seen(params, ids, segment):
        x = jnp.take(params["embed"], ids, axis=0).astype(
            jnp.dtype(cfg.compute_dtype))
        routed, conv, attention = [], None, None
        positions = jnp.arange(ids.shape[1])
        for mixer, ffn, p in ref.layers_of(params, sizes):
            u = rms_norm(x, p["norm1"], cfg.rms_norm_eps)
            if mixer == "conv" and conv is None:
                mine = {name: p[name] for name in (
                    "in_proj", "conv_w", "out_proj")}
                conv = (u, gc.conv_mixer(cfg, mine, u, segment), in_f32(
                    lambda u, *w: gc.conv_mixer(cfg, dict(zip(
                        mine, w)), u, segment), (u, *mine.values())))
            if mixer == "attention" and attention is None:
                operands = gc.attention_operands(cfg, p, u, positions)
                attention = (*operands, gc.attention(
                    cfg, *operands, segment), in_f32(
                        lambda *a: gc.attention(cfg, *a, segment),
                        operands))
            x = gc.mixer_half(cfg, mixer, p, x, segment)
            if ffn == "experts":
                u = rms_norm(x, p["norm2"], cfg.rms_norm_eps)
                x, routing = gc.expert_ffn(cfg, p, x)
                routed.append((u.reshape(-1, u.shape[-1]),
                               routing["experts"],
                               routing["rows_per_expert"]))
            else:
                x = gc.dense_ffn(cfg, p, x)
        return routed, conv, attention

    routed, conv, attention = seen(params, batch["x"], batch["segment"])

    # the routers
    weights_of = jax.jit(lambda p, u: ref.routing_weights(
        p, f32(u), sizes))
    layers = list(ref.layers_of(params, sizes))
    flipped = []
    for (_, _, p), (u, experts, _) in zip(
            [layer for layer in layers if layer[1] == "experts"], routed):
        plain = np.asarray(weights_of(p, u)) > 0
        chosen = np.zeros(plain.shape, bool)
        np.put_along_axis(chosen, np.asarray(experts), True, axis=1)
        flipped.append(float(np.mean((chosen != plain).any(axis=1))))

    # the first convolution layer: the same normed input through the
    # reference's taps, a row at a time
    p = next(p for mixer, _, p in layers if mixer == "conv")
    taps = jax.jit(lambda p, u, segment: ref.conv_mixer(
        p, f32(u), segment, sizes))
    u, out, out_f32 = conv
    plain = np.stack([np.asarray(taps(p, u[i], batch["segment"][i]))
                      for i in range(u.shape[0])])
    conv_distance = (_relative_distance(out, plain),
                     _relative_distance(out_f32, plain))

    # the attention: the same q, k, v through a plain f32 softmax, a
    # block of queries at a time
    softmax = jax.jit(lambda q, k, v, segment: ref.softmax_attention(
        f32(q), f32(k), f32(v), segment, sizes.query_block))
    q, k, v, out, out_f32 = attention
    plain = np.stack([
        np.asarray(softmax(q[i], k[i], v[i], batch["segment"][i]))
        for i in range(out.shape[0])])
    return {"flipped": flipped,
            "rows": [np.asarray(rows).tolist() for _, _, rows in routed],
            "conv": conv_distance[0], "conv_in_f32": conv_distance[1],
            "attention": _relative_distance(out, plain),
            "attention_in_f32": _relative_distance(out_f32, plain)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bf16-router", action="store_true",
                    help="make the router's scores from bfloat16 logits: "
                         "the comparison then has to fail")
    ap.add_argument("--bf16-scores", action="store_true",
                    help="round the attention's scores to bfloat16: the "
                         "comparison then has to fail")
    ap.add_argument("--bf16-taps", action="store_true",
                    help="sum the short convolution's taps in bfloat16: "
                         "the comparison then has to fail")
    ap.add_argument("--own-inputs-only", action="store_true",
                    help="only the three mechanisms on the step's own "
                         "inputs: no step, no reference gradient")
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

    from benchmark.compare_kimi import (
        round_the_attentions_scores_to_bfloat16, step_once)
    from benchmark.compare_nemotron import (
        keep_the_routers_scores_in_bfloat16, split_the_buffers)
    from benchmark.compare_sdar import compare_leaves, update_distance
    from benchmark.job import TrainJob
    from benchmark.reference import lfm2_moe as ref

    hvt.enable_compile_cache()
    hvt.init()
    device = jax.devices()[0]
    if not args.rehearse_on_cpu and (
            device.platform != "tpu" or len(jax.devices()) != cell.chips):
        print(f"compare_lfm2.py: the cell asks for {cell.chips} TPU "
              f"chip(s), found {device.platform}. Nothing was compared.",
              file=sys.stderr)
        return 2
    lowered = {"bf16_router": args.bf16_router,
               "bf16_scores": args.bf16_scores, "bf16_taps": args.bf16_taps}
    if args.bf16_router:
        keep_the_routers_scores_in_bfloat16()
    if args.bf16_scores:
        round_the_attentions_scores_to_bfloat16()
    if args.bf16_taps:
        sum_the_taps_in_bfloat16()
    config = cell.config
    job = TrainJob(cells.load_builder(config).build(config), config,
                   cell.traffic, args.seed)
    try:
        job.first_batch = next(job.batches)
        batch = {k: np.asarray(v) for k, v in job.first_batch.items()}
        if args.own_inputs_only:
            params = jax.tree_util.tree_map(
                np.asarray, job.fresh_state()[0])
        else:
            params, loss, grads, new_params = step_once(
                job, job.first_batch)
    finally:
        job.close()
    del job
    jax.clear_caches()      # the step's program and its buffers go

    sizes = sizes_of(config, blocks=not args.rehearse_on_cpu)
    own = own_inputs(params, batch, config, sizes)
    jax.clear_caches()
    even = (batch["x"].size * config["num_experts_per_tok"]
            // config["published"]["num_experts"])
    print(f"{prefix}routing: rows the held experts got on the first batch, "
          f"layer by layer {own['rows']} (an even routing gives each {even})")
    print(f"{prefix}on the step's own inputs: routers chose other experts "
          f"for {[round(100 * f, 4) for f in own['flipped']]} % of the "
          f"tokens, layer by layer (limit {100 * OWN_INPUT_FLIPPED_SHARE} "
          f"%); the gated convolution {own['conv']:.3e} from the "
          f"reference's taps (limit {CONV_OWN_INPUT_DISTANCE}) and "
          f"{own['conv_in_f32']:.3e} on float32 operands (limit "
          f"{CONV_IN_F32_DISTANCE}); the attention {own['attention']:.3e} "
          f"from the plain softmax (limit {ATTENTION_OWN_INPUT_DISTANCE}) "
          f"and {own['attention_in_f32']:.3e} on float32 operands (limit "
          f"{ATTENTION_IN_F32_DISTANCE}); lowered: "
          f"{[k for k, v in lowered.items() if v] or 'nothing'}")
    if args.own_inputs_only:
        failures = own_input_failures(own)
        print(f"{prefix}passed by {failures or 'nothing'}")
        hvt.shutdown()
        if not args.rehearse_on_cpu:
            print(json.dumps({"own_inputs": own, **lowered,
                              "agrees": not failures}))
        return 1 if failures else 0
    ref_batch = {**batch, "w": batch["w"].astype(np.float32)}
    ref_loss, ref_grads = ref.loss_and_gradient(params, ref_batch, sizes)

    got, want, buffers = split_the_buffers(grads, ref_grads)
    leaves = compare_leaves(got, want)
    rate = config["optimizer"]["learning_rate"]
    update = update_distance(params, new_params, ref_grads, rate)
    # the nearest precision below the configuration's param_dtype
    update_bf16 = update_distance(params, new_params, ref_grads, rate,
                                  store=jnp.bfloat16)
    failures = verdict(loss, ref_loss, leaves, buffers, own, update)
    failures_bf16 = verdict(loss, ref_loss, leaves, buffers, own,
                            update_bf16)
    documents = int(np.sum(batch["segment"][:, 1:] != batch["segment"][:, :-1])
                    ) + batch["segment"].shape[0]
    print(f"{prefix}batch: {documents} documents in "
          f"{batch['segment'].shape[0]} rows, {int(ref_batch['w'].sum())} "
          "weighted positions")
    print(f"{prefix}loss: system {loss}, reference {ref_loss} (relative "
          f"{abs(loss - ref_loss) / abs(ref_loss):.3e})")
    for name, (distance, cosine) in leaves.items():
        print(f"{prefix}gradient {name}: distance {distance:.3e} cosine "
              f"{cosine:.6f}")
    print(f"{prefix}buffers (the reference's gradient is exact zeros): "
          f"the step's gradient norms {buffers}")
    print(f"{prefix}update: distance {update:.4f} from -rate * reference "
          f"gradient; {update_bf16:.4f} in a bfloat16 store of the "
          "parameters")
    print(f"{prefix}limits: loss {LOSS_RTOL}, leaf distance "
          f"{LEAF_DISTANCE} ({ROUTER_LEAF_DISTANCE} for "
          f"{', '.join(ROUTER_LEAVES)}; {EXPERT_LEAF_DISTANCE} for "
          f"{', '.join(EXPERT_LEAVES)} and an expert layer's norm2), "
          f"cosine {LEAF_COSINE} "
          f"({ROUTER_LEAF_COSINE}; {EXPERT_LEAF_COSINE}), update "
          f"{UPDATE_DISTANCE}; passed by {failures or 'nothing'}; "
          f"in a bfloat16 store by {failures_bf16 or 'nothing'}")
    hvt.shutdown()
    if args.rehearse_on_cpu:
        print("REHEARSAL not a chip result")
        return 0
    print(json.dumps({
        "agrees": not failures, "lower_precision_fails": bool(failures_bf16),
        **lowered, "loss": loss, "reference_loss": ref_loss,
        "leaves": leaves, "buffers": buffers, "own_inputs": own,
        "update_distance": update,
        "update_distance_bf16_store": update_bf16,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())}}))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
