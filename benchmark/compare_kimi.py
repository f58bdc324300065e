#!/usr/bin/env python3
"""The timed step of a cell of Kimi Linear's layers (delta-rule linear
attention with a decay a channel, latent attention, a dense MLP, sparse
experts beside a shared expert) against the plain reference, at the
cell's own sizes, on the chip.

    python3 benchmark/compare_kimi.py --workload <cell> --seed <n>

Builds the cell's ``TrainJob`` as ``run.py`` does, so what is compared
is what the timed path itself produces: from ``fresh_state()`` one
``job.step`` on the first batch (``compare_nemotron.py`` is the method,
``compare_sdar.py`` has the helpers).  Its loss is held to the
reference's (``benchmark/reference/kimi_linear.py``: float32, the delta
rule one position at a time, a dense mask, every held expert over every
token with a 0/1 choice, every product at ``highest``; given the same
share: the experts held, their first index, the slice of the
vocabulary), and so is its gradient, which after one step of SGD from
zero momentum *is* the momentum, leaf by leaf (relative L2 distance and
cosine), and the step's update of the parameters against
``-learning_rate * reference gradient``.  The selection bias is a
buffer: the reference's gradient of it is exact zeros, and the step's
has to be.

Beside the step, three mechanisms are held to the reference **on the
step's own inputs** (``own_inputs``: one more jitted program of the
model's own functions over the first batch): every expert layer's normed
input routed by the program's router and by the reference's, the share
of tokens whose eight experts differ; the first KDA layer's ``q, k, v,
g, beta`` as the program makes them through ``chunked_delta_rule`` and
through the reference's recurrence a token at a time, the relative
distance of the two ``o``; the MLA layer's ``q, k, v`` through
``causal_document_attention`` and through a plain f32 softmax, the
relative distance of the two results.  The last two twice: as the step
runs them (bfloat16 operands), and with the same numbers as float32
operands at ``highest``, where what is left is the mechanism's own
arithmetic (the cumulative sums, the decays, the triangular system; the
scores and the softmax) and not the rounding of its products.  A
gradient leaf cannot tell these precisions apart (PERF.md, finding (6)
of PR 38: a leaf's distance is set by what bfloat16 hidden states flip
upstream); the same numbers through both can.

Five precisions lie next below the configuration's, and each has to
fail a limit:

* a bfloat16 *store of the parameters*: the step's own old and new
  parameters, rounded to such a store, give the update's distance a
  second reading near 1, a state left as it was.  ``UPDATE_DISTANCE``
  lies between the two readings.
* bfloat16 *cumulative log-decays* (``--bf16-decay``: the sums of ``g``
  along a chunk are made and kept in bfloat16, by a patch of
  ``models.kimi_linear``): fails ``DELTA_IN_F32_DISTANCE``.
* a bfloat16 *triangular solve* (``--bf16-solve``: the system is
  rounded to bfloat16 and the products of ``unit_lower_inverse`` take
  bfloat16 operands): fails ``DELTA_IN_F32_DISTANCE``.
* bfloat16 *router logits* (``--bf16-router``: ``compare_nemotron``'s
  patch of ``parallel.moe``): fails ``OWN_INPUT_FLIPPED_SHARE``.
* bfloat16 *attention scores* (``--bf16-scores``: a pair's scores are
  rounded to bfloat16 before the mask and the softmax, by a patch of
  ``ops.flash_attention``): fails ``ATTENTION_IN_F32_DISTANCE``.

``--own-inputs-only`` makes the three mechanisms' measures alone (under
two minutes, no step, no reference gradient), which is where the four
flags show.

The limits, and why (readings on the chip at the published widths,
seeds 11, 12 and 13, smallest ... largest; the lowered precisions on
seed 11: PERF.md, findings of PR 40):

* ``UPDATE_DISTANCE`` 0.3: ``|dp - dp_ref| / |dp_ref|`` over all
  parameters read 0.0372 ... 0.0383, and 0.9757 ... 0.9760 in a
  bfloat16 store (a state left as it was reads 1: at a rate of 0.01 the
  change of a weight of 0.02 is below bfloat16's eighth bit): the norm
  of the change between the first reading and 1, the more room above
  the reading.
* ``LOSS_RTOL`` 5e-4, the limit of ``compare_sdar.py``: both losses are
  means of some 16,370 f32 cross-entropies of f32 logits over bf16
  hidden states; read 9.6e-6 ... 2.1e-5.
* ``LEAF_DISTANCE`` 0.25 / ``LEAF_COSINE`` 0.97, every leaf that has no
  limit of its own below: read 0.0086 ... 0.0847 (the last KDA layer's
  ``conv_w``; ``A_log`` and ``dt_bias``, which get their gradient
  through the decays alone, 0.030 ... 0.065), cosines 0.99642 and
  above: three times the largest reading.
* ``EXPERT_LEAF_DISTANCE`` 0.4 / ``EXPERT_LEAF_COSINE`` 0.9, the held
  experts' ``w_gate``, ``w_up`` and ``w_down``: read 0.178 ... 0.228,
  cosines 0.9740 and above, growing with depth.  An expert's gradient is
  a sum over the 61 to 1,755 rows it got (512 at an even routing), and
  the bf16 hidden states before a router flip some tokens' eighth
  choice against the float32 reference's.
* ``ROUTER_LEAF_DISTANCE`` 0.5 / ``ROUTER_LEAF_COSINE`` 0.85: read
  0.233 ... 0.310 and 0.9523 and above.  A router gets its gradient
  through the weights of the chosen experts held here alone, 8 of 256,
  so every flipped token moves it (``compare_nemotron.py`` says the
  same of its routers); it is the flips, not a fault, that set it.
* ``OWN_INPUT_FLIPPED_SHARE`` 0.003: read 0 of 16,384 tokens in every
  layer on all three seeds, and 1.50 ... 1.89 % of the tokens a layer
  with the logits in bfloat16 (two runs).
* ``DELTA_OWN_INPUT_DISTANCE`` 0.009: read 0.00303 ... 0.00310 (what the
  bfloat16 operands of the walk's products leave): three times the
  reading; it is there for a wrong rule, and no lowered precision aims
  at it (bfloat16 log-decays read 0.00337 here, a bfloat16 solve
  0.00311).
* ``DELTA_IN_F32_DISTANCE`` 1.5e-4: read 4.8e-5 ... 6.2e-5 (8,192
  positions of f32 exponentials and sums against the recurrence's);
  3.2e-4 with the triangular solve from bfloat16 operands and 1.3e-3
  with the cumulative log-decays in bfloat16: between the largest
  reading and the smaller of the two, 2.4 times the one, under half the
  other.
* ``ATTENTION_OWN_INPUT_DISTANCE`` 0.005: read 0.00169 ... 0.00171 (the
  bfloat16 probabilities and result): three times the reading.
* ``ATTENTION_IN_F32_DISTANCE`` 1e-5: read 1.9e-7 ... 2.0e-7; 3.9e-4
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
DELTA_OWN_INPUT_DISTANCE = 0.009
DELTA_IN_F32_DISTANCE = 1.5e-4
ATTENTION_OWN_INPUT_DISTANCE = 0.005
ATTENTION_IN_F32_DISTANCE = 1e-5
# positions of the recurrence and queries of the attention the reference
# computes at a time (and recomputes in its backward pass)
TIME_BLOCK = 128
QUERY_BLOCK = 512


def sizes_of(config, blocks: bool):
    from benchmark.builders import kimi_linear_lm
    from benchmark.reference import kimi_linear as ref

    mixers, ffns = kimi_linear_lm.layer_kinds(config)
    return ref.Sizes(
        mixers=mixers, ffns=ffns,
        kda_heads=config["linear_attn_config"]["num_heads"],
        mla_heads=config["num_attention_heads"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        first_expert=config["deployment"]["first_expert"],
        top_k=config["num_experts_per_token"],
        renormalise=config["moe_renormalize"],
        routed_scaling_factor=config["routed_scaling_factor"],
        rms_norm_eps=config["rms_norm_eps"],
        time_block=TIME_BLOCK if blocks else None,
        query_block=QUERY_BLOCK if blocks else None,
        recompute_layers=blocks)


# -- the precisions next below ------------------------------------------------

def keep_the_log_decays_sums_in_bfloat16():
    """Inside ``models.kimi_linear`` the cumulative sums of the
    log-decays along a chunk are made and kept in bfloat16 (the module's
    ``jnp`` is replaced by one whose ``cumsum`` rounds; nothing else of
    it calls ``cumsum``)."""
    import jax.numpy as jnp

    from horovod_tpu.models import kimi_linear

    class RoundedSums:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def cumsum(a, axis):
            return jnp.cumsum(a.astype(jnp.bfloat16), axis=axis).astype(
                jnp.float32)

    kimi_linear.jnp = RoundedSums()


def solve_the_chunks_systems_in_bfloat16():
    """``unit_lower_inverse``'s products take bfloat16 operands (and add
    up in f32), the system itself rounded to bfloat16 first: while it
    runs, the module's ``_product`` is one that rounds."""
    import jax.numpy as jnp

    from horovod_tpu.models import kimi_linear

    exact, inverse = kimi_linear._product, kimi_linear.unit_lower_inverse

    def rounded(spec, a, b):
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    def lowered(a):
        kimi_linear._product = rounded
        try:
            return inverse(a.astype(jnp.bfloat16).astype(jnp.float32))
        finally:
            kimi_linear._product = exact

    kimi_linear.unit_lower_inverse = lowered


def round_the_attentions_scores_to_bfloat16():
    """A pair's scores are rounded to bfloat16 before the mask and the
    softmax, in the kernels (``ops.flash_attention._scores``)."""
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention

    exact = flash_attention._scores

    def rounded(a, b, scale, keep, mask_value):
        s = exact(a, b, scale, None, mask_value).astype(
            jnp.bfloat16).astype(jnp.float32)
        return s if keep is None else jnp.where(keep, s, mask_value)

    flash_attention._scores = rounded


def _limits_of(name):
    """(distance, cosine) a gradient leaf is held to, by its name."""
    def is_one_of(leaves):
        return any(f"'{leaf}'" in name for leaf in leaves)

    if is_one_of(ROUTER_LEAVES):
        return ROUTER_LEAF_DISTANCE, ROUTER_LEAF_COSINE
    if is_one_of(EXPERT_LEAVES):
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
            ("delta", DELTA_OWN_INPUT_DISTANCE,
             "the chunked delta rule from the recurrence"),
            ("delta_in_f32", DELTA_IN_F32_DISTANCE,
             "the chunked delta rule in float32 from the recurrence"),
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
    for name, (distance, cosine) in leaves.items():
        limit, least = _limits_of(name)
        if not (distance <= limit and cosine >= least):
            failures.append(f"{name}: distance {distance:.3e} (limit "
                            f"{limit}), cosine {cosine:.6f} (limit {least})")
    failures += [f"{name}: a buffer's gradient of norm {norm:.3e}"
                 for name, norm in buffers.items() if norm != 0.0]
    return failures + own_input_failures(own)


def step_once(job, batch):
    """One ``job.step`` from ``fresh_state()``: the parameters it started
    from, the loss, the gradient (the momentum after one step from zero)
    and the new parameters, all on the host.  ``compare_sdar.system_step``
    with one copy of the parameters on the chip, not two: this cell's
    step is compiled to the chip's whole memory (PERF.md, findings of PR
    40) and finds no room beside a second copy."""
    import jax
    import numpy as np
    import optax

    params, model_state, opt_state = job.fresh_state()
    before = jax.tree_util.tree_map(np.asarray, params)
    new_params, _, opt_state, loss = job.step(
        params, model_state, opt_state, batch)
    del params, model_state
    grads = optax.tree_utils.tree_get(opt_state, "trace")
    return (before, float(loss), jax.tree_util.tree_map(np.asarray, grads),
            jax.tree_util.tree_map(np.asarray, new_params))


def _relative_distance(got, want):
    import numpy as np

    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def own_inputs(params, batch, config, sizes):
    """The three mechanisms on the step's own inputs (the module's
    docstring): ``{"flipped": [share a layer], "rows": [[rows an expert]
    a layer], "delta": distance, "attention": distance}``.  The layers'
    inputs are the program's own forward pass's (the step's functions,
    one more jitted program; bf16 activations and all)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.builders import kimi_linear_lm
    from benchmark.reference import kimi_linear as ref
    from horovod_tpu.models import kimi_linear as kl
    from horovod_tpu.models.block_diffusion import rms_norm

    cfg = kimi_linear_lm.model_config(config)

    def in_f32(mechanism, operands):
        """The same numbers as float32 operands, every product at
        ``highest``: what is left is the mechanism's own arithmetic."""
        with jax.default_matmul_precision("highest"):
            return mechanism(*(a.astype(jnp.float32) for a in operands))

    @jax.jit
    def seen(params, ids, segment):
        x = jnp.take(params["embed"], ids, axis=0).astype(
            jnp.dtype(cfg.compute_dtype))
        routed, delta, attention = [], None, None
        for mixer, ffn, p in ref.layers_of(params, sizes):
            u = rms_norm(x, p["norm1"], cfg.rms_norm_eps)
            if mixer == "kda" and delta is None:
                operands = kl.kda_operands(cfg, p, u, segment)
                delta = (*operands, kl.chunked_delta_rule(
                    *operands, segment, cfg.chunk_size), in_f32(
                        lambda *a: kl.chunked_delta_rule(
                            *a, segment, cfg.chunk_size), operands))
            if mixer == "mla" and attention is None:
                operands = kl.mla_operands(cfg, p, u)
                attention = (*operands, kl.mla_attention(
                    cfg, *operands, segment), in_f32(
                        lambda *a: kl.mla_attention(cfg, *a, segment),
                        operands))
            x = kl.mixer_half(cfg, mixer, p, x, segment)
            if ffn == "experts":
                u = rms_norm(x, p["norm2"], cfg.rms_norm_eps)
                x, routing = kl.expert_ffn(cfg, p, x)
                routed.append((u.reshape(-1, u.shape[-1]),
                               routing["experts"],
                               routing["rows_per_expert"]))
            else:
                x = kl.dense_ffn(cfg, p, x)
        return routed, delta, attention

    routed, delta, attention = seen(params, batch["x"], batch["segment"])

    # the routers
    weights_of = jax.jit(lambda p, u: ref.routing_weights(
        p, u.astype(jnp.float32), sizes))
    expert_layers = [p for _, ffn, p in ref.layers_of(params, sizes)
                     if ffn == "experts"]
    flipped = []
    for p, (u, experts, _) in zip(expert_layers, routed):
        plain = np.asarray(weights_of(p, u)) > 0
        chosen = np.zeros(plain.shape, bool)
        np.put_along_axis(chosen, np.asarray(experts), True, axis=1)
        flipped.append(float(np.mean((chosen != plain).any(axis=1))))

    # the delta rule: the same q, k, v, g, beta a token at a time, a row
    # at a time
    f32 = lambda a: a.astype(jnp.float32)      # noqa: E731
    recurrence = jax.jit(lambda q, k, v, g, beta, segment: ref.delta_rule(
        f32(q), f32(k), f32(v), g, beta, ref.first_of_a_document(segment),
        sizes.time_block))
    *operands, out, out_f32 = delta
    plain = np.stack([
        np.asarray(recurrence(*(a[i] for a in operands),
                              batch["segment"][i]))
        for i in range(out.shape[0])])
    delta_distance = (_relative_distance(out, plain),
                      _relative_distance(out_f32, plain))

    # the attention: the same q, k, v through a plain f32 softmax, a
    # block of queries at a time
    @jax.jit
    def softmax_attention(q, k, v, segment):
        with jax.default_matmul_precision("highest"):
            q, k, v = f32(q), f32(k), f32(v)
            t = q.shape[0]
            step = min(sizes.query_block or t, t)
            mask = ref.dense_mask(segment)

            def rows(block):
                q_rows, mask_rows = block
                s = jnp.einsum("qhd,khd->hqk", q_rows, k) * (
                    k.shape[-1] ** -0.5)
                s = jnp.where(mask_rows[None], s, -jnp.inf)
                return jnp.einsum("hqk,khd->qhd",
                                  jax.nn.softmax(s, axis=-1), v)

            return jax.lax.map(rows, (
                q.reshape(t // step, step, *q.shape[1:]),
                mask.reshape(t // step, step, t))).reshape(
                    t, *v.shape[1:])

    q, k, v, out, out_f32 = attention
    plain = np.stack([
        np.asarray(softmax_attention(q[i], k[i], v[i], batch["segment"][i]))
        for i in range(out.shape[0])])
    return {"flipped": flipped,
            "rows": [np.asarray(rows).tolist() for _, _, rows in routed],
            "delta": delta_distance[0], "delta_in_f32": delta_distance[1],
            "attention": _relative_distance(out, plain),
            "attention_in_f32": _relative_distance(out_f32, plain)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bf16-decay", action="store_true",
                    help="keep the chunks' cumulative log-decays in "
                         "bfloat16: the comparison then has to fail")
    ap.add_argument("--bf16-solve", action="store_true",
                    help="solve the chunks' triangular systems from "
                         "bfloat16 operands: the comparison then has to fail")
    ap.add_argument("--bf16-router", action="store_true",
                    help="make the router's scores from bfloat16 logits: "
                         "the comparison then has to fail")
    ap.add_argument("--bf16-scores", action="store_true",
                    help="round the attention's scores to bfloat16: the "
                         "comparison then has to fail")
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

    from benchmark.compare_nemotron import (
        keep_the_routers_scores_in_bfloat16, split_the_buffers)
    from benchmark.compare_sdar import compare_leaves, update_distance
    from benchmark.job import TrainJob
    from benchmark.reference import kimi_linear as ref

    hvt.enable_compile_cache()
    hvt.init()
    device = jax.devices()[0]
    if not args.rehearse_on_cpu and (
            device.platform != "tpu" or len(jax.devices()) != cell.chips):
        print(f"compare_kimi.py: the cell asks for {cell.chips} TPU "
              f"chip(s), found {device.platform}. Nothing was compared.",
              file=sys.stderr)
        return 2
    lowered = {"bf16_decay": args.bf16_decay, "bf16_solve": args.bf16_solve,
               "bf16_router": args.bf16_router,
               "bf16_scores": args.bf16_scores}
    if args.bf16_decay:
        keep_the_log_decays_sums_in_bfloat16()
    if args.bf16_solve:
        solve_the_chunks_systems_in_bfloat16()
    if args.bf16_router:
        keep_the_routers_scores_in_bfloat16()
    if args.bf16_scores:
        round_the_attentions_scores_to_bfloat16()
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
    even = (batch["x"].size * config["num_experts_per_token"]
            // config["published"]["num_experts"])
    print(f"{prefix}routing: rows the held experts got on the first batch, "
          f"layer by layer {own['rows']} (an even routing gives each {even})")
    print(f"{prefix}on the step's own inputs: routers chose other experts "
          f"for {[round(100 * f, 4) for f in own['flipped']]} % of the "
          f"tokens, layer by layer (limit {100 * OWN_INPUT_FLIPPED_SHARE} "
          f"%); the chunked delta rule {own['delta']:.3e} from the "
          f"recurrence (limit {DELTA_OWN_INPUT_DISTANCE}) and "
          f"{own['delta_in_f32']:.3e} on float32 operands (limit "
          f"{DELTA_IN_F32_DISTANCE}); the attention {own['attention']:.3e} "
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
          f"{', '.join(EXPERT_LEAVES)}), cosine {LEAF_COSINE} "
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
