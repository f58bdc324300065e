"""A looped decoder: one stack of layers walked ``total_ut_steps`` times
with one set of weights, an exit after every pass, trained under the
expected loss over the exits on packed documents: the ``ouro`` family
(Ouro-2.6B, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741).

Pure functions over a parameter tree, like ``block_diffusion`` and
``hybrid_ssm``, whose ``rms_norm``, ``rope`` and
``causal_document_attention`` it calls where they lie.

*The model* (``config.json`` of ``ByteDance/Ouro-2.6B`` and the
modelling code it names).  With ``N*`` an RMSNorm with its own scale::

    h_0      = E[ids]                       (no multipliers; E and W_head
                                             are two matrices)
    layer(h) : a = h + N2( Attn( N1(h) ) )
               out = a + N4( MLP( N3(a) ) )          (sandwich norms)
    Attn(u)  : q, k, v = W_q u, W_k u, W_v u  (no bias); rotate-half RoPE
               on q and k; softmax(q k^T / sqrt(head_dim)) over the keys
               at or before the query in its own document; W_o
    MLP(u)   : W_down( silu(W_gate u) * W_up u )
    for t = 1..T :  h_t = Nf( layer_L( ... layer_1( h_{t-1} ) ... ) )
                    logits_t = W_head h_t
                    lambda_t = sigmoid( w_g . h_t + b_g )
    p_t = lambda_t * prod_{j<t} (1 - lambda_j)   (t < T)
    p_T = prod_{j<T} (1 - lambda_j)
    loss = sum_pos w * [ sum_t p_t * CE_t - beta * H(p) ] / sum_pos w

the same ``L`` layers and the same ``Nf`` in every pass; ``CE_t`` the
next-token cross-entropy of ``logits_t``; ``H(p)`` the entropy of the
exit distribution at the position.  That is the paper's stage-I
objective: the expected task loss under the learned exit distribution
less ``beta`` times its entropy, a uniform prior over the exit steps.

*What* ``config.json`` *has no key for* (the configuration's file lists
each under ``assumed``): the four norms' placement (before and after
the attention, before and after the MLP, the residual added outside
them); that ``Nf``'s output is what the next pass starts from; the
gate's form (``Linear(hidden -> 1)`` with bias, read from ``Nf``'s
output; the last pass's gate is computed and takes no part: ``p_T`` is
what is left); ``beta``.

*How it is computed.*  One ``lax.scan`` over the passes whose body is
the ``lax.scan`` over the stacked layers: the parameters are closed
over, not carried, so a weight's gradient is the sum over its uses,
added up in float32 (its cast to the compute type is made inside the
use).  Every layer is recomputed in the backward pass, so what is kept
is a ``[B, T, D]`` boundary a layer and pass.  What closes a pass
(``Nf``, the gate, the exit's logits and cross-entropy) is made inside
the pass's body and made again in its backward pass (``jax.checkpoint``):
no more than one exit's f32 ``[B, T, V]`` logits live at a time,
forward or backward.  bf16 products over f32 parameters; norms,
softmax, logits, ``logsumexp``, gates, ``p_t``, entropy and loss in
f32.

*Documents.*  A row is several documents back to back; ``segment``
gives the document's index at every position and a query sees only
keys of its own document.  Positions count along the row: RoPE's
scores depend on ``q_pos - k_pos`` alone, so positions restarted at
each document give the same scores inside a document (a test holds
the equivalence).  ``w`` is 0 where the next token belongs to another
document or lies past the row's end.

*Not here*: exit by the gate's threshold at inference
(``early_exit_threshold``: the repository has no serving path); a
sliding window (``use_sliding_window`` false in the published file).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import metrics
from .block_diffusion import rms_norm, rope
from .hybrid_ssm import causal_document_attention

Params = Dict[str, Any]

# queries of a tile of the attention where it runs in XLA: the f32
# scores of a tile against every earlier key, 16 heads x 256 x 8,192,
# are 134 MB a row
_ATTENTION_TILE = 256


@dataclasses.dataclass(frozen=True)
class LoopedConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int              # layers held; each runs total_ut_steps times
    mlp_width: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    rms_norm_eps: float
    total_ut_steps: int          # passes over the stack, an exit after each
    entropy_weight: float        # beta
    compute_dtype: str = "bfloat16"


def init_params(key, cfg: LoopedConfig) -> Params:
    """Float32 parameters: normal(0.02) matrices, embedding and gate
    weight, unit norm scales, a zero gate bias (so ``lambda`` starts
    near a half).  ``mlp_in`` holds ``W_gate`` and ``W_up`` side by
    side."""
    d, f, n = cfg.hidden_size, cfg.mlp_width, cfg.num_layers
    n_q, n_kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim

    def normal(k, shape):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)

    ks = jax.random.split(key, 9)
    layers = {name: jnp.ones((n, d), jnp.float32)
              for name in ("norm1", "norm2", "norm3", "norm4")}
    layers.update(wq=normal(ks[0], (n, d, n_q)),
                  wk=normal(ks[1], (n, d, n_kv)),
                  wv=normal(ks[2], (n, d, n_kv)),
                  wo=normal(ks[3], (n, n_q, d)),
                  mlp_in=normal(ks[4], (n, d, 2 * f)),
                  mlp_out=normal(ks[5], (n, f, d)))
    return {"embed": normal(ks[6], (cfg.vocab_size, d)),
            "head": normal(ks[7], (d, cfg.vocab_size)),
            "final_norm": jnp.ones((d,), jnp.float32),
            "gate_w": normal(ks[8], (d,)),
            "gate_b": jnp.zeros((1,), jnp.float32),
            "layers": layers}


def layer(cfg: LoopedConfig, p: Params, h, segment, positions):
    """One layer on ``h`` ``[B, T, D]``: ``p`` is its slice of the
    stacked parameters."""
    b, t, _ = h.shape
    dtype, eps = h.dtype, cfg.rms_norm_eps
    with jax.named_scope("hvtpu:loop.proj"):
        u = rms_norm(h, p["norm1"], eps)

        def heads(w, count):
            return (u @ w.astype(dtype)).reshape(b, t, count, cfg.head_dim)

        q = rope(heads(p["wq"], cfg.num_heads), positions, cfg.rope_theta)
        k = rope(heads(p["wk"], cfg.num_kv_heads), positions, cfg.rope_theta)
        v = heads(p["wv"], cfg.num_kv_heads)
    o = causal_document_attention(
        q, k, v, segment, scale=cfg.head_dim ** -0.5, tile=_ATTENTION_TILE)
    with jax.named_scope("hvtpu:loop.proj"):
        a = h + rms_norm(o.reshape(b, t, -1) @ p["wo"].astype(dtype),
                         p["norm2"], eps)
    with jax.named_scope("hvtpu:loop.mlp"):
        u = rms_norm(a, p["norm3"], eps)
        g, v = jnp.split(u @ p["mlp_in"].astype(dtype), 2, axis=-1)
        return a + rms_norm((jax.nn.silu(g) * v) @ p["mlp_out"].astype(dtype),
                            p["norm4"], eps)


def _by_pass(params: Params, ids, cfg: LoopedConfig, segment, at_exit):
    """What ``at_exit`` makes of ``h_t``, the normed output of pass
    ``t``, stacked over the passes."""
    if segment is None:
        segment = jnp.zeros(ids.shape, jnp.int32)
    metrics.note_loop_layer_uses(cfg.num_layers * cfg.total_ut_steps)
    positions = jnp.arange(ids.shape[1])
    with jax.named_scope("hvtpu:lm_head"):
        h = jnp.take(params["embed"], ids, axis=0).astype(
            jnp.dtype(cfg.compute_dtype))
    one_layer = jax.checkpoint(
        lambda h, p: (layer(cfg, p, h, segment, positions), None))

    @jax.checkpoint
    def close(h):
        with jax.named_scope("hvtpu:loop.exit"):
            h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        return h, at_exit(h)

    def one_pass(h, _):
        return close(lax.scan(one_layer, h, params["layers"])[0])

    return lax.scan(one_pass, h, None, length=cfg.total_ut_steps)[1]


def hidden_states_by_pass(params: Params, ids, cfg: LoopedConfig,
                          segment: Optional[jax.Array] = None):
    """``ids`` ``[B, T]`` -> ``h_t`` for every pass, ``[passes, B, T,
    D]``.  Without ``segment`` a row is one document."""
    return _by_pass(params, ids, cfg, segment, lambda h: h)


def gate_logits(params: Params, h):
    """f32 ``w_g . h + b_g`` of ``h`` ``[..., D]``."""
    with jax.named_scope("hvtpu:loop.exit"):
        return jnp.sum(h.astype(jnp.float32) * params["gate_w"],
                       axis=-1) + params["gate_b"][0]


def exit_cross_entropy(head, h, label):
    """f32 next-token cross-entropy ``[B, T]`` of the logits ``h
    W_head``.  The label's logit is picked by comparing an index with
    the label, so that its gradient is a selection and no scatter into
    ``[B, T, V]`` zeros."""
    with jax.named_scope("hvtpu:lm_head"):
        logits = jnp.dot(h, head.astype(h.dtype),
                         preferred_element_type=jnp.float32)
        at_label = lax.broadcasted_iota(
            jnp.int32, logits.shape, logits.ndim - 1) == label[..., None]
        return jax.nn.logsumexp(logits, axis=-1) - jnp.sum(
            jnp.where(at_label, logits, 0.0), axis=-1)


def exit_distribution(logits):
    """``(p, log p)`` over the exits from the gates' logits ``[passes,
    ...]``, in f32: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` and the
    last exit takes what is left (its own gate takes no part), so ``p``
    sums to one.  Made from ``log lambda`` and ``log (1 - lambda)``: no
    product underflows to a zero whose logarithm the entropy would
    need."""
    with jax.named_scope("hvtpu:loop.exit"):
        logits = logits.astype(jnp.float32)
        none = jnp.zeros_like(logits[:1])
        # log prod_{j<t} (1 - lambda_j) for every t, the first an empty one
        stayed = jnp.concatenate(
            [none, jnp.cumsum(jax.nn.log_sigmoid(-logits[:-1]), axis=0)])
        log_p = stayed + jnp.concatenate(
            [jax.nn.log_sigmoid(logits[:-1]), none])
        return jnp.exp(log_p), log_p


def expected_exit_loss(params: Params, batch, cfg: LoopedConfig):
    """``batch``: ``x`` int ``[B, T]``, ``segment`` (the document's
    index at every position) and ``w``, the weight of position ``t``'s
    prediction of ``x[t + 1]``.  Returns the objective, ``sum_pos w [
    sum_t p_t CE_t - beta H(p) ] / sum_pos w``, and the batch's weighted
    means of ``p_t`` and of ``CE_t`` for every exit, ``{"loop_exit_mass",
    "loop_exit_loss"}`` f32 ``[passes]``
    (``obs.metrics.note_loop_exits``)."""
    x = batch["x"]
    label = jnp.roll(x, -1, axis=1)

    def at_exit(h):
        return gate_logits(params, h), exit_cross_entropy(
            params["head"], h, label)

    gates, ce = _by_pass(params, x, cfg, batch["segment"], at_exit)
    p, log_p = exit_distribution(gates)
    with jax.named_scope("hvtpu:loop.exit"):
        w = batch["w"].astype(jnp.float32)
        total = jnp.sum(w)
        entropy = -jnp.sum(p * log_p, axis=0)
        loss = jnp.sum(w * (jnp.sum(p * ce, axis=0)
                            - cfg.entropy_weight * entropy)) / total
        return loss, {"loop_exit_mass": jnp.sum(w * p, axis=(1, 2)) / total,
                      "loop_exit_loss": jnp.sum(w * ce, axis=(1, 2)) / total}
