"""The least time the chip could take for the ungated experts' products
a step requires (``flops_hybrid_moe_lm``: the rows the held experts get
at an even routing, two products a row forward and four of gradients,
over the bf16 peak; compute bound at 768 rows an expert: a weight is
read once for them) over the device time under ``hvtpu:moe.experts``
and in the kernels XLA makes of ``lax.ragged_dot`` under names of its
own (``routed_experts_ms_per_step.experts_ms``)."""

from benchmark import flops_hybrid_moe_lm as flops
from benchmark.layer_metrics import routed_experts_ms_per_step
from benchmark.peaks import peaks

LAYER, UNIT, MOVES = "kernels", "%", "samples_per_s_per_chip"


def read(obs):
    ms = routed_experts_ms_per_step.experts_ms(obs)
    if not ms:
        return None
    required = flops.expert_train_flops_per_step(
        obs.config, obs.traffic["batch_per_chip"])
    peak = peaks(obs.device_kind)["bf16_flops_per_s"]
    return 100.0 * (1e3 * required / peak) / ms
