"""The least time the chip could take for the attention a step requires
where sixteen query heads read one key/value head
(``flops_hybrid_moe_lm``: a score and a weighted value for every pair
*causal and same document* allows at the law's mean, forward and both
gradients, over the bf16 peak; compute bound: a key/value block is read
once for sixteen heads of a query block) over
``wide_group_attention_ms_per_step``."""

from benchmark import flops_hybrid_moe_lm as flops
from benchmark.builders import hybrid_ssm_lm as packed
from benchmark.layer_metrics import wide_group_attention_ms_per_step
from benchmark.peaks import peaks

LAYER, UNIT, MOVES = "kernels", "%", "samples_per_s_per_chip"


def read(obs):
    ms = wide_group_attention_ms_per_step.read(obs)
    if not ms:
        return None
    required = flops.attention_train_flops_per_step(
        obs.config, packed.expected_pairs_per_row(obs.config),
        obs.traffic["batch_per_chip"])
    peak = peaks(obs.device_kind)["bf16_flops_per_s"]
    return 100.0 * (1e3 * required / peak) / ms
