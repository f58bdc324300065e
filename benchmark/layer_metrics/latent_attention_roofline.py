"""The least time the chip could take for the attention a step requires
where a head's keys are wider than its values
(``flops_kimi_linear_lm``: a score over 192 channels and a weighted
value over 128 for every pair *causal and same document* allows at the
law's mean, forward and both gradients, over the bf16 peak; compute
bound) over the device time under ``hvtpu:attention`` alone."""

from benchmark import flops_kimi_linear_lm as flops
from benchmark import scopes
from benchmark.builders import hybrid_ssm_lm as packed
from benchmark.peaks import peaks

LAYER, UNIT, MOVES = "kernels", "%", "samples_per_s_per_chip"


def read(obs):
    by_scope = scopes.ms_per_step(obs.trace, obs.compiled_text)
    ms = by_scope and by_scope.get("hvtpu:attention")
    if not ms:
        return None
    required = flops.attention_train_flops_per_step(
        obs.config, packed.expected_pairs_per_row(obs.config),
        obs.traffic["batch_per_chip"])
    peak = peaks(obs.device_kind)["bf16_flops_per_s"]
    return 100.0 * (1e3 * required / peak) / ms
