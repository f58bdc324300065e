"""The least time the chip could take for what the looped stack requires
of a step (``flops_looped_lm``: every use of every layer held, its
projections, the attention pairs the mask allows at the law's mean and
its MLP, forward and both gradients, over the bf16 peak; compute
bound: a use's weights are read once for 8,192 tokens) over
``loop_stack_ms_per_step``."""

from benchmark import flops_looped_lm as flops
from benchmark.builders import hybrid_ssm_lm as packed
from benchmark.layer_metrics import loop_stack_ms_per_step
from benchmark.peaks import peaks

LAYER, UNIT, MOVES = "kernels", "%", "samples_per_s_per_chip"


def read(obs):
    ms = loop_stack_ms_per_step.stack_ms(obs)
    if not ms:
        return None
    required = flops.train_flops_per_step(
        obs.config, packed.expected_pairs_per_row(obs.config),
        obs.traffic["batch_per_chip"], flops.STACK)
    peak = peaks(obs.device_kind)["bf16_flops_per_s"]
    return 100.0 * (1e3 * required / peak) / ms
