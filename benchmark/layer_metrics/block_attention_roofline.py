"""The least time the chip could take for the masked attention a step
requires (``flops_block_diffusion_lm``: the pairs the mask allows,
forward and both gradients, over the bf16 peak; compute bound: keys and
values are read once a query tile) over the device time under
``hvtpu:attention``."""

from benchmark import flops_block_diffusion_lm as flops
from benchmark import scopes
from benchmark.peaks import peaks

LAYER, UNIT, MOVES = "kernels", "%", "samples_per_s_per_chip"


def read(obs):
    ms = scopes.scoped_ms(obs, "hvtpu:attention")
    if not ms:
        return None
    required = flops.attention_train_flops_per_step(
        obs.config, obs.traffic["batch_per_chip"])
    peak = peaks(obs.device_kind)["bf16_flops_per_s"]
    return 100.0 * (1e3 * required / peak) / ms
