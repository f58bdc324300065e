"""The least time the chip could take for the expert products a step
requires (``flops_block_diffusion_lm``: the rows the held experts get
at an even routing, three products a row, forward and both gradients,
over the bf16 peak; compute bound at 2,048 rows an expert) over the
device time under ``hvtpu:moe.experts``."""

from benchmark import flops_block_diffusion_lm as flops
from benchmark import scopes
from benchmark.peaks import peaks

LAYER, UNIT, MOVES = "kernels", "%", "samples_per_s_per_chip"


def read(obs):
    ms = scopes.scoped_ms(obs, "hvtpu:moe.experts")
    if not ms:
        return None
    required = flops.expert_train_flops_per_step(
        obs.config, obs.traffic["batch_per_chip"])
    peak = peaks(obs.device_kind)["bf16_flops_per_s"]
    return 100.0 * (1e3 * required / peak) / ms
