"""The least time the chip could take for the heads a step requires
(``flops_looped_lm``: the whole vocabulary against every token after
every pass, forward and both gradients, over the bf16 peak; compute
bound) over the device time under ``hvtpu:lm_head`` in the looped
cell."""

from benchmark import flops_looped_lm as flops
from benchmark import scopes
from benchmark.peaks import peaks

LAYER, UNIT, MOVES = "kernels", "%", "samples_per_s_per_chip"


def read(obs):
    if scopes.scoped_ms(obs, "hvtpu:loop.") is None:
        return None               # another program's head
    ms = scopes.scoped_ms(obs, "hvtpu:lm_head")
    if not ms:
        return None
    required = flops.train_flops_per_step(
        obs.config, 0.0, obs.traffic["batch_per_chip"], flops.EXITS)
    peak = peaks(obs.device_kind)["bf16_flops_per_s"]
    return 100.0 * (1e3 * required / peak) / ms
