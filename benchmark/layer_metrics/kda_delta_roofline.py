"""The least time the chip could take for the delta rule a step requires
(``flops_kimi_linear_lm``: the fewest products of the chunked form at
the configuration's chunk, forward and both gradients, over the bf16
peak; or the bytes an implementation that keeps decays, systems and
states on the chip still moves, ``q, k, v, g, beta, o`` once forward,
they and their gradients once backward, over the HBM's peak; the larger
of the two) over the device time under ``hvtpu:kda.delta``: the same
work whatever implements it."""

from benchmark import flops_kimi_linear_lm as flops
from benchmark import scopes
from benchmark.peaks import peaks

LAYER, UNIT, MOVES = "kernels", "%", "samples_per_s_per_chip"


def read(obs):
    ms = scopes.scoped_ms(obs, "hvtpu:kda.delta")
    if not ms:
        return None
    tokens = obs.traffic["batch_per_chip"] * obs.traffic["sequence_length"]
    peak = peaks(obs.device_kind)
    least_s = max(
        flops.delta_train_flops_per_step(obs.config, tokens)
        / peak["bf16_flops_per_s"],
        flops.delta_train_bytes_per_step(obs.config, tokens)
        / peak["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least_s / ms
