"""The least time the chip could take for the gated convolution a step
requires (``flops_lfm2_moe_lm``: its taps and its two gates, forward and
both gradients, over the bf16 peak; or the bytes an implementation that
makes one pass must move, ``b, c, x`` read and ``y`` written forward,
``b, c, x, dy`` read and their gradients written backward, over the
HBM's peak; the larger of the two) over the device time under
``hvtpu:shortconv.conv``: the same work whatever implements it."""

from benchmark import flops_lfm2_moe_lm as flops
from benchmark import scopes
from benchmark.peaks import peaks

LAYER, UNIT, MOVES = "kernels", "%", "samples_per_s_per_chip"


def read(obs):
    by_scope = scopes.ms_per_step(obs.trace, obs.compiled_text)
    ms = by_scope and by_scope.get("hvtpu:shortconv.conv")
    if not ms:
        return None
    tokens = obs.traffic["batch_per_chip"] * obs.traffic["sequence_length"]
    peak = peaks(obs.device_kind)
    least_s = max(
        flops.short_conv_train_flops_per_step(obs.config, tokens)
        / peak["bf16_flops_per_s"],
        flops.short_conv_train_bytes_per_step(obs.config, tokens)
        / peak["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least_s / ms
