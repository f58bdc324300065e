"""The least time the chip could take for the state-space recurrence a
step requires where ``B`` and ``C`` come in groups
(``flops_hybrid_moe_lm``: the chunked form at the published chunk size
with ``C B^T`` a group, forward and both gradients, over the bf16 peak;
or the bytes a scan that keeps decays and states on the chip still
moves, ``B`` and ``C`` every group wide, over the HBM's peak; the larger
of the two) over the device time under ``hvtpu:ssm.scan``."""

from benchmark import flops_hybrid_moe_lm as flops
from benchmark import scopes
from benchmark.peaks import peaks

LAYER, UNIT, MOVES = "kernels", "%", "samples_per_s_per_chip"


def read(obs):
    ms = scopes.scoped_ms(obs, "hvtpu:ssm.scan")
    if not ms:
        return None
    tokens = obs.traffic["batch_per_chip"] * obs.traffic["sequence_length"]
    peak = peaks(obs.device_kind)
    least_s = max(
        flops.scan_train_flops_per_step(obs.config, tokens)
        / peak["bf16_flops_per_s"],
        flops.scan_train_bytes_per_step(obs.config, tokens)
        / peak["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least_s / ms
