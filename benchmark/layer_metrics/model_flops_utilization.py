"""Operations the forward and backward passes require per sample
(benchmark/flops.py, from the configuration's shapes) times samples per
second per chip, over the chip's bf16 peak.  End to end: it counts idle
time against the chip, and is no kernel's roofline share."""

from benchmark.end_to_end import samples_per_s_per_chip
from benchmark.peaks import peaks

LAYER, UNIT, MOVES = "train_step", "%", "samples_per_s_per_chip"


def read(obs):
    peak = peaks(obs.device_kind)["bf16_flops_per_s"]
    return (100.0 * obs.train_flops_per_sample
            * samples_per_s_per_chip.read(obs) / peak)
