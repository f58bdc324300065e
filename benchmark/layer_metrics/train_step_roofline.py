"""The least time the chip could take for one step's required
operations (compute bound: FLOPs over the bf16 peak; the bytes a whole
step must move are not a function of its shapes) over the step
program's measured device time.  Stands in for a kernel's share until a
cell's step reaches a kernel of ops/."""

from benchmark.peaks import peaks

LAYER, UNIT, MOVES = "kernels", "%", "samples_per_s_per_chip"


def read(obs):
    if not obs.trace:
        return None
    peak = peaks(obs.device_kind)["bf16_flops_per_s"]
    least_ms = (1e3 * obs.train_flops_per_sample
                * obs.samples_per_step_per_chip / peak)
    return 100.0 * least_ms / obs.trace.device_step_ms
