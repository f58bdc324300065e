"""Device time a step under the expert layer's scopes, the shared
expert's among them (every scope that starts ``hvtpu:moe.``: the
router, the plan, the row movement both ways, the held SwiGLU experts'
products, the shared expert, the combine), forward, recomputed and
backward, summed over the expert layers.

Where the routed products run as ``lax.ragged_dot`` (at 2304 x 1024 the
gated grouped kernels do not fit their VMEM rule: the layer counts the
path when it is traced), XLA:TPU builds each as kernels of its own and
names them by itself (``ragged-dot-*``, no ``op_name`` of the
program's), so no scope reaches them: their device time is found by the
instructions' names, as ``routed_experts_ms_per_step`` finds it, and
counted here."""

from benchmark import scopes
from benchmark.layer_metrics.routed_experts_ms_per_step import (
    compilers_products_ms)

LAYER, UNIT, MOVES = "moe", "ms", "samples_per_s_per_chip"


def read(obs):
    ms = scopes.scoped_ms(obs, "hvtpu:moe.")
    return ms and ms + compilers_products_ms(obs)
