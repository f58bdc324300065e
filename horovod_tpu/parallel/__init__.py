"""horovod_tpu.parallel — hybrid-parallelism layer (dp/tp/pp/sp/ep).

The reference is data-parallel only (SURVEY.md §2.7); this package is
the TPU-first superset: mesh layouts, Megatron-style tensor parallelism,
GPipe pipeline parallelism, ring-attention and Ulysses sequence/context
parallelism for long sequences, and Switch-style expert parallelism —
all expressed as shard_map-compatible functions whose collectives XLA
lowers onto the ICI torus.
"""

from .mesh import LOGICAL_AXES, MeshLayout, auto_layout, make_layout
from .moe import dropless_topk_moe, expert_parallel_moe, switch_route
from .pipeline import bubble_fraction, pipeline_apply
from .ring import ring_attention
from .tp import column_parallel, row_parallel, tp_shard_dim
from .ulysses import heads_to_seq, seq_to_heads, ulysses_attention

__all__ = [
    "LOGICAL_AXES",
    "MeshLayout",
    "auto_layout",
    "make_layout",
    "ring_attention",
    "ulysses_attention",
    "seq_to_heads",
    "heads_to_seq",
    "column_parallel",
    "row_parallel",
    "tp_shard_dim",
    "pipeline_apply",
    "bubble_fraction",
    "expert_parallel_moe",
    "dropless_topk_moe",
    "switch_route",
]
