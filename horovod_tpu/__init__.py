"""horovod_tpu — a TPU-native data-parallel training framework with the
capabilities of Horovod (reference: sj6077/horovod), rebuilt on
JAX/XLA/Pallas.

Public surface parity (reference: horovod/torch/__init__.py,
horovod/common/basics.py ``HorovodBasics``): ``init``, ``shutdown``,
``rank``/``size``/``local_rank``/..., eager collectives
(``allreduce``/``allgather``/``broadcast``/``alltoall``/
``reducescatter`` + async/grouped variants), ``DistributedOptimizer``,
``Compression``, ``ProcessSet``, elastic training, plus the SPMD layer
(``horovod_tpu.spmd``) that is the TPU-idiomatic hot path inside
jit/shard_map.

Typical JAX use::

    import horovod_tpu as hvt
    hvt.init()
    mesh = hvt.world_mesh()
    tx = hvt.DistributedOptimizer(optax.sgd(0.1), axis_name="world")
    # ... jit a shard_map train step over `mesh`; gradients are
    # bucket-fused and psum'd over ICI inside the compiled program.
"""

from __future__ import annotations

from typing import Optional

import jax

from . import comm, core
from . import data  # noqa: F401  (elastic-aware input pipeline)
from . import elastic  # noqa: F401  (hvt.elastic.State/run parity surface)
from .api import functions as _functions
from .api import optimizer as _optimizer
from .api.handles import manager as _handle_manager
from .comm import eager as _eager
from .comm import spmd
from .comm.compression import Compression
from .comm.stall import stall_guard  # noqa: F401  (jit-plane watchdog)
from .comm.reduce_ops import Adasum, Average, Max, Min, Product, ReduceOp, Sum
from .core import (
    Config,
    HorovodInternalError,
    HorovodTpuError,
    HostsUpdatedInterrupt,
    HvtpuDivergenceError,
    HvtpuMismatchError,
    ProcessSet,
    add_process_set,
    remove_process_set,
)
from .core import state as _state
from .core.compile_cache import enable_compile_cache
from .version import __version__

# ---------------------------------------------------------------------------
# lifecycle (parity: horovod_init / horovod_shutdown / HorovodBasics)
# ---------------------------------------------------------------------------

def init(config: Optional[Config] = None):
    """Initialize horovod_tpu (idempotent).

    Rank ↔ process ↔ device model (pod shape):

    * One **Horovod rank = one process**: ``rank()``/``size()`` count
      processes, exactly like the reference (``hvd.rank/size``).  A
      process may own **several accelerator devices** (the usual TPU
      pod shape: P hosts × D chips each).
    * The **jit/SPMD path** (``world_mesh()`` + ``shard_map`` +
      ``DistributedOptimizer(axis_name=...)``) spans ALL
      ``jax.device_count()`` devices — the same jitted program runs on
      every process and XLA executes per-host partitions over the
      global mesh.  This is the flagship path and uses every chip.
    * The **eager path** (``allreduce``/``allgather``/... on concrete
      arrays) is PROCESS-granularity: each process contributes one
      tensor, carried on its designated transport device (the first
      local device).  With D>1 local devices the other devices are
      simply not participants of eager collectives — they are the
      jit path's compute surface, not extra eager ranks.  ``init()``
      logs this at INFO when it detects D>1.
    """
    return _state.init(config)


def shutdown():
    _state.shutdown()


def is_initialized() -> bool:
    return _state.initialized()


def rank() -> int:
    return _state.require_init("rank()").rank


def size() -> int:
    return _state.require_init("size()").size


def local_rank() -> int:
    return _state.require_init("local_rank()").local_rank


def local_size() -> int:
    return _state.require_init("local_size()").local_size


def cross_rank() -> int:
    return _state.require_init("cross_rank()").cross_rank


def cross_size() -> int:
    return _state.require_init("cross_size()").cross_size


def is_homogeneous() -> bool:
    """True when every host runs the same number of ranks (parity:
    ``hvd.is_homogeneous``).  Upstream allgathers local sizes; here a
    single-host world (``cross_size == 1``) is provably homogeneous
    from held state, and multi-host worlds rely on the launcher's
    uniformity certificate (``HVTPU_UNIFORM_LOCAL_SIZE``)."""
    st = _state.require_init("is_homogeneous()")
    if st.size == 1 or st.cross_size == 1:
        return True
    return bool(st.config and st.config.uniform_local_size > 0)


def __getattr__(name: str):
    # PEP 562: `hvt.global_process_set` mirrors the reference's
    # module-level attribute (horovod/common/process_sets.py) while
    # resolving to the LIVE table entry, which only exists after init.
    # Must raise AttributeError (never NotInitializedError) so
    # hasattr/getattr-with-default probes keep their contract.
    if name == "global_process_set":
        if not _state.initialized():
            raise AttributeError(
                "global_process_set is available after hvt.init()"
            )
        return _state.global_state().process_set_table.global_process_set
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def num_devices() -> int:
    """Total accelerator devices in the job (devices ≠ ranks on TPU:
    one process drives many chips)."""
    return _state.require_init("num_devices()").topology.num_devices


def local_devices():
    return jax.local_devices()


def world_mesh():
    """The flat 1-D device mesh (axis ``world``) for SPMD programs."""
    return _state.require_init("world_mesh()").topology.world_mesh()


def hierarchical_mesh():
    """(dcn, ici) mesh separating cross-host from intra-slice links."""
    return _state.require_init("hierarchical_mesh()").topology.hierarchical_mesh()


def mesh(axis_names, shape):
    """Arbitrary N-D mesh, e.g. ``hvt.mesh(("dp","tp"), (4, 2))``."""
    return _state.require_init("mesh()").topology.nd_mesh(
        tuple(axis_names), tuple(shape)
    )


# ---------------------------------------------------------------------------
# build/runtime feature probes (parity: basics.py mpi_built/nccl_built/...)
# ---------------------------------------------------------------------------

def mpi_enabled() -> bool:
    return False


def mpi_built() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> int:
    return 0


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def xla_built() -> bool:
    """This framework *is* the XLA backend."""
    return True


def ici_built() -> bool:
    """True when a TPU (ICI-connected) backend is present."""
    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except Exception:
        return False


# ---------------------------------------------------------------------------
# eager collectives (parity: horovod/torch/mpi_ops.py surface)
# ---------------------------------------------------------------------------

def allreduce(
    tensor,
    *,
    op=None,
    average=None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    compression=Compression.none,
    process_set=None,
    name: Optional[str] = None,
):
    _state.require_init("allreduce")
    return _eager.allreduce(
        tensor,
        op=op,
        average=average,
        prescale_factor=prescale_factor,
        postscale_factor=postscale_factor,
        compression=compression,
        process_set=process_set,
        name=name,
    )


def grouped_allreduce(tensors, *, op=None, average=None,
                      compression=Compression.none, process_set=None,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0, name=None):
    """Reduce a list of tensors as one fused unit (parity:
    hvd.grouped_allreduce / group_table.cc).

    Sum/Average fuse into one flat wire buffer; Min/Max/Product/Adasum
    keep per-tensor semantics (matching spmd.grouped_allreduce).
    """
    _state.require_init("grouped_allreduce")
    return _eager.grouped_allreduce(
        tensors, op=op, average=average,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor,
        compression=compression, process_set=process_set,
    )


def allgather(tensor, *, process_set=None, name: Optional[str] = None):
    _state.require_init("allgather")
    return _eager.allgather(tensor, process_set=process_set, name=name)


def broadcast(tensor, root_rank: int = 0, *, process_set=None,
              name: Optional[str] = None):
    _state.require_init("broadcast")
    return _eager.broadcast(tensor, root_rank=root_rank,
                           process_set=process_set, name=name)


def alltoall(tensor, splits=None, *, process_set=None,
             name: Optional[str] = None):
    _state.require_init("alltoall")
    return _eager.alltoall(tensor, splits, process_set=process_set,
                           name=name)


def reducescatter(tensor, *, op=None, process_set=None,
                  name: Optional[str] = None):
    _state.require_init("reducescatter")
    return _eager.reducescatter(tensor, op=op, process_set=process_set,
                                name=name)


def barrier(*, process_set=None):
    _state.require_init("barrier")
    return _eager.barrier(process_set=process_set)


# --- async variants (parity: *_async + synchronize/poll in
# horovod/torch/mpi_ops.py).  Async ops go through the eager
# mini-controller (horovod_tpu.eager): ranks may enqueue in ANY order —
# the controller negotiates an agreed, fused execution schedule each
# cycle, exactly the reference's background-thread semantics.  Sync ops
# (above) bypass it and require identical issuance order across ranks,
# like any SPMD program. ---

def _controller():
    from .eager import get_controller

    return get_controller()


def allreduce_async(tensor, *, op=None, average=None, name=None,
                    compression=Compression.none, process_set=None,
                    prescale_factor: float = 1.0,
                    postscale_factor: float = 1.0):
    _state.require_init("allreduce_async")
    from .comm.reduce_ops import normalize_op

    fut = _controller().enqueue(
        "allreduce", tensor, name=name, op=normalize_op(op, average),
        compression=compression, process_set=process_set,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor,
    )
    return _handle_manager().allocate(fut)


def grouped_allreduce_async(tensors, *, op=None, average=None, names=None,
                            compression=Compression.none, process_set=None):
    """Async grouped allreduce: the set executes only when every member
    is ready on every rank (parity: group_table.cc)."""
    _state.require_init("grouped_allreduce_async")
    from .comm.reduce_ops import normalize_op

    futs = _controller().grouped_enqueue(
        "allreduce", list(tensors), names=names,
        op=normalize_op(op, average), compression=compression,
        process_set=process_set,
    )
    return [_handle_manager().allocate(f) for f in futs]


def grouped_allgather(tensors, *, process_set=None):
    """Allgather a list of tensors (parity: hvd.grouped_allgather —
    newer-upstream surface; sync form gathers each in order)."""
    _state.require_init("grouped_allgather")
    return [_eager.allgather(t, process_set=process_set) for t in tensors]


def grouped_allgather_async(tensors, *, names=None, process_set=None):
    """Async grouped allgather: executes only when every member is
    ready on every rank (parity: hvd.grouped_allgather_async)."""
    _state.require_init("grouped_allgather_async")
    futs = _controller().grouped_enqueue(
        "allgather", list(tensors), names=names, process_set=process_set,
    )
    return [_handle_manager().allocate(f) for f in futs]


def grouped_reducescatter(tensors, *, op=None, process_set=None):
    """Reducescatter a list of tensors (parity:
    hvd.grouped_reducescatter)."""
    _state.require_init("grouped_reducescatter")
    return [
        _eager.reducescatter(t, op=op, process_set=process_set)
        for t in tensors
    ]


def grouped_reducescatter_async(tensors, *, op=None, names=None,
                                process_set=None):
    """Async grouped reducescatter (parity:
    hvd.grouped_reducescatter_async)."""
    _state.require_init("grouped_reducescatter_async")
    from .comm.reduce_ops import normalize_op

    futs = _controller().grouped_enqueue(
        "reducescatter", list(tensors), names=names,
        op=normalize_op(op, None), process_set=process_set,
    )
    return [_handle_manager().allocate(f) for f in futs]


def allgather_async(tensor, *, name=None, process_set=None):
    _state.require_init("allgather_async")
    fut = _controller().enqueue(
        "allgather", tensor, name=name, process_set=process_set
    )
    return _handle_manager().allocate(fut)


def broadcast_async(tensor, root_rank: int = 0, *, name=None,
                    process_set=None):
    _state.require_init("broadcast_async")
    fut = _controller().enqueue(
        "broadcast", tensor, name=name, root_rank=root_rank,
        process_set=process_set,
    )
    return _handle_manager().allocate(fut)


def alltoall_async(tensor, splits=None, *, name=None, process_set=None):
    _state.require_init("alltoall_async")
    fut = _controller().enqueue(
        "alltoall", tensor, name=name, splits=splits,
        process_set=process_set,
    )
    return _handle_manager().allocate(fut)


def reducescatter_async(tensor, *, op=None, name=None, process_set=None):
    _state.require_init("reducescatter_async")
    from .comm.reduce_ops import normalize_op

    fut = _controller().enqueue(
        "reducescatter", tensor, name=name,
        op=normalize_op(op, None), process_set=process_set,
    )
    return _handle_manager().allocate(fut)


def synchronize(handle: int):
    """Block until an async op completes and return its result."""
    return _handle_manager().synchronize(handle)


def poll(handle: int) -> bool:
    return _handle_manager().poll(handle)


def start_timeline(filename: str, mark_cycles: bool = False):
    """Begin writing a Chrome-trace timeline (parity: hvd.start_timeline)."""
    st = _state.require_init("start_timeline")
    from .obs.timeline import Timeline

    old = st.timeline
    new_tl = Timeline(filename, st.rank, mark_cycles=mark_cycles)
    if old is not None:
        # carry in-flight spans over so their 'E' events land in the
        # new file instead of silently vanishing; close() below writes
        # matching 'E's into the old file
        for name, phase in list(old._open_spans.items()):
            new_tl.begin(name, phase)
    st.timeline = new_tl
    if st.controller is not None:
        # a live eager controller captured the previous timeline (or
        # None) at construction; hand it the new one
        st.controller._timeline = new_tl
    if old is not None:
        old.close()
    return new_tl


def stop_timeline():
    """Stop and flush the timeline (parity: hvd.stop_timeline)."""
    st = _state.require_init("stop_timeline")
    if st.timeline is not None:
        st.timeline.close()
        st.timeline = None
    if st.controller is not None:
        st.controller._timeline = None


def join(device=None) -> int:
    """Signal this rank has no more work this epoch (uneven final
    batches; parity: hvd.join / EnqueueJoin + JoinOp).

    While joined, this rank's controller keeps cycling and contributes
    ZEROS to collectives the remaining ranks run (allreduce: zero
    tensor; allgather/alltoall: zero rows), so their training steps
    complete without stalling.  All ranks must eventually call
    ``join``; it returns the rank that joined last, on every rank.
    """
    st = _state.require_init("join")
    if st.size == 1:
        return 0
    # Dynamic form through the mini-controller: ranks may keep issuing
    # async collectives; join resolves once every rank has joined.
    return int(_controller().join().result())


# ---------------------------------------------------------------------------
# higher-level API
# ---------------------------------------------------------------------------

DistributedOptimizer = _optimizer.DistributedOptimizer
ShardedDistributedOptimizer = _optimizer.ShardedDistributedOptimizer
allreduce_gradients = _optimizer.allreduce_gradients
broadcast_parameters = _functions.broadcast_parameters
broadcast_optimizer_state = _functions.broadcast_optimizer_state
broadcast_object = _functions.broadcast_object
allgather_object = _functions.allgather_object

from .api.checkpoint import (  # noqa: E402
    Checkpointer,
    restore_checkpoint,
    save_checkpoint,
)
from .api.sharded_checkpoint import ShardedCheckpointer  # noqa: E402

__all__ = [
    "__version__",
    "init", "shutdown", "is_initialized", "enable_compile_cache",
    "rank", "size", "local_rank", "local_size", "cross_rank", "cross_size",
    "num_devices", "local_devices", "world_mesh", "hierarchical_mesh", "mesh",
    "allreduce", "grouped_allreduce", "allgather", "broadcast", "alltoall",
    "reducescatter", "barrier", "join",
    "grouped_allgather", "grouped_allgather_async",
    "grouped_reducescatter", "grouped_reducescatter_async",
    "allreduce_async", "grouped_allreduce_async", "allgather_async",
    "broadcast_async", "alltoall_async",
    "reducescatter_async", "synchronize", "poll",
    "start_timeline", "stop_timeline",
    "DistributedOptimizer", "ShardedDistributedOptimizer",
    "allreduce_gradients",
    "broadcast_parameters", "broadcast_optimizer_state", "broadcast_object",
    "allgather_object",
    "Checkpointer", "save_checkpoint", "restore_checkpoint",
    "is_homogeneous",
    "ShardedCheckpointer",
    "Compression", "ReduceOp", "Average", "Sum", "Adasum", "Min", "Max",
    "Product",
    "ProcessSet", "add_process_set", "remove_process_set",
    "Config", "HorovodTpuError", "HorovodInternalError",
    "HostsUpdatedInterrupt", "HvtpuMismatchError", "HvtpuDivergenceError",
    "spmd", "comm", "core", "data",
    "mpi_enabled", "mpi_built", "mpi_threads_supported", "gloo_enabled",
    "gloo_built", "nccl_built", "ddl_built", "ccl_built", "cuda_built",
    "rocm_built", "xla_built", "ici_built",
]
