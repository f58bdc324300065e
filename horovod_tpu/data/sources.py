"""Pluggable data sources for :class:`~horovod_tpu.data.ElasticDataLoader`.

A source answers exactly two questions — how many samples exist
(``len(source)``) and "materialize these global indices as a batch"
(``fetch(indices)``).  Everything elastic (sharding, cursors, resize
re-sharding) lives in the loader/sharder; sources stay dumb and
stateless in *what* they deliver, so a relaunched incarnation can
rebuild one from scratch and land on byte-identical batches.

``fetch`` returns a *batch structure*: a numpy array, or a dict/tuple
of them, each with the batch as the leading dimension.  The loader
treats the structure opaquely (optionally ``device_put``-ing every
array leaf), so torch loops can consume the same sources as JAX ones.

Where the memory comes from
---------------------------
``ArraySource`` keeps a small cache of *memory*, never of data: the
blocks that its larger gathers wrote into.  ``a[indices]`` allocates
its result anew every batch, and a fresh allocation of that size is
faulted in page by page: on a host without transparent hugepages the
faults of a 77 MB batch cost twelve times the copy itself, 80 ms
against 6.5 (PERF.md section 6, PR 26).  So ``fetch`` gathers into a
block it already owns whenever it can see that nothing else refers to
that block any more: the block's reference count is back at what the
pool alone accounts for.  Every numpy view of a delivered leaf, every consumer that keeps
the batch, ``torch.from_numpy``, and jax for as long as a copy to the
device (or a zero-copy alias on the CPU) needs the bytes, hold a
reference, so a batch somebody still has is never written over, and a
loop that keeps its host batches simply gets fresh memory as before.
Same rows, same bytes, same dtype and structure either way.
``hvtpu_data_fetch_blocks_total`` counts how often the cache engaged.
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..obs import metrics as obs_metrics

Batch = Union[np.ndarray, Dict[str, "Batch"], Tuple["Batch", ...]]

_M_BLOCKS = obs_metrics.counter(
    "hvtpu_data_fetch_blocks_total",
    "Leaves of at least 1 MiB that ArraySource.fetch gathered, by where "
    "the result's memory came from: block=\"reused\" (a block of the "
    "source's pool that nothing referred to any more) or \"fresh\" (a "
    "new allocation: the pool still growing, or every block in use).")

# A gathered leaf under this many bytes takes ``a[indices]``'s own fresh
# result.  A fresh page costs about 4.4 us to fault in on a host without
# transparent hugepages and about 0.35 us to copy into afterwards, so
# under 1 MiB (256 pages) reuse saves a millisecond at most, while
# glibc's own caching, which gives out recycled memory below its mmap
# threshold, stops being dependable long before that threshold's 32 MiB
# ceiling: 19 MB results came back faulted for a process's first
# fetches and then now and again.
_POOLED_BYTES = 1 << 20

# Blocks the pool keeps for one leaf.  A loader with the default prefetch
# depth of 2 whose consumer holds host batches has five alive at once
# (two queued, one parked, one being used, one being gathered); eight
# leaves room for a depth of 4 or a loop that looks one batch back.
# Beyond it a result is allocated as ever and never pooled, which bounds
# what the cache can hold on to at eight batches a leaf.
_BLOCKS_PER_LEAF = 8


def _references(blocks: list, k: int) -> int:
    return sys.getrefcount(blocks[k])


# What ``_references`` reads for a block that only its pool's list
# holds: measured the way it is used, not assumed, since the count of a
# call's own temporaries differs between interpreters.
_NOBODY_ELSE = _references([np.empty(0, dtype=np.uint8)], 0)


def map_structure(fn, struct):
    """Apply ``fn`` to every array leaf of a batch structure (dict /
    tuple / list / ndarray) — a tiny dependency-free tree map so
    sources and the loader never need jax on their import path."""
    if isinstance(struct, dict):
        return {k: map_structure(fn, v) for k, v in struct.items()}
    if isinstance(struct, (tuple, list)):
        mapped = [map_structure(fn, v) for v in struct]
        return tuple(mapped) if isinstance(struct, tuple) else mapped
    return fn(struct)


class DataSource:
    """Base source protocol: ``__len__`` + ``fetch(indices)``."""

    def __len__(self) -> int:
        raise NotImplementedError

    def fetch(self, indices: np.ndarray) -> Batch:
        """Materialize the batch for ``indices`` (global sample ids,
        possibly empty on a ragged epoch tail)."""
        raise NotImplementedError


class ArraySource(DataSource):
    """In-memory arrays (or a dict/tuple of them sharing the leading
    dimension): ``fetch`` is a fancy-index gather per leaf, written
    into a block of the source's own where one is free (see the module
    docstring)."""

    def __init__(self, data: Batch):
        self.data = data
        # leaf's position in the structure -> the raw uint8 blocks its
        # gathers wrote into; two loaders may share a source
        self._pool_lock = threading.Lock()
        self._pool: Dict[int, List[np.ndarray]] = {}  # hvtpulint: guarded-by(_pool_lock)
        lengths = []
        map_structure(lambda a: lengths.append(len(a)), data)
        if not lengths:
            raise ValueError("ArraySource needs at least one array")
        if len(set(lengths)) != 1:
            raise ValueError(
                f"ArraySource arrays disagree on the sample dimension: "
                f"{sorted(set(lengths))}")
        self._n = lengths[0]

    def __len__(self) -> int:
        return self._n

    def fetch(self, indices: np.ndarray) -> Batch:
        # np.take(mode="clip") checks nothing, so only indices already
        # known to be plain take the pooled path; anything else (out of
        # range, negative, empty, a mask or a list) gets numpy's own
        # indexing and its errors
        plain = (isinstance(indices, np.ndarray) and indices.ndim == 1
                 and indices.dtype.kind == "i" and indices.size > 0
                 and 0 <= indices.min() and indices.max() < self._n)
        position = itertools.count()

        def gather(a):
            leaf = next(position)
            block = None
            # np.take copies the whole of a source that is not
            # C-contiguous before it gathers: such a leaf, a subclass
            # (a memmap) and object arrays keep the old path as well
            if (plain and type(a) is np.ndarray and a.flags.c_contiguous
                    and a.flags.aligned and not a.dtype.hasobject):
                nbytes = a.nbytes // len(a) * indices.size
                if nbytes >= _POOLED_BYTES:
                    block = self._free_block(leaf, nbytes)
            if block is None:
                return np.asarray(a)[indices]
            out = block.view(a.dtype).reshape(
                (indices.size,) + a.shape[1:])
            del block  # the result alone says who still has the memory
            # mode="raise" would gather into a temporary and copy
            np.take(a, indices, axis=0, out=out, mode="clip")
            return out

        return map_structure(gather, self.data)

    def _free_block(self, leaf: int, nbytes: int) -> Optional[np.ndarray]:
        """A block of ``nbytes`` for ``leaf`` that nothing but the pool
        refers to: one gathered into before, else a new one while the
        leaf has fewer than ``_BLOCKS_PER_LEAF``, else None."""
        with self._pool_lock:
            blocks = self._pool.setdefault(leaf, [])
            for k in range(len(blocks)):
                if blocks[k].nbytes == nbytes \
                        and _references(blocks, k) == _NOBODY_ELSE:
                    _M_BLOCKS.inc(block="reused")
                    return blocks[k]
            _M_BLOCKS.inc(block="fresh")
            if len(blocks) >= _BLOCKS_PER_LEAF:
                return None
            blocks.append(np.empty(nbytes, dtype=np.uint8))
            return blocks[-1]


class FileListSource(DataSource):
    """One sample per file path: ``fetch`` loads and stacks the
    selected files (default loader ``np.load``); an optional parallel
    ``labels`` sequence rides along as the second tuple element."""

    def __init__(self, paths: Sequence[str],
                 load_fn: Optional[Callable[[str], np.ndarray]] = None,
                 labels: Optional[Sequence] = None):
        self.paths: List[str] = list(paths)
        self.load_fn = load_fn if load_fn is not None else np.load
        self.labels = None if labels is None else np.asarray(labels)
        if self.labels is not None and len(self.labels) != len(self.paths):
            raise ValueError(
                f"labels ({len(self.labels)}) and paths "
                f"({len(self.paths)}) disagree")

    def __len__(self) -> int:
        return len(self.paths)

    def fetch(self, indices: np.ndarray) -> Batch:
        samples = [np.asarray(self.load_fn(self.paths[i]))
                   for i in indices]
        if samples:
            x = np.stack(samples)
        else:  # ragged-tail empty batch keeps a stackable shape
            x = np.empty((0,), dtype=np.float32)
        if self.labels is None:
            return x
        return (x, self.labels[indices])

    @classmethod
    def from_glob(cls, pattern: str, **kwargs) -> "FileListSource":
        import glob

        paths = sorted(glob.glob(pattern))
        if not paths:
            raise FileNotFoundError(
                f"FileListSource.from_glob: no files match {pattern!r} "
                f"(cwd {os.getcwd()})")
        return cls(paths, **kwargs)


class SyntheticSource(DataSource):
    """Deterministic index-derived samples for benchmarks and tests:
    sample ``i`` is a cheap pure function of ``i`` (a broadcast scalar
    pattern plus a modular label), so generation costs one memset-speed
    fill per batch and any two processes agree byte-for-byte without
    sharing data."""

    def __init__(self, num_samples: int, shape: Tuple[int, ...],
                 dtype=np.float32, num_classes: int = 1000,
                 seed: int = 0):
        self.num_samples = int(num_samples)
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype) if not hasattr(dtype, "itemsize") \
            else dtype
        self.num_classes = int(num_classes)
        self.seed = int(seed)

    def __len__(self) -> int:
        return self.num_samples

    def fetch(self, indices: np.ndarray) -> Batch:
        idx = np.asarray(indices, dtype=np.int64)
        # per-sample scalar in [0, 1): a fixed-point hash of (seed, i)
        mixed = (idx * 2654435761 + self.seed * 97) % 104729
        base = (mixed / 104729.0).astype(np.float32)
        x = np.broadcast_to(
            base.reshape((-1,) + (1,) * len(self.shape)),
            (len(idx),) + self.shape).astype(self.dtype)
        y = ((idx + self.seed) % self.num_classes).astype(np.int32)
        return {"x": x, "y": y}
