"""Wire-byte accounting: compression must SHRINK what crosses the link.

The reference's claim is 'fp16 compression: up to ~2x on comm-bound
models' (BASELINE.md).  Correctness of compress/decompress is covered
elsewhere; these tests pin the *bytes* story so the feature's value is
measurable, not asserted:

- HLO-level: lower the jitted SPMD allreduce and assert the
  ``all-reduce`` op's operand element type is the WIRE dtype — f16/bf16
  under 2-byte compression (half the f32 bytes), 8-bit codes under
  int8.  XLA moves exactly the lowered operand over ICI, so this is
  the strongest available proof without hardware link counters.
- Fusion-level: a compressed fused bucket's wire buffer is half (fp16)
  / about a quarter (int8 + scale sidecar) of the f32 payload bytes.

What the smaller wire buys in step time has not been measured on a
chip (PERF.md §7, ``vgg16-b64-dp4-int8``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.comm import spmd
from horovod_tpu.comm.compression import Compression
from horovod_tpu.comm.reduce_ops import ReduceOp


def _lowered_allreduce_text(compression, dtype=jnp.float32, n=4096):
    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("dp",))

    def body(x):
        return spmd.allreduce(x, axis_name="dp", op=ReduceOp.SUM,
                              compression=compression)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
        check_vma=False))
    x = jnp.zeros((8 * n,), dtype)
    return fn.lower(x).as_text()


def _allreduce_operand_types(text):
    """Element types fed to all-reduce ops in the lowered module (the
    operand signature sits on the op region's closing line — the
    StableHLO all_reduce is multi-line)."""
    import re

    types = []
    for m in re.finditer(
            r"stablehlo\.all_reduce.*?\}\)\s*:\s*\((.*?)\)\s*->",
            text, re.S):
        types.extend(re.findall(
            r"tensor<(?:\d+x)*([a-z]+\d+)>", m.group(1)))
    return types


class TestWireDtypeInHLO:
    def test_uncompressed_wire_is_f32(self):
        text = _lowered_allreduce_text(Compression.none)
        types = _allreduce_operand_types(text)
        assert types and all(t == "f32" for t in types), types

    def test_fp16_wire_halves_bytes(self):
        text = _lowered_allreduce_text(Compression.fp16)
        types = _allreduce_operand_types(text)
        assert types and all(t == "f16" for t in types), types

    def test_bf16_wire_halves_bytes(self):
        text = _lowered_allreduce_text(Compression.bf16)
        types = _allreduce_operand_types(text)
        assert types and all(t == "bf16" for t in types), types

    def test_int8_wire_quarters_payload(self):
        """int8 lowers to the two-phase quantized exchange (store-and-
        forward all_to_all + all_gather of i8 CODES, with scalar f32
        scale sidecars) — no f32-payload all-reduce may remain, and
        f32 bytes on the wire must be a sliver of the i8 code bytes."""
        import re

        text = _lowered_allreduce_text(Compression.int8)
        assert not _allreduce_operand_types(text), (
            "int8 path should not lower to a dense all-reduce")
        i8_bytes = f32_bytes = 0
        for line in text.splitlines():
            if "all_to_all" not in line and "all_gather" not in line:
                continue
            for shape, t in re.findall(
                    r"tensor<((?:\d+x)*)([a-z]+\d+)>", line):
                if t == "i64":  # replica_groups attribute, not payload
                    continue
                n = int(np.prod([int(d) for d in
                                 shape.rstrip("x").split("x") or [1]]))
                if t == "i8":
                    i8_bytes += n
                elif t == "f32":
                    f32_bytes += n * 4
        assert i8_bytes > 0
        # sidecar scales are per-chunk scalars: far under 5% of codes
        assert f32_bytes < 0.05 * i8_bytes, (i8_bytes, f32_bytes)


class TestFusedBufferBytes:
    def _fused_wire_nbytes(self, compression):
        from horovod_tpu.comm.packing import pack_flat

        tensors = [jnp.ones((1024,), jnp.float32) for _ in range(8)]
        flat, _ = pack_flat(tensors)
        wire, _ctx = compression.compress(flat)
        sidecar = 0
        if isinstance(_ctx, (tuple, list)):
            sidecar = sum(
                int(np.prod(c.shape)) * c.dtype.itemsize
                for c in _ctx if hasattr(c, "dtype"))
        return wire.nbytes + sidecar, flat.nbytes

    def test_fp16_fused_bucket_is_half(self):
        wire, payload = self._fused_wire_nbytes(Compression.fp16)
        assert wire == payload // 2

    def test_bf16_fused_bucket_is_half(self):
        wire, payload = self._fused_wire_nbytes(Compression.bf16)
        assert wire == payload // 2

    def test_int8_fused_bucket_is_quarterish(self):
        wire, payload = self._fused_wire_nbytes(Compression.int8)
        # 1 byte/element + per-chunk scale sidecar: ≤ 30% of f32
        assert wire <= payload * 0.30, (wire, payload)

    def test_none_is_identity(self):
        wire, payload = self._fused_wire_nbytes(Compression.none)
        assert wire == payload
