"""Compile the TPU kernels with the chip's own toolchain — no chip.

libtpu ships the XLA:TPU and Mosaic compilers, and
``jax.experimental.topologies`` hands out a v5e topology to compile
against from a CPU-only box.  That goes one step past the tier-1
lowering tests (``TestLowersForTpu``): it catches what Mosaic itself
refuses — VMEM overflow, unsupported vector types, a DMA slice that is
not tile-aligned — before a chip call is spent on it.  It says nothing
about what the program does when it runs.

Slow-marked: loading libtpu into the test process is seconds of
start-up noise tier-1 does not need.  Run with
``pytest -m slow tests/test_tpu_aot.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.ops import pallas_ops, ring

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies

    return topologies.get_topology_desc("v5e:2x2", "tpu").devices


@pytest.fixture(autouse=True)
def compiled_mode(monkeypatch):
    monkeypatch.delenv("HVTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)


def _compile(f, *args):
    return jax.jit(f).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_ops_compile_at_resnet50_bucket_size(v5e_2x2, dtype):
    sh = NamedSharding(Mesh(np.array(v5e_2x2[:1]), ("world",)), P())
    n = 25_557_032

    def roundtrip(x, seed):
        q, s, _ = pallas_ops.quantize_int8_blocks(x)
        qs, _, _ = pallas_ops.quantize_int8_blocks(
            x, stochastic=True, seed=seed)
        return (qs, pallas_ops.dequantize_int8_blocks(q, s, n),
                pallas_ops.fused_scale_cast(x, 0.125, jnp.bfloat16))

    _compile(roundtrip,
             jax.ShapeDtypeStruct((n,), dtype, sharding=sh),
             jax.ShapeDtypeStruct((), jnp.int32, sharding=sh))


@pytest.mark.parametrize("n_dev", [2, 4])
@pytest.mark.parametrize("per_rank", [5, 3_000_001])
def test_ring_kernels_compile(v5e_2x2, n_dev, per_rank):
    mesh = Mesh(np.array(v5e_2x2[:n_dev]), ("world",))

    def wrap(body):
        return jax.shard_map(body, mesh=mesh, in_specs=(P("world"),),
                             out_specs=P(), check_vma=False)

    x = jax.ShapeDtypeStruct(
        (n_dev, per_rank), jnp.float32,
        sharding=NamedSharding(mesh, P("world")))
    for quantized in (False, True):
        _compile(wrap(lambda xs: ring.ring_allreduce(
            xs[0], axis_name="world", quantized=quantized)), x)
    block = jax.ShapeDtypeStruct(
        (n_dev * 512, 128), jnp.float32,
        sharding=NamedSharding(mesh, P("world")))
    _compile(wrap(lambda xs: ring.ring_allgather_2d(
        xs, axis_name="world")), block)
