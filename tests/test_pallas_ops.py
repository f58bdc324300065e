"""Pallas TPU kernel tests (ops/pallas_ops.py).

The kernels are the TPU analog of the reference's hand-written device
kernels (horovod/common/ops/cuda/cuda_kernels.cu scale-buffer kernels;
MemcpyInFusionBuffer pack path).  On the CPU test platform the kernel
bodies execute under the Pallas interpreter (HVTPU_PALLAS_INTERPRET=1)
and must agree exactly with the pure-XLA twin lowering the production
fallback uses — the same executable-spec pattern as test_native.py's
C++/Python cross-check.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import (
    QBLOCK,
    dequantize_int8_blocks,
    fused_scale_cast,
    quantize_int8_blocks,
)
from horovod_tpu.ops import pallas_ops


@pytest.fixture
def interpret_mode(monkeypatch):
    monkeypatch.setenv("HVTPU_PALLAS_INTERPRET", "1")


def _rand(n, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(n).astype(np.float32)
    )


class TestFusedScaleCast:
    @pytest.mark.parametrize("n", [17, 1024, 32768, 40000])
    @pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_xla(self, interpret_mode, n, out_dtype):
        x = _rand(n)
        got = fused_scale_cast(x, 0.125, out_dtype)
        want = (x * 0.125).astype(out_dtype)
        assert got.shape == (n,)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_xla_fallback_identical(self, interpret_mode, monkeypatch):
        x = _rand(5000, seed=3)
        kernel = fused_scale_cast(x, 2.0, jnp.bfloat16)
        monkeypatch.setenv("HVTPU_PALLAS", "0")
        xla = fused_scale_cast(x, 2.0, jnp.bfloat16)
        np.testing.assert_array_equal(np.asarray(kernel), np.asarray(xla))


class TestQuantizeInt8:
    @pytest.mark.parametrize("n", [100, QBLOCK, 3 * QBLOCK + 5, 70000])
    def test_roundtrip_error_bound(self, interpret_mode, n):
        x = _rand(n, seed=1)
        q, scale, n_out = quantize_int8_blocks(x)
        assert n_out == n
        assert q.dtype == jnp.int8
        out = dequantize_int8_blocks(q, scale, n)
        # absmax block quantisation: error <= scale/2 per block
        per_block_tol = (
            np.asarray(scale).reshape(-1, 1) * 0.51
        )
        err = np.abs(
            np.asarray(out) - np.asarray(x)
        )
        padded = np.zeros(q.shape[0] * 128 // QBLOCK * QBLOCK)
        padded[:n] = err
        blocks = padded.reshape(-1, QBLOCK)
        assert (blocks <= per_block_tol + 1e-7).all()

    def test_kernel_matches_xla_twin(self, interpret_mode, monkeypatch):
        x = _rand(9000, seed=2)
        qk, sk, _ = quantize_int8_blocks(x)
        monkeypatch.setenv("HVTPU_PALLAS", "0")
        qx, sx, _ = quantize_int8_blocks(x)
        # kernel pads rows further than the twin; the shared prefix must
        # be byte-identical (codes AND scales)
        rows = qx.shape[0]
        np.testing.assert_array_equal(np.asarray(qk)[:rows], np.asarray(qx))
        np.testing.assert_array_equal(
            np.asarray(sk)[: sx.shape[0]], np.asarray(sx)
        )
        # padding region quantises zeros -> zero codes
        assert not np.asarray(qk)[rows:].any()

    def test_zero_block_scale(self, interpret_mode):
        x = jnp.zeros((2048,), jnp.float32)
        q, scale, n = quantize_int8_blocks(x)
        assert not np.asarray(q).any()
        out = dequantize_int8_blocks(q, scale, n)
        assert not np.asarray(out).any()


class TestLowersForTpu:
    """Lower every ``pallas_call`` site for the chip, interpreter off:
    the interpreter accepts what the chip's toolchain refuses, and a
    refusal should fail here, not on a chip run."""

    # ResNet-50's gradient count: a main grid plus a remainder tile
    # that is not a multiple of the int8 tile height
    N = 25_557_032

    @pytest.fixture(autouse=True)
    def compiled_mode(self, monkeypatch):
        monkeypatch.delenv("HVTPU_PALLAS_INTERPRET", raising=False)
        monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)

    def _lower(self, f, *shapes):
        return jax.jit(f).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text()

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("stochastic", [False, True])
    def test_quantize(self, dtype, stochastic):
        text = self._lower(
            lambda x, seed: quantize_int8_blocks(
                x, stochastic=stochastic, seed=seed)[:2],
            jax.ShapeDtypeStruct((self.N,), dtype),
            jax.ShapeDtypeStruct((), jnp.int32))
        assert text.count("tpu_custom_call") >= 2  # main + remainder

    def test_dequantize(self):
        rows = -(-self.N // QBLOCK) * 8
        text = self._lower(
            lambda q, s: dequantize_int8_blocks(q, s, self.N),
            jax.ShapeDtypeStruct((rows, 128), jnp.int8),
            jax.ShapeDtypeStruct((rows // 8, 1), jnp.float32))
        assert text.count("tpu_custom_call") >= 2

    @pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
    def test_fused_scale_cast(self, out_dtype):
        text = self._lower(
            lambda x: fused_scale_cast(x, 0.125, out_dtype),
            jax.ShapeDtypeStruct((self.N,), jnp.float32))
        assert text.count("tpu_custom_call") >= 2

    def test_float16_takes_the_xla_twin(self):
        """Mosaic has no f16 vector type on v5e (the chip's compiler
        said so): f16 buffers must not reach a kernel."""
        x16 = jax.ShapeDtypeStruct((70000,), jnp.float16)
        x32 = jax.ShapeDtypeStruct((70000,), jnp.float32)
        for text in (
                self._lower(lambda x: fused_scale_cast(x, 2.0), x16),
                self._lower(
                    lambda x: fused_scale_cast(x, 2.0, jnp.float16), x32)):
            assert "tpu_custom_call" not in text
        # quantize pre-casts f16 in XLA; the kernel sees f32
        text = self._lower(lambda x: quantize_int8_blocks(x)[:2], x16)
        assert "tpu_custom_call" in text and "f16" in text


class TestInt8CompressorIntegration:
    def test_compressor_uses_block_layout(self):
        from horovod_tpu.comm.compression import Compression

        x = _rand(5000, seed=4).reshape(50, 100)
        wire, ctx = Compression.int8.compress(x)
        assert wire.dtype == jnp.int8
        assert wire.shape[1] == Compression.int8.BLOCK
        back = Compression.int8.decompress(wire, ctx)
        assert back.shape == x.shape
        amax = float(jnp.max(jnp.abs(x)))
        assert float(jnp.max(jnp.abs(back - x))) <= amax / 127 * 0.51 + 1e-7

    def test_stochastic_falls_back_deterministic_off_tpu(self):
        from horovod_tpu.comm.compression import Compression

        x = _rand(3000, seed=5)
        w1, c1 = Compression.int8_stochastic.compress(x)
        w2, c2 = Compression.int8_stochastic.compress(x)
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
        back = Compression.int8_stochastic.decompress(w1, c1)
        amax = float(jnp.max(jnp.abs(x)))
        assert float(jnp.max(jnp.abs(back - x))) <= amax / 127 * 0.51 + 1e-7
