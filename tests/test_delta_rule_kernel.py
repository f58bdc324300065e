"""The chunked delta rule's Pallas kernels (``ops/delta_rule.py``), run
by the interpreter on the CPU, against the XLA form
(``models.kimi_linear.chunked_delta_rule`` with ``HVTPU_PALLAS=0``) and
the plain recurrence a position at a time
(``benchmark/reference/kimi_linear.py``): the result and the gradients of
``q, k, v, g, beta``, over document layouts, both operand types, a
padded tail and decays steep enough that ``exp(-G)`` overflows.  Heads
of 128, 100 positions; each case compiles the interpreted kernels once,
some fifteen seconds."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import kimi_linear as ref  # noqa: E402
from horovod_tpu.models import kimi_linear as kl  # noqa: E402
from horovod_tpu.obs import metrics  # noqa: E402
from horovod_tpu.ops import delta_rule  # noqa: E402

# f32 against f32: two orders of summing the same products (read 1e-6)
RTOL = 3e-5
# bfloat16 operands and products against the f32 recurrence: the XLA
# form reads 4e-3 here, and so do the kernels
BF16_DISTANCE = 1e-2
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setenv("HVTPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)


def segment_of(starts, positions):
    """Rows whose documents start at 0 and at ``starts[row]``."""
    segment = np.zeros((len(starts), positions), np.int32)
    for row, at in zip(segment, starts):
        for start in at:
            row[start:] += 1
    return jnp.asarray(segment)


def operands(rows, positions, heads=2, width=128, strength=1.0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (rows, positions, heads, width)
    return (ref.unit_length(jax.random.normal(ks[0], shape)) * width ** -0.5,
            ref.unit_length(jax.random.normal(ks[1], shape)),
            jax.random.normal(ks[2], shape),
            -strength * jax.nn.softplus(jax.random.normal(ks[3], shape)),
            jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])),
            jax.random.normal(ks[5], shape))


def recurrent(q, k, v, g, beta, segment):
    f32 = jnp.float32
    return jnp.stack([
        ref.delta_rule(q[i].astype(f32), k[i].astype(f32), v[i].astype(f32),
                       g[i], beta[i], ref.first_of_a_document(segment[i]))
        for i in range(q.shape[0])])


def with_gradients(rule, inputs, target):
    """The result and the gradients of ``q, k, v, g, beta``, one program."""
    def loss(*a):
        return jnp.sum(rule(*a).astype(jnp.float32) * target)
    return jax.jit(lambda *a: (rule(*a), *jax.grad(
        loss, tuple(range(5)))(*a)))(*inputs)


def distance(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def xla_form(monkeypatch, segment, chunk):
    def rule(*a):
        monkeypatch.setenv("HVTPU_PALLAS", "0")
        try:
            return kl.chunked_delta_rule(*a, segment, chunk)
        finally:
            monkeypatch.delenv("HVTPU_PALLAS")
    return rule


# a row a layout, in 100 positions, no whole number of chunks of 16 or of
# 64 (the tail is padded): one document; documents of one to twenty-five
# positions starting inside chunks; starts on chunks' first positions;
# and a document whose decays are sixteen times steeper, so that a
# chunk's ``exp(-G)`` overflows f32
STARTS = [[], [3, 4, 5, 6, 20, 45, 70], [16, 32, 64], [40]]
STEEP_ROW = 3


def steep_operands():
    *inputs, target = operands(len(STARTS), 100)
    inputs[3] = inputs[3].at[STEEP_ROW].multiply(16.0)
    return inputs, target


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernels_equal_the_xla_form_and_the_recurrence(
        kernels, monkeypatch, dtype, chunk):
    """Through ``kl.chunked_delta_rule`` as the model calls it: the
    kernels' result and gradients against the XLA form on the same
    operands and against the recurrence a position at a time."""
    segment = segment_of(STARTS, 100)
    inputs, target = steep_operands()
    inputs[:3] = [a.astype(dtype) for a in inputs[:3]]
    with jax.default_matmul_precision("highest"):
        got = with_gradients(
            lambda *a: kl.chunked_delta_rule(*a, segment, chunk), inputs,
            target)
        xla = with_gradients(xla_form(monkeypatch, segment, chunk), inputs,
                             target)
        want = with_gradients(lambda *a: recurrent(*a, segment),
                              [a.astype(jnp.float32) if i < 3 else a
                               for i, a in enumerate(inputs)], target)
    for name, g, x, w in zip(NAMES, got, xla, want):
        assert g.dtype == x.dtype, name
        assert np.isfinite(np.asarray(g, np.float32)).all(), name
        limit = RTOL if dtype == "float32" else BF16_DISTANCE
        assert distance(g, x) < limit, name
        assert distance(g, w) < limit, name
        assert distance(x, w) < limit, name
        # the steep row alone, where a decay made as a quotient overflows
        assert distance(g[STEEP_ROW], w[STEEP_ROW]) < limit, name


def test_the_steep_rows_decays_overflow_as_a_quotient():
    """What the steep row of the cases above holds: a chunk of 64 decays
    by far more than f32 can invert, so ``exp(-G)`` is infinite."""
    inputs, _ = steep_operands()
    g = np.asarray(inputs[3][STEEP_ROW, 40:104], np.float32)
    assert g.min() < -40.0
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-np.cumsum(g, axis=0))).all()


def counter(path):
    return metrics.REGISTRY.counter("hvtpu_kda_calls_total").value(path=path)


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_the_path_taken_is_counted_when_a_program_is_traced(
        kernels, monkeypatch, path):
    if path == "xla":
        monkeypatch.setenv("HVTPU_PALLAS", "0")
    before = {p: counter(p) for p in ("pallas", "xla")}
    *inputs, _ = operands(1, 64)
    jax.jit(lambda *a: kl.chunked_delta_rule(
        *a, jnp.zeros((1, 64), jnp.int32), 64)).lower(*inputs)
    assert counter(path) == before[path] + 1
    other, = {"pallas", "xla"} - {path}
    assert counter(other) == before[other]


@pytest.mark.parametrize("width, value_width, chunk, dtype", [
    (64, 128, 64, "bfloat16"),       # a head of half a vector
    (128, 96, 64, "bfloat16"),
    (128, 128, 8, "bfloat16"),       # chunks smaller than a sub-chunk
    (128, 128, 48, "float32"),       # no power of two
    (128, 128, 256, "float32"),
    (128, 128, 64, "float16"),       # no float16 vectors on a v5e
])
def test_supports_refuses_what_the_kernels_do_not_take(
        width, value_width, chunk, dtype):
    assert delta_rule.supports(128, 128, 64, "bfloat16")
    assert not delta_rule.supports(width, value_width, chunk, dtype)


def test_shapes_the_kernels_do_not_take_run_the_xla_form(kernels):
    """Heads of 16 (the model tests' toy size) under the interpreter:
    the XLA form, counted as such, equal to the recurrence."""
    before = counter("xla")
    segment = segment_of([[20], [7]], 48)
    *inputs, _ = operands(2, 48, heads=3, width=16)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: kl.chunked_delta_rule(*a, segment, 16))(
            *inputs)
        want = jax.jit(lambda *a: recurrent(*a, segment))(*inputs)
    assert counter("xla") == before + 1
    assert distance(got, want) < RTOL


@pytest.mark.parametrize("heads, block", [(32, 8), (12, 6), (3, 3), (7, 7),
                                          (16, 8)])
def test_a_grid_step_carries_the_most_heads_that_divide(heads, block):
    assert delta_rule.block_heads(heads) == block
