"""The framework's own part of a step under named scopes, and the step
read by pass.

``comm/fusion.py`` and ``api/optimizer.py`` mark the exchange's pack,
reduction and unpack, the non-finite guard and the wrapped update with
``jax.named_scope("hvtpu:...")``; ``benchmark/passes.py`` reads the pass
(forward, recomputed, backward, the rest, or no name at all) from the
``op_name`` JAX writes by itself.  A toy step of the cells' shape, on two
virtual devices: ``DistributedOptimizer(optax.sgd(momentum))`` in
``shard_map`` around one checkpointed layer.  The scopes are metadata:
the optimised program with and without them is the same program."""

import contextlib
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)            # ``benchmark`` is a package of the root

from benchmark import passes, scopes  # noqa: E402

EXCHANGE = ["hvtpu:exchange.pack", "hvtpu:exchange.reduce",
            "hvtpu:exchange.unpack"]
OPTIMIZER = ["hvtpu:optimizer.guard", "hvtpu:optimizer.update"]
OPTIMIZERS = {
    "plain": lambda tx: hvt.DistributedOptimizer(tx, axis_name="world"),
    "accumulating": lambda tx: hvt.DistributedOptimizer(
        tx, axis_name="world", backward_passes_per_step=2),
    "sharded": lambda tx: hvt.ShardedDistributedOptimizer(
        tx, axis_name="world"),
}
_METADATA = re.compile(r',? ?metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')


def lowered_step(kind):
    """The toy step, traced anew at every call (so that a patched
    ``jax.named_scope`` is seen) and lowered for two devices."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("world",))
    tx = OPTIMIZERS[kind](optax.sgd(0.1, momentum=0.9))
    params = {"w1": jnp.ones((16, 32)), "w2": jnp.ones((32, 8)),
              "b": jnp.zeros((8,))}

    @jax.checkpoint
    def layer(w1, x):
        return jnp.tanh(x @ w1)

    def loss_fn(p, x):
        return jnp.mean((layer(p["w1"], x) @ p["w2"] + p["b"]) ** 2)

    def body(p, s, x):
        loss, grads = jax.value_and_grad(loss_fn)(p, x)
        updates, s = tx.update(grads, s, p)
        return (optax.apply_updates(p, updates), s,
                jax.lax.pmean(loss, "world"))

    # the sharded optimizer's state is a shard a device
    state_spec = P("world") if kind == "sharded" else P()
    state = jax.eval_shape(jax.shard_map(
        tx.init, mesh=mesh, in_specs=P(), out_specs=state_spec,
        check_vma=False), params)
    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), state_spec, P("world")),
        out_specs=(P(), state_spec, P()), check_vma=False))
    return step.lower(params, state,
                      jax.ShapeDtypeStruct((8, 16), jnp.float32))


@pytest.fixture(scope="module")
def compiled_text():
    return {kind: lowered_step(kind).compile().as_text()
            for kind in OPTIMIZERS}


@pytest.mark.parametrize("scope", EXCHANGE + OPTIMIZER)
@pytest.mark.parametrize("kind", ["plain", "accumulating"])
def test_the_compiled_step_carries_the_scope(kind, scope, compiled_text):
    emitted = set(scopes.scope_by_instruction(compiled_text[kind]).values())
    assert scope in emitted, sorted(emitted)


@pytest.mark.parametrize("scope", EXCHANGE + ["hvtpu:optimizer.update"])
def test_the_sharded_optimizer_takes_the_same_names(scope, compiled_text):
    emitted = set(
        scopes.scope_by_instruction(compiled_text["sharded"]).values())
    assert scope in emitted, sorted(emitted)
    assert emitted <= set(EXCHANGE + OPTIMIZER)


def test_both_branches_of_the_guard_carry_the_updates_scope(compiled_text):
    """The scope lies around the whole ``lax.cond``: the traced program
    names the instructions of the skipping branch (its zeros) and of
    the applying one under it.  The compiler folds the zeros into
    constants, so the optimised text keeps the conditional itself and
    the applying branch's momentum."""
    traced = lowered_step("plain").as_text(debug_info=True)
    for branch in ("branch_0_fun", "branch_1_fun"):
        assert re.search(
            rf'"[^"]*hvtpu:optimizer\.update/cond/{branch}/', traced), branch
    program = passes.parse(compiled_text["plain"])
    under = [n for n in program.op_names.values()
             if "hvtpu:optimizer.update/cond" in n]
    assert any(n.endswith("/cond") for n in under)
    assert any("/cond/branch_1_fun/mul" in n for n in under)
    # the guard's reductions are outside the conditional they decide
    assert not any("hvtpu:optimizer.guard" in n for n in under)


@pytest.mark.parametrize("kind", sorted(OPTIMIZERS))
def test_the_scopes_change_nothing_but_metadata(kind, compiled_text,
                                                monkeypatch):
    def instructions(text):
        # the tables of files, functions and stack frames come first
        return _METADATA.sub("", text[text.index("\n%"):])

    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = lowered_step(kind).compile().as_text()
    assert "hvtpu:" not in bare
    assert "hvtpu:" in compiled_text[kind]
    assert instructions(bare) == instructions(compiled_text[kind])


@pytest.mark.parametrize("suffix, part", [
    ("/jvp()/tanh", passes.FORWARD),
    ("/checkpoint/rematted_computation/tanh", passes.RECOMPUTE),
    ("/transpose(jvp())/dot_general", passes.BACKWARD),
    ("/hvtpu:optimizer.update/cond/branch_1_fun/mul", passes.REST),
    ("", passes.UNNAMED),
])
def test_an_instruction_is_put_down_to_its_pass(suffix, part, compiled_text):
    """A layer's forward ``tanh``, its rematerialised copy, the
    transposed product, the momentum's multiply and an instruction the
    compiler left without a name."""
    op_names = passes.parse(compiled_text["plain"]).op_names
    found = [n for n in op_names.values()
             if (n.endswith(suffix) if suffix else not n)]
    assert found, sorted(set(op_names.values()))
    assert {passes.pass_of(n) for n in found} == {part}


# An optimised text made by hand: a fusion whose body holds two scopes
# and two passes, one of a single scope, an instruction printed over
# three lines (a library kernel's frontend attributes) and one the
# compiler named nothing.
CRAFTED = '''HloModule crafted

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %is-finite.1 = pred[8]{0} is-finite(%param_0), metadata={op_name="jit(step)/hvtpu:optimizer.guard/is_finite"}
  ROOT %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/transpose(jvp(hvtpu:mlp))/mul"}
}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %slice.1 = f32[8]{0} slice(%param_0.1), slice={[0:8]}, metadata={op_name="jit(step)/hvtpu:exchange.unpack/slice"}
  ROOT %convert.1 = f32[8]{0} convert(%slice.1), metadata={op_name="jit(step)/hvtpu:exchange.unpack/convert_element_type"}
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="p"}
  %mixed_fusion = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(hvtpu:mlp))/mul"}
  %pure_fusion = f32[8]{0} fusion(%mixed_fusion), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/hvtpu:exchange.unpack/convert_element_type"}
  %kernel.1 = f32[8]{0} custom-call(%pure_fusion), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
    "xprof_metadata": "{}"
  }}, metadata={op_name="jit(step)/jvp(hvtpu:attention)/pallas_call"}
  ROOT %copy.1 = f32[8]{0} copy(%kernel.1)
}
'''


def crafted_trace(op_ms, steps=2):
    """A reduction of one chip that ran each op ``op_ms`` a step."""
    device = types.SimpleNamespace(
        step_ns=[1e6] * steps, busy_ns=1e6 * steps * sum(op_ms.values()),
        op_ns={f"{op} opcode f32[8]": 1e6 * ms * steps
               for op, ms in op_ms.items()})
    return types.SimpleNamespace(devices=[device])


def test_a_fusion_of_two_scopes_is_counted_as_mixed():
    trace = crafted_trace({"mixed_fusion": 3.0, "pure_fusion": 2.0,
                           "kernel.1": 4.0, "copy.1": 1.0,
                           "not_in_the_text.7": 0.5})
    program = passes.parse(CRAFTED)
    assert set(program.bodies) == {"mixed_fusion", "pure_fusion"}
    assert passes.mixed_fusion_ms(trace, CRAFTED) == {
        "by_scope": 3.0, "by_pass": 3.0, "either": 3.0}
    # a three-line instruction keeps the ``op_name`` of its last line,
    # which ``scopes.py`` still reads as no scope
    assert program.op_names["kernel.1"].endswith("/pallas_call")
    assert "kernel.1" not in scopes.scope_by_instruction(CRAFTED)
    parts = passes.ms_per_step(trace, CRAFTED)
    assert parts == {"forward": 4.0, "recompute": 0.0, "backward": 3.0,
                     "rest": 2.0, "unnamed": 1.5}
    assert passes.table(trace, CRAFTED)[
        ("backward", "hvtpu:mlp")] == 3.0
    line = passes.account(trace, CRAFTED)
    assert re.search(r"sum 10\.500 against the trace's busy time 10\.500 "
                     r"\([+-]0\.00 %\)", line), line
    assert "more than one scope 3.000" in line


def test_nothing_to_read_is_none_and_a_scope_compiled_away_is_zero():
    trace = crafted_trace({"mixed_fusion": 3.0})

    def obs(trace, text):
        return types.SimpleNamespace(trace=trace, compiled_text=text)

    assert passes.ms_per_step(None, CRAFTED) is None     # the CPU rehearsal
    assert passes.account(trace, None) is None
    assert passes.pass_ms(obs(None, None), passes.FORWARD) is None
    assert passes.framework_ms(obs(None, CRAFTED), "hvtpu:exchange.") is None
    # no timed op carries the scope: 0, not nothing, in a program that
    # marks the framework's part at all (one chip: the compiler takes
    # the whole exchange away and the guard stays)
    assert passes.framework_ms(obs(trace, CRAFTED), "hvtpu:exchange.") == 0.0
    guard_alone = CRAFTED.replace("hvtpu:exchange.unpack", "hvtpu:mlp")
    assert passes.framework_ms(
        obs(trace, guard_alone), "hvtpu:exchange.") == 0.0
    # a parent commit's program marks no such part: nothing
    before = guard_alone.replace("hvtpu:optimizer.guard", "hvtpu:mlp")
    assert passes.framework_ms(obs(trace, before), "hvtpu:exchange.") is None
    assert passes.framework_ms(obs(trace, before), "hvtpu:optimizer.") is None
