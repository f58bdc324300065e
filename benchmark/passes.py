"""Device time by the pass of the step an op belongs to.

JAX writes the pass into every instruction's ``op_name`` by itself:
what ``jax.grad`` differentiates runs under ``jvp(...)``, its backward
pass under ``transpose(jvp(...))``, and what a ``jax.checkpoint`` makes
a second time under ``checkpoint/rematted_computation``.  So a step
splits into five parts with no scope of the program's own:

    recompute   the ``op_name`` holds ``rematted_computation``
    backward    else it holds ``transpose(``
    forward     else it holds ``jvp(``
    rest        else it is not empty: the exchange, the guard, the
                update, ``optax.apply_updates``, what the model computes
                from integers (nothing to differentiate)
    unnamed     the instruction has no ``op_name`` at all, or the text
                does not name it: what the compiler made by itself
                (copies, converts, relayouts)

The join is ``scopes.py``'s: the profiler names a device op by its
instruction, and the compiled step's text gives the instruction's
``op_name``.  An instruction's text is read up to the next
instruction's start, so one printed over several lines (a library
kernel's ``frontend_attributes``) keeps the ``op_name`` of its last.

A fusion is timed whole and named by its root.  ``mixed_fusion_ms``
says how much device time ran in fusions whose bodies hold
instructions of more than one scope, or of more than one pass: the
error bar on every metric read by scope or by pass.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Dict, Optional, Tuple

from benchmark import scopes

FORWARD, RECOMPUTE, BACKWARD = "forward", "recompute", "backward"
REST, UNNAMED = "rest", "unnamed"
PASSES = (FORWARD, RECOMPUTE, BACKWARD, REST, UNNAMED)
# the scopes of the part of a step that is the framework's own
# (``comm/fusion.py``, ``api/optimizer.py``)
FRAMEWORK = ("hvtpu:exchange.", "hvtpu:optimizer.")

# where an instruction (`  %name = ...`, `  ROOT %name = ...`) or a
# computation (`%name (params) -> shape {`, `ENTRY %name (...`) starts
_START = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<instruction>[^\s=]+) = "
    r"|^(?:ENTRY\s+)?%(?P<computation>[^\s(]+) \(", re.MULTILINE)
_CALLS = re.compile(r" fusion\(.*\bcalls=%(?P<body>[^\s,)}]+)", re.DOTALL)
_OPCODE = re.compile(r"\s(?P<opcode>[a-z][a-z0-9\-]*)\(")
# instructions that compute nothing: a fusion is not mixed for holding
# a constant and its broadcast, which carry the name of the whole step
_NO_WORK = frozenset(("parameter", "constant", "broadcast", "iota", "tuple",
                      "get-tuple-element", "bitcast"))


def pass_of(op_name: Optional[str]) -> str:
    if not op_name:
        return UNNAMED
    if "rematted_computation" in op_name:
        return RECOMPUTE
    if "transpose(" in op_name:
        return BACKWARD
    if "jvp(" in op_name:
        return FORWARD
    return REST


def scope_of(op_name: str) -> str:
    """The innermost ``hvtpu:`` scope, as ``scopes.py`` reads it."""
    found = scopes._SCOPE.findall(op_name)
    return found[-1] if found else scopes.UNSCOPED


@dataclasses.dataclass(frozen=True)
class Program:
    op_names: Dict[str, str]            # instruction -> op_name ("" if none)
    bodies: Dict[str, Tuple[str, ...]]  # fusion -> the op_names of its
                                        # body's instructions that compute


@functools.lru_cache(maxsize=2)
def parse(text: str) -> Program:
    """The optimised HLO text, read once for every reader of a run."""
    starts = list(_START.finditer(text))
    op_names: Dict[str, str] = {}
    calls: Dict[str, str] = {}              # fusion -> computation called
    inside: Dict[str, list] = {}            # computation -> op_names
    computation = None
    for m, nxt in zip(starts, starts[1:] + [None]):
        if m["computation"]:
            computation = m["computation"]
            continue
        chunk = text[m.end():nxt.start() if nxt else len(text)]
        found = scopes._OP_NAME.findall(chunk)
        op_name = found[-1] if found else ""
        op_names[m["instruction"]] = op_name
        opcode = _OPCODE.search(chunk)
        if op_name and opcode and opcode["opcode"] not in _NO_WORK:
            inside.setdefault(computation, []).append(op_name)
        called = _CALLS.search(chunk)
        if called:
            calls[m["instruction"]] = called["body"]
    return Program(op_names=op_names, bodies={
        fusion: tuple(inside.get(body, ())) for fusion, body in calls.items()})


def _op_ms(trace):
    """(instruction, ms a step) of every op of the trace: mean over the
    chips, over the step periods kept — ``scopes.ms_per_step``'s."""
    for device in trace.devices:
        for op, ns in device.op_ns.items():
            yield op.split(" ", 1)[0], ns / (
                1e6 * len(device.step_ns) * len(trace.devices))


def table(trace, text: Optional[str]
          ) -> Optional[Dict[Tuple[str, str], float]]:
    """(pass, scope) -> device ms a step.  None without a trace or a
    program text."""
    if not trace or not text:
        return None
    op_names = parse(text).op_names
    cells: Dict[Tuple[str, str], float] = {}
    for instruction, ms in _op_ms(trace):
        op_name = op_names.get(instruction, "")
        key = (pass_of(op_name), scope_of(op_name))
        cells[key] = cells.get(key, 0.0) + ms
    return cells


def _by_pass(cells: Dict[Tuple[str, str], float]) -> Dict[str, float]:
    parts = dict.fromkeys(PASSES, 0.0)
    for (part, _), ms in cells.items():
        parts[part] += ms
    return parts


def ms_per_step(trace, text: Optional[str]) -> Optional[Dict[str, float]]:
    """Pass -> device ms a step, every one of ``PASSES`` (0.0 where
    nothing ran in it); they add up to the trace's busy time."""
    cells = table(trace, text)
    return None if cells is None else _by_pass(cells)


def mixed_fusion_ms(trace, text: Optional[str]
                    ) -> Optional[Dict[str, float]]:
    """Device ms a step in fusions whose body holds instructions of
    more than one scope (``by_scope``; no scope counts as one), of more
    than one pass (``by_pass``), or either (``either``)."""
    if not trace or not text:
        return None
    bodies = parse(text).bodies
    mixed = {"by_scope": 0.0, "by_pass": 0.0, "either": 0.0}
    for instruction, ms in _op_ms(trace):
        body = bodies.get(instruction, ())
        by_scope = len({scope_of(n) for n in body}) > 1
        by_pass = len({pass_of(n) for n in body}) > 1
        mixed["by_scope"] += ms * by_scope
        mixed["by_pass"] += ms * by_pass
        mixed["either"] += ms * (by_scope or by_pass)
    return mixed


def framework_ms(obs, prefix: str) -> Optional[float]:
    """``scopes.scoped_ms`` for a scope of the framework's own part of
    the step (``FRAMEWORK``), but 0.0 and not None where the program
    marks that part and no timed op carries ``prefix``: on one chip the
    compiler takes a pack, a ``psum`` over one device and the unpack
    away whole.  None is for a program that marks no such part, a
    parent commit's."""
    if not obs.trace or not obs.compiled_text:
        return None
    emitted = scopes.scope_by_instruction(obs.compiled_text).values()
    if not any(scope.startswith(FRAMEWORK) for scope in emitted):
        return None
    return scopes.scoped_ms(obs, prefix) or 0.0


def pass_ms(obs, part: str) -> Optional[float]:
    parts = ms_per_step(obs.trace, obs.compiled_text)
    return None if parts is None else parts[part]


def account(trace, text: Optional[str]) -> Optional[str]:
    """One line for the run's log: the five parts beside the time an op
    ran at all (they should add up to it), the same time by pass and
    scope, and what ran in fusions of more than one scope or pass."""
    cells = table(trace, text)
    if cells is None:
        return None
    # a chip's busy time over its own kept periods, then the mean over
    # the chips, as the op times are averaged: the chips of one trace
    # keep different numbers of periods
    busy = sum(d.busy_ns / len(d.step_ns) for d in trace.devices) / (
        1e6 * len(trace.devices))
    parts = _by_pass(cells)
    total = sum(parts.values())
    unscoped = sum(ms for (_, scope), ms in cells.items()
                   if scope == scopes.UNSCOPED)
    mixed = mixed_fusion_ms(trace, text)
    return (
        "passes: device ms a step by pass: "
        + ", ".join(f"{k} {parts[k]:.3f}" for k in PASSES)
        + f"; sum {total:.3f} against the trace's busy time {busy:.3f} "
        f"({100 * (total / busy - 1):+.2f} %); scoped "
        f"{total - unscoped:.3f} + unscoped {unscoped:.3f}; by pass and "
        "scope: " + ", ".join(
            f"{part}/{scope} {ms:.3f}"
            for (part, scope), ms in sorted(cells.items()))
        + "; in fusions that hold more than one scope "
        f"{mixed['by_scope']:.3f}, more than one pass "
        f"{mixed['by_pass']:.3f}, either {mixed['either']:.3f}")
