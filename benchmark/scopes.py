"""Device time by the program's named scopes.

The program marks its parts with ``jax.named_scope("hvtpu:<part>")``;
the compiler keeps the scope in every instruction's ``op_name``
metadata, through the backward pass, recomputation and fusion (a fusion
carries its root's).  The profiler names a device op by its
instruction, so the trace's time by op (``DeviceReduction.op_ns``)
joins to the scopes through the compiled step's text
(``Observations.compiled_text``), as ``hlo.py`` joins collectives.
An op belongs to the innermost ``hvtpu:`` scope of its ``op_name``;
one with none is counted under ``UNSCOPED``.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional

UNSCOPED = "unscoped"

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+) = .*$", re.MULTILINE)
_OP_NAME = re.compile(r'op_name="(?P<op_name>[^"]*)"')
_SCOPE = re.compile(r"hvtpu:[A-Za-z0-9_.]+")


@functools.lru_cache(maxsize=2)
def scope_by_instruction(text: str) -> Dict[str, str]:
    """Instruction name -> its innermost ``hvtpu:`` scope, for the
    instructions of the optimised HLO text that have one.  (Five
    readers ask about the same 30 MB of text in a run: parsed once.)"""
    scopes = {}
    for m in _INSTRUCTION.finditer(text):
        op_name = _OP_NAME.search(m.group(0))
        found = op_name and _SCOPE.findall(op_name["op_name"])
        if found:
            scopes[m["name"]] = found[-1]
    return scopes


def ms_per_step(trace, text: Optional[str]) -> Optional[Dict[str, float]]:
    """Scope -> milliseconds of device time a step, mean over the
    chips, over the step periods the reduction kept; ``UNSCOPED`` holds
    the rest.  None without a trace or a program text, or where the
    program has no such scope (a parent commit without them)."""
    if not trace or not text:
        return None
    scopes = scope_by_instruction(text)
    if not scopes:
        return None
    totals: Dict[str, float] = {}
    for device in trace.devices:
        for op, ns in device.op_ns.items():
            scope = scopes.get(op.split(" ", 1)[0], UNSCOPED)
            totals[scope] = totals.get(scope, 0.0) + ns / (
                1e6 * len(device.step_ns) * len(trace.devices))
    return totals


def account(trace, text: Optional[str]) -> Optional[str]:
    """One line for the run's log: scoped and unscoped op time a step
    beside the time an op ran at all (the reduction's busy time), which
    they should add up to."""
    by_scope = ms_per_step(trace, text)
    if by_scope is None:
        return None
    steps = sum(len(d.step_ns) for d in trace.devices) / len(trace.devices)
    busy = 1e3 * trace.busy_s / steps
    total = sum(by_scope.values())
    parts = ", ".join(f"{k} {v:.3f}" for k, v in sorted(by_scope.items()))
    return (f"scopes: device ms a step by scope: {parts}; sum {total:.3f} "
            f"against the trace's busy time {busy:.3f} "
            f"({100 * (total / busy - 1):+.2f} %)")


def scoped_ms(obs, prefix: str) -> Optional[float]:
    """Device ms a step under the scopes that start with ``prefix``;
    None where there is nothing to read."""
    by_scope = ms_per_step(obs.trace, obs.compiled_text)
    if by_scope is None:
        return None
    found = [v for k, v in by_scope.items() if k.startswith(prefix)]
    return sum(found) if found else None
