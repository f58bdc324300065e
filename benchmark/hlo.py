"""What the compiled step program says of itself (optimised HLO text)."""

from __future__ import annotations

import re
from typing import List, Tuple

# `%name = shape opcode(operands)`: the shape ends in ], } or ) and the
# opcode follows it after one space.  An asynchronous collective is a
# `-start` / `-done` pair.
_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+) = .*?[\]})] "
    r"(?P<op>all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute)(?P<phase>-start|-done)?\(", re.MULTILINE)


def collective_instructions(text: str) -> List[Tuple[str, str, str]]:
    """``(instruction name, opcode, "" | "-start" | "-done")`` of every
    instruction that moves data between chips.  The name is what the
    profiler calls the op; it need not contain the opcode (a ``psum``
    lowers to ``%psum.3 = ... all-reduce(...)``)."""
    return [(m["name"], m["op"], m["phase"] or "")
            for m in _COLLECTIVE.finditer(text)]


def collective_calls(text: str) -> int:
    """Collectives one run of the program issues; a pair counts once."""
    return sum(1 for _, _, phase in collective_instructions(text)
               if phase != "-done")
