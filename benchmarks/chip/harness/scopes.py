"""Device time by the program's ``jax.named_scope``: the device trace's
operations joined to the compiled program's HLO instructions.

The trace names each operation by its HLO instruction (``%fusion.12 =
bf16[...] fusion(...)``) and says nothing of where in the program it
came from; the compiled program's HLO text gives each instruction its
``op_name`` metadata, which holds the scopes it was traced under, also
for the operations of the backward pass and of recomputation.  The join
is by instruction name.  Where it covers under ``COVERAGE`` of the
window's operation time, the trace is not of that program (or the names
changed between the two), and no number is read.
"""

from __future__ import annotations

import re
import weakref
from typing import Dict, Optional

COVERAGE = 0.95
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=\s")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
# one join per traced run, keyed by the run's trace
_JOINS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name (without its ``%``) to its ``op_name``, or "" for
    an instruction that has none."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            n = _OP_NAME.search(line)
            out[m.group(1)] = n.group(1) if n else ""
    return out


def instruction(event: str) -> str:
    """The instruction name of a device trace's operation."""
    return event.partition(" = ")[0].strip().lstrip("%")


def scope_s(trace, names: Dict[str, str], scope: str) -> Optional[float]:
    """Seconds of the window's operations whose ``op_name`` holds
    ``scope``; None where the join covers under ``COVERAGE`` of the
    window's operation time."""
    total = trace.op_s()
    joined = trace.op_s(lambda e: instruction(e) in names)
    if total <= 0 or joined < COVERAGE * total:
        return None
    return trace.op_s(lambda e: scope in names.get(instruction(e), ""))


def read_scope_s(ctx, facts, scope: str) -> Optional[float]:
    """``scope_s`` of a traced run, whose driver's facts give ``hlo_text``,
    a function returning the compiled step's HLO text (called once per
    run); None in an untraced run or without it."""
    trace = ctx.trace
    if trace is None or "hlo_text" not in facts:
        return None
    if trace not in _JOINS:
        _JOINS[trace] = op_names(facts["hlo_text"]())
    return scope_s(trace, _JOINS[trace], scope)
