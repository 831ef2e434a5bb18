"""How the program's compiled programs appear in a device trace (a kernel's
operations are found by the ``PATTERN`` in its roofline metric's file).

The engine jits ``functools.partial(prefill)`` and ``functools.partial(
decode_step)``, which the trace names ``jit__unknown(<fingerprint>)``: one
program per prompt length for prefill, one for the slot batch's decode. The
graft is a jitted lambda (``jit__lambda(<fingerprint>)``). So, until the
program names its programs: among the ``jit__unknown`` programs the one run
most often is the decode step (it runs once per wave; a prompt length
recurs only on admission) and the rest are prefills.

A gang compiles a decode program for each size it takes, but the reduction
reads one layout's programs: the span's planes are those of the gang's mesh
over it, and no event falls inside it. The first engine call after a resize
runs a variant of its own, taking the slot state uncommitted, which would
count as a prefill here: ``spec.cell`` refuses an event less than
``spec.EVENT_MARGIN_S`` before the span, and ``harness.drive`` fails a run
in which that call has not ended when the span opens.
"""
from typing import Dict, List

UNNAMED = "jit__unknown("
GRAFT = "jit__lambda("


def classify(module_n: Dict[str, int]) -> Dict[str, List[str]]:
    """Program names by kind: ``prefill``, ``graft``, ``decode``."""
    unnamed = {n: c for n, c in module_n.items() if n.startswith(UNNAMED)}
    decode = [max(sorted(unnamed), key=unnamed.get)] if unnamed else []
    return {"prefill": sorted(n for n in unnamed if n not in decode),
            "graft": sorted(n for n in module_n if n.startswith(GRAFT)),
            "decode": decode}


def seconds(red, kind: str) -> float:
    """Device seconds of one kind of program in a reduction."""
    return sum(red.module_s[n] for n in classify(red.module_n)[kind])
