"""How the program's compiled programs appear in a device trace (a kernel's
operations are found by the ``PATTERN`` in its roofline metric's file).

The engine jits ``functools.partial(prefill)`` and ``functools.partial(
decode_step)``, which the trace names ``jit__unknown(<fingerprint>)``: one
program per prompt length for prefill, one for the slot batch's decode. The
graft is a jitted lambda (``jit__lambda(<fingerprint>)``). So, until the
program names its programs: among the ``jit__unknown`` programs the one run
most often is the decode step (it runs once per wave; a prompt length
recurs only on admission) and the rest are prefills.
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
